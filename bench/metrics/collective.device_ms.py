"""Device milliseconds per decomposition of the collective ops, averaged
over the chips: every op of the trace whose HLO opcode is an all-reduce,
all-gather, reduce-scatter, collective-permute or all-to-all, or the
start or done half of one of them.  A ``psum`` prints as ``%psum.<i> =
... all-reduce(...)``, so the opcode is matched, not the name.  A run on
one chip has none and reads nothing."""
import re

_COLLECTIVE = re.compile(r" = .*? (?:all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(?:-start|-done)?\(")


def read(w):
    s = sum(e.dur for evs in w.trace.ops.values() for e in evs
            if _COLLECTIVE.search(e.name))
    return 1e3 * s / w.trace.chips / w.decomps if s > 0 else None
