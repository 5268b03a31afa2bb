"""Device milliseconds per decomposition of the in-memory column gather
``B = A[:, J]`` (``jnp.take``).  A streamed decomposition gathers on the
host in pass 2 and has none."""
PROGRAMS = ("_take",)


def read(w):
    s = w.trace.module_s(PROGRAMS)
    return 1e3 * s / w.decomps if s > 0 else None
