"""Device idle laid against what the host was doing at the same instant.

The profiler's host plane ``/host:CPU`` holds the benchmark's ``bench.*``
annotations and, while a ``repro.obs`` tracer is installed, the
program's own spans (``rid*``, ``stream.*``, ``qr.*``).  Each idle
instant of a chip goes to the innermost annotation open at that instant:
the shortest of those open, since the annotations of one thread nest.
``xplane.reduce`` keeps only the ``bench.*`` annotations; :func:`reduce`
keeps the program's too.

The profiler puts the device's events on the host's clock only so far:
on a TPU v5e the device's timestamps read early by 0.2 to 1.8 ms, a
different amount in each profiling session (:func:`clock_offset` bounds
it from causality).  That is as long as the idle gaps of a closed loop,
so the attribution takes the offset as an argument.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

from bench.metrics import xplane

PROGRAM = ("rid", "stream.", "qr.")      # name prefixes of program spans
WAIT = "bench.block_until_ready"


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


def reduce(log_dir: str, device_ids) -> xplane.Trace:
    """``xplane.reduce``, with the program's annotations in ``host``
    beside the benchmark's."""
    from jax.profiler import ProfileData
    trace = xplane.reduce(log_dir, device_ids)
    t0, t1 = trace.window
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    trace.host = [
        xplane.Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith("bench.") or is_program(e.name)]
    trace.host = [e for e in trace.host if e.end >= t0 and e.start <= t1]
    return trace


def segments(host, t0: float, t1: float) -> list:
    """``[t0, t1]`` cut where the innermost open annotation changes:
    ``(start, end, name)`` in order, ``name`` None where none is open."""
    events = sorted((e for e in host if e.end > t0 and e.start < t1),
                    key=lambda e: e.start)
    cuts = sorted({t0, t1, *(x for e in events for x in (e.start, e.end)
                             if t0 < x < t1)})
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(events) and events[i].start <= a:
            active.append(events[i])
            i += 1
        active = [e for e in active if e.end > a]
        top = min(active, key=lambda e: e.dur, default=None)
        out.append((a, b, None if top is None else top.name))
    return out


def _shifted_segments(trace: xplane.Trace, offset: float) -> list:
    t0, t1 = trace.window
    return segments(trace.host, t0 + min(0.0, offset), t1 + max(0.0, offset))


def idle_intervals(trace: xplane.Trace, chip) -> list:
    """The window's intervals in which no program ran on ``chip``."""
    out, t = [], trace.window[0]
    for s, e in trace.busy_intervals(chip) + [[trace.window[1]] * 2]:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    return out


def _jit(name: str) -> str:
    return name.split("(")[0]


def clock_offset(trace: xplane.Trace, span: str, first: str, last: str,
                 wait: str = WAIT, chip=0) -> tuple:
    """``(lo, hi)``: bounds on the seconds by which ``chip``'s timestamps
    read early against the host's, from a closed loop's causality.  The
    k-th run of the program ``first`` cannot start before the host opens
    its k-th ``span``; the k-th run of ``last`` has ended before the
    host's k-th ``wait`` returns."""
    def host(name, end):
        return sorted(e.end if end else e.start
                      for e in trace.host if e.name == name)

    def device(name, end):
        return sorted(e.end if end else e.start
                      for e in trace.modules[chip] if _jit(e.name) == name)
    lo = max(h - d for h, d in zip(host(span, False), device(first, False)))
    hi = min(h - d for h, d in zip(host(wait, True), device(last, True)))
    return lo, hi


def idle_by_host(trace: xplane.Trace, offset: float = 0.0) -> dict:
    """Idle seconds of the window by the innermost host annotation open
    at each idle instant, the device's clock read ``offset`` seconds
    late, averaged over the chips.  Idle that the offset moves outside
    the window goes to None."""
    segs = _shifted_segments(trace, offset)
    starts = [s for s, _, _ in segs]
    out = defaultdict(float)
    for chip in trace.modules:
        for a, b in idle_intervals(trace, chip):
            a, b = a + offset, b + offset
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(segs) and segs[i][0] < b:
                s, e, name = segs[i]
                out[name] += max(0.0, min(b, e) - max(a, s))
                i += 1
    return {name: s / trace.chips for name, s in out.items()}


def name_gaps(trace: xplane.Trace, top: int = 10,
              offset: float = 0.0) -> list:
    """The longest idle gaps as ``[name, seconds]``: the innermost host
    annotation open at the gap's midpoint (the device's clock read
    ``offset`` seconds late), then the programs on either side, e.g.
    ``rid.gather [jit__qr_interp > jit__take]``."""
    segs = _shifted_segments(trace, offset)
    starts = [s for s, _, _ in segs]
    gaps = []
    for chip in trace.modules:
        mods = sorted((e.start, e.end, _jit(e.name))
                      for e in trace.modules[chip])
        for a, b in idle_intervals(trace, chip):
            i = bisect.bisect_right(starts, (a + b) / 2 + offset) - 1
            host = segs[i][2] if i >= 0 else None
            before = [m for s, e, m in mods if e <= a]
            after = [m for s, e, m in mods if s >= b]
            gaps.append((b - a, f"{host or 'outside annotations'} "
                                f"[{before[-1] if before else '-'} > "
                                f"{after[0] if after else '-'}]"))
    gaps.sort(key=lambda g: -g[0])
    return [[name, dur] for dur, name in gaps[:top]]
