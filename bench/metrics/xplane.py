"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

A TPU trace holds one plane per chip, ``/device:TPU:<id>``, whose line
``XLA Modules`` has one event per execution of a compiled program (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per HLO instruction (named by the instruction's text, which holds
its operand shapes).  The host plane ``/host:CPU`` holds the benchmark's
own ``jax.profiler.TraceAnnotation`` spans (``bench.*``) on the same
clock.  Everything here is computed over the span ``bench.window``.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
_KERNEL = re.compile(r'^%([A-Za-z_][\w-]*?)(?:\.\d+)? = .*'
                     r'custom_call_target="tpu_custom_call"')
_OPERANDS = re.compile(r'custom-call\((.*?)\), custom_call_target')
_SHAPE = re.compile(r'\b[a-z]\w*\[([\d,]*)\]')


@contextlib.contextmanager
def profile(log_dir: str):
    """Trace the device and the benchmark's own annotations (no Python
    tracer, no framework events on the host) into ``log_dir``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclass
class Event:
    name: str
    start: float      # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def _merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def kernel_call(op_name: str):
    """``(kernel name, operand shapes)`` of a Mosaic kernel's op, or None
    for any other op."""
    m = _KERNEL.match(op_name)
    if m is None:
        return None
    args = _OPERANDS.search(op_name)
    shapes = [tuple(int(d) for d in s.split(",") if d)
              for s in _SHAPE.findall(args.group(1) if args else "")]
    return m.group(1), shapes


@dataclass
class Trace:
    """The window's device events, per chip, and the host's annotations."""
    window: tuple
    modules: dict = field(default_factory=dict)   # chip -> [Event]
    ops: dict = field(default_factory=dict)       # chip -> [Event]
    host: list = field(default_factory=list)      # [Event] bench.*

    @property
    def chips(self) -> int:
        return max(1, len(self.modules))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, chip) -> list:
        """Disjoint intervals of the window in which a program ran."""
        t0, t1 = self.window
        return _merge((max(e.start, t0), min(e.end, t1))
                      for e in self.modules[chip])

    @property
    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the chips."""
        return sum(e - s for c in self.modules
                   for s, e in self.busy_intervals(c)) / self.chips

    def module_s(self, names) -> float:
        """Device seconds of the programs ``jit_<name>`` for ``name`` in
        ``names``, averaged over the chips."""
        want = {f"jit_{n}" for n in names}
        return sum(e.dur for evs in self.modules.values() for e in evs
                   if e.name.split("(")[0] in want) / self.chips

    def kernel_calls(self, kernel: str) -> list:
        """``(operand shapes, device seconds)`` of each call of the Mosaic
        kernel ``kernel``, on every chip."""
        out = []
        for evs in self.ops.values():
            for e in evs:
                call = kernel_call(e.name)
                if call is not None and call[0] == kernel:
                    out.append((call[1], e.dur))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the longest idle gaps
        named by the benchmark's annotation the host was in."""
        per_op = defaultdict(float)
        for c, evs in self.ops.items():
            mods = sorted((e.start, e.end, e.name.split("(")[0])
                          for e in self.modules[c])
            starts = [s for s, _, _ in mods]
            for e in evs:
                i = bisect.bisect_right(starts, e.start) - 1
                module = mods[i][2] if i >= 0 and e.start <= mods[i][1] \
                    else "?"
                per_op[f"{module}/{e.name.split(' = ')[0].lstrip('%')}"] \
                    += e.dur
        per_op = {k: v / self.chips for k, v in per_op.items()}
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for c in self.modules:
            t = self.window[0]
            for s, e in self.busy_intervals(c) + [[self.window[1]] * 2]:
                if s > t:
                    gaps.append((s - t, self._host_at((s + t) / 2)))
                t = max(t, e)
        gaps.sort(key=lambda g: -g[0])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[name, dur] for dur, name in gaps[:top]]}

    def _host_at(self, t: float) -> str:
        """The innermost ``bench.*`` annotation open at ``t``."""
        best = None
        for e in self.host:
            if e.start <= t <= e.end and (best is None or e.dur < best.dur):
                best = e
        return best.name if best is not None else "outside bench.window"


def _events(line, t0, t1):
    out = []
    for e in line.events:
        s = e.start_ns * 1e-9
        d = e.duration_ns * 1e-9
        if s + d >= t0 and s <= t1:
            out.append(Event(e.name, s, d))
    return out


def reduce(log_dir: str, device_ids) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``: the device events of
    chips ``device_ids`` that overlap the span ``bench.window``, and the
    benchmark's annotations in it."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    host = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events if e.name.startswith("bench.")]
    windows = [e for e in host if e.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(windows)}")
    t0, t1 = windows[0].start, windows[0].end
    trace = Trace(window=(t0, t1),
                  host=[e for e in host if e.end >= t0 and e.start <= t1])
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    for plane in data.planes:
        if plane.name not in wanted:
            continue
        lines = {line.name: line for line in plane.lines}
        chip = int(plane.name.rsplit(":", 1)[1])
        trace.modules[chip] = _events(lines["XLA Modules"], t0, t1)
        trace.ops[chip] = _events(lines["XLA Ops"], t0, t1)
    return trace
