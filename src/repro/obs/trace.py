"""Nested runtime spans with device-bracketed timing.

A :class:`Tracer` records a tree of :class:`Span` s — name, wall-clock
interval, attributes, and point-in-time events — plus a
:class:`~repro.obs.metrics.MetricsRegistry`, and hands everything to
pluggable exporters (``repro.obs.export``) when the trace finishes.

THE JIT RULE: spans are opened and closed in HOST code, outside every
``jax.jit`` boundary.  Instrumented engines never read a clock (or run
any callback) inside traced code — that would plant a host sync on the
device hot path, which ``analysis/lint.py`` (clock calls) and the
``jaxpr.host-transfer`` rule (callbacks in traced programs) both ban,
with ``fixture.in-jit-timer`` as the planted positive control.  Device
work is timed by BRACKETING instead: register the output arrays on the
span (``span.block_on(out)``) and the tracer calls
``jax.block_until_ready`` on them before reading the closing timestamp,
so the span covers dispatch + device execution without touching the
traced program.

Ambient usage (the instrumented engines' pattern — zero overhead when no
tracer is installed; every helper returns a shared no-op object then):

    from repro.obs import trace as obs_trace

    with obs_trace.tracing(chrome="trace.json"):
        rid_streamed(key, src, k)        # engines pick the tracer up

    # inside an engine:
    with obs_trace.span("stream.accumulate", chunk=c) as sp:
        acc = sketch_accum(omega_c, cur, acc)
        sp.block_on(acc)                 # close waits for the device

While a tracer is installed every span is also a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
holds the program's spans on the host beside the device's programs, on
one clock (aligned by the profiler to about a millisecond on a TPU
v5e): an idle gap on the device can be laid against the host work open
at that instant.  Each JIT compile, or load from the persistent
compilation cache, that runs while a tracer is installed is recorded as
a ``jax.compile`` span (a child of the span that compiled) and counted
in the counters ``jax.compiles`` and ``jax.compile_s``.

``deep=True`` additionally switches engines that support it into their
step-at-a-time profiling schedule (e.g. ``core.qr.pivoted_qr`` runs the
blocked engine panel-by-panel with a span per panel).  Deep tracing is
a PROFILING mode: results are numerically equivalent but the execution
schedule differs (per-step jit boundaries, pipeline syncs), so never
leave it on in a latency-sensitive loop.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from dataclasses import dataclass, field
from typing import Optional

import jax

from .clock import Clock, MONOTONIC
from .metrics import Counter, Gauge, Histogram, MetricsRegistry

__all__ = ["Span", "Tracer", "tracing", "current_tracer", "deep_tracing",
           "span", "event", "counter", "gauge", "histogram", "attributes",
           "COMPILE_SPAN"]

# What JAX records around every backend compile or persistent-cache load
# (``jax._src.dispatch.BACKEND_COMPILE_EVENT``), and what a tracer makes
# of it: one span per compile, a count and a sum of seconds.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_SPAN = "jax.compile"
COMPILES = "jax.compiles"
COMPILE_S = "jax.compile_s"


@dataclass
class Span:
    """One timed interval.  ``t1`` is None while the span is open;
    ``events`` are (name, ts, attrs) points inside the interval;
    ``parent`` is the ``index`` of the enclosing span (None at the
    root)."""
    name: str
    t0: float
    depth: int
    index: int
    track: str = "main"
    t1: Optional[float] = None
    attrs: dict = field(default_factory=dict)
    events: list = field(default_factory=list)
    parent: Optional[int] = None
    _pending: list = field(default_factory=list, repr=False)
    _annotation: object = field(default=None, repr=False)

    @property
    def dur(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def event(self, name: str, *, ts: Optional[float] = None, **attrs):
        self.events.append((name, ts, dict(attrs)))

    def block_on(self, value) -> "Span":
        """Register ``value`` (any pytree of jax arrays) to be
        ``block_until_ready``-ed before the closing timestamp is read —
        the device-bracketed timing contract."""
        self._pending.append(value)
        return self


class _NullSpan:
    """The no-tracer fast path: every instrumentation call is a no-op
    attribute access on this shared singleton."""

    def set(self, **attrs):
        return self

    def event(self, name, *, ts=None, **attrs):
        pass

    def block_on(self, value):
        return self


class _NullInstrument:
    """No-op Counter/Gauge/Histogram stand-in."""

    def add(self, v: float = 1.0):
        pass

    def set(self, v: float, *, ts=None):
        pass

    def observe(self, v: float):
        pass


NULL_SPAN = _NullSpan()
_NULL_INSTRUMENT = _NullInstrument()


@contextlib.contextmanager
def _null_span_cm():
    yield NULL_SPAN


def _leave(sp: Span) -> None:
    """End ``sp``'s profiler annotation."""
    if sp._annotation is not None:
        sp._annotation.__exit__(None, None, None)
        sp._annotation = None


@functools.cache
def _listen_for_compiles() -> None:
    """Register, once per process, the listener that hands each compile
    to the ambient tracer.  JAX fires it only when it compiles or loads
    from its cache, never on a warm call, so it costs the hot path
    nothing; with no tracer installed it returns at once."""
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _on_duration(event: str, seconds: float, **attrs) -> None:
    if event != COMPILE_EVENT:
        return
    tr = _CURRENT.get()
    if tr is not None:
        tr.compiled(seconds, program=attrs.get("fun_name"))


class Tracer:
    """Span recorder + metrics registry + exporter fan-out.

    ``clock`` is injectable (``FakeClock`` in tests); ``deep`` opts
    engines into their step-at-a-time profiling schedules (module
    docstring).  Spans are exception-safe: a span closed by an error
    still records its interval (with ``error=...`` attrs) and still
    exports.
    """

    def __init__(self, *, clock: Clock = MONOTONIC, deep: bool = False,
                 exporters=()):
        self.clock = clock
        self.deep = deep
        self.exporters = list(exporters)
        self.metrics = MetricsRegistry(clock=clock)
        self.spans: list[Span] = []          # finished, in closing order
        self._stack: list[Span] = []
        self._n = 0
        self._defaults: list[dict] = []      # bind() attribute stack
        self.t_origin: Optional[float] = None
        _listen_for_compiles()

    # ------------------------------------------------------------- spans
    @contextlib.contextmanager
    def bind(self, **attrs):
        """Default attributes for every span started in this dynamic
        extent (explicit span attrs win on key collision).  This is how
        a job stamps its fingerprint onto all descendant spans without
        threading an id through every engine API."""
        self._defaults.append(dict(attrs))
        try:
            yield
        finally:
            self._defaults.pop()

    def start(self, name: str, **attrs) -> Span:
        sp = self._new_span(name, self.clock(), attrs)
        self._stack.append(sp)
        sp._annotation = jax.profiler.TraceAnnotation(name)
        sp._annotation.__enter__()
        return sp

    def _new_span(self, name: str, t0: float, attrs: dict) -> Span:
        """A span under the one open now, with the bound default attrs
        (explicit ``attrs`` win)."""
        if self.t_origin is None:
            self.t_origin = t0
        merged: dict = {}
        for d in self._defaults:
            merged.update(d)
        merged.update(attrs)
        sp = Span(name=name, t0=t0, depth=len(self._stack), index=self._n,
                  attrs=merged,
                  parent=self._stack[-1].index if self._stack else None)
        self._n += 1
        return sp

    def end(self, sp: Span) -> Span:
        if sp._pending:
            jax.block_until_ready(sp._pending)
            sp._pending = []
        sp.t1 = self.clock()
        # Tolerate out-of-order closes (an engine that leaks a span must
        # not corrupt the rest of the trace): pop through to sp.
        while self._stack:
            top = self._stack.pop()
            if top is sp:
                break
            top.t1 = sp.t1
            top.attrs.setdefault("error", "span leaked (closed by child)")
            _leave(top)
            self.spans.append(top)
        _leave(sp)
        self.spans.append(sp)
        return sp

    def compiled(self, seconds: float, **attrs) -> Span:
        """Record a compile (or persistent-cache load) of ``seconds`` that
        has just ended: a closed ``jax.compile`` span, a child of the span
        open now, and the counters ``jax.compiles`` and ``jax.compile_s``."""
        t1 = self.clock()
        sp = self._new_span(COMPILE_SPAN, t1 - seconds, attrs)
        sp.t1 = t1
        self.spans.append(sp)
        self.counter(COMPILES).add(1)
        self.counter(COMPILE_S).add(seconds)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        sp = self.start(name, **attrs)
        try:
            yield sp
        except BaseException as e:
            sp.set(error=f"{type(e).__name__}: {e}")
            raise
        finally:
            self.end(sp)

    def event(self, name: str, **attrs):
        """Point event on the current span (or a root-level zero-length
        span when none is open)."""
        ts = self.clock()
        if self._stack:
            self._stack[-1].event(name, ts=ts, **attrs)
        else:
            sp = self._new_span(name, ts, attrs)
            sp.t1 = ts                   # zero-length at the single read
            self.spans.append(sp)

    # ------------------------------------------------------------ metrics
    def counter(self, name: str) -> Counter:
        return self.metrics.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.metrics.gauge(name)

    def histogram(self, name: str) -> Histogram:
        return self.metrics.histogram(name)

    # ------------------------------------------------------------- export
    def finish(self) -> None:
        """Close any leaked spans and run every exporter."""
        while self._stack:
            self.end(self._stack[-1])
        for ex in self.exporters:
            ex.export(self)


# ---------------------------------------------------------------------------
# Ambient tracer: contextvar + no-op fallbacks
# ---------------------------------------------------------------------------

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_tracer", default=None)


def current_tracer() -> Optional[Tracer]:
    return _CURRENT.get()


def deep_tracing() -> bool:
    """True when an ambient tracer with ``deep=True`` is installed —
    engines consult this to switch into their profiling schedules."""
    tr = _CURRENT.get()
    return tr is not None and tr.deep


@contextlib.contextmanager
def tracing(tracer: Optional[Tracer] = None, *, chrome=None, jsonl=None,
            clock: Clock = MONOTONIC, deep: bool = False):
    """Install a tracer as the ambient one for the dynamic extent.

    Either pass a prebuilt :class:`Tracer`, or let this build one with
    the named exporters: ``chrome=path`` (Chrome trace-event JSON, load
    in Perfetto / chrome://tracing) and/or ``jsonl=path`` (one event per
    line).  The trace is finished (and exported) on exit — including
    exceptional exit, so a crashed run still leaves its trace behind.
    """
    if tracer is None:
        from .export import ChromeTraceExporter, JsonlExporter
        exporters = []
        if chrome is not None:
            exporters.append(ChromeTraceExporter(chrome))
        if jsonl is not None:
            exporters.append(JsonlExporter(jsonl))
        tracer = Tracer(clock=clock, deep=deep, exporters=exporters)
    token = _CURRENT.set(tracer)
    try:
        yield tracer
    finally:
        _CURRENT.reset(token)
        tracer.finish()


def span(name: str, **attrs):
    """Ambient span: a real span on the current tracer, or a shared
    no-op context when tracing is off."""
    tr = _CURRENT.get()
    return _null_span_cm() if tr is None else tr.span(name, **attrs)


def attributes(**attrs):
    """Ambient :meth:`Tracer.bind`: default attrs for every span in the
    extent, or a shared no-op context when tracing is off."""
    tr = _CURRENT.get()
    return _null_span_cm() if tr is None else tr.bind(**attrs)


def event(name: str, **attrs) -> None:
    tr = _CURRENT.get()
    if tr is not None:
        tr.event(name, **attrs)


def counter(name: str):
    tr = _CURRENT.get()
    return _NULL_INSTRUMENT if tr is None else tr.counter(name)


def gauge(name: str):
    tr = _CURRENT.get()
    return _NULL_INSTRUMENT if tr is None else tr.gauge(name)


def histogram(name: str):
    tr = _CURRENT.get()
    return _NULL_INSTRUMENT if tr is None else tr.histogram(name)
