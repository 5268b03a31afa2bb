"""repro.obs — the observability layer's own contracts.

Everything timing-dependent runs on a FakeClock so span intervals,
gauge tracks, and exported timestamps are exact integers, not
tolerances.  The last test block pins the OBSERVER-EFFECT contracts the
instrumented engines promise in their docstrings: tracing (normal or
deep) never changes a decomposition's bits, and the deep per-panel QR
driver returns the same pivots as the fused in-jit engine.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (ChromeTraceExporter, FakeClock, JsonlExporter,
                       MetricsRegistry, Tracer, tracing)
from repro.obs import trace as obs_trace
from repro.obs.export import exporter_names, get_exporter, register_exporter
from repro.obs.metrics import Counter, Gauge, Histogram


# ------------------------------------------------------------------ clock

def test_fake_clock_advance_and_tick():
    clk = FakeClock(10.0)
    assert clk() == 10.0 and clk() == 10.0      # frozen until told
    clk.advance(2.5)
    assert clk() == 12.5
    auto = FakeClock(tick=1.0)
    assert [auto(), auto(), auto()] == [0.0, 1.0, 2.0]


def test_fake_clock_rejects_time_travel():
    with pytest.raises(ValueError, match="monotonic"):
        FakeClock().advance(-1.0)


def test_fake_clock_sleep_advances_and_records():
    """``Clock.sleep`` (ISSUE 8): the injectable wait primitive.  On a
    FakeClock it advances virtual time instantly and logs each request,
    so retry/backoff tests assert exact sleep schedules with no real
    waiting."""
    clk = FakeClock(5.0)
    clk.sleep(2.0)
    clk.sleep(0.5)
    assert clk() == 7.5
    assert clk.sleeps == [2.0, 0.5]
    with pytest.raises(ValueError, match="dt=-1"):
        clk.sleep(-1.0)


def test_monotonic_clock_sleep_really_waits():
    from repro.obs.clock import MONOTONIC
    t0 = MONOTONIC()
    MONOTONIC.sleep(0.01)
    assert MONOTONIC() - t0 >= 0.009
    with pytest.raises(ValueError, match="dt=-0.5"):
        MONOTONIC.sleep(-0.5)


# ------------------------------------------------------------------ spans

def test_span_nesting_depths_and_durations():
    clk = FakeClock(tick=1.0)
    tr = Tracer(clock=clk)
    with tr.span("outer", m=4) as outer:
        with tr.span("inner") as inner:
            inner.set(k=2)
    assert (outer.depth, inner.depth) == (0, 1)
    assert inner.dur == 1.0                      # one tick inside
    assert outer.t0 < inner.t0 and inner.t1 <= outer.t1
    assert tr.spans == [inner, outer]            # closing order
    assert outer.attrs == {"m": 4} and inner.attrs == {"k": 2}


def test_span_exception_safety_and_export_on_crash(tmp_path):
    out = tmp_path / "crash.jsonl"
    with pytest.raises(RuntimeError):
        with tracing(jsonl=out, clock=FakeClock(tick=1.0)) as tr:
            with obs_trace.span("doomed"):
                raise RuntimeError("boom")
    sp = tr.spans[0]
    assert sp.t1 is not None and "RuntimeError: boom" in sp.attrs["error"]
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert any(l["type"] == "span" and l["name"] == "doomed" for l in lines)


def test_leaked_span_closed_by_finish_and_by_child():
    tr = Tracer(clock=FakeClock(tick=1.0))
    leaked = tr.start("leaked")
    child = tr.start("child")
    tr.end(leaked)                               # out-of-order close
    assert child.t1 == leaked.t1
    assert child.attrs["error"] == "span leaked (closed by child)"
    dangling = tr.start("dangling")
    tr.finish()
    assert dangling.t1 is not None


def test_event_lands_on_open_span_or_becomes_instant():
    tr = Tracer(clock=FakeClock(tick=1.0))
    with tr.span("host") as sp:
        tr.event("inside", chunk=3)
    tr.event("orphan")
    assert sp.events[0][0] == "inside" and sp.events[0][2] == {"chunk": 3}
    orphan = tr.spans[-1]
    assert orphan.name == "orphan" and orphan.dur == 0.0


def test_ambient_helpers_are_noops_without_tracer():
    assert obs_trace.current_tracer() is None
    with obs_trace.span("nothing") as sp:
        sp.set(x=1).block_on(jnp.zeros(2))
        sp.event("still nothing")
    obs_trace.event("nope")
    obs_trace.counter("c").add(5)
    obs_trace.gauge("g").set(1.0)
    obs_trace.histogram("h").observe(2.0)
    assert obs_trace.current_tracer() is None    # nothing was installed


def test_tracing_installs_and_restores_ambient_tracer():
    with tracing(clock=FakeClock(tick=1.0)) as tr:
        assert obs_trace.current_tracer() is tr
        assert not obs_trace.deep_tracing()
        with obs_trace.span("s"):
            pass
    assert obs_trace.current_tracer() is None
    assert [s.name for s in tr.spans] == ["s"]
    with tracing(deep=True, clock=FakeClock()) as tr2:
        assert obs_trace.deep_tracing()
    assert tr2.deep


# ---------------------------------------------------------------- metrics

def test_counter_monotonic():
    c = Counter("bytes")
    c.add(3.0)
    c.add()
    assert c.value == 4.0
    with pytest.raises(ValueError, match="monotonic"):
        c.add(-1.0)


def test_gauge_track_and_histogram_summary():
    clk = FakeClock(tick=1.0)
    g = Gauge("depth", clock=clk)
    g.set(2)
    g.set(5, ts=100.0)
    assert g.samples == [(0.0, 2.0), (100.0, 5.0)] and g.value == 5.0
    h = Histogram("lat")
    for v in (1.0, 3.0):
        h.observe(v)
    snap = h.snapshot()
    assert (snap["count"], snap["sum"], snap["min"], snap["max"],
            snap["mean"]) == (2, 4.0, 1.0, 3.0, 2.0)


def test_registry_reuse_and_kind_conflict():
    reg = MetricsRegistry(clock=FakeClock())
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    kinds = {s["type"] for s in reg.snapshot()}
    assert kinds == {"counter"}


# ---------------------------------------------------------------- export

def _tiny_trace():
    """Two nested spans + an instant + one gauge/counter on a unit-tick
    clock: every exported timestamp below is an exact small integer."""
    clk = FakeClock(tick=1.0)
    tr = Tracer(clock=clk)
    with tr.span("outer", m=8):
        tr.counter("chunks").add(2)
        tr.gauge("depth").set(3)
        with tr.span("inner") as sp:
            sp.event("mark", note="hi")
    return tr


def test_jsonl_schema(tmp_path):
    tr = _tiny_trace()
    out = tmp_path / "t.jsonl"
    JsonlExporter(out).export(tr)
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    spans = [l for l in lines if l["type"] == "span"]
    # origin-rebased, index order (opening order), not closing order
    assert [s["name"] for s in spans] == ["outer", "inner"]
    assert spans[0]["ts"] == 0.0 and spans[0]["depth"] == 0
    assert spans[1]["depth"] == 1 and spans[1]["dur"] > 0
    ev = next(l for l in lines if l["type"] == "event")
    assert ev["name"] == "mark" and ev["span"] == "inner"
    assert {l["name"] for l in lines if l["type"] == "counter"} == {"chunks"}
    assert {l["name"] for l in lines if l["type"] == "gauge"} == {"depth"}


def test_chrome_schema_nesting_and_counter_tracks(tmp_path):
    tr = _tiny_trace()
    out = tmp_path / "t.json"
    ChromeTraceExporter(out).export(tr)
    payload = json.loads(out.read_text())
    ev = payload["traceEvents"]
    assert {e["ph"] for e in ev} <= {"M", "X", "i", "C"}
    xs = {e["name"]: e for e in ev if e["ph"] == "X"}
    outer, inner = xs["outer"], xs["inner"]
    # microsecond unit, origin at zero, nesting by interval containment
    assert outer["ts"] == 0.0
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["dur"] >= 1e6                   # >= one 1s tick, in us
    assert outer["args"] == {"m": 8}
    instants = [e for e in ev if e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["mark"]
    tracks = [e for e in ev if e["ph"] == "C"]
    assert [(e["name"], e["args"]["value"]) for e in tracks] == [
        ("depth", 3.0)]
    names = {c["name"] for c in payload["otherData"]["counters"]}
    assert names == {"chunks"}                   # non-gauge snapshots


def test_exporter_registry_roundtrip(tmp_path):
    assert {"chrome", "jsonl"} <= set(exporter_names())
    ex = get_exporter("jsonl", tmp_path / "x.jsonl")
    assert isinstance(ex, JsonlExporter)
    with pytest.raises(ValueError, match="unknown exporter"):
        get_exporter("otlp")
    with pytest.raises(ValueError, match="duplicate"):
        register_exporter("chrome")(object)


def test_span_parent_recorded_and_exported(tmp_path):
    """Each span names the span that caused it: ``parent`` is the
    enclosing span's index (None at the root), in both exporters."""
    tr = _tiny_trace()
    tr.event("orphan")                           # root-level instant span
    outer, inner, orphan = sorted(tr.spans, key=lambda s: s.index)
    assert (outer.parent, inner.parent, orphan.parent) == (None, 0, None)
    JsonlExporter(tmp_path / "t.jsonl").export(tr)
    lines = [json.loads(x) for x in
             (tmp_path / "t.jsonl").read_text().splitlines()]
    assert [(l["name"], l["parent"]) for l in lines
            if l["type"] == "span"] == [("outer", None), ("inner", 0),
                                        ("orphan", None)]
    ChromeTraceExporter(tmp_path / "t.json").export(tr)
    ev = json.loads((tmp_path / "t.json").read_text())["traceEvents"]
    assert [(e["name"], e["index"], e["parent"]) for e in ev
            if e["ph"] == "X"] == [("outer", 0, None), ("inner", 1, 0),
                                   ("orphan", 2, None)]


def _host_events(log_dir):
    """``(name, start_ns, end_ns)`` of every event on the profiler's host
    plane, from the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = log_dir.glob("**/*.xplane.pb")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("traced", [False, True])
def test_spans_on_the_profiler_host_plane(tmp_path, traced):
    """With a tracer installed every span is also a profiler annotation on
    ``/host:CPU``, on the device trace's clock and nested as the spans
    are, including spans left open and closed by a child or by
    ``finish()``; with none installed nothing is annotated."""
    from repro.core import rid
    A = jnp.asarray(np.random.default_rng(5).standard_normal((64, 48)),
                    jnp.float32)
    rid(jax.random.key(2), A, 4)                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        if traced:
            with tracing() as tr:
                rid(jax.random.key(2), A, 4)
                leaked = tr.start("obs.leaked")
                tr.start("obs.leaked_child")
                tr.end(leaked)
                tr.start("obs.dangling")
        else:
            rid(jax.random.key(2), A, 4)
    finally:
        jax.profiler.stop_trace()
    ours = {name: (t0, t1) for name, t0, t1 in _host_events(tmp_path)
            if name.startswith(("rid", "obs."))}
    if not traced:
        assert ours == {}
        return
    assert set(ours) == {"rid", "rid.sketch", "rid.qr_interp", "rid.gather",
                         "obs.leaked", "obs.leaked_child", "obs.dangling"}
    r0, r1 = ours["rid"]
    for child in ("rid.sketch", "rid.qr_interp", "rid.gather"):
        assert r0 <= ours[child][0] <= ours[child][1] <= r1
    assert ours["rid.sketch"][1] <= ours["rid.qr_interp"][0]
    assert ours["obs.leaked"][0] <= ours["obs.leaked_child"][0]


def test_compile_counted_once_under_tracer():
    """A fresh jit compiles once: one ``jax.compile`` span under the span
    that compiled, ``jax.compiles`` 1 and ``jax.compile_s`` its seconds;
    the warm second call counts 0."""
    f = jax.jit(lambda x: x * 3 - 1)
    x = jnp.ones(5)
    with tracing() as tr:
        with obs_trace.span("first") as first:
            f(x)
    (c,) = [s for s in tr.spans if s.name == "jax.compile"]
    assert c.parent == first.index and c.attrs["program"] == "jit(<lambda>)"
    assert tr.metrics.counter("jax.compiles").value == 1
    assert tr.metrics.counter("jax.compile_s").value == c.dur > 0
    assert first.t0 <= c.t0 <= c.t1 <= first.t1
    with tracing() as tr:
        f(x)
    assert tr.metrics.counter("jax.compiles").value == 0
    assert tr.spans == []
    jax.jit(lambda x: x + 7)(x)                  # no tracer: nothing to count


# ------------------------------------- observer effect: engines under trace

def test_rid_streamed_bits_unchanged_by_tracing():
    """The tentpole no-observer-effect contract: the streamed RID returns
    bit-identical factors untraced, traced, and deep-traced — and the
    traced runs carry the per-chunk span census + eq.(3) certificate."""
    from repro.core import rid_streamed
    from repro.stream import ArraySource

    A = np.asarray(np.random.default_rng(0).standard_normal((384, 64)),
                   np.float32)
    src, key, k = ArraySource(A, 128), jax.random.key(4), 8
    base = rid_streamed(key, src, k)
    with tracing(chrome=None) as tr:
        traced = rid_streamed(key, src, k)
    with tracing(deep=True) as tr_deep:
        deep = rid_streamed(key, src, k)
    for dec in (traced, deep):
        np.testing.assert_array_equal(np.asarray(base.J), np.asarray(dec.J))
        np.testing.assert_array_equal(np.asarray(base.B), np.asarray(dec.B))
    for t in (tr, tr_deep):
        names = [s.name for s in t.spans]
        assert names.count("stream.h2d") == 3            # 384 / 128 chunks
        assert names.count("stream.accumulate") == 3
        assert names.count("stream.gather") == 3
        root = next(s for s in t.spans if s.name == "rid_streamed")
        assert any(e[0] == "eq3.certificate" for e in root.events)
        assert t.metrics.counter("stream.chunks").value == 3  # pass-1 chunks


def test_deep_qr_driver_pivot_parity():
    """core/qr.py's promise: the deep (per-panel jit) driver is the SAME
    factorization as the fused in-jit engine — identical pivots, same
    Q/R — it only changes where the jit boundaries sit."""
    from repro.core.qr import pivoted_qr

    Y = jnp.asarray(np.random.default_rng(1).standard_normal((48, 96)),
                    jnp.float32)
    k, panel = 24, 8
    Qn, pn, Rn = pivoted_qr(Y, k, impl="blocked", panel=panel,
                            panel_impl="fused")
    with tracing(deep=True) as tr:
        Qd, pd, Rd = pivoted_qr(Y, k, impl="blocked", panel=panel,
                                panel_impl="fused")
    np.testing.assert_array_equal(np.asarray(pn), np.asarray(pd))
    np.testing.assert_allclose(np.asarray(Qn), np.asarray(Qd),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(Rn), np.asarray(Rd),
                               rtol=1e-5, atol=1e-6)
    panels = [s for s in tr.spans if s.name == "qr.panel"]
    assert len(panels) == k // panel
    assert tr.metrics.counter("qr.panels").value == k // panel


def test_jitted_caller_skips_spans():
    """pivoted_qr called FROM jitted code must take the plain traced
    path: no spans (they would be trace-time artifacts), same result."""
    from repro.core.qr import pivoted_qr

    Y = jnp.asarray(np.random.default_rng(2).standard_normal((32, 40)),
                    jnp.float32)

    @jax.jit
    def inner(Y):
        Q, piv, R = pivoted_qr(Y, 8, impl="blocked", panel=8)
        return Q, piv, R

    with tracing(deep=True) as tr:
        Q, piv, R = inner(Y)
    jax.block_until_ready(Q)
    # no trace-time spans: the one span is the caller's compile
    assert [(s.name, s.attrs["program"]) for s in tr.spans] == [
        ("jax.compile", "jit(inner)")]
    Q0, piv0, R0 = pivoted_qr(Y, 8, impl="blocked", panel=8)
    np.testing.assert_array_equal(np.asarray(piv), np.asarray(piv0))
