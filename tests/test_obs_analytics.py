"""Trace analytics, live progress, and telemetry (ISSUE 10).

Every timing-sensitive contract runs on a FakeClock against SYNTHETIC
traces, so psum-overlap fractions, critical paths, and ETAs are
asserted as exact arithmetic, not tolerances.  The last block
re-pins the observer-effect contract for the newly instrumented paths:
progress reporting + tracing never change a decomposition's bits.
"""
import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.obs import (FakeClock, ProgressReporter, Timeline, Tracer,
                       prometheus_text, tracing)
from repro.obs import trace as obs_trace
from repro.obs.export import exporter_names, get_exporter
from repro.obs.metrics import MetricsRegistry
from repro.obs.telemetry import PrometheusExporter, TelemetryServer
from repro.stream import ArraySource
from repro.stream.rid_stream import rid_streamed

KEY = jax.random.key(0)


def _stream_trace(acc_dur: float, *, h2d_dur: float = 1.0, chunks: int = 2,
                  job: str = "job0") -> Tracer:
    """A synthetic pass-1 trace: per chunk, one h2d span of ``h2d_dur``
    and one accumulate span of ``acc_dur``."""
    clk = FakeClock(100.0)
    tr = Tracer(clock=clk)
    with tr.bind(job=job):
        with tr.span("rid_streamed"):
            with tr.span("stream.pass1"):
                for c in range(chunks):
                    with tr.span("stream.h2d", chunk=c):
                        clk.advance(h2d_dur)
                    with tr.span("stream.accumulate", chunk=c, rows=64):
                        clk.advance(acc_dur)
    tr.finish()
    return tr


# ----------------------------------------------------------------- timeline

def test_critical_path_uses_self_time_no_double_count():
    """Nested spans must not double-count: the parent's contribution is
    its SELF time (duration minus direct children), and the ranked self
    totals sum to the root's duration."""
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk)
    with tr.span("root"):
        clk.advance(1.0)                   # root self time
        with tr.span("phase.a"):
            clk.advance(2.0)
            with tr.span("phase.b"):
                clk.advance(5.0)
        with tr.span("phase.a"):
            clk.advance(3.0)
    tr.finish()
    tl = Timeline.from_tracer(tr)
    ranked = dict(tl.critical_path())
    assert ranked == {"phase.b": 5.0, "phase.a": 5.0, "root": 1.0}
    assert sum(ranked.values()) == tl.wall() == 11.0
    st = tl.phases()["phase.a"]
    assert st.count == 2 and st.total == 10.0 and st.self_total == 5.0
    assert st.max_dur == 7.0               # the instance containing b


def test_psum_overlap_fraction_from_schedule_events():
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk)
    with tr.span("qr.panel_parallel"):
        for i, kind in enumerate(("overlapped", "overlapped", "serialized",
                                  "overlapped")):
            tr.event("qr.panel_schedule", panel=i, psum=kind)
        clk.advance(1.0)
    tr.finish()
    tl = Timeline.from_tracer(tr)
    assert tl.psum_overlap() == 0.75
    assert Timeline.from_tracer(_stream_trace(1.0)).psum_overlap() is None


def test_timeline_throughput_and_stragglers():
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk)
    tr.counter("stream.h2d_bytes").add(4000)
    with tr.span("rid_streamed"):
        for c, dur in enumerate((1.0, 1.0, 6.0, 1.0)):
            with tr.span("stream.h2d", chunk=c):
                clk.advance(dur)
            with tr.span("stream.accumulate", chunk=c, rows=25):
                clk.advance(1.0)
    tr.finish()
    tl = Timeline.from_tracer(tr)
    thr = tl.throughput()
    assert thr["seconds"] == 13.0 and thr["chunks"] == 4
    assert thr["rows"] == 100 and thr["bytes"] == 4000
    assert thr["rows_per_s"] == 100 / 13.0
    worst = tl.stragglers()[0]
    assert worst["phase"] == "stream.h2d" and worst["chunk"] == 2
    assert worst["max_s"] == 6.0 and worst["ratio"] == 6.0 / 2.25


def test_timeline_jsonl_roundtrip_matches_live(tmp_path):
    """from_jsonl(file written by the jsonl exporter) and from_tracer
    (the live object) must agree — one analysis code path for post-hoc
    and in-process use."""
    out = tmp_path / "t.jsonl"
    clk = FakeClock(50.0)
    with tracing(jsonl=out, clock=clk) as tr:
        with obs_trace.attributes(job="deadbeef"):
            with obs_trace.span("rid_streamed"):
                with obs_trace.span("stream.h2d", chunk=0):
                    clk.advance(2.0)
                obs_trace.event("eq3.certificate", bound=1.5)
        obs_trace.counter("stream.chunks").add(1)
    live = Timeline.from_tracer(tr)
    disk = Timeline.from_jsonl(out)
    assert [(s.name, s.ts, s.dur, s.depth, s.index, s.attrs)
            for s in live.spans] == \
           [(s.name, s.ts, s.dur, s.depth, s.index, s.attrs)
            for s in disk.spans]
    assert disk.spans[0].attrs["job"] == "deadbeef"
    assert disk.metrics["stream.chunks"]["value"] == 1
    (name, ts, attrs), = [e for s in disk.spans for e in s.events]
    assert name == "eq3.certificate" and attrs == {"bound": 1.5}
    assert live.report() == disk.report()


def test_tracer_bind_merges_and_explicit_wins():
    tr = Tracer(clock=FakeClock(0.0))
    with tr.bind(job="j", extra=1):
        with tr.bind(extra=2):
            with tr.span("a", extra=3):
                pass
            with tr.span("b"):
                pass
        with tr.span("c"):
            pass
    with tr.span("d"):
        pass
    attrs = {s.name: s.attrs for s in tr.spans}
    assert attrs["a"] == {"job": "j", "extra": 3}   # explicit beats bound
    assert attrs["b"] == {"job": "j", "extra": 2}   # inner beats outer
    assert attrs["c"] == {"job": "j", "extra": 1}
    assert attrs["d"] == {}                          # bind scope ended
    # ambient helper is a shared no-op when untraced
    with obs_trace.attributes(job="x") as nul:
        assert nul is obs_trace.NULL_SPAN


# ----------------------------------------------------------------- progress

def test_progress_eta_ewma_deterministic(tmp_path):
    clk = FakeClock(0.0)
    rep = ProgressReporter(tmp_path / "s.json", clock=clk, alpha=0.5)
    rep.update(total=10, phase="pass1")
    assert rep.eta_s() is None                 # no cadence yet
    clk.advance(2.0)
    rep.update(done=1)                         # first gap: ewma = 2.0
    assert rep.eta_s() == 2.0 * 9
    clk.advance(4.0)
    rep.update(done=2)                         # ewma = .5*4 + .5*2 = 3.0
    assert rep._ewma_unit_s == 3.0
    assert rep.eta_s() == 3.0 * 8
    clk.advance(3.0)
    rep.update(done=5)                         # 3 units in 3s: dt = 1.0
    assert rep._ewma_unit_s == 0.5 * 1.0 + 0.5 * 3.0
    rep.update(done=10)
    assert rep.eta_s() == 0.0                  # complete
    st = json.loads((tmp_path / "s.json").read_text())
    assert st["done"] == 10 and st["fraction"] == 1.0
    assert st["elapsed_s"] == 9.0


def test_progress_status_file_atomic_and_never_torn(tmp_path):
    """The status file must parse after EVERY publish and no tmp file
    may linger — the checkpoint/store.py atomic-rename discipline."""
    path = tmp_path / "status.json"
    clk = FakeClock(0.0)
    rep = ProgressReporter(path, clock=clk, job="j")
    rep.update(total=50)
    for i in range(1, 51):
        clk.advance(0.1)
        rep.update(done=i, extra={"blob": "x" * 4096})
        st = json.loads(path.read_text())      # parses at every step
        assert st["done"] == i and st["job"] == "j"
    assert [p.name for p in tmp_path.iterdir()] == ["status.json"]


def test_progress_publish_rate_limit_and_force(tmp_path):
    clk = FakeClock(0.0)
    seen = []
    rep = ProgressReporter(clock=clk, callbacks=[seen.append],
                           min_publish_s=10.0)
    rep.update(total=5)                        # first publish always lands
    clk.advance(1.0)
    rep.update(done=1)                         # rate-limited: suppressed
    assert [s.get("done") for s in seen] == [0]
    rep.update(done=2, force=True)             # force bypasses
    clk.advance(11.0)
    rep.update(done=3)                         # window elapsed
    assert [s["done"] for s in seen] == [0, 2, 3]
    assert rep.done == 3                       # suppressed updates still count


def test_progress_checkpoint_age_retries_and_terminal(tmp_path):
    clk = FakeClock(0.0)
    rep = ProgressReporter(tmp_path / "s.json", clock=clk)
    assert rep.status()["checkpoint_age_s"] is None
    rep.checkpoint_saved(3)
    clk.advance(7.0)
    st = rep.status()
    assert st["checkpoint_age_s"] == 7.0 and st["checkpoint_step"] == 3
    rep.on_retry(1, ValueError("transient"))
    rep.on_retry(2, ValueError("transient"))
    rep.on_failure()
    rep.finish("failed")
    st = json.loads((tmp_path / "s.json").read_text())
    assert st["retries"] == 2 and st["failures"] == 1
    assert st["state"] == "failed" and st["checkpoints"] == 1


def test_progress_rejects_bad_alpha():
    with pytest.raises(ValueError, match="alpha"):
        ProgressReporter(alpha=0.0)


# ---------------------------------------------------------------- telemetry

def test_prometheus_text_exposition_roundtrip():
    clk = FakeClock(0.0)
    reg = MetricsRegistry(clock=clk)
    reg.counter("stream.chunks").add(7)
    reg.gauge("device.live_bytes").set(12345.0)
    h = reg.histogram("runtime.step_seconds")
    for v in (0.1, 0.3):
        h.observe(v)
    text = prometheus_text(reg)
    lines = text.strip().splitlines()
    assert "repro_stream_chunks_total 7.0" in lines
    assert "# TYPE repro_stream_chunks_total counter" in lines
    assert "repro_device_live_bytes 12345.0" in lines
    assert "repro_runtime_step_seconds_count 2.0" in lines
    assert f"repro_runtime_step_seconds_sum {0.1 + 0.3!r}" in lines
    assert "repro_runtime_step_seconds_min 0.1" in lines
    # every sample line parses as "name value" with a sanitized name
    for line in lines:
        if line.startswith("#"):
            continue
        name, value = line.split(" ")
        assert name.startswith("repro_") and "." not in name
        float(value)
    with pytest.raises(ValueError, match="summary"):
        prometheus_text([{"type": "summary", "name": "x"}])


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, r.read().decode()


def test_telemetry_server_routes_and_live_scrape():
    clk = FakeClock(0.0)
    reg = MetricsRegistry(clock=clk)
    reg.counter("stream.chunks").add(3)
    rep = ProgressReporter(clock=clk, job="abc")
    rep.update(done=2, total=8, phase="pass1")
    with TelemetryServer(registry=reg, progress=rep, clock=clk) as srv:
        assert srv.port != 0                   # ephemeral port read back
        code, body = _get(srv.url + "/metrics")
        assert code == 200
        assert "repro_stream_chunks_total 3.0" in body
        assert "repro_progress_done 2.0" in body
        assert "repro_uptime_seconds" in body
        reg.counter("stream.chunks").add(1)    # live registry: scrapes see
        _, body = _get(srv.url + "/metrics")   # current values
        assert "repro_stream_chunks_total 4.0" in body
        code, body = _get(srv.url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        code, body = _get(srv.url + "/progress")
        st = json.loads(body)
        assert code == 200 and st["done"] == 2 and st["job"] == "abc"
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/nope")
        assert e.value.code == 404
        assert "/metrics" in e.value.read().decode()   # routes are named
    # stopped: the port no longer accepts scrapes
    with pytest.raises(OSError):
        urllib.request.urlopen(srv.url + "/healthz", timeout=0.5)


def test_telemetry_server_concurrent_scrapes():
    reg = MetricsRegistry(clock=FakeClock(0.0))
    reg.counter("stream.chunks").add(1)
    errors = []
    with TelemetryServer(registry=reg) as srv:
        def scrape():
            try:
                code, body = _get(srv.url + "/metrics")
                assert code == 200 and "repro_stream_chunks_total" in body
            except Exception as e:             # surfaced after join
                errors.append(e)
        threads = [threading.Thread(target=scrape) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert errors == []


def test_prometheus_exporter_registered_and_writes(tmp_path):
    assert "prometheus" in exporter_names()
    out = tmp_path / "metrics.prom"
    ex = get_exporter("prometheus", out)
    assert isinstance(ex, PrometheusExporter)
    clk = FakeClock(0.0)
    with tracing(Tracer(clock=clk, exporters=[ex])) as tr:
        tr.counter("stream.chunks").add(5)
    assert "repro_stream_chunks_total 5.0" in out.read_text()


# ------------------------------------------- engine wiring + observer effect

def _source(m=512, n=64, chunk_rows=128):
    A = jax.random.normal(jax.random.key(1), (m, n), jnp_dtype())
    return ArraySource(A, chunk_rows)


def jnp_dtype():
    import jax.numpy as jnp
    return jnp.float32


def test_rid_streamed_reports_progress_per_chunk(tmp_path):
    path = tmp_path / "status.json"
    snaps = []
    rep = ProgressReporter(path, callbacks=[snaps.append])
    src = _source()
    out = rid_streamed(KEY, src, 8, progress=rep)
    assert out.B.shape == (512, 8)
    C = 4
    final = json.loads(path.read_text())
    assert final["state"] == "done"
    assert final["done"] == final["total"] == 2 * C
    assert final["job"] and len(final["job"]) == 12
    phases = [s["phase"] for s in snaps]
    for ph in ("pass1", "qr_interp", "pass2"):
        assert ph in phases
    # one update per chunk in each pass
    assert [s["done"] for s in snaps if s["phase"] == "pass1"][-C:] == \
        [1, 2, 3, 4]
    assert [s["done"] for s in snaps if s["phase"] == "pass2"
            and s["state"] == "running"][-C:] == [5, 6, 7, 8]


def test_rid_streamed_progress_counts_retries(tmp_path):
    from repro.runtime import FaultPlan, FlakySource, RetryPolicy
    clk = FakeClock(0.0)
    rep = ProgressReporter(clock=clk)
    src = FlakySource(_source(), FaultPlan(transient={1: 2}), clock=clk)
    policy = RetryPolicy(max_attempts=4, clock=clk, jitter=0.0)
    out = rid_streamed(KEY, src, 8, retry=policy, progress=rep)
    assert out.B.shape == (512, 8)
    # chunk 1's two leading reads fail deterministically -> two retries,
    # each surfaced to the reporter through RetryPolicy(on_retry=...)
    assert rep.retries == 2
    assert rep.state == "done" and rep.failures == 0


def test_rid_streamed_spans_carry_job_and_chunk_attrs():
    src = _source()
    with tracing() as tr:
        rid_streamed(KEY, src, 8)
    per_chunk = [s for s in tr.spans
                 if s.name in ("stream.h2d", "stream.accumulate",
                               "stream.gather")]
    assert per_chunk
    jobs = {s.attrs.get("job") for s in tr.spans}
    assert len(jobs) == 1 and None not in jobs    # every span, one job
    for s in per_chunk:
        assert "chunk" in s.attrs
    gathers = [s for s in per_chunk if s.name == "stream.gather"]
    assert all(s.attrs["sync"] is False for s in gathers)
    with tracing(deep=True) as tr_deep:
        rid_streamed(KEY, src, 8)
    deep_gathers = [s for s in tr_deep.spans if s.name == "stream.gather"]
    assert deep_gathers and all(s.attrs["sync"] for s in deep_gathers)


def test_rid_streamed_bits_unchanged_by_progress_and_telemetry(tmp_path):
    """Observer-effect pin for the newly instrumented path: progress
    reporting + tracing + a live telemetry scrape change NOTHING about
    the result bits."""
    src = _source()
    plain = rid_streamed(KEY, src, 8)
    rep = ProgressReporter(tmp_path / "s.json")
    with tracing(jsonl=tmp_path / "t.jsonl") as tr:
        with TelemetryServer(registry=tr.metrics, progress=rep) as srv:
            watched = rid_streamed(KEY, src, 8, progress=rep)
            code, _ = _get(srv.url + "/metrics")
            assert code == 200
    for f in ("B", "P", "J", "Q", "R"):
        assert np.array_equal(np.asarray(getattr(plain, f)),
                              np.asarray(getattr(watched, f))), f


@pytest.mark.slow
def test_serve_engine_reports_progress():
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serving import GenerationRequest, ServeEngine
    cfg = get_smoke_config("granite_3_2b").replace(dtype="float32")
    params = init_params(KEY, cfg)
    snaps = []
    rep = ProgressReporter(callbacks=[snaps.append])
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64, progress=rep)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(GenerationRequest(
            request_id=i, prompt=rng.integers(0, cfg.vocab_size, 4
                                              ).astype(np.int32),
            max_new_tokens=4))
    done = eng.run()
    assert len(done) == 3
    assert snaps[-1]["done"] == snaps[-1]["total"] == 3
    assert snaps[-1]["phase"] == "serve"
    assert snaps[-1]["extra"]["queue"] == 0
    assert any(s["extra"].get("active", 0) > 0 for s in snaps)
