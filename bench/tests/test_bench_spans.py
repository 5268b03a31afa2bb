"""Device idle laid against the host's annotations (``metrics/host_spans``)
and the readers of the program's spans and compiles.

Hand-built traces fix the arithmetic; ``data/spans.xplane.pb.gz`` is a
trace recorded on a TPU v5e with a ``repro.obs`` tracer installed: a few
tiny ``rid`` srft decompositions (complex64, k = 16, m = 1024, n = 2048)
in one ``run_window`` under ``xplane.profile``, after three warm-up
calls."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import run
from bench.metrics import host_spans, xplane

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event


def _hand_trace():
    """One decomposition in a 10 s window: an idle gap inside
    ``rid.sketch`` (0.4 s), one while the host waits in
    ``bench.block_until_ready`` (0.5 s), and one that starts in the wait
    (0.2 s) and runs on into the loop's own code (0.6 s)."""
    host = [E("bench.window", 0.0, 10.0), E("bench.decompose", 0.0, 3.0),
            E("rid", 0.5, 2.4), E("rid.sketch", 0.5, 1.0),
            E("rid.qr_interp", 1.5, 0.5), E("rid.gather", 2.0, 0.8),
            E("bench.block_until_ready", 3.0, 6.2)]
    modules = [E("jit_prev(1)", 0.0, 0.6), E("jit_srft_sketch(2)", 1.0, 3.0),
               E("jit__qr_interp(3)", 4.0, 1.0), E("jit__take(4)", 5.5, 3.5),
               E("jit_next(5)", 9.8, 0.2)]
    return xplane.Trace(window=(0.0, 10.0), modules={0: modules},
                        ops={0: []}, host=host)


def test_idle_goes_to_the_annotation_open_at_each_instant():
    idle = host_spans.idle_by_host(_hand_trace())
    assert idle == pytest.approx({"rid.sketch": 0.4,
                                  "bench.block_until_ready": 0.7,
                                  "bench.window": 0.6})
    trace = _hand_trace()
    assert sum(idle.values()) == pytest.approx(
        trace.window_s - trace.busy_s)


def test_segments_cover_the_window_innermost_first():
    segs = host_spans.segments(_hand_trace().host, 0.0, 10.0)
    assert segs[0] == (0.0, 0.5, "bench.decompose")
    assert (0.5, 1.5, "rid.sketch") in segs
    assert (1.5, 2.0, "rid.qr_interp") in segs
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
    assert segs[-1] == (9.2, 10.0, "bench.window")
    assert host_spans.segments([], 0.0, 1.0) == [(0.0, 1.0, None)]


def test_gaps_named_by_host_span_and_neighbouring_programs():
    assert host_spans.name_gaps(_hand_trace()) == [
        ["bench.window [jit__take > jit_next]", pytest.approx(0.8)],
        ["bench.block_until_ready [jit__qr_interp > jit__take]",
         pytest.approx(0.5)],
        ["rid.sketch [jit_prev > jit_srft_sketch]", pytest.approx(0.4)]]


def test_clock_offset_bounds_from_causality():
    """``jit_srft_sketch`` starts 0.5 s after ``rid.sketch`` opens, and
    ``jit__take`` ends 0.2 s before the wait returns: the device's clock
    reads between 0.5 s late and 0.2 s early."""
    assert host_spans.clock_offset(
        _hand_trace(), "rid.sketch", "jit_srft_sketch", "jit__take") == \
        pytest.approx((-0.5, 0.2))


@pytest.mark.parametrize("offset,expected", [
    (0.2, {"rid.sketch": 0.4, "bench.block_until_ready": 0.5,
           "bench.window": 0.8}),
    (0.6, {"rid.sketch": 0.3, "rid.qr_interp": 0.1, "bench.window": 0.4,
           "bench.block_until_ready": 0.5, None: 0.4}),
])
def test_idle_attributed_on_the_corrected_clock(offset, expected):
    """The device's idle moved ``offset`` later against the host's
    annotations; what leaves the window goes to None."""
    assert host_spans.idle_by_host(_hand_trace(), offset) == \
        pytest.approx(expected)


@pytest.mark.parametrize("name,spans,expected", [
    ("rid.dispatch_ms", {"rid": [0.002, 0.004]}, 3.0),
    ("rid.dispatch_ms", {}, None),
    ("window.compiles", {}, 0),
    ("window.compiles", {"rid": [0.1], "jax.compile": [1.5, 0.5]}, 2),
])
def test_new_readers_on_a_hand_built_trace(name, spans, expected):
    window = run.TracedWindow(_hand_trace(), spans, 2, PEAKS)
    assert run.read_metric(name, window) == pytest.approx(expected)


def test_window_compiles_reads_nothing_without_compile_spans(monkeypatch):
    """A program that records no compiles (no ``COMPILE_SPAN``) reads
    nothing, not zero."""
    from repro.obs import trace
    monkeypatch.delattr(trace, "COMPILE_SPAN")
    window = run.TracedWindow(_hand_trace(), {}, 1, PEAKS)
    assert run.read_metric("window.compiles", window) is None


def _unpack(tmp_path_factory, name):
    d = tmp_path_factory.mktemp(name)
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, \
            open(d / f"{name}.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(d)


@pytest.mark.parametrize("name,value", [
    ("sketch.device_ms", 0.275119), ("qr_interp.device_ms", 0.134448),
    ("gather.device_ms", 0.043742), ("device_idle", 99.78886657973149)])
def test_device_readers_unchanged_on_the_small_trace(tmp_path_factory,
                                                     name, value):
    trace = xplane.reduce(_unpack(tmp_path_factory, "small"), [0])
    window = run.TracedWindow(trace, {}, 1, PEAKS)
    assert run.read_metric(name, window) == pytest.approx(value, rel=1e-12)


@pytest.fixture(scope="module")
def spans_trace(tmp_path_factory):
    return host_spans.reduce(_unpack(tmp_path_factory, "spans"), [0])


def test_recorded_trace_holds_the_program_spans(spans_trace):
    names = {e.name for e in spans_trace.host}
    assert {"rid", "rid.sketch", "rid.qr_interp", "rid.gather",
            "bench.decompose", "bench.block_until_ready"} <= names
    assert all(n.startswith("bench.") or host_spans.is_program(n)
               for n in names)


def test_recorded_clock_offset(spans_trace):
    """On the recorded trace the device's timestamps read early by 1.24
    to 1.65 ms: the length of the idle gaps to be attributed."""
    lo, hi = host_spans.clock_offset(spans_trace, "rid.sketch",
                                     "jit_srft_sketch", "jit__take")
    assert lo == pytest.approx(1.236152e-3, abs=1e-9)
    assert hi == pytest.approx(1.652611e-3, abs=1e-9)


def test_recorded_gaps_named_by_program_spans(spans_trace):
    lo, hi = host_spans.clock_offset(spans_trace, "rid.sketch",
                                     "jit_srft_sketch", "jit__take")
    gaps = host_spans.name_gaps(spans_trace, 50, (lo + hi) / 2)
    assert gaps and all(" [" in name for name, _ in gaps)
    assert any(name.startswith("rid.") for name, _ in gaps)
    idle = host_spans.idle_by_host(spans_trace, (lo + hi) / 2)
    assert {"rid.sketch", "rid.qr_interp", "rid.gather"} <= set(idle)
    assert sum(idle.values()) == pytest.approx(
        spans_trace.window_s - spans_trace.busy_s)


@pytest.mark.parametrize("name", ["rid.dispatch_ms", "window.compiles",
                                  "sketch.device_ms", "device_idle"])
def test_readers_on_the_recorded_trace(spans_trace, name):
    """The window's spans, as the tracer would hand them over, rebuilt
    from the program's annotations in the trace."""
    spans = {}
    for e in spans_trace.host:
        if host_spans.is_program(e.name):
            spans.setdefault(e.name, []).append(e.dur)
    decomps = len(spans["rid"])
    value = run.read_metric(name, run.TracedWindow(spans_trace, spans,
                                                   decomps, PEAKS))
    assert value is not None and value >= 0
    if name == "window.compiles":
        assert value == 0
