"""The trace reduction and the per-layer readers, on a small trace
recorded on a TPU v5e (``data/small.xplane.pb.gz``): one tiny
decomposition (k = 16, m = 1024, n = 2048) each through ``rid`` with the
srft sketch (complex64), ``rid`` with the gaussian sketch (float32) and
``rid_streamed`` (float32, 256-row chunks), in one ``run_window`` under
``xplane.profile``."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import run
from bench.metrics import kernel_work, xplane

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(DATA / "small.xplane.pb.gz") as src, \
            open(d / "small.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.reduce(str(d), [0])


def test_window_and_busy(trace):
    assert trace.chips == 1
    assert 0 < trace.busy_s <= trace.window_s
    busy = trace.busy_intervals(0)
    assert all(s < e for s, e in busy)
    assert all(a[1] < b[0] for a, b in zip(busy, busy[1:]))


def test_programs_by_jit_name(trace):
    for name in ("srft_sketch", "sketch_accum", "_omega_blocks",
                 "_qr_interp", "_take"):
        assert trace.module_s([name]) > 0, name
    assert trace.module_s(["no_such_program"]) == 0
    every = {e.name.split("(")[0] for e in trace.modules[0]}
    assert trace.module_s([n[len("jit_"):] for n in every]) == \
        pytest.approx(sum(e.dur for e in trace.modules[0]))


def test_kernel_calls_carry_operand_shapes(trace):
    accum = trace.kernel_calls("sketch_accum")
    assert accum and all(d > 0 for _, d in accum)
    for (x, a, acc), _ in accum:
        assert x[1] == a[0] and acc == (x[0], a[1])
    panels = trace.kernel_calls("panel_step")
    assert panels
    for (c, z), _ in panels:
        assert c[0] == z[0]


def test_kernel_call_parses_an_op():
    op = ('%panel_step.3 = (f32[32,16]{1,0:T(8,128)}, f32[32,2048]{1,0}) '
          'custom-call(f32[32,16]{1,0:T(8,128)S(1)} %c, f32[32,2048]{1,0} '
          '%z), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={f32[32,16]{1,0}}')
    assert xplane.kernel_call(op) == ("panel_step", [(32, 16), (32, 2048)])
    assert xplane.kernel_call('%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)') \
        is None


def test_breakdown(trace):
    b = trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") for name, _ in b["idle_gaps"])
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)


def test_merge():
    assert xplane._merge([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]


def test_kernel_work():
    assert kernel_work.sketch_accum(2, 3, 4) == (48.0, 4 * (6 + 12 + 16))
    ops, nbytes = kernel_work.panel_step(8, 2, 16)
    assert ops == 4 * 8 * 2 * 16 + 2 * 8 * 16 + 6 * 8 * 2 * 2
    assert nbytes == 4 * (2 * 8 * 2 + 2 * 8 * 16 + 16)
    assert kernel_work.roofline_s(197e12, 0, PEAKS) == pytest.approx(1.0)
    assert kernel_work.roofline_s(0, 819e9, PEAKS) == pytest.approx(1.0)


@pytest.mark.parametrize("name", [
    "sketch.device_ms", "qr_interp.device_ms", "gather.device_ms",
    "sketch_accum_roofline", "panel_step_roofline", "device_idle"])
def test_device_readers(trace, name):
    window = run.TracedWindow(trace, {}, 1, PEAKS)
    value = run.read_metric(name, window)
    assert value is not None and value > 0
    if name.endswith("_roofline") or name == "device_idle":
        assert value <= 100


@pytest.mark.parametrize("name", ["stream.pass1_ms", "stream.pass2_ms"])
def test_span_readers(trace, name):
    window = run.TracedWindow(trace, {"stream.pass1": [0.25, 0.5],
                                      "stream.pass2": [0.1]}, 2, PEAKS)
    assert run.read_metric(name, window) == pytest.approx(
        {"stream.pass1_ms": 375.0, "stream.pass2_ms": 50.0}[name])
    empty = run.TracedWindow(trace, {}, 2, PEAKS)
    assert run.read_metric(name, empty) is None
