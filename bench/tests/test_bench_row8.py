"""Grid row 8 streamed over four chips (``r8-f32-stream-4chip``): the
cell's spec, its configuration, a run through the harness at a shrunken
size (the same dtype, traffic and ``l = 2k``) on four virtual CPU
devices, with and without the profiler, and the cell's readers on
traces built by hand at the cell's shard shapes and on a trace recorded
on four chips."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import run
from bench.metrics import host_spans, kernel_work, panel_work, xplane
from bench.tests.test_bench_run import PEAKS
from bench.tests.test_bench_spans import _unpack
from repro.configs.paper_rid import PAPER_GRID
from repro.kernels.sketch_accum import ACCUM_BLOCK

ROOT = Path(run.__file__).resolve().parents[1]
CELL = "r8-f32-stream-4chip"
PER_LAYER = {"stream.pass1_ms", "stream.pass2_ms", "stream.qr_interp_ms",
             "stream.h2d_ms", "collective.device_ms",
             "sketch_accum_roofline", "panel_coeff_roofline",
             "panel_apply_roofline"}


def test_cell_lists_its_metrics():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 4
    assert {m["name"] for m in cell["end_to_end"]} == {"decomp_s", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == PER_LAYER
    assert all(m["moves"] == "decomp_s" for m in cell["per_layer"])


def test_config_is_grid_row_8():
    cell = run.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    row = PAPER_GRID[config["grid_row"] - 1]
    assert (config["k"], config["m"], config["n"]) == (row.k, row.m, row.n)
    assert config["l"] == 2 * config["k"] == row.l
    assert config["dtype"] == "float32"
    assert traffic == {"entry": "rid_streamed", "chunk_rows": 256}
    assert traffic["chunk_rows"] % ACCUM_BLOCK == 0
    assert config["n"] % cell["chips"] == 0
    assert set(config["limits"]) == {"pivots_invalid", "identity_err",
                                     "gather_err", "rel_err"}


SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    from bench import run
    from bench.tests.test_bench_run import PEAKS
    root = __import__("pathlib").Path(sys.argv[1])
    for d in ("bench/configs", "bench/traffic"):
        root.joinpath(d).mkdir(parents=True)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in spec["configs"]}["paper-row8-f32"]
    config = json.loads((run.ROOT / conf["file"]).read_text())
    config.update(k=40, l=80, m=1024, n=4 * 512)
    (root / conf["file"]).write_text(json.dumps(config))
    for name in ("stream-4chip",):
        (root / f"bench/traffic/{name}.json").write_text(
            (run.ROOT / f"bench/traffic/{name}.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    run.enable_cache = lambda: None
    devices = lambda chips: (jax.devices()[:chips], PEAKS)
    out = {}
    for trace in (False, True):
        r = run.run("r8-f32-stream-4chip", 2 ** 40 + 3, 0.2, trace,
                    devices_fn=devices, root=root)
        out[str(trace)] = [r["correct"], r["device"]["count"],
                           sorted(r["metrics"]), r["checks"]]
    print(json.dumps(out))
""")


def test_shrunken_cell_runs_on_four_devices(tmp_path):
    """The cell's own spec, traffic and dtype, its matrix cut to k = 40
    (l = 80, m = 1024, n = 2048: four chunks, two panels): the reference
    judges it correct.  Traced on the CPU, the span readers read and the
    device readers, with no TPU in the trace, read nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT,
                           str(tmp_path / "checkout")],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for trace, (correct, count, metrics, checks) in out.items():
        assert correct and count == 4, (trace, checks)
    assert out["False"][2] == ["decomp_s", "setup_s"]
    assert out["True"][2] == ["stream.h2d_ms", "stream.pass1_ms",
                              "stream.pass2_ms", "stream.qr_interp_ms"]


# ------------------------------------------------- readers on a traced run

def _op(kernel, result, operands):
    """A Mosaic kernel's op as the chip's trace prints it."""
    args = ", ".join(f"f32[{','.join(map(str, s))}]{{1,0}} %a{i}"
                     for i, s in enumerate(operands))
    return (f"%{kernel}.1 = {result} custom-call({args}), "
            f'custom_call_target="tpu_custom_call"')


def _shard_trace():
    """One chip's calls at row 8's shard shapes (l = 2000, n/4 = 2^16,
    32-wide panels, 256-row chunks), each at 1.25x its least time, and
    the span sums of two decompositions."""
    l, n, b, rows = 2000, 2 ** 16, 32, 256
    calls = [("sketch_accum", kernel_work.sketch_accum(l, rows, n),
              [(l, rows), (rows, n), (l, n)]),
             ("panel_coeff", panel_work.panel_coeff(l, b, n),
              [(l, b), (l, n), (1, n)]),
             ("panel_apply", panel_work.panel_apply(l, b, n),
              [(l, b), (b, n), (l, n)])]
    ops = [xplane.Event(_op(name, f"f32[{l},{n}]{{1,0}}", shapes), 0.0,
                        1.25 * kernel_work.roofline_s(*work, PEAKS))
           for name, work, shapes in calls]
    trace = xplane.Trace(window=(0.0, 1.0), modules={0: []}, ops={0: ops})
    spans = {"stream.qr_interp": [0.25, 0.35], "stream.h2d": [0.01] * 8}
    return run.TracedWindow(trace, spans, 2, PEAKS)


@pytest.mark.parametrize("name", ["sketch_accum_roofline",
                                  "panel_coeff_roofline",
                                  "panel_apply_roofline"])
def test_roofline_shares_at_shard_shapes(name):
    assert run.read_metric(name, _shard_trace()) == pytest.approx(80.0)


@pytest.mark.parametrize("name,value", [("stream.qr_interp_ms", 300.0),
                                        ("stream.h2d_ms", 40.0)])
def test_span_readers_per_decomposition(name, value):
    window = _shard_trace()
    assert run.read_metric(name, window) == pytest.approx(value)
    window.spans = {}
    assert run.read_metric(name, window) is None


def test_no_collectives_on_one_chip(tmp_path_factory):
    small = xplane.reduce(_unpack(tmp_path_factory, "small"), [0])
    assert run.read_metric("collective.device_ms",
                           run.TracedWindow(small, {}, 1, PEAKS)) is None


def test_panel_work_by_hand():
    l, b, n = 8, 2, 16
    ops, nbytes = panel_work.panel_coeff(l, b, n)
    assert ops == 6 * 8 * 2 * 2 + 2 * 8 * 2 * 16 + 2 * 2 * 16 + 16 == 784
    assert nbytes == 4 * (2 * 8 * 2 + 8 * 16 + 2 * 16 + 2 * 16) == 896
    assert panel_work.panel_apply(l, b, n) == (2 * 8 * 2 * 16, 4 * (
        8 * 2 + 2 * 16 + 2 * 8 * 16)) == (512, 1216)
    assert panel_work.panel_apply(l, b, n, norms=True) == (
        512 + 2 * 8 * 16, 1216 + 4 * 16)


def test_panel_apply_roofline_counts_the_norms_of_a_recompute_panel():
    """A call whose result is a tuple also wrote the column norms."""
    def op(result):
        return (f"%panel_apply.1 = {result} custom-call(f32[8,2]{{1,0}} %q, "
                f"f32[2,16]{{1,0}} %w, f32[8,16]{{1,0}} %z), "
                f'custom_call_target="tpu_custom_call"')
    ops = [xplane.Event(op("f32[8,16]{1,0}"), 0.0, 1.0),
           xplane.Event(op("(f32[8,16]{1,0}, f32[1,16]{1,0})"), 2.0, 1.0)]
    trace = xplane.Trace(window=(0.0, 3.0), modules={0: []}, ops={0: ops})
    least = sum(kernel_work.roofline_s(
        *panel_work.panel_apply(8, 2, 16, norms), PEAKS)
        for norms in (False, True))
    assert run.read_metric("panel_apply_roofline", run.TracedWindow(
        trace, {}, 1, PEAKS)) == pytest.approx(100 * least / 2.0)


def test_collectives_matched_on_the_opcode():
    """``psum`` prints as an all-reduce named ``%psum``; async halves count
    too, and an op that only mentions a collective in its metadata does
    not."""
    ops = [xplane.Event("%psum.3 = f32[8]{0} all-reduce(f32[8]{0} %x), "
                        "channel_id=1, replica_groups={{0,1,2,3}}", 0.0, 0.5),
           xplane.Event("%all-gather-start.1 = (f32[2]{0}, f32[8]{0}) "
                        "all-gather-start(f32[2]{0} %y)", 1.0, 0.25),
           xplane.Event("%collective-permute-done = f32[2]{0} "
                        "collective-permute-done(f32[2]{0} %z)", 2.0, 0.25),
           xplane.Event('%fusion.2 = f32[8]{0} fusion(f32[8]{0} %psum.3), '
                        'metadata={op_name="jit(f)/psum"}', 3.0, 4.0)]
    trace = xplane.Trace(window=(0.0, 8.0), modules={0: [], 1: []},
                         ops={0: ops, 1: ops[:1]})
    value = run.read_metric("collective.device_ms",
                            run.TracedWindow(trace, {}, 2, PEAKS))
    assert value == pytest.approx(1e3 * (1.0 + 0.5) / 2 / 2)


# ---------------------------------------- readers on a four-chip recording

@pytest.fixture(scope="module")
def four_chip(tmp_path_factory):
    """``data/four_chip_stream.xplane.pb.gz``, recorded on a TPU v5e host
    with four chips under a ``repro.obs`` tracer: ``rid_streamed(mesh=4
    chips)`` at k = 64, l = 128, m = 512, n = 8192 (two 256-row chunks,
    two panels), after three warm-up calls, two decompositions in one
    ``bench.window`` under ``xplane.profile``.  Returns the reduced trace
    (program spans kept) and the spans as the tracer hands them over."""
    trace = host_spans.reduce(_unpack(tmp_path_factory, "four_chip_stream"),
                              range(4))
    spans = {}
    for e in trace.host:
        if host_spans.is_program(e.name):
            spans.setdefault(e.name, []).append(e.dur)
    return trace, spans


def test_four_chip_recording(four_chip):
    trace, spans = four_chip
    assert trace.chips == 4 and len(spans["rid_streamed"]) == 2
    assert {"stream.pass1", "stream.h2d", "stream.accumulate",
            "stream.qr_interp", "stream.pass2", "stream.gather"} <= set(spans)
    assert all(trace.kernel_calls(k) for k in ("sketch_accum", "panel_coeff",
                                               "panel_apply"))


def test_collectives_on_four_chips(four_chip):
    trace, spans = four_chip
    value = run.read_metric("collective.device_ms",
                            run.TracedWindow(trace, spans, 2, PEAKS))
    assert value is not None and value > 0


@pytest.mark.parametrize("name", ["sketch_accum_roofline",
                                  "panel_coeff_roofline",
                                  "panel_apply_roofline"])
def test_roofline_shares_on_four_chips(four_chip, name):
    trace, spans = four_chip
    value = run.read_metric(name, run.TracedWindow(trace, spans, 2, PEAKS))
    assert value is not None and 0 < value <= 100


@pytest.mark.parametrize("name,span", [("stream.qr_interp_ms",
                                        "stream.qr_interp"),
                                       ("stream.h2d_ms", "stream.h2d")])
def test_span_readers_on_four_chips(four_chip, name, span):
    trace, spans = four_chip
    assert run.read_metric(name, run.TracedWindow(trace, spans, 2, PEAKS)) \
        == pytest.approx(1e3 * sum(spans[span]) / 2)
