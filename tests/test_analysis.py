"""repro.analysis — every rule must fire on its known-bad fixture and
stay silent on production code (single-device in-process; the 8-device
behaviour of the same contracts is covered by test_qr_dist's ported
overlap test and the CI analyze job)."""
import dataclasses
import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.fixtures import BAD_LINT_SRC, BADKERNEL_BASE, FIXTURES
from repro.analysis.jaxpr import analyze_entry, dependency_cones, trace_entry
from repro.analysis.kernels import (check_all_kernels, check_package,
                                    kernel_packages)
from repro.analysis.lint import lint_file, lint_tree
from repro.analysis.registry import (EntryPoint, load_entry_points, register)
from repro.analysis.report import (Finding, Report, diff_against_baseline,
                                   load_baseline)
from repro.analysis.runner import run_all, run_controls


def rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------ jaxpr rules on fixtures

def test_serialized_fixture_trips_overlap_rule():
    fs = analyze_entry(FIXTURES["fixture.serialized-psum"])
    assert rules(fs) == ["jaxpr.collective-overlap"], fs
    # one finding per panel whose psum waits on its own deflation
    assert {f.key for f in fs} == {"panel-0", "panel-1", "panel-2"}


def test_overlapped_fixture_is_clean():
    assert analyze_entry(FIXTURES["fixture.overlapped-psum"]) == []


def test_gather_blowup_fixture_trips_replication_rule():
    fs = analyze_entry(FIXTURES["fixture.gather-blowup"])
    assert rules(fs) == ["jaxpr.replicated-collective"], fs
    assert "all_gather" in fs[0].key


def test_complex_truncation_fixture_trips_dtype_rule():
    fs = analyze_entry(FIXTURES["fixture.complex-truncation"])
    assert rules(fs) == ["jaxpr.dtype-promotion"], fs
    assert "complex-truncation" in fs[0].key


def test_host_transfer_fixture_trips_host_rule():
    fs = analyze_entry(FIXTURES["fixture.host-transfer"])
    assert rules(fs) == ["jaxpr.host-transfer"], fs
    assert {f.key for f in fs} == {"device_put", "pure_callback"}


def test_in_jit_timer_fixture_trips_host_rule():
    """The obs-layer positive control: a clock read smuggled into traced
    code via pure_callback (reading the SANCTIONED repro.obs clock, so
    only its placement is wrong) must trip the host-transfer rule."""
    fs = analyze_entry(FIXTURES["fixture.in-jit-timer"])
    assert rules(fs) == ["jaxpr.host-transfer"], fs
    assert any("pure_callback" in f.key for f in fs)


def test_instrumented_entries_free_of_host_transfers():
    """The obs instrumentation contract (repro.obs.trace docstring):
    spans live in HOST code, so the traced programs of the instrumented
    engines carry ZERO host transfers.  The in-jit-timer fixture above
    is the positive control proving the rule would catch a violation."""
    for ep in load_entry_points():
        if ep.name.startswith("fixture."):
            continue
        bad = [f for f in analyze_entry(ep)
               if f.rule == "jaxpr.host-transfer"]
        assert bad == [], (ep.name, bad)


def test_f64_leak_fixture_trips_dtype_rule(subproc):
    # f64 avals only exist under x64 — the env the CI analyze job uses.
    r = subproc("""
from repro.analysis.fixtures import FIXTURES
from repro.analysis.jaxpr import analyze_entry
fs = analyze_entry(FIXTURES["fixture.f64-leak"])
assert fs and all(f.rule == "jaxpr.dtype-promotion" for f in fs), fs
assert any("float64" in f.key for f in fs), [f.key for f in fs]
print("OK")
""", n_devices=1, x64=True)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_unregistered_overlap_entry_reports_control_failure():
    # An OverlapSpec whose structures don't exist must FAIL, not pass
    # vacuously.
    ep = dataclasses.replace(
        FIXTURES["fixture.gather-blowup"], max_collective_elems=None,
        overlap=FIXTURES["fixture.serialized-psum"].overlap)
    fs = analyze_entry(ep)
    assert rules(fs) == ["jaxpr.control-failed"], fs


def test_dependency_cones_match_bruteforce():
    def fn(a, b):
        c = a + b          # 0
        d = c * a          # 1
        e = b - 1.0        # 2 (independent of c, d)
        return d + e       # 3
    closed = jax.make_jaxpr(fn)(jnp.ones(3), jnp.ones(3))
    cones = dependency_cones(list(closed.jaxpr.eqns))
    assert cones[1] == {0} and cones[2] == set()
    assert cones[3] == {0, 1, 2}


# ---------------------------------------------- production entries: clean

def test_all_registered_entries_are_clean():
    findings = []
    for ep in load_entry_points():
        findings.extend(analyze_entry(ep))
    assert findings == [], [(f.rule, f.subject, f.key) for f in findings]


def test_registry_names_and_duplicate_rejection():
    names = [e.name for e in load_entry_points()]
    assert names == sorted(names)
    for expect in ("rid", "pivoted_qr.blocked", "rid_streamed.step",
                   "panel_parallel_qr_local.fused",
                   "panel_parallel_qr_local.gram",
                   "rid_distributed.panel_parallel",
                   "rid_distributed.blocked"):
        assert expect in names, names
    with pytest.raises(ValueError, match="duplicate analysis entry"):
        register("rid", lambda: None)


def test_traced_entry_exposes_avals():
    te = trace_entry(load_entry_points()[len(load_entry_points()) - 1])
    assert te.in_avals and te.name


# --------------------------------------------------- kernel contract pass

def test_kernel_packages_discovered():
    assert kernel_packages() == ["cgs", "flash", "panel_gram", "panel_step",
                                 "sketch_accum", "sketch_matmul", "srht",
                                 "tsolve"]


def test_all_kernel_contracts_pass():
    findings, pkgs = check_all_kernels()
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], [(f.rule, f.subject, f.key, f.message)
                          for f in errors]
    assert len(pkgs) == 8
    # the measured-residency info finding is present for sketch_accum
    assert any(f.rule == "kernels.residency" and f.subject == "sketch_accum"
               for f in findings)


def test_badkernel_fixture_trips_vmem_rule():
    fs = check_package("badkernel", base=BADKERNEL_BASE)
    assert "kernels.vmem-overflow" in rules(fs), fs
    # and ONLY the planted failure — the package is otherwise well-formed
    assert rules(fs) == ["kernels.vmem-overflow"], fs


def test_constant_drift_detected(monkeypatch):
    K = importlib.import_module("repro.kernels.sketch_accum.kernel")
    monkeypatch.setattr(K, "ACCUM_BLOCK", 64)
    fs = check_package("sketch_accum")
    assert any(f.rule == "kernels.constant-drift" and f.key == "ACCUM_BLOCK"
               for f in fs), fs


def test_missing_export_and_validation_regression_detected(monkeypatch):
    C = importlib.import_module(f"{BADKERNEL_BASE}.badkernel.contract")
    broken = dataclasses.replace(
        C.CONTRACT, ops=C.CONTRACT.ops + ("nonexistent",),
        bad_call=lambda: None)          # "validates" by not raising
    monkeypatch.setattr(C, "CONTRACT", broken)
    fs = check_package("badkernel", base=BADKERNEL_BASE)
    got = rules(fs)
    assert "kernels.missing-export" in got, fs
    assert "kernels.validation-missing" in got, fs


def test_signature_mismatch_detected(monkeypatch):
    R = importlib.import_module(f"{BADKERNEL_BASE}.badkernel.ref")
    monkeypatch.setattr(R, "big_copy_ref", lambda y: y)
    fs = check_package("badkernel", base=BADKERNEL_BASE)
    assert any(f.rule == "kernels.signature-mismatch" for f in fs), fs


def test_bad_call_raising_wrong_type_detected(monkeypatch):
    C = importlib.import_module(f"{BADKERNEL_BASE}.badkernel.contract")

    def _boom():
        raise TypeError("wrong exception class")
    monkeypatch.setattr(C, "CONTRACT",
                        dataclasses.replace(C.CONTRACT, bad_call=_boom))
    fs = check_package("badkernel", base=BADKERNEL_BASE)
    assert any(f.rule == "kernels.validation-missing" and
               "TypeError" in f.message for f in fs), fs


# --------------------------------------------------------------- lint pass

def test_lint_fixture_trips_every_rule(tmp_path):
    p = tmp_path / "core" / "bad.py"
    p.parent.mkdir()
    p.write_text(BAD_LINT_SRC)
    got = rules(lint_file(p, pathlib.Path("core/bad.py")))
    assert got == ["lint.duplicate-validation", "lint.global-clock-prng",
                   "lint.jax-config-mutation", "lint.string-switch",
                   "lint.valueerror-no-value"], got


def test_lint_rules_scoped_to_library_dirs(tmp_path):
    p = tmp_path / "launch" / "bad.py"
    p.parent.mkdir()
    p.write_text(BAD_LINT_SRC)
    got = rules(lint_file(p, pathlib.Path("launch/bad.py")))
    # behavioral rules don't apply to launch/; message rules still do
    assert got == ["lint.duplicate-validation", "lint.valueerror-no-value"]


def test_lint_clock_rule_allowlists_obs_clock_home(tmp_path):
    """obs/clock.py is the ONE file allowed to import time and call
    time.* clocks; identical source anywhere else in a library dir trips
    lint.global-clock-prng (both the call and the import check)."""
    src = ("import time\n\n"
           "def now():\n"
           "    return time.perf_counter()\n")
    home = tmp_path / "obs" / "clock.py"
    home.parent.mkdir()
    home.write_text(src)
    assert lint_file(home, pathlib.Path("obs/clock.py")) == []
    stray = tmp_path / "core" / "clocky.py"
    stray.parent.mkdir()
    stray.write_text(src)
    fs = lint_file(stray, pathlib.Path("core/clocky.py"))
    assert rules(fs) == ["lint.global-clock-prng"], fs
    assert {f.key for f in fs} == {"import-time", "clock-time.perf_counter"}


def test_lint_time_sleep_rule_and_allowlist(tmp_path):
    """ISSUE 8 satellite: time.sleep in a library dir trips the new
    lint.time-sleep rule; the same source as obs/clock.py (the one
    sanctioned Clock.sleep implementation) or under launch/ does not."""
    from repro.analysis.fixtures import BAD_SLEEP_SRC
    p = tmp_path / "bad_sleep.py"
    p.write_text(BAD_SLEEP_SRC)
    fs = lint_file(p, pathlib.Path("runtime/bad_sleep.py"))
    assert "lint.time-sleep" in rules(fs)
    msg = next(f for f in fs if f.rule == "lint.time-sleep").message
    assert "Clock.sleep" in msg
    assert "lint.time-sleep" not in rules(
        lint_file(p, pathlib.Path("obs/clock.py")))
    assert "lint.time-sleep" not in rules(
        lint_file(p, pathlib.Path("launch/bad_sleep.py")))


def test_lint_socket_server_rule_and_allowlist(tmp_path):
    """ISSUE 10 satellite: socket / socketserver / http.server imports
    in a library dir trip lint.socket-server; the same source as
    obs/telemetry.py (the one sanctioned /metrics server module) or
    under launch/ does not."""
    from repro.analysis.fixtures import BAD_SERVER_SRC
    p = tmp_path / "bad_server.py"
    p.write_text(BAD_SERVER_SRC)
    fs = lint_file(p, pathlib.Path("serving/bad_server.py"))
    assert rules(fs) == ["lint.socket-server"], fs
    assert len(fs) == 2                     # one finding per banned door
    assert {f.key for f in fs} == {"import-socket", "import-http.server"}
    assert "obs/telemetry.py" in fs[0].message
    assert "lint.socket-server" not in rules(
        lint_file(p, pathlib.Path("obs/telemetry.py")))
    assert "lint.socket-server" not in rules(
        lint_file(p, pathlib.Path("launch/bad_server.py")))


def test_lint_clean_on_production_tree():
    findings, files = lint_tree()
    assert len(files) > 60
    assert findings == [], [(f.rule, f.subject, f.key) for f in findings]


# -------------------------------------------------- report, baseline, CLI

def test_fingerprint_stable_under_message_changes():
    a = Finding("r.x", "s", "k", "message one")
    b = Finding("r.x", "s", "k", "completely different text")
    c = Finding("r.x", "s", "other", "message one")
    assert a.fingerprint == b.fingerprint != c.fingerprint


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError, match="severity"):
        Finding("r", "s", "k", "m", severity="fatal")


def test_baseline_diff_new_suppressed_stale(tmp_path):
    old = Finding("r.a", "s1", "k1", "m")
    new = Finding("r.b", "s2", "k2", "m")
    gone = Finding("r.c", "s3", "k3", "m")
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"suppressions": [
        {"fingerprint": f.fingerprint, "rule": f.rule, "subject": f.subject,
         "key": f.key, "reason": "t"} for f in (old, gone)]}))
    rep = Report()
    rep.extend([old, new, Finding("r.i", "s", "k", "m", severity="info")])
    got_new, suppressed, stale = diff_against_baseline(
        rep, load_baseline(base))
    assert got_new == [new] and suppressed == [old]
    assert [e["rule"] for e in stale] == ["r.c"]


def test_checked_in_baseline_is_empty():
    # main must stay clean; suppressions need a PR justification
    assert load_baseline() == {}


def test_controls_pass():
    assert run_controls() == []


@pytest.mark.slow
def test_runner_end_to_end_and_report_schema(tmp_path):
    report = run_all()
    assert report.passes_run == ["jaxpr", "kernels", "lint", "controls"]
    assert report.errors() == [], [(f.rule, f.subject, f.key)
                                   for f in report.errors()]
    out = tmp_path / "r.json"
    report.write(out)
    data = json.loads(out.read_text())
    assert set(data) == {"passes_run", "subjects", "findings"}
    for f in data["findings"]:
        assert {"rule", "subject", "key", "message", "severity",
                "fingerprint"} <= set(f)


def test_collective_bytes_without_touching_xla_flags():
    """The decomposition path reads collective payloads from
    ``analysis.hlo``, whose import sets nothing: after importing the core
    and running the deep-traced sharded QR (the caller of
    ``collective_bytes``), ``XLA_FLAGS`` is still unset."""
    import os
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1]
                            / "src") + os.pathsep + env.get("PYTHONPATH", "")
    code = """
import os, jax, jax.numpy as jnp
from jax.sharding import Mesh
import numpy as np
import repro.core, repro.stream
from repro.core import panel_parallel_pivoted_qr
from repro.obs import tracing
from repro.analysis.hlo import collective_bytes
mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
with tracing(deep=True):
    panel_parallel_pivoted_qr(jnp.ones((16, 64)), 4, mesh=mesh, axis="data",
                              panel=4)
assert "XLA_FLAGS" not in os.environ, os.environ["XLA_FLAGS"]
line = "  %ar = f32[16,64]{1,0} all-reduce(f32[16,64]{1,0} %x), to_apply=%add"
assert collective_bytes(line) == {"all-reduce": 16 * 64 * 4}
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_cli_gates_on_new_findings(subproc, tmp_path):
    # full CLI in the CI environment: clean tree -> exit 0; a baseline
    # that pretends main is clean of a finding we plant -> exit 1.
    r = subproc(f"""
import json, pathlib, sys
from repro.analysis.__main__ import main
rc = main(["--report", {str(tmp_path / 'a.json')!r}, "--fail-on-new"])
assert rc == 0, rc

# plant: register a known-bad entry, rerun -> the gate must trip
from repro.analysis.fixtures import FIXTURES
from repro.analysis import registry
bad = FIXTURES["fixture.serialized-psum"]
registry._REGISTRY[bad.name] = bad
rc = main(["--report", {str(tmp_path / 'b.json')!r}, "--fail-on-new"])
assert rc == 1, rc
rep = json.load(open({str(tmp_path / 'b.json')!r}))
assert any(f["rule"] == "jaxpr.collective-overlap"
           for f in rep["findings"])
print("OK")
""", n_devices=8, x64=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
