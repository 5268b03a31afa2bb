"""Pallas kernel contract checker (``kernels.*`` rules).

Every ``kernels/<name>/`` package ships a ``contract.py`` declaring a
:class:`~repro.kernels.common.KernelContract`; this pass verifies the
declarations against the code:

  ``kernels.missing-contract``    a kernel package without a contract.py
  ``kernels.missing-export``      a declared ops/kernel/ref name that the
                                  module does not export
  ``kernels.signature-mismatch``  an (ops, ref) pair whose leading
                                  positional parameter names disagree —
                                  the kernel drifted from the oracle it
                                  is validated against
  ``kernels.constant-drift``      a pinned module constant (ACCUM_BLOCK)
                                  whose value changed
  ``kernels.validation-missing``  the declared known-bad call did not
                                  raise ValueError eagerly
  ``kernels.vmem-overflow``       the example call's captured BlockSpecs
                                  imply a per-grid-step VMEM working set
                                  over the package budget
  ``kernels.control-failed``      the example traced but issued NO
                                  pallas_call (the check was vacuous)

The VMEM estimate is static: every ``pl.pallas_call`` the example issues
is captured (the call is replaced by a recorder returning zeros of
``out_shape``, under ``jax.eval_shape`` so nothing executes), and each
operand/output block contributes ``prod(block_shape) * itemsize`` bytes
— once if its index_map is grid-invariant (resident across steps), twice
otherwise (double-buffered pipeline).  Scratch shapes count once.  With
``measure_residency=True`` the example also runs for real and the shared
sampler (:mod:`repro.obs.metrics`) plus XLA's
``cost_analysis()`` record measured bytes as an ``info``
finding next to the estimate.
"""
from __future__ import annotations

import contextlib
import importlib
import inspect
import pkgutil
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import live_device_bytes
from .report import Finding

__all__ = ["kernel_packages", "check_package", "check_all_kernels",
           "capture_pallas_calls", "estimate_vmem_bytes", "PallasCapture"]


def kernel_packages() -> list:
    """Names of all ``repro.kernels.*`` packages (directories)."""
    import repro.kernels as K
    return sorted(m.name for m in pkgutil.iter_modules(K.__path__)
                  if m.ispkg)


# ----------------------------------------------------------- pallas capture

class PallasCapture:
    """One recorded ``pl.pallas_call``: the kwargs plus the concrete
    operand shapes/dtypes seen when the returned callable was applied."""

    def __init__(self, kwargs):
        self.kwargs = kwargs
        self.in_shaped = ()          # [(shape, dtype), ...] at apply time

    @property
    def grid(self):
        g = self.kwargs.get("grid", ())
        return tuple(g) if isinstance(g, (tuple, list)) else (g,)

    @property
    def out_shapes(self):
        out = self.kwargs.get("out_shape")
        return tuple(out) if isinstance(out, (tuple, list)) else (out,)


@contextlib.contextmanager
def capture_pallas_calls():
    """Replace ``jax.experimental.pallas.pallas_call`` with a recorder
    whose returned callable yields zeros of ``out_shape`` — tracing any
    ops wrapper under this context records every kernel launch without
    executing one.

    Runs under ``jax.disable_jit()`` so NESTED jitted wrappers (e.g.
    ``srht -> fwht``) re-run their python bodies instead of hitting the
    compile cache — a cache hit would both hide the launch from the
    recorder and, worse, a cache populated here would serve the
    recorder's zeros to later REAL calls.  ``jax.clear_caches()`` on
    exit removes anything traced meanwhile, for the same reason."""
    from jax.experimental import pallas as pl
    captured = []
    real = pl.pallas_call

    def recorder(kernel_fn, **kwargs):
        cap = PallasCapture(kwargs)
        captured.append(cap)

        def apply(*args):
            cap.in_shaped = tuple((tuple(a.shape), jnp.dtype(a.dtype))
                                  for a in args)
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                kwargs.get("out_shape"))
        return apply

    pl.pallas_call = recorder
    try:
        with jax.disable_jit():
            yield captured
    finally:
        pl.pallas_call = real
        jax.clear_caches()


def _block_bytes(block_shape, full_shape, dtype) -> int:
    shape = tuple(full_shape[i] if b is None else b
                  for i, b in enumerate(block_shape)) \
        if block_shape is not None else tuple(full_shape)
    return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize


def _is_resident(spec, grid) -> bool:
    """A block whose index_map is grid-invariant stays resident in VMEM
    across steps (weight 1); a varying block is double-buffered by the
    pipeline (weight 2)."""
    imap = getattr(spec, "index_map", None)
    if imap is None or not grid:
        return True
    try:
        return imap(*([0] * len(grid))) == imap(*([1] * len(grid)))
    except Exception:
        return False


def estimate_vmem_bytes(cap: PallasCapture) -> int:
    """Static per-grid-step VMEM bytes of one captured pallas_call."""
    grid = cap.grid
    total = 0
    in_specs = cap.kwargs.get("in_specs") or []
    for spec, (shape, dtype) in zip(in_specs, cap.in_shaped):
        nbytes = _block_bytes(getattr(spec, "block_shape", None), shape,
                              dtype)
        total += nbytes if _is_resident(spec, grid) else 2 * nbytes
    out_specs = cap.kwargs.get("out_specs")
    out_specs = out_specs if isinstance(out_specs, (tuple, list)) \
        else [out_specs]
    for spec, sds in zip(out_specs, cap.out_shapes):
        if sds is None:
            continue
        nbytes = _block_bytes(
            getattr(spec, "block_shape", None) if spec is not None else None,
            sds.shape, sds.dtype)
        total += nbytes if _is_resident(spec, grid) else 2 * nbytes
    for scratch in cap.kwargs.get("scratch_shapes") or []:
        shape = getattr(scratch, "shape", None)
        dtype = getattr(scratch, "dtype", jnp.float32)
        if shape is not None:
            total += int(np.prod(shape, dtype=np.int64)) * \
                jnp.dtype(dtype).itemsize
    return total


# ------------------------------------------------------------- the checker

def _positional_names(fn) -> list:
    """Leading POSITIONAL_OR_KEYWORD parameter names (follows __wrapped__
    through jit; tuning/interpret kwargs are keyword-only and excluded)."""
    sig = inspect.signature(fn)
    return [p.name for p in sig.parameters.values()
            if p.kind is inspect.Parameter.POSITIONAL_OR_KEYWORD]


def _unjitted(fn):
    """The raw python callable under a jit wrapper — tracing it bypasses
    the jit cache so the pallas recorder always fires."""
    return getattr(fn, "__wrapped__", fn)


def check_package(pkg: str, *, base: str = "repro.kernels") -> list:
    """All contract checks for one ``<base>.<pkg>`` kernel package."""
    findings = []
    base = f"{base}.{pkg}"
    try:
        contract_mod = importlib.import_module(f"{base}.contract")
        contract = contract_mod.CONTRACT
    except (ImportError, AttributeError) as e:
        return [Finding("kernels.missing-contract", pkg, "contract",
                        f"kernel package has no importable contract.py "
                        f"with a CONTRACT: {e}")]

    mods = {}
    for role, names in (("ops", contract.ops), ("kernel", contract.kernels),
                        ("ref", contract.refs)):
        try:
            mods[role] = importlib.import_module(f"{base}.{role}")
        except ImportError as e:
            findings.append(Finding(
                "kernels.missing-export", pkg, f"{role}-module",
                f"contract names {role}.py exports but the module does "
                f"not import: {e}"))
            continue
        for name in names:
            if not hasattr(mods[role], name):
                findings.append(Finding(
                    "kernels.missing-export", pkg, f"{role}.{name}",
                    f"contract declares {role}.py exports {name!r} but "
                    f"the module has no such attribute"))

    # --- (ops, ref) signature coupling -------------------------------
    if "ops" in mods and "ref" in mods:
        for ops_name, ref_name in contract.pairs:
            ops_fn = getattr(mods["ops"], ops_name, None)
            ref_fn = getattr(mods["ref"], ref_name, None)
            if ops_fn is None or ref_fn is None:
                continue          # already reported as missing-export
            got, want = _positional_names(ops_fn), _positional_names(ref_fn)
            if got != want:
                findings.append(Finding(
                    "kernels.signature-mismatch", pkg,
                    f"{ops_name}/{ref_name}",
                    f"positional parameters disagree: {ops_name}{got} "
                    f"vs {ref_name}{want} — the kernel drifted from its "
                    f"oracle"))

    # --- pinned constants --------------------------------------------
    if "kernel" in mods:
        for cname, expect in contract.constants.items():
            got = getattr(mods["kernel"], cname, None)
            if got != expect:
                findings.append(Finding(
                    "kernels.constant-drift", pkg, cname,
                    f"kernel.py {cname} = {got!r}, contract pins "
                    f"{expect!r} (a replay/bit-for-bit constant)"))

    # --- eager validation ---------------------------------------------
    if contract.bad_call is not None:
        try:
            contract.bad_call()
        except ValueError:
            pass
        except Exception as e:
            findings.append(Finding(
                "kernels.validation-missing", pkg, "bad-call",
                f"known-bad call raised {type(e).__name__} instead of "
                f"ValueError: {e}"))
        else:
            findings.append(Finding(
                "kernels.validation-missing", pkg, "bad-call",
                "known-bad call returned without raising — the ops "
                "wrapper no longer validates its geometry eagerly"))

    # --- VMEM estimate from the example call --------------------------
    if contract.example is not None:
        fn, args, kwargs = contract.example()
        try:
            with capture_pallas_calls() as caps:
                jax.eval_shape(partial(_unjitted(fn), **kwargs), *args)
        except Exception as e:
            findings.append(Finding(
                "kernels.control-failed", pkg, "example-trace",
                f"example call failed to trace: {type(e).__name__}: {e}"))
            caps = []
        if contract.example is not None and not caps and not any(
                f.rule == "kernels.control-failed" for f in findings):
            findings.append(Finding(
                "kernels.control-failed", pkg, "no-pallas-call",
                "example traced but issued no pallas_call — the VMEM "
                "check was vacuous (complex fallback path?)"))
        for i, cap in enumerate(caps):
            est = estimate_vmem_bytes(cap)
            if est > contract.vmem_budget:
                findings.append(Finding(
                    "kernels.vmem-overflow", pkg, f"call-{i}",
                    f"pallas_call #{i}: per-grid-step block residency "
                    f"~{est} bytes exceeds the {contract.vmem_budget}-"
                    f"byte budget (grid {cap.grid})"))

    # --- measured residency + cost analysis (advisory) ----------------
    if contract.measure_residency and contract.example is not None:
        findings.extend(_measure_example(pkg, contract))
    return findings


def _measure_example(pkg, contract) -> list:
    """Run the example for REAL once: sample live bytes via the shared
    sampler and record XLA's cost analysis — the measured counterpart of
    the static estimate (info only, never gates)."""
    fn, sds_args, kwargs = contract.example()
    args = [jnp.zeros(a.shape, a.dtype) for a in sds_args]
    before = live_device_bytes()
    jitted = jax.jit(partial(fn, **kwargs))
    try:
        lowered = jitted.lower(*args)
        cost = lowered.compile().cost_analysis() or {}
        out = jitted(*args)
        jax.block_until_ready(out)
    except Exception as e:
        return [Finding("kernels.control-failed", pkg, "residency-run",
                        f"measured-residency example failed: "
                        f"{type(e).__name__}: {e}")]
    peak = live_device_bytes()
    return [Finding(
        "kernels.residency", pkg, "measured",
        f"example call: live device bytes {before} -> {peak}, XLA "
        f"bytes accessed ~{int(cost.get('bytes accessed', 0))}, flops "
        f"~{int(cost.get('flops', 0))}", severity="info")]


def check_all_kernels() -> tuple:
    """(findings, packages-checked) across every kernel package."""
    findings, pkgs = [], kernel_packages()
    for pkg in pkgs:
        findings.extend(check_package(pkg))
    return findings, pkgs
