"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling with
MXU-aligned tiles) and are VALIDATED on CPU via ``interpret=True``,
which executes the kernel body with the same blocking semantics.

Every kernel package additionally ships a ``contract.py`` declaring a
:class:`KernelContract` — the static metadata ``repro.analysis.kernels``
checks in CI: the kernel/ref/ops triple with matching signatures, the
package's replay/blocking constants, a representative example call whose
declared BlockSpecs must fit the per-backend VMEM budget, and (where the
ops wrapper validates geometry eagerly) a known-bad call that must raise
``ValueError`` before tracing.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# MXU/VPU native tile granularity on TPU: last dim 128 lanes, second-to-last
# 8 sublanes (f32).  Matmul tiles should be multiples of 128 on both MXU dims.
LANE = 128
SUBLANE = 8

# VMEM is ~16 MiB/core on v5e; keep per-step working sets well under half so
# the pipeline can double-buffer.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Interpret kernels everywhere except on real TPU hardware."""
    return not on_tpu()


def full_precision(fn: Callable) -> Callable:
    """Trace ``fn`` with every f32 matmul at full precision (``HIGHEST``).

    At the default precision a TPU runs an f32 matmul as one bfloat16
    pass (unit roundoff 2^-9), in XLA and in Mosaic kernels alike, and
    the interpolation solve amplifies that by the conditioning of the
    pivot block: on a v5e the ID's relative error was 1.6e-2 at paper
    grid row 1 (k = 100) and above 1 at row 8 (k = 1000).  Put under
    ``jax.jit`` (or inside a ``shard_map`` body), the context holds while
    the body is traced, so every dot it emits — nested jits and Pallas
    kernel bodies included — carries the precision explicitly.  The CPU
    computes f32 dots in full either way."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_to(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Zero-pad trailing edges of ``x`` up to ``shape``."""
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def acc_dtype_for(dtype) -> jnp.dtype:
    """Accumulator dtype: f32 for <=32-bit floats (MXU accumulates f32),
    f64 when the input is f64 (interpret-mode / CPU validation path)."""
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


@dataclass(frozen=True)
class KernelContract:
    """Static contract of one ``kernels/<name>/`` package, checked by
    ``repro.analysis.kernels`` (the CI-gated contract pass).

    ``pairs`` couples each public ops wrapper to its pure-jnp oracle:
    the checker requires both to exist and their leading positional
    parameter NAMES to agree (tuning/interpret kwargs excluded), so a
    kernel cannot silently drift from the reference it is validated
    against.  ``example`` builds ``(fn, args, static_kwargs)`` for one
    representative REAL-dtype call at production-like shapes; the
    checker traces it abstractly while capturing every ``pl.pallas_call``
    it issues and sums the declared per-grid-step block bytes against
    ``vmem_budget`` (the single-VMEM-residency claims become checked
    numbers).  ``constants`` pins named module attributes — replay /
    canonicality constants like ``ACCUM_BLOCK=128`` whose silent change
    would break bit-for-bit contracts elsewhere.  ``bad_call``, when
    given, must raise ``ValueError`` EAGERLY (the geometry-lie check:
    validation happens in ``ops.py``, not deep inside a traced GEMM).
    """
    name: str
    ops: tuple            # public names exported by ops.py
    kernels: tuple        # raw pallas_call wrappers exported by kernel.py
    refs: tuple           # oracle names exported by ref.py
    pairs: tuple = ()     # ((ops_name, ref_name), ...) signature couples
    example: Optional[Callable] = None   # () -> (fn, args, static_kwargs)
    constants: dict = field(default_factory=dict)  # kernel.py attr -> value
    bad_call: Optional[Callable] = None  # () -> None, must raise ValueError
    vmem_budget: int = VMEM_BUDGET_BYTES
    measure_residency: bool = False      # sample live bytes on a real call
