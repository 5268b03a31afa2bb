"""Compile rehearsals: the main path's Pallas kernels lowered by Mosaic for
a described TPU v5e at the paper's real widths, with no chip attached.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses — a block over the VMEM budget, a slice off the tiling —
so each test here compiles with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only the test process given this file loads the TPU
compiler library.  Where it cannot be described, the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import panel_apply, panel_coeff, panel_step, sketch_accum

f32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    mp.undo()


def compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, f32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("l,m,n", [(200, 2048, 2 ** 14),
                                   (2000, 512, 2 ** 16)])
def test_sketch_accum_compiles(one_chip, l, m, n):
    """Grid row 1's sketch (l = 200, n = 2^14) and a four-chip shard of
    row 8 (l = 2000, n/4 = 2^16): the accumulator no longer has to fit
    VMEM as one block."""
    text = compiled_text(
        lambda x, a, acc: sketch_accum(x, a, acc, interpret=False),
        one_chip, (l, m), (m, n), (l, n))
    assert "tpu_custom_call" in text


def test_panel_step_compiles(one_chip):
    text = compiled_text(lambda c, z: panel_step(c, z, interpret=False),
                         one_chip, (2000, 32), (2000, 2 ** 14))
    assert "tpu_custom_call" in text


def test_panel_coeff_compiles(one_chip):
    text = compiled_text(
        lambda c, z, r2: panel_coeff(c, z, r2, interpret=False),
        one_chip, (2000, 32), (2000, 2 ** 16), (2 ** 16,))
    assert "tpu_custom_call" in text


def test_panel_apply_compiles(one_chip):
    text = compiled_text(
        lambda q, w, z: panel_apply(q, w, z, interpret=False),
        one_chip, (2000, 32), (32, 2 ** 16), (2000, 2 ** 16))
    assert "tpu_custom_call" in text
