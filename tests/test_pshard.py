"""Unit tests for the guarded sharding-hint layer (models/pshard.py) —
the mechanism behind the §Perf G1/M2 wins."""
import jax
import jax.numpy as jnp
import pytest

from repro.models.pshard import current_mesh, dp_axes, hint


def test_hint_noop_without_mesh():
    x = jnp.ones((8, 4))
    y = hint(x, "data", "model")
    assert y is x                      # literally untouched
    assert current_mesh() is None


def test_hint_in_subprocess_mesh(subproc):
    r = subproc("""
import jax, jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType
from repro.models.pshard import hint, dp_axes

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
with mesh:
    # dp token resolves to (pod, data); divisible dims get sharded
    y = jax.jit(lambda x: hint(x * 1.0, "dp", None, "model"))(
        jnp.ones((8, 3, 4)))
    spec = y.sharding.spec
    assert spec[0] == ("pod", "data"), spec
    assert spec[2] == "model", spec

    # non-divisible dims are dropped, not errored (7 % 4 != 0)
    y2 = jax.jit(lambda x: hint(x * 1.0, "dp", "model"))(jnp.ones((7, 4)))
    assert y2.sharding.spec[0] is None

    # an axis used by an earlier slot cannot repeat (fsdp batch mode);
    # note trailing Nones are trimmed from PartitionSpec
    with dp_axes(("pod", "data", "model")):
        y3 = jax.jit(lambda x: hint(x * 1.0, "dp", None, "model"))(
            jnp.ones((8, 3, 4)))
        s3 = tuple(y3.sharding.spec) + (None,) * 3
        assert s3[0] == ("pod", "data", "model")
        assert s3[2] is None                   # model already consumed

    # unknown axis names are ignored gracefully (hint becomes a no-op;
    # the output may then carry no sharding at all)
    y4 = jax.jit(lambda x: hint(x * 1.0, "nonexistent", None))(jnp.ones((4, 2)))
    spec4 = tuple(getattr(y4.sharding, "spec", ())) + (None,) * 2
    assert all(s is None for s in spec4)
print("OK")
""")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_dp_axes_context_restores():
    from repro.models.pshard import _DP_AXES
    assert _DP_AXES.get() == ("pod", "data")
    with dp_axes(("data",)):
        assert _DP_AXES.get() == ("data",)
        with dp_axes(("data", "model")):
            assert _DP_AXES.get() == ("data", "model")
        assert _DP_AXES.get() == ("data",)
    assert _DP_AXES.get() == ("pod", "data")
