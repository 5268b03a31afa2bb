"""The plain reference: it accepts a sound ID and refuses the control and
every planted fault, at a size a CPU test run holds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import matrices, reference

M, N, K = 384, 512, 12


def _blocks(A, rows=128):
    return [(r0, r0 + rows, A[r0:r0 + rows]) for r0 in range(0, M, rows)]


@pytest.fixture(scope="module", params=["float32", "complex64"])
def matrix(request):
    return matrices.device_matrix(matrices.seed_key(2 ** 33 + 7), M, N, K,
                                  request.param)


def _reference(A, low):
    P, J = reference.reference_id(jax.random.key(1), _blocks(A), N, K, 2 * K,
                                  A.dtype, low)
    return reference.gather(_blocks(A), J), P, J


def test_seed_key_keeps_all_bits():
    a = jax.random.key_data(matrices.seed_key(2 ** 40 + 5))
    b = jax.random.key_data(matrices.seed_key(5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_host_matrix_matches_device_matrix():
    key = matrices.seed_key(3)
    d = np.asarray(matrices.device_matrix(key, M, N, K, "float32"))
    h = matrices.host_matrix(key, M, N, K, "float32", 128)
    np.testing.assert_allclose(h, d, rtol=1e-5, atol=1e-4)


def test_reference_id_is_sound(matrix):
    rec, = reference.check_factors(_blocks(matrix), [_reference(matrix, False)],
                                   N, K)
    assert rec["pivots_invalid"] == 0
    assert rec["identity_err"] == 0.0 and rec["gather_err"] == 0.0
    assert rec["rel_err"] < 1e-5


def test_control_reads_far_above_the_reference(matrix):
    full, = reference.check_factors(_blocks(matrix),
                                    [_reference(matrix, False)], N, K)
    low, = reference.check_factors(_blocks(matrix),
                                   [_reference(matrix, True)], N, K)
    assert low["rel_err"] > 100 * full["rel_err"]
    assert low["rel_err"] > 1e-3


@pytest.mark.parametrize("fault", ["pivot", "duplicate", "gather",
                                   "identity", "coefficient"])
def test_check_catches_fault(matrix, fault):
    B, P, J = _reference(matrix, False)
    B, P, J = np.array(B), np.array(P), np.array(J)
    if fault == "pivot":
        J[0] = N
    elif fault == "duplicate":
        J[1] = J[0]
    elif fault == "gather":
        B[:, 0] = np.asarray(matrix)[:, (J[0] + 1) % N]
    elif fault == "identity":
        P[0, J[0]] = 0.5
    else:
        free = np.setdiff1d(np.arange(N), J)[0]
        P[0, free] += 0.5
    rec, = reference.check_factors(_blocks(matrix), [(B, P, J)], N, K)
    if fault in ("pivot", "duplicate"):
        assert rec["pivots_invalid"] > 0
    elif fault == "gather":
        assert rec["gather_err"] > 0
    elif fault == "identity":
        assert rec["identity_err"] > 0
    else:
        assert rec["rel_err"] > 1e-3


def test_low_matmul_rounds_to_bfloat16():
    a = jnp.asarray([[1.0 + 2.0 ** -12]], jnp.float32)
    assert float(reference.matmul(a, a)[0, 0]) > 1.0
    assert float(reference.matmul(a, a, low=True)[0, 0]) == 1.0
