"""Pallas kernels (interpret mode) vs jnp oracles: gemm_hd, stencil_hd.
Shape/dtype sweeps per the deliverable-(c) requirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gemm_hd.kernel import gemm_pallas
from repro.kernels.gemm_hd.ref import gemm_ref
from repro.kernels.stencil_hd.kernel import jacobi_pallas
from repro.kernels.stencil_hd.ref import jacobi_ref


@pytest.mark.parametrize("M,K,N", [(64, 64, 64), (96, 160, 128),
                                   (33, 70, 17), (128, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_matches_ref(M, K, N, dtype):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((M, K)), dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    want = gemm_ref(a, b, alpha=0.5)
    got = gemm_pallas(a, b, alpha=0.5, block_m=32, block_n=32, block_k=32,
                      interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("M,N", [(64, 128), (100, 256), (32, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_matches_ref(M, N, dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((M, N)), dtype)
    want = jacobi_ref(x)
    got = jacobi_pallas(x, block_m=32, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# arrays wider than one column block, ragged in both axes: the left /
# right halo tiles, their clamped index maps and the ragged last block
@pytest.mark.parametrize("M,N", [(100, 300), (37, 530)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_column_blocks_bit_identical(M, N, dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((M, N)), dtype)
    got = jacobi_pallas(x, block_m=32, block_n=128, interpret=True)
    # the kernel sums in float32 and rounds once to the array's dtype
    want = jacobi_ref(x.astype(jnp.float32)).astype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_jacobi_iterated_vs_numpy():
    """Multiple sweeps = the paper's Jacobi benchmark inner loop."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((48, 64)).astype(np.float32)
    want = x.copy()
    for _ in range(5):
        nxt = want.copy()
        nxt[1:-1, 1:-1] = (want[1:-1, :-2] + want[1:-1, 2:]
                           + want[:-2, 1:-1] + want[2:, 1:-1]) / 4
        want = nxt
    got = jnp.asarray(x)
    for _ in range(5):
        got = jacobi_pallas(got, block_m=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
