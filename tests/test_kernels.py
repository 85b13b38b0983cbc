"""Pallas kernel sweeps: shapes × dtypes against the ref.py jnp oracles
(interpret mode on CPU — the kernel body itself executes)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,k", [(64, 4), (512, 8), (777, 9), (1531, 33),
                                 (2048, 26)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_spmv_sweep(n, k, dtype):
    rng = np.random.default_rng(n * k)
    cols = rng.integers(0, n, size=(n, k)).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    vals[rng.uniform(size=(n, k)) < 0.4] = 0.0
    diag = rng.uniform(1, 3, size=n).astype(np.float32)
    v = rng.standard_normal(n).astype(np.float32)
    args = (jnp.asarray(cols), jnp.asarray(vals, dtype),
            jnp.asarray(diag, dtype), jnp.asarray(v, dtype))
    y = ops.ell_spmv(*args)
    y_ref = ref.ell_spmv_ref(*args)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("m,n", [(100, 64), (4096, 512), (5000, 300),
                                 (12288, 1024)])
@pytest.mark.parametrize("eps", [1e-6, 1e-2])
def test_edge_reweight_sweep(m, n, eps):
    rng = np.random.default_rng(m + n)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    c = rng.uniform(0.1, 3.0, m).astype(np.float32)
    v = rng.uniform(0, 1, n).astype(np.float32)
    r = ops.edge_reweight_r(jnp.asarray(src), jnp.asarray(dst),
                            jnp.asarray(c), jnp.asarray(v), eps)
    r_ref = ref.edge_reweight_ref(jnp.asarray(src), jnp.asarray(dst),
                                  jnp.asarray(c), jnp.asarray(v), eps)
    np.testing.assert_allclose(r, r_ref, rtol=3e-5)


@pytest.mark.parametrize("n,k", [(64, 4), (512, 8), (777, 9), (1100, 17)])
@pytest.mark.parametrize("eps", [1e-6, 1e-2])
def test_fused_ell_sweep_sweep(n, k, eps):
    """The single-sweep system-build kernel vs the jnp oracle AND the
    production jnp fallback (core.laplacian.fused_ell_sweep) — all three
    must agree on (vals, diag, r_s, r_t)."""
    from repro.core import laplacian as lap

    rng = np.random.default_rng(n * k)
    cols = rng.integers(0, n, size=(n, k)).astype(np.int32)
    c_ell = rng.uniform(0.1, 3.0, size=(n, k)).astype(np.float32)
    c_ell[rng.uniform(size=(n, k)) < 0.4] = 0.0       # padded slots
    c_s = rng.uniform(0, 2, size=n).astype(np.float32)
    c_t = rng.uniform(0, 2, size=n).astype(np.float32)
    c_s[rng.uniform(size=n) < 0.3] = 0.0              # absent terminals
    c_t[rng.uniform(size=n) < 0.3] = 0.0
    v = rng.uniform(0, 1, size=n).astype(np.float32)
    args = tuple(jnp.asarray(a) for a in (cols, c_ell, c_s, c_t, v))
    out_k = ops.fused_ell_sweep(*args, eps)
    out_r = ref.fused_ell_sweep_ref(*args, eps)
    out_j = lap.fused_ell_sweep(*args, eps)
    for yk, yr, yj in zip(out_k, out_r, out_j):
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                                   rtol=3e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(yj), np.asarray(yr),
                                   rtol=3e-5, atol=1e-6)


@pytest.mark.parametrize("p,bs", [(1, 16), (4, 100), (8, 128), (3, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_block_diag_matvec_sweep(p, bs, dtype):
    rng = np.random.default_rng(p * bs)
    A = rng.standard_normal((p, bs, bs)).astype(np.float32)
    x = rng.standard_normal((p, bs)).astype(np.float32)
    y = ops.block_diag_matvec(jnp.asarray(A, dtype), jnp.asarray(x, dtype))
    y_ref = ref.block_diag_matvec_ref(jnp.asarray(A, dtype), jnp.asarray(x, dtype))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@given(st.integers(8, 600), st.integers(1, 12), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_ell_spmv_property(n, k, seed):
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, n, size=(n, k)).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    diag = rng.uniform(0.5, 2, size=n).astype(np.float32)
    v = rng.standard_normal(n).astype(np.float32)
    y = ops.ell_spmv(jnp.asarray(cols), jnp.asarray(vals),
                     jnp.asarray(diag), jnp.asarray(v))
    y_ref = ref.ell_spmv_ref(jnp.asarray(cols), jnp.asarray(vals),
                             jnp.asarray(diag), jnp.asarray(v))
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)


def test_kernel_in_solver_path(grid_instance):
    """End-to-end: Pallas-routed IRLS reaches the same cut as jnp-routed
    (voltage trajectories may differ slightly through inexact PCG stops, so
    compare the rounded cut — the solver's actual output)."""
    from repro.core import IRLSConfig, solve, two_level
    v1, _ = solve(grid_instance, IRLSConfig(n_irls=12, n_blocks=4))
    v2, _ = solve(grid_instance, IRLSConfig(n_irls=12, n_blocks=4,
                                            layout="ell", use_pallas=True))
    c1 = two_level(grid_instance, v1).cut_value
    c2 = two_level(grid_instance, v2).cut_value
    assert c1 == pytest.approx(c2, rel=1e-6)
