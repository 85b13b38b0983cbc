"""PCG + preconditioners: correctness, warm starts, block-Jacobi."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.pcg import pcg, pcg_fixed_iters, pcg_masked
from repro.core import precond as pc
from repro.core import laplacian as lap
from repro.core.incidence import device_graph_from_instance
from conftest import tiny_instance


def _spd(n, seed, cond=100.0):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eigs = np.geomspace(1.0, cond, n)
    return (q * eigs) @ q.T


def test_pcg_solves_spd():
    A = jnp.asarray(_spd(50, 0), jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).standard_normal(50), jnp.float32)
    res = pcg(lambda x: A @ x, b, tol=1e-6, max_iters=500)
    x_ref = np.linalg.solve(np.asarray(A, np.float64), np.asarray(b, np.float64))
    np.testing.assert_allclose(np.asarray(res.x), x_ref, rtol=2e-3, atol=2e-3)


def test_pcg_jacobi_accelerates():
    # strongly diagonally-scaled SPD matrix: Jacobi must clearly help
    A = jnp.asarray(_spd(60, 2, cond=10) * np.outer(
        np.linspace(1, 40, 60), np.linspace(1, 40, 60)) ** 0.5
        + np.diag(np.linspace(1, 1600, 60)), jnp.float32)
    b = jnp.ones(60, jnp.float32)
    plain = pcg(lambda x: A @ x, b, tol=1e-6, max_iters=2000)
    precond = pcg(lambda x: A @ x, b, tol=1e-6, max_iters=2000,
                  precond=lambda r: r / jnp.diag(A))
    assert int(precond.iters) < int(plain.iters)


def test_warm_start_reduces_iterations():
    A = jnp.asarray(_spd(80, 3), jnp.float32)
    x_true = jnp.asarray(np.random.default_rng(4).standard_normal(80), jnp.float32)
    b = A @ x_true
    cold = pcg(lambda x: A @ x, b, tol=1e-6, max_iters=500)
    # warm start near the solution
    x0 = x_true + 0.01 * jnp.asarray(
        np.random.default_rng(5).standard_normal(80), jnp.float32)
    warm = pcg(lambda x: A @ x, b, x0=x0, tol=1e-6, max_iters=500)
    assert int(warm.iters) < int(cold.iters)


def test_block_jacobi_exact_on_block_diagonal():
    """When L̃ IS block diagonal (no cut edges), the preconditioner is an
    exact inverse → PCG converges in O(1) iterations."""
    from repro.graphs.structures import EdgeList, STInstance
    # two disconnected triangles + terminal edges (graph stays 'connected'
    # through s/t, which is all the reduced system needs)
    src = np.array([0, 1, 2, 3, 4, 5], dtype=np.int32)
    dst = np.array([1, 2, 0, 4, 5, 3], dtype=np.int32)
    w = np.ones(6)
    g = EdgeList(src=src, dst=dst, weight=w, n=6)
    inst = STInstance(graph=g, s_weight=np.full(6, 0.7), t_weight=np.full(6, 0.3))
    dg = device_graph_from_instance(inst)
    rw = lap.initial_weights(dg)
    labels = np.array([0, 0, 0, 1, 1, 1])
    plan = pc.build_block_plan(src, dst, labels, 2)
    M = pc.factorize_blocks(plan, rw)
    mv = lambda v: lap.matvec_coo(dg, rw, v)
    res = pcg(mv, lap.rhs(rw), precond=lambda x: pc.apply_block_jacobi(M, x),
              tol=1e-6, max_iters=50)
    assert int(res.iters) <= 2


def test_block_jacobi_explicit_inverse_matches_solve(road_instance):
    from repro.graphs import partition as gp
    from repro.graphs.structures import permute_instance
    labels = gp.partition_kway(road_instance.graph, 4)
    perm = gp.partition_order(labels)
    inst = permute_instance(road_instance, perm)
    labels = np.sort(labels)
    dg = device_graph_from_instance(inst)
    rw = lap.initial_weights(dg)
    plan = pc.build_block_plan(inst.graph.src, inst.graph.dst, labels, 4)
    M1 = pc.factorize_blocks(plan, rw, explicit_inverse=False)
    M2 = pc.factorize_blocks(plan, rw, explicit_inverse=True)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(dg.n), jnp.float32)
    y1 = pc.apply_block_jacobi(M1, x)
    y2 = pc.apply_block_jacobi(M2, x)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-4 * float(jnp.abs(y1).max()))


def test_chebyshev_preconditioner_accelerates(grid_instance):
    dg = device_graph_from_instance(grid_instance)
    rw = lap.reweight(dg, jnp.full((dg.n,), 0.5), 1e-2)
    mv = lambda v: lap.matvec_coo(dg, rw, v)
    b = lap.rhs(rw)
    plain = pcg(mv, b, tol=1e-6, max_iters=3000,
                precond=lambda x: x / rw.diag)
    cheb = pcg(mv, b, tol=1e-6, max_iters=3000,
               precond=pc.make_chebyshev_apply(mv, rw.diag, degree=4))
    assert int(cheb.iters) < int(plain.iters)


def test_pcg_fixed_iters_matches_pcg():
    A = jnp.asarray(_spd(40, 7), jnp.float32)
    b = jnp.ones(40, jnp.float32)
    r1 = pcg(lambda x: A @ x, b, tol=0.0, max_iters=30)
    r2 = pcg_fixed_iters(lambda x: A @ x, b, n_iters=30)
    np.testing.assert_allclose(r1.x, r2.x, rtol=1e-4, atol=1e-5)


def test_pcg_fixed_iters_no_history_same_solution():
    A = jnp.asarray(_spd(40, 11), jnp.float32)
    b = jnp.ones(40, jnp.float32)
    r1 = pcg_fixed_iters(lambda x: A @ x, b, n_iters=25)
    r2 = pcg_fixed_iters(lambda x: A @ x, b, n_iters=25,
                         record_history=False)
    np.testing.assert_allclose(r1.x, r2.x, rtol=0, atol=0)  # identical math
    assert r1.history.shape == (25,) and r2.history.shape == (1,)


# ---------------------------------------------------------------------------
# masked early-exit PCG (the adaptive scanned driver's inner loop)
# ---------------------------------------------------------------------------

def test_pcg_masked_matches_pcg():
    A = jnp.asarray(_spd(60, 9), jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).standard_normal(60), jnp.float32)
    r1 = pcg(lambda x: A @ x, b, tol=1e-5, max_iters=500)
    r2 = pcg_masked(lambda x: A @ x, b, tol=1e-5, max_iters=500)
    assert int(r1.iters) == int(r2.iters)
    np.testing.assert_allclose(r1.x, r2.x, rtol=0, atol=0)  # same updates


def test_pcg_masked_vmap_batch_matches_solo():
    """The explicit update masking contract: a converged lane's state stops
    changing, so co-batched (vmapped) solves are BIT-identical to solo ones
    even though the batch keeps looping for the slowest lane."""
    rng = np.random.default_rng(5)
    As = jnp.asarray(np.stack([_spd(48, s, cond=c)
                               for s, c in ((0, 5), (1, 2000), (2, 50))]),
                     jnp.float32)
    bs = jnp.asarray(rng.standard_normal((3, 48)), jnp.float32)
    solve = lambda A, b: pcg_masked(lambda x: A @ x, b, tol=1e-5,
                                    max_iters=400)
    batch = jax.vmap(solve)(As, bs)
    solo_iters = []
    for i in range(3):
        solo = solve(As[i], bs[i])
        np.testing.assert_array_equal(np.asarray(batch.x[i]),
                                      np.asarray(solo.x))
        assert int(batch.iters[i]) == int(solo.iters)
        solo_iters.append(int(solo.iters))
    # the lanes genuinely differ in difficulty (otherwise this tests nothing)
    assert len(set(solo_iters)) > 1


def test_pcg_masked_inf_tol_is_noop():
    """tol=inf is how the IRLS driver parks done lanes: zero iterations,
    x0 passed through untouched."""
    A = jnp.asarray(_spd(20, 3), jnp.float32)
    b = jnp.ones(20, jnp.float32)
    x0 = jnp.asarray(np.random.default_rng(0).standard_normal(20), jnp.float32)
    res = pcg_masked(lambda x: A @ x, b, x0=x0, tol=jnp.inf, max_iters=50)
    assert int(res.iters) == 0
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(x0))


# ---------------------------------------------------------------------------
# lazy preconditioner builder (the host IRLS stepper's form)
# ---------------------------------------------------------------------------

def _nan_builder():
    """A builder whose apply poisons every step: any use shows as NaN."""
    return lambda r: r * jnp.nan


@pytest.mark.parametrize("jit", [False, True])
def test_pcg_builder_skips_when_warm_start_meets_tol(jit):
    A = jnp.asarray(_spd(40, 13), jnp.float32)
    x_true = jnp.asarray(np.random.default_rng(2).standard_normal(40),
                         jnp.float32)
    b = A @ x_true
    x0 = x_true + 1e-6 * jnp.asarray(
        np.random.default_rng(3).standard_normal(40), jnp.float32)
    mv = lambda x: A @ x
    lazy = lambda x0: pcg(mv, b, x0=x0, tol=1e-3, max_iters=50,
                          record_history=True, make_precond=_nan_builder)
    eager = lambda x0: pcg(mv, b, x0=x0, tol=1e-3, max_iters=50,
                           record_history=True,
                           precond=lambda r: r / jnp.diag(A))
    if jit:
        lazy, eager = jax.jit(lazy), jax.jit(eager)
    r1, r2 = lazy(x0), eager(x0)
    assert not bool(r1.factored) and not bool(r2.factored)
    assert int(r1.iters) == 0 == int(r2.iters)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(x0))
    assert float(r1.rel_res) == float(r2.rel_res) <= 1e-3
    np.testing.assert_array_equal(np.asarray(r1.history),
                                  np.asarray(r2.history))


@pytest.mark.parametrize("with_x0", [False, True])
def test_pcg_builder_matches_apply_form(with_x0):
    A = jnp.asarray(_spd(50, 17), jnp.float32)
    b = jnp.asarray(np.random.default_rng(8).standard_normal(50), jnp.float32)
    x0 = (jnp.asarray(np.random.default_rng(9).standard_normal(50),
                      jnp.float32) if with_x0 else None)
    apply_M = lambda r: r / jnp.diag(A)
    calls = []

    def build():
        calls.append(1)
        return apply_M

    kw = dict(x0=x0, tol=1e-5, max_iters=500, record_history=True)
    lazy = pcg(lambda x: A @ x, b, make_precond=build, **kw)
    eager = pcg(lambda x: A @ x, b, precond=apply_M, **kw)
    assert calls == [1]
    assert bool(lazy.factored) and int(lazy.iters) > 0
    assert int(lazy.iters) == int(eager.iters)
    np.testing.assert_allclose(lazy.x, eager.x, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(lazy.rel_res, eager.rel_res, rtol=1e-6)


def test_pcg_rejects_both_preconditioner_forms():
    A = jnp.eye(4)
    with pytest.raises(ValueError, match="not both"):
        pcg(lambda x: A @ x, jnp.ones(4), precond=lambda r: r,
            make_precond=lambda: None)
