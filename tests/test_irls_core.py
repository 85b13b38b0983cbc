"""IRLS core invariants (paper Props 2.1-2.3, Thm 2.6)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import IRLSConfig, solve
from repro.core.incidence import (device_graph_from_instance, l1_objective,
                                  smoothed_objective)
from repro.core import laplacian as lap
from conftest import tiny_instance


def test_matvec_layout_parity(road_instance):
    dg = device_graph_from_instance(road_instance)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.uniform(size=dg.n).astype(np.float32))
    rw = lap.reweight(dg, v, 1e-3)
    y_coo = lap.matvec_coo(dg, rw, v)
    plan = lap.build_ell_plan(road_instance.graph.src, road_instance.graph.dst, dg.n)
    vals, diag = lap.fill_ell(plan, rw)
    y_ell = lap.matvec_ell(plan.cols, vals, diag, v)
    L = lap.dense_reduced_laplacian(dg, rw)
    y_dense = L @ v
    scale = float(jnp.abs(y_dense).max())
    np.testing.assert_allclose(y_coo, y_dense, rtol=0, atol=3e-5 * scale)
    np.testing.assert_allclose(y_ell, y_dense, rtol=0, atol=3e-5 * scale)


def test_wls_solution_in_unit_interval_exact():
    """Prop 2.2: the exact WLS solution lies in [0,1]^n."""
    for seed in range(5):
        inst = tiny_instance(12, seed)
        dg = device_graph_from_instance(inst)
        rng = np.random.default_rng(seed)
        v0 = jnp.asarray(rng.uniform(size=dg.n).astype(np.float32))
        rw = lap.reweight(dg, v0, 1e-2)
        L = np.asarray(lap.dense_reduced_laplacian(dg, rw), dtype=np.float64)
        b = np.asarray(lap.rhs(rw), dtype=np.float64)
        v = np.linalg.solve(L, b)
        assert v.min() >= -1e-9
        assert v.max() <= 1 + 1e-9


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_irls_iterates_in_unit_interval_property(seed):
    """The IRLS driver keeps every iterate inside [0,1] (up to PCG tol)."""
    inst = tiny_instance(10, seed % 100)
    cfg = IRLSConfig(n_irls=5, n_blocks=2, pcg_max_iters=200, pcg_tol=1e-8,
                     eps=1e-4)
    v, diag = solve(inst, cfg)
    assert v.min() >= -1e-3
    assert v.max() <= 1 + 1e-3


def test_smoothed_objective_decreases(grid_instance):
    """Thm 2.4/2.6: S_eps decreases monotonically (up to solver tolerance)."""
    cfg = IRLSConfig(n_irls=15, n_blocks=4, pcg_max_iters=300, pcg_tol=1e-7,
                     eps=1e-3)
    v, diag = solve(grid_instance, cfg)
    obj = np.asarray(diag.objective)
    # allow tiny non-monotonicity from inexact inner solves
    assert np.all(np.diff(obj) <= np.abs(obj[:-1]) * 1e-3 + 1e-6), obj


def test_fractional_cut_converges_to_mincut(grid_instance):
    """The ℓ1 relaxation of s-t min-cut is TIGHT: min ‖CBx‖₁ = mincut, and
    every feasible x upper-bounds it.  IRLS is only δ-accurate (paper §1),
    so assert (a) the lower bound holds exactly and (b) the gap is small
    and shrinking with iterations."""
    from repro.core import max_flow
    cfg = IRLSConfig(n_irls=60, n_blocks=4, pcg_max_iters=300, pcg_tol=1e-4,
                     eps=1e-6, eps_schedule="anneal")
    v, diag = solve(grid_instance, cfg)
    exact = max_flow(grid_instance).value
    frac = diag.l1_objective[-1]
    assert frac >= exact * (1 - 5e-3)           # relaxation lower bound
    assert frac <= exact * 1.10                 # δ-accurate convergence
    assert diag.l1_objective[-1] <= diag.l1_objective[2] + 1e-6


def test_eps_annealing_converges(grid_instance):
    from repro.core import max_flow, two_level
    cfg = IRLSConfig(n_irls=20, n_blocks=4, eps_schedule="anneal")
    v, _ = solve(grid_instance, cfg)
    res = two_level(grid_instance, v)
    exact = max_flow(grid_instance).value
    assert res.cut_value == pytest.approx(exact, rel=0.01)


def test_initial_weights_are_conductances(road_instance):
    dg = device_graph_from_instance(road_instance)
    rw = lap.initial_weights(dg)
    np.testing.assert_allclose(rw.r, dg.c)
    np.testing.assert_allclose(rw.r_s, dg.c_s)
