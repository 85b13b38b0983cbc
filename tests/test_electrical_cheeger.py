"""Electrical-flow view (Prop 2.3) + Cheeger-type inequality (Thm 2.7)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cheeger_lambda2, max_flow, phi_of_cut
from repro.core.incidence import device_graph_from_instance
from repro.core import laplacian as lap
from repro.core.electrical import (conservation_residual, electrical_flow,
                                   flow_value_quadratic)
from conftest import tiny_instance


def _exact_wls(inst, v0, eps):
    dg = device_graph_from_instance(inst)
    rw = lap.reweight(dg, jnp.asarray(v0, jnp.float32), eps)
    L = np.asarray(lap.dense_reduced_laplacian(dg, rw), np.float64)
    b = np.asarray(lap.rhs(rw), np.float64)
    return dg, rw, np.linalg.solve(L, b)


@pytest.mark.parametrize("seed", range(5))
def test_flow_conservation_at_wls_solution(seed):
    """Prop 2.3: the WLS solution is an electrical flow — Kirchhoff holds."""
    inst = tiny_instance(14, seed)
    rng = np.random.default_rng(seed)
    dg, rw, v = _exact_wls(inst, rng.uniform(size=inst.n), eps=1e-2)
    fl = electrical_flow(dg, rw, jnp.asarray(v, jnp.float32))
    net = conservation_residual(dg, fl)
    scale = float(jnp.abs(fl.flow_e).max()) + 1.0
    assert float(jnp.abs(net).max()) < 2e-4 * scale


@pytest.mark.parametrize("seed", range(5))
def test_flow_value_identity(seed):
    """μ(z) = xᵀLx: source outflow equals the quadratic form."""
    inst = tiny_instance(14, seed + 50)
    rng = np.random.default_rng(seed)
    dg, rw, v = _exact_wls(inst, rng.uniform(size=inst.n), eps=1e-2)
    vj = jnp.asarray(v, jnp.float32)
    fl = electrical_flow(dg, rw, vj)
    quad = flow_value_quadratic(dg, rw, vj)
    assert float(fl.value) == pytest.approx(float(quad), rel=2e-3)


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_cheeger_bounds_property(seed):
    """Thm 2.7: φ²/2 ≤ λ₂ ≤ 2φ on random float-weighted instances."""
    inst = tiny_instance(12, seed % 89)
    dg = device_graph_from_instance(inst)
    est = cheeger_lambda2(dg, tol=1e-9, max_iters=5000)
    mf = max_flow(inst)
    C = 2 * (inst.graph.total_weight() + float(inst.s_weight.sum())
             + float(inst.t_weight.sum()))
    phi = phi_of_cut(mf.value, C)
    lam2 = float(est.lam2)
    assert lam2 <= 2 * phi * (1 + 1e-3), (lam2, phi)
    assert lam2 >= phi ** 2 / 2 * (1 - 1e-3), (lam2, phi)


def test_cheeger_diagnostic_bounds_consistent(grid_instance):
    dg = device_graph_from_instance(grid_instance)
    est = cheeger_lambda2(dg, tol=1e-8, max_iters=5000)
    assert float(est.lower_phi) <= float(est.upper_phi)
