"""Sweep cut + two-level rounding (paper §3.4, Prop 3.1)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import max_flow, sweep_cut, two_level
from repro.core.rounding import coarsen, kmeans_thresholds
from conftest import tiny_instance


def brute_sweep(inst, v):
    """Reference: evaluate every voltage-ordered prefix cut directly."""
    order = np.argsort(-v)
    best = inst.cut_value(np.zeros(inst.n, bool))
    ind = np.zeros(inst.n, dtype=bool)
    for u in order:
        ind[u] = True
        best = min(best, inst.cut_value(ind))
    return best


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_sweep_cut_matches_bruteforce(seed):
    inst = tiny_instance(10, seed % 97)
    rng = np.random.default_rng(seed)
    v = rng.uniform(size=inst.n)
    res = sweep_cut(inst, v)
    expect = brute_sweep(inst, v)
    assert res.cut_value == pytest.approx(expect, rel=1e-5)


def test_sweep_cut_on_indicator_is_exact(grid_instance):
    """Feeding the true min-cut indicator as 'voltages' must recover it."""
    mf = max_flow(grid_instance)
    v = mf.in_source[: grid_instance.n].astype(np.float64)
    res = sweep_cut(grid_instance, v)
    assert res.cut_value == pytest.approx(mf.value, rel=1e-6)


def test_coarsen_lift_consistency(grid_instance):
    """Any cut on the coarse graph + lift = the same cut value on the fine
    graph (the two-level construction preserves cut values; §3.4 rules)."""
    rng = np.random.default_rng(0)
    v = np.clip(rng.normal(0.5, 0.35, grid_instance.n), 0, 1)
    g0, g1 = 0.25, 0.75
    coarse, labels, contour_ids, st_cross = coarsen(grid_instance, v, g0, g1)
    if coarse.n == 0:
        return
    # random coarse-side assignment
    side = rng.random(coarse.n) < 0.5
    coarse_cut = coarse.cut_value(side) + st_cross
    fine = labels == 1
    fine[contour_ids] = side
    assert grid_instance.cut_value(fine) == pytest.approx(coarse_cut, rel=1e-9)


def test_two_level_recovers_exact_on_polarized(grid_instance):
    """Prop 3.1: when the voltages are already the (perturbed) min-cut
    indicator, two-level returns an EXACT min cut."""
    mf = max_flow(grid_instance)
    rng = np.random.default_rng(1)
    ind = mf.in_source[: grid_instance.n]
    v = np.where(ind, 0.97, 0.03) + rng.uniform(-0.02, 0.02, grid_instance.n)
    res = two_level(grid_instance, v)
    assert res.cut_value == pytest.approx(mf.value, rel=1e-9)
    assert res.meta["reduction"] > 10


def test_two_level_beats_or_ties_sweep(grid_instance):
    from repro.core import IRLSConfig, solve
    v, _ = solve(grid_instance, IRLSConfig(n_irls=20, n_blocks=4))
    r_sweep = sweep_cut(grid_instance, v)
    r_two = two_level(grid_instance, v)
    assert r_two.cut_value <= r_sweep.cut_value * (1 + 1e-9)


def test_kmeans_thresholds_ordered():
    rng = np.random.default_rng(2)
    v = np.concatenate([rng.uniform(0, 0.2, 100), rng.uniform(0.8, 1.0, 80)])
    g0, g1 = kmeans_thresholds(v)
    assert 0 < g0 < g1 < 1
    assert g0 < 0.4 and g1 > 0.6
