"""Exact max-flow oracle: brute-force cut enumeration + flow/cut duality."""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.maxflow import max_flow
from repro.graphs import generators as gen
from repro.graphs.structures import EdgeList, STInstance


def brute_force_min_cut(inst: STInstance) -> float:
    n = inst.n
    best = np.inf
    for bits in itertools.product([False, True], repeat=n):
        ind = np.asarray(bits)
        best = min(best, inst.cut_value(ind))
    return best


def random_tiny(n, seed):
    rng = np.random.default_rng(seed)
    g = gen.random_regular(n, 3, seed=seed)
    s_w = np.where(rng.random(n) < 0.4, rng.uniform(0.5, 3.0, n), 0.0)
    t_w = np.where(rng.random(n) < 0.4, rng.uniform(0.5, 3.0, n), 0.0)
    return STInstance(graph=g, s_weight=s_w, t_weight=t_w)


@pytest.mark.parametrize("seed", range(10))
def test_maxflow_matches_bruteforce(seed):
    inst = random_tiny(9, seed)
    res = max_flow(inst)
    expect = brute_force_min_cut(inst)
    assert res.value == pytest.approx(expect, rel=1e-9)
    # the extracted cut achieves the min value (strong duality)
    assert inst.cut_value(res.in_source[: inst.n]) == pytest.approx(expect, rel=1e-9)


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_maxflow_cut_duality_property(seed):
    """Flow value == value of the extracted cut (max-flow/min-cut duality),
    on random small instances with float weights."""
    inst = random_tiny(12, seed)
    res = max_flow(inst)
    cut = inst.cut_value(res.in_source[: inst.n])
    assert res.value == pytest.approx(cut, rel=1e-8, abs=1e-8)
    # s side contains s (index n) and never t
    assert res.in_source[inst.s]
    assert not res.in_source[inst.t]


def test_maxflow_disconnected_terminal():
    # no s edges → min cut 0
    g = gen.random_regular(6, 3, seed=1)
    inst = STInstance(graph=g, s_weight=np.zeros(6), t_weight=np.ones(6))
    assert max_flow(inst).value == pytest.approx(0.0, abs=1e-12)


def test_maxflow_grid_instance(grid_instance):
    res = max_flow(grid_instance)
    assert res.value > 0
    assert res.value == pytest.approx(
        grid_instance.cut_value(res.in_source[: grid_instance.n]), rel=1e-9)


def brute_force_pair_min_cut(g, u, v) -> float:
    """Min graph-only cut separating u from v, by bipartition enumeration."""
    others = [i for i in range(g.n) if i not in (u, v)]
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w = np.asarray(g.weight, dtype=np.float64)
    best = np.inf
    for bits in itertools.product([False, True], repeat=len(others)):
        ind = np.zeros(g.n, dtype=bool)
        ind[u] = True
        ind[others] = bits
        best = min(best, float(w[ind[src] != ind[dst]].sum()))
    return best


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_maxflow_arbitrary_pair_matches_bruteforce(seed):
    """The cut-tree builder's ground truth: max_flow on a terminal-rebound
    (u, v) pair — large one-hot c_s/c_t — equals the brute-force minimum
    over bipartitions of the non-terminal graph, for ARBITRARY pairs on
    random ≤10-node weighted graphs (not just designated terminals)."""
    from repro.core.session import rebind_terminals

    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 11))
    g = gen.random_regular(n, 3, seed=seed)
    u, v = (int(x) for x in rng.choice(n, 2, replace=False))
    w = rebind_terminals(STInstance(graph=g, s_weight=np.zeros(n),
                                    t_weight=np.zeros(n)), u, v)
    inst = STInstance(graph=g, s_weight=w.c_s, t_weight=w.c_t)
    res = max_flow(inst)
    expect = brute_force_pair_min_cut(g, u, v)
    assert res.value == pytest.approx(expect, rel=1e-9, abs=1e-12)
    side = res.in_source[: n]
    assert side[u] and not side[v]
    # the extracted side achieves the value with NO terminal edge cut
    crossing = side[np.asarray(g.src)] != side[np.asarray(g.dst)]
    assert float(np.asarray(g.weight)[crossing].sum()) == \
        pytest.approx(expect, rel=1e-9, abs=1e-12)
