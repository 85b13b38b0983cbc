"""Partitioner + graph substrate tests."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphs import generators as gen
from repro.graphs import partition as gp
from repro.graphs.structures import edgelist_to_csr, edgelist_to_ell


@pytest.mark.parametrize("p", [2, 4, 8])
def test_partition_balanced_and_valid(p):
    g = gen.grid_2d(24, 24, seed=0)
    labels = gp.partition_kway(g, p, seed=0)
    assert labels.min() >= 0 and labels.max() < p
    w = g.weighted_degrees()
    part_w = np.zeros(p)
    np.add.at(part_w, labels, w)
    assert part_w.max() <= part_w.sum() / p * 1.6  # balanced-ish


@pytest.mark.parametrize("family,p", [("road", 64), ("grid3d_26", 32)])
def test_partition_node_counts_balanced_at_many_parts(family, p):
    """Many parts on a sparse road network and a 26-connected grid: the
    largest part (the dense block-Jacobi block size) stays within 2x the
    mean node count, so no part swallows the graph."""
    g = (gen.road_like(100, seed=0) if family == "road"
         else gen.grid_3d(16, 16, 16, conn=26, seed=0))
    labels = gp.partition_kway(g, p, seed=0)
    assert labels.min() >= 0 and labels.max() < p
    counts = np.bincount(labels, minlength=p)
    assert counts.max() <= 2 * g.n / p, (counts.max(), g.n / p)


def test_partition_cut_beats_random():
    g = gen.grid_2d(20, 20, seed=1)
    labels = gp.partition_kway(g, 4, seed=1)
    rng = np.random.default_rng(0)
    rand = rng.integers(0, 4, g.n)
    assert gp.cut_weight(g, labels) < 0.5 * gp.cut_weight(g, rand)


def test_partition_order_groups_contiguously():
    g = gen.road_like(16, seed=2)
    labels = gp.partition_kway(g, 4, seed=2)
    perm = gp.partition_order(labels)
    sorted_labels = np.asarray(labels)[np.argsort(perm)]
    # after reordering, labels are non-decreasing
    assert np.all(np.diff(sorted_labels) >= 0)


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_generators_connected_property(seed):
    g = gen.road_like(10, seed=seed)
    csr = edgelist_to_csr(g)
    seen = np.zeros(g.n, bool)
    stack = [0]
    seen[0] = True
    while stack:
        u = stack.pop()
        for v in csr.indices[csr.indptr[u]:csr.indptr[u + 1]]:
            if not seen[v]:
                seen[v] = True
                stack.append(int(v))
    assert seen.all()
    assert np.all(g.weight > 0)


def test_ell_conversion_roundtrip():
    g = gen.grid_2d(8, 8, seed=3)
    ell = edgelist_to_ell(g)
    # Laplacian row sums are ~0 (diag = -sum(offdiag))
    rowsum = ell.diag + ell.vals.sum(axis=1)
    np.testing.assert_allclose(rowsum, 0, atol=1e-9)


def test_triplet_builder_correct():
    from repro.data.graphs import build_triplets
    # path graph 0->1->2 plus 3->1: edges j->i
    src = np.array([0, 1, 3])
    dst = np.array([1, 2, 1])
    tri_kj, tri_ji = build_triplets(src, dst, 4)
    pairs = set(zip(tri_kj.tolist(), tri_ji.tolist()))
    # edge 1 (1->2): in-edges of node 1 are edges 0 (0->1) and 2 (3->1);
    # neither source equals 2 → both triplets valid
    assert (0, 1) in pairs and (2, 1) in pairs
    # edge 0 (0->1): node 0 has no in-edges → nothing
    assert not any(ji == 0 for _, ji in pairs)


def test_neighbor_sampler_shapes_and_validity():
    from repro.data.sampler import NeighborSampler
    g = gen.random_regular(500, 6, seed=4)
    csr = edgelist_to_csr(g)
    s = NeighborSampler(csr, fanouts=(5, 3), batch_nodes=16, seed=0)
    b = s.sample()
    assert b["edge_src"].shape == (s.max_edges,)
    assert b["sub_nodes"].shape == (s.max_nodes,)
    n_valid = int(b["node_mask"].sum())
    e_valid = int(b["edge_mask"].sum())
    assert n_valid >= 16 and e_valid > 0
    # all edge endpoints point at valid local slots
    ev = b["edge_mask"] > 0
    assert b["edge_src"][ev].max() < n_valid
    assert b["edge_dst"][ev].max() < n_valid
    # edges exist in the original graph
    su = b["sub_nodes"][b["edge_src"][ev]]
    du = b["sub_nodes"][b["edge_dst"][ev]]
    adj = set()
    for u, v in zip(g.src.tolist(), g.dst.tolist()):
        adj.add((u, v)); adj.add((v, u))
    for u, v in list(zip(su.tolist(), du.tolist()))[:50]:
        assert (u, v) in adj
