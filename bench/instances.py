"""Seeded s-t min-cut instances, generated from a configuration's
``instance`` block alone.

A copy of the program's generators (``repro.graphs.generators``:
``road_like``, ``grid_3d``, ``flow_improve_instance``,
``segmentation_instance``; ``repro.graphs.partition.bfs_grow``), kept with
the benchmark so that a change to the program cannot change the inputs it
is measured on.  The draws are the same, in the same order; only the
connecting step differs.  The program joins components through a Python
union-find; this copy finds them with ``scipy.sparse.csgraph`` and joins
each component's lowest node to the next one's.  That adds the same
number of edges with the same weights, between other endpoints, and takes
seconds less at the benchmark's sizes.

An instance is plain numpy: ``src, dst`` (int32, ``src < dst``),
``weight`` (float64), ``s_weight, t_weight`` (float64[n]).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


class Instance(NamedTuple):
    n: int
    src: np.ndarray
    dst: np.ndarray
    weight: np.ndarray
    s_weight: np.ndarray
    t_weight: np.ndarray

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


def _weighted_degrees(n, src, dst, w) -> np.ndarray:
    return (np.bincount(src, weights=w, minlength=n)
            + np.bincount(dst, weights=w, minlength=n))


def _dedup_and_connect(src, dst, w, n, rng):
    """Orient ``lo < hi``, drop self-loops, keep the first of parallel
    edges (sorted by ``(lo, hi)``), then join the components."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    w = np.asarray(w, dtype=np.float64)
    keep = src != dst
    lo = np.minimum(src, dst)[keep]
    hi = np.maximum(src, dst)[keep]
    w = w[keep]
    key = lo * n + hi
    order = np.argsort(key, kind="stable")
    key = key[order]
    first = np.ones(key.shape[0], dtype=bool)
    first[1:] = key[1:] != key[:-1]
    lo, hi, w = lo[order][first], hi[order][first], w[order][first]
    adj = coo_matrix((np.ones(lo.shape[0], dtype=np.int8), (lo, hi)),
                     shape=(n, n))
    k, labels = connected_components(adj, directed=False)
    if k > 1:
        reps = np.full(k, n, dtype=np.int64)
        np.minimum.at(reps, labels, np.arange(n))
        reps = np.sort(reps)
        lo = np.concatenate([lo, reps[:-1]])
        hi = np.concatenate([hi, reps[1:]])
        w = np.concatenate([w, rng.uniform(0.5, 1.5, size=k - 1)])
    return lo.astype(np.int32), hi.astype(np.int32), w


def road_like(side: int, seed: int, keep_prob: float = 0.62):
    """Jittered-grid planar road proxy: 4-neighbour links kept with
    probability ``keep_prob``, segment lengths U[0.2, 2]."""
    rng = np.random.default_rng(seed)
    n = side * side
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    idx = (ii * side + jj).ravel()
    right = np.stack([idx[(jj < side - 1).ravel()],
                      (idx + 1)[(jj < side - 1).ravel()]], axis=1)
    down = np.stack([idx[(ii < side - 1).ravel()],
                     (idx + side)[(ii < side - 1).ravel()]], axis=1)
    edges = np.concatenate([right, down], axis=0)
    edges = edges[rng.uniform(size=edges.shape[0]) < keep_prob]
    w = rng.uniform(0.2, 2.0, size=edges.shape[0])
    return n, *_dedup_and_connect(edges[:, 0], edges[:, 1], w, n, rng)


def _smooth_field(shape, rng) -> np.ndarray:
    f = rng.standard_normal(shape)
    for axis in range(len(shape)):
        for _ in range(3):
            f = (f + np.roll(f, 1, axis=axis) + np.roll(f, -1, axis=axis)) / 3
    return f


def grid_3d(side: int, seed: int, conn: int = 26):
    """``side``³ voxel grid, 6- or 26-connected, capacities from a smooth
    random field plus U[0, 1] noise (the UWO MRI instances' shape)."""
    if conn not in (6, 26):
        raise ValueError(f"conn must be 6 or 26, got {conn}")
    rng = np.random.default_rng(seed)
    d = h = w = side
    n = d * h * w
    coords = np.stack(np.meshgrid(np.arange(d), np.arange(h), np.arange(w),
                                  indexing="ij"), axis=-1).reshape(-1, 3)
    idx = coords[:, 0] * h * w + coords[:, 1] * w + coords[:, 2]
    offs = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
            for dx in (-1, 0, 1)
            if (dz, dy, dx) > (0, 0, 0)
            and (conn == 26 or abs(dz) + abs(dy) + abs(dx) == 1)]
    srcs, dsts = [], []
    for off in offs:
        nc = coords + np.array(off)
        ok = np.all((nc >= 0) & (nc < side), axis=1)
        srcs.append(idx[ok])
        dsts.append(nc[ok, 0] * h * w + nc[ok, 1] * w + nc[ok, 2])
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    field = _smooth_field((d, h, w), rng).ravel()
    wts = (1.0 + 4.0 * np.exp(-np.abs(field[src] - field[dst]) * 3.0)
           + rng.uniform(0, 1, size=src.shape[0]))
    return n, *_dedup_and_connect(src, dst, wts, n, rng)


def _bfs_grow(n, src, dst, d, frac: float, seed: int) -> np.ndarray:
    """BFS region from a random node until ``frac`` of the total volume."""
    rng = np.random.default_rng(seed)
    adj = coo_matrix((np.ones(2 * src.shape[0], dtype=np.int8),
                      (np.concatenate([src, dst]),
                       np.concatenate([dst, src]))), shape=(n, n)).tocsr()
    adj.sort_indices()
    indptr, indices = adj.indptr, adj.indices
    target = float(d.sum()) * frac
    start = int(rng.integers(n))
    visited = np.zeros(n, dtype=bool)
    visited[start] = True
    vol = d[start]
    out = [start]
    frontier = [start]
    while frontier and vol < target:
        nxt = []
        for u in frontier:
            for v in indices[indptr[u]:indptr[u + 1]].tolist():
                if not visited[v]:
                    visited[v] = True
                    nxt.append(v)
                    out.append(v)
                    vol += d[v]
                    if vol >= target:
                        break
            if vol >= target:
                break
        frontier = nxt
    return np.asarray(out, dtype=np.int64)


def flow_improve(n, src, dst, w, seed) -> Instance:
    """FlowImprove terminals from a BFS seed bisection A (paper §5.1):
    s joins u ∈ A with d_w(u), t joins u ∉ A with α·d_w(u),
    α = vol(A) / vol(Ā).  ``seed`` is anything ``default_rng`` takes."""
    rng = np.random.default_rng(seed)
    d = _weighted_degrees(n, src, dst, w)
    seed_set = _bfs_grow(n, src, dst, d, 0.5, int(rng.integers(1 << 31)))
    ind = np.zeros(n, dtype=bool)
    ind[seed_set] = True
    alpha = float(d[ind].sum()) / max(float(d[~ind].sum()), 1e-12)
    return Instance(n, src, dst, w, np.where(ind, d, 0.0),
                    np.where(~ind, alpha * d, 0.0))


def segmentation(n, src, dst, w, side: int, seed) -> Instance:
    """Unary potentials from a smooth field: source affinity above the
    65th percentile, sink affinity below the 35th, 5% noise on both."""
    rng = np.random.default_rng(seed)
    field = _smooth_field((side,) * 3, rng).ravel()
    u = 0.55 * float(_weighted_degrees(n, src, dst, w).mean())
    lo, hi = np.quantile(field, [0.35, 0.65])
    s_w = (np.where(field > hi, u * (1.0 + field - hi), 0.0)
           + rng.uniform(0, 0.05 * u, n))
    t_w = (np.where(field < lo, u * (1.0 + lo - field), 0.0)
           + rng.uniform(0, 0.05 * u, n))
    return Instance(n, src, dst, w, s_w, t_w)


def topology(spec: dict):
    """``(n, src, dst, weight)`` of the graph a configuration's ``instance``
    block describes: ``{"family": "road", "side", "seed", "keep"}`` or
    ``{"family": "grid3d", "side", "seed", "conn"}``.  Its seed fixes it, as
    a deployment's road network or volume is fixed."""
    family, side, seed = spec["family"], int(spec["side"]), int(spec["seed"])
    if family == "road":
        return road_like(side, seed, float(spec.get("keep", 0.62)))
    if family == "grid3d":
        return grid_3d(side, seed, int(spec.get("conn", 26)))
    raise ValueError(f"unknown instance family {family!r}")


def draw(spec: dict, topo, seed) -> Instance:
    """One s-t instance on ``topo``, with the terminals its family draws
    from ``seed``: a FlowImprove seed set on a road network, unary
    potentials on a volume."""
    if spec["family"] == "road":
        return flow_improve(*topo, seed=seed)
    return segmentation(*topo, side=int(spec["side"]), seed=seed)


def build(spec: dict) -> Instance:
    """The instance of ``spec`` with terminals drawn from its own seed."""
    return draw(spec, topology(spec), int(spec["seed"]) + 1)
