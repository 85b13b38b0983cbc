"""Traffic and instances are functions of the seed alone."""
import json
import os

import numpy as np
import pytest
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from bench import generator, harness, instances

BIG = 2**31 + 2**30 + 12345


@pytest.mark.parametrize("spec", [
    {"family": "road", "side": 30, "seed": 4, "keep": 0.69},
    {"family": "grid3d", "side": 5, "seed": 1},
])
def test_pool_is_deterministic_per_seed(spec):
    """Each instance of a pool keeps the topology's edge weights and draws
    its terminals from (seed, k): the same seed gives the same pool, and
    every instance differs from the others and from another seed's."""
    topo = instances.topology(spec)
    mix = {"backend": "host", "pool": 3}
    a = generator.pool(spec, topo, mix, BIG)
    b = generator.pool(spec, instances.topology(spec), mix, BIG)
    c = generator.pool(spec, topo, mix, BIG + 1)
    assert len(a) == 4
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            assert np.array_equal(np.asarray(u), np.asarray(v))
    terminals = [np.concatenate([i.s_weight, i.t_weight]) for i in a + c]
    for i, x in enumerate(terminals):
        assert np.array_equal(a[0].weight, (a + c)[i].weight)
        for y in terminals[i + 1:]:
            assert not np.array_equal(x, y)


@pytest.mark.parametrize("spec,n", [
    ({"family": "road", "side": 30, "seed": 4}, 900),
    ({"family": "grid3d", "side": 5, "seed": 1}, 125),
])
def test_instances_are_deterministic_and_connected(spec, n):
    a, b = instances.build(spec), instances.build(spec)
    assert a.n == n
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert np.all(a.src < a.dst) and np.all(a.weight > 0)
    keys = a.src.astype(np.int64) * a.n + a.dst
    assert len(np.unique(keys)) == a.m
    k, _ = connected_components(coo_matrix(
        (np.ones(a.m), (a.src, a.dst)), shape=(a.n, a.n)), directed=False)
    assert k == 1
    assert a.s_weight.any() and a.t_weight.any()


def test_26_connected_grid_has_every_neighbour():
    side = 5
    inst = instances.build({"family": "grid3d", "side": side, "seed": 0})
    # pairs of voxels at Chebyshev distance 1 in a side^3 grid
    per_axis = [(side, side), (side, side - 1)]
    expected = sum(
        np.prod([per_axis[abs(d)][1] for d in off])
        for off in [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                    for dx in (-1, 0, 1)] if off > (0, 0, 0))
    assert inst.m == expected


def test_every_traffic_mix_loads():
    tdir = os.path.join(harness.ROOT, "bench", "traffic")
    for fname in sorted(os.listdir(tdir)):
        mix = generator.load(harness.ROOT, fname[:-len(".json")])
        assert mix["pool"] >= 1 and mix["backend"] in ("host", "scanned")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        used = {w["traffic"] for w in json.load(f)["workloads"]}
    assert used <= {f[:-5] for f in os.listdir(tdir)}
