"""GNN + recsys smoke tests (one per assigned arch, reduced configs) and
permutation-equivariance properties."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.configs.gnn import REDUCED_CELL
from repro.data.graphs import synthetic_gnn_batch
from repro.models import gnn as g
from repro.models import recsys as r

GNN_IDS = [a for a, e in registry.ARCHS.items() if e.family == "gnn"]
_INITS = {"gcn-cora": g.gcn_init, "schnet": g.schnet_init,
          "dimenet": g.dimenet_init, "meshgraphnet": g.mgn_init}
_LOSSES = {"gcn-cora": g.gcn_loss, "schnet": g.schnet_loss,
           "dimenet": g.dimenet_loss, "meshgraphnet": g.mgn_loss}


def _batch_for(arch, cfg, seed=0):
    cell = REDUCED_CELL
    b = synthetic_gnn_batch(
        arch, cell["n_nodes"], cell["n_edges"],
        d_feat=getattr(cfg, "in_dim", None) or cell["d_feat"],
        n_graphs=cell["n_graphs"], n_classes=cell["n_classes"],
        max_triplets=cell["n_triplets"],
        in_edge_dim=getattr(cfg, "in_edge_dim", 7),
        out_dim=getattr(cfg, "out_dim", 3),
        sbf_dim=getattr(cfg, "sbf_dim", 42), seed=seed)
    ng = b.pop("n_graphs", None)
    jb = {k: jnp.asarray(v) for k, v in b.items()}
    if ng is not None:
        jb["n_graphs"] = ng
    return jb


@pytest.mark.parametrize("arch", GNN_IDS)
def test_gnn_arch_smoke(arch):
    cfg = registry.get(arch).make_reduced()
    params = _INITS[arch](cfg, jax.random.PRNGKey(0))
    batch = _batch_for(arch, cfg)
    loss, grads = jax.value_and_grad(
        lambda p: _LOSSES[arch](p, batch, cfg))(params)
    assert np.isfinite(float(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # a small AdamW step along the gradient lowers the (same-batch) loss
    from repro.train.optimizer import AdamWConfig, apply_updates, init_state
    oc = AdamWConfig(lr=1e-4, warmup_steps=1, weight_decay=0.0)
    p2, s2, _ = apply_updates(oc, params, grads, init_state(oc, params))
    loss2 = _LOSSES[arch](p2, batch, cfg)
    assert float(loss2) < float(loss)


def test_gcn_permutation_equivariance():
    """Relabeling nodes permutes GCN outputs identically."""
    cfg = registry.get("gcn-cora").make_reduced()
    params = g.gcn_init(cfg, jax.random.PRNGKey(0))
    batch = _batch_for("gcn-cora", cfg)
    n = batch["node_feat"].shape[0]
    rng = np.random.default_rng(1)
    perm = rng.permutation(n)
    out1 = g.gcn_forward(params, batch, cfg)
    pb = dict(batch)
    pb["node_feat"] = batch["node_feat"][perm]
    inv = np.argsort(perm)
    pb["edge_src"] = jnp.asarray(inv)[batch["edge_src"]]
    pb["edge_dst"] = jnp.asarray(inv)[batch["edge_dst"]]
    out2 = g.gcn_forward(params, pb, cfg)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out1)[perm],
                               rtol=2e-4, atol=2e-4)


def test_schnet_energy_extensive():
    """Doubling a molecule (disjoint copy) doubles its SchNet energy."""
    cfg = registry.get("schnet").make_reduced()
    params = g.schnet_init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    n, e = 10, 20
    zt = rng.integers(0, 50, n).astype(np.int32)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n - 1, e)) % n).astype(np.int32)
    d = rng.uniform(0.5, 5, e).astype(np.float32)

    def make(m):
        return {
            "node_type": jnp.asarray(np.tile(zt, m)),
            "edge_src": jnp.asarray(np.concatenate(
                [src + i * n for i in range(m)])),
            "edge_dst": jnp.asarray(np.concatenate(
                [dst + i * n for i in range(m)])),
            "edge_dist": jnp.asarray(np.tile(d, m)),
            "edge_mask": jnp.ones(e * m), "node_mask": jnp.ones(n * m),
            "graph_ids": jnp.zeros(n * m, jnp.int32), "n_graphs": 1,
        }

    e1 = g.schnet_forward(params, make(1), cfg)
    e2 = g.schnet_forward(params, make(2), cfg)
    assert float(e2[0]) == pytest.approx(2 * float(e1[0]), rel=1e-4)


def test_mgn_edge_masking():
    """Masked (padding) edges must not affect MeshGraphNet outputs."""
    cfg = registry.get("meshgraphnet").make_reduced()
    params = g.mgn_init(cfg, jax.random.PRNGKey(0))
    batch = _batch_for("meshgraphnet", cfg)
    out1 = g.mgn_forward(params, batch, cfg)
    b2 = dict(batch)
    # add garbage edges with mask 0
    b2["edge_src"] = jnp.concatenate([batch["edge_src"],
                                      jnp.zeros(8, jnp.int32)])
    b2["edge_dst"] = jnp.concatenate([batch["edge_dst"],
                                      jnp.ones(8, jnp.int32)])
    b2["edge_feat"] = jnp.concatenate([batch["edge_feat"],
                                       jnp.full((8, batch["edge_feat"].shape[1]), 9.)])
    b2["edge_mask"] = jnp.concatenate([batch["edge_mask"], jnp.zeros(8)])
    out2 = g.mgn_forward(params, b2, cfg)
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-5)


def test_din_smoke_and_training():
    from repro.data.recsys import din_batch
    cfg = registry.get("din").make_reduced()
    params = r.din_init(cfg, jax.random.PRNGKey(0))
    b = {k: jnp.asarray(v) for k, v in din_batch(
        32, cfg.seq_len, cfg.n_items, cfg.n_cates, cfg.n_tags,
        cfg.tag_bag_width, seed=0).items()}
    loss, grads = jax.value_and_grad(lambda p: r.din_loss(p, b, cfg))(params)
    assert np.isfinite(float(loss))
    from repro.train.optimizer import AdamWConfig, apply_updates, init_state
    oc = AdamWConfig(lr=1e-2, warmup_steps=1)
    state = init_state(oc, params)
    p2 = params
    for _ in range(5):
        l, grads = jax.value_and_grad(lambda p: r.din_loss(p, b, cfg))(p2)
        p2, state, _ = apply_updates(oc, p2, grads, state)
    assert float(r.din_loss(p2, b, cfg)) < float(loss)


def test_din_retrieval_matches_pointwise():
    """retrieval_cand scoring == din_logits evaluated per candidate."""
    from repro.data.recsys import din_retrieval_batch
    cfg = registry.get("din").make_reduced()
    params = r.din_init(cfg, jax.random.PRNGKey(0))
    rb = {k: jnp.asarray(v) for k, v in din_retrieval_batch(
        16, cfg.seq_len, cfg.n_items, cfg.n_cates, cfg.n_tags,
        cfg.tag_bag_width, seed=1).items()}
    scores = r.din_retrieval_scores(params, rb, cfg)
    C = rb["cand_items"].shape[0]
    pb = {
        "hist_items": jnp.tile(rb["hist_items"], (C, 1)),
        "hist_cates": jnp.tile(rb["hist_cates"], (C, 1)),
        "hist_mask": jnp.tile(rb["hist_mask"], (C, 1)),
        "target_item": rb["cand_items"],
        "target_cate": rb["cand_cates"],
        "profile_tags": jnp.tile(rb["profile_tags"], (C, 1)),
        "profile_mask": jnp.tile(rb["profile_mask"], (C, 1)),
    }
    ref = r.din_logits(params, pb, cfg)
    np.testing.assert_allclose(scores, ref, rtol=2e-4, atol=2e-4)
