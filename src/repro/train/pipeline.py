"""GPipe-style pipeline parallelism over the "pod" mesh axis.

At multi-pod scale, cross-pod (DCI) bandwidth is far below in-pod ICI, so
pure FSDP/TP across pods pays a heavy collective tax.  Pipelining turns the
cross-pod traffic into ONE activation transfer per microbatch per stage
boundary — O(mb·S·D) point-to-point ``ppermute`` instead of O(params)
all-reduce/all-gather.

Implementation: ``jax.shard_map`` manual over the "pod" axis only (data and
model axes stay GSPMD-auto inside the body).  Per-stage layer stacks are
sharded on the pod axis; the schedule is the classic GPipe fill-drain loop
of length M + n_stages − 1 run under ``lax.scan``.  The whole program is
DIFFERENTIABLE — reverse-mode AD through ``ppermute`` yields the backward
pipeline automatically, so one ``jax.grad`` gives pipelined training.

Scope: dense LMs with a homogeneous layer pattern (period 1); embedding and
LM head are replicated across pods (they're small next to the stacks).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import transformer as tr
from repro.models.sharding import ShardingRules


def stage_param_shapes(cfg: tr.LMConfig, n_stages: int):
    """Layer stacks reshaped [L] → [n_stages, L/n_stages]."""
    assert cfg.n_layers % n_stages == 0
    per = cfg.n_layers // n_stages
    base = tr.param_shapes(cfg)
    staged = {}
    for k, (shape, dtype) in base["layers"].items():
        staged[k] = ((n_stages, per) + shape[1:], dtype)
    return {"embed": base["embed"], "final_norm": base["final_norm"],
            "layers": staged}


def stage_params_from_flat(params, n_stages: int):
    """Reshape a standard param pytree into the staged layout."""
    per = None
    staged = {}
    for k, a in params["layers"].items():
        L = a.shape[0]
        per = L // n_stages
        staged[k] = a.reshape((n_stages, per) + a.shape[1:])
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "layers": staged}


def build_pipeline_loss(cfg: tr.LMConfig, mesh: Mesh, rules: ShardingRules,
                        n_microbatches: int, pod_axis: str = "pod"):
    """Returns loss_fn(staged_params, tokens[M, mb, S]) → scalar.

    staged_params["layers"] leaves are [n_stages, per, ...] and sharded on
    the pod axis; tokens are replicated over pods (data axis shards mb)."""
    n_stages = mesh.shape[pod_axis]
    M = n_microbatches
    # inside the manual-pod body, constraints must not mention the pod axis
    from repro.models.sharding import lm_rules
    rules = lm_rules(mesh, data_axes=("data",))

    def body(staged_params, tokens):
        # inside: layers leaves are [1, per, ...]; drop the stage axis
        lp = jax.tree.map(lambda a: a[0], staged_params["layers"])
        embed = staged_params["embed"]          # replicated
        final_norm = staged_params["final_norm"]
        stage = jax.lax.axis_index(pod_axis)
        is_first = stage == 0
        is_last = stage == n_stages - 1

        mb, S = tokens.shape[1], tokens.shape[2]
        D = cfg.d_model
        positions = jnp.broadcast_to(jnp.arange(S), (mb, S))

        def run_stage(x):
            def layer_body(x, p):
                x, _, _ = tr._layer(x, p, cfg, rules, "G", positions)
                return x, None
            lb = jax.checkpoint(layer_body) if cfg.remat else layer_body
            x, _ = jax.lax.scan(lb, x, lp)
            return x

        def _final_loss(x, toks):
            xh = tr.nn.rms_norm(x, final_norm, cfg.norm_eps)
            inputs = xh[:, :-1]
            labels = toks[:, 1:]
            T = S - 1
            ch = min(cfg.loss_chunk, T)
            nf = T // ch

            def chunk_loss(xc, lc):
                logits = (xc @ embed.T).astype(jnp.float32)
                logits = rules.constraint(logits, "batch", None, "vocab")
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
                return jnp.sum(lse - ll)

            tot = jnp.zeros((), jnp.float32)
            for i in range(nf):
                tot = tot + chunk_loss(inputs[:, i * ch:(i + 1) * ch],
                                       labels[:, i * ch:(i + 1) * ch])
            if nf * ch < T:
                tot = tot + chunk_loss(inputs[:, nf * ch:], labels[:, nf * ch:])
            return tot / (mb * T)

        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            x_in, loss_acc = carry
            # microbatch index this stage works on at tick t
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            idx = jnp.clip(mb_idx, 0, M - 1)
            toks = jax.lax.dynamic_index_in_dim(tokens, idx, axis=0,
                                                keepdims=False)
            fresh = jnp.take(embed, toks, axis=0).astype(cfg.dtype)
            x = jnp.where(is_first, fresh, x_in)
            x = run_stage(x)
            lval = _final_loss(x, toks)
            loss_acc = loss_acc + jnp.where(active & is_last, lval, 0.0)
            # hand activations to the next stage
            x_next = jax.lax.ppermute(x, pod_axis, perm)
            return (x_next, loss_acc), None

        x0 = jnp.zeros((mb, S, D), cfg.dtype)
        (x_last, loss_acc), _ = jax.lax.scan(
            tick, (x0, jnp.zeros((), jnp.float32)),
            jnp.arange(M + n_stages - 1))
        # only the last stage holds the loss; share it
        return jax.lax.psum(loss_acc, pod_axis) / M

    layer_keys = stage_param_shapes(cfg, n_stages)["layers"].keys()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=({"embed": P(), "final_norm": P(),
                   "layers": {k: P(pod_axis) for k in layer_keys}},
                  P()),
        out_specs=P(),
        axis_names={pod_axis}, check_vma=False)
    return fn
