"""Incidence-operator primitives for the IRLS min-cut solver.

The paper's objective is ``min ‖C B x‖₁`` (eq. 1) where ``B`` is the oriented
edge-node incidence matrix and ``C = diag(c)``.  We never materialize ``B``:
on device a graph is the triplet of arrays ``(src, dst, c)`` plus terminal
weights, and the two operators we need are

* ``incidence_apply``   — ``(B x)_e   = x[src_e] - x[dst_e]``   (gather)
* ``incidence_t_apply`` — ``(Bᵀ y)_u  = Σ_{e: src_e=u} y_e - Σ_{e: dst_e=u} y_e``
  (``segment_sum`` scatter)

Terminal edges are kept separate (the STInstance layout of §3.3): the voltage
vector ``v`` covers only the n non-terminal nodes, with the boundary condition
x_s = 1, x_t = 0 folded in analytically.  The edge residual vector therefore
has three segments::

    z = [ c_e (v[src]-v[dst])   for non-terminal edges   ]
        [ c_su (1 - v[u])       for terminal s-edges      ]
        [ c_tu (v[u] - 0)       for terminal t-edges      ]

which is exactly ``C B x`` on the full graph restricted to the free variables.
All functions are jit-safe and shard_map-safe (pure gathers/segment ops).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.trace import named_scope


class DeviceGraph(NamedTuple):
    """Device-resident s-t instance (see graphs.structures.STInstance).

    src, dst : int32[m]   non-terminal edge endpoints
    c        : f[m]       non-terminal edge weights
    c_s, c_t : f[n]       terminal edge weights to s / t (0 where absent)
    """

    src: jax.Array
    dst: jax.Array
    c: jax.Array
    c_s: jax.Array
    c_t: jax.Array

    @property
    def n(self) -> int:
        return self.c_s.shape[0]

    @property
    def m(self) -> int:
        return self.src.shape[0]


def device_graph_from_instance(inst, dtype=jnp.float32) -> DeviceGraph:
    """Move a host STInstance onto the default device."""
    import numpy as np

    return DeviceGraph(
        src=jnp.asarray(np.asarray(inst.graph.src), dtype=jnp.int32),
        dst=jnp.asarray(np.asarray(inst.graph.dst), dtype=jnp.int32),
        c=jnp.asarray(np.asarray(inst.graph.weight), dtype=dtype),
        c_s=jnp.asarray(np.asarray(inst.s_weight), dtype=dtype),
        c_t=jnp.asarray(np.asarray(inst.t_weight), dtype=dtype),
    )


def edge_residuals(g: DeviceGraph, v: jax.Array):
    """``C B x`` with the boundary condition folded in.

    Returns (z_edges, z_s, z_t): the weighted differences along non-terminal
    edges, terminal s-edges and terminal t-edges.
    """
    z_edges = g.c * (v[g.src] - v[g.dst])
    z_s = g.c_s * (1.0 - v)
    z_t = g.c_t * v
    return z_edges, z_s, z_t


@named_scope("irls.objective")
def smoothed_objective(g: DeviceGraph, v: jax.Array, eps: float) -> jax.Array:
    """S_ε(x) = Σ_e sqrt((CBx)_e² + ε²)  (eq. 9), full-graph edge sum.

    Terminal entries with zero capacity contribute the constant ε each; we
    exclude them so S_ε → ‖CBx‖₁ as ε → 0 (matches the paper's objective on
    the actual edge set).
    """
    z_e, z_s, z_t = edge_residuals(g, v)
    s = jnp.sum(jnp.sqrt(z_e * z_e + eps * eps))
    s += jnp.sum(jnp.where(g.c_s > 0, jnp.sqrt(z_s * z_s + eps * eps), 0.0))
    s += jnp.sum(jnp.where(g.c_t > 0, jnp.sqrt(z_t * z_t + eps * eps), 0.0))
    return s


@named_scope("irls.objective")
def l1_objective(g: DeviceGraph, v: jax.Array) -> jax.Array:
    """Exact ‖C B x‖₁ (the fractional cut value of the embedding x)."""
    z_e, z_s, z_t = edge_residuals(g, v)
    return jnp.abs(z_e).sum() + jnp.abs(z_s).sum() + jnp.abs(z_t).sum()


def scatter_edge_to_node(g: DeviceGraph, y: jax.Array) -> jax.Array:
    """``Bᵀ y`` over the non-terminal edges only: +y into src, −y into dst."""
    out = jax.ops.segment_sum(y, g.src, num_segments=g.n)
    out = out - jax.ops.segment_sum(y, g.dst, num_segments=g.n)
    return out
