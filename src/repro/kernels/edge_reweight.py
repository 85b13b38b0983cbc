"""Fused IRLS edge-sweep Pallas TPU kernels (paper eq. 4 → eq. 8).

Two generations of fusion live here:

``edge_reweight_pallas`` — one pass over the COO edge list computes, per
edge,

    z_e = c_e · Δv_e                           (scale the voltage drop)
    w_e = sqrt(z_e² + ε²)                      (smoothed ℓ1 weight)
    r_e = c_e² / w_e                           (reweighted conductance)

The unfused jnp path materializes z, w and r separately (3 HBM round trips
over m-length vectors); the kernel keeps everything in VREGs so the edge
arrays stream through VMEM exactly once.  Diagonal assembly still needs a
segment_sum scatter OUTSIDE the kernel — which is why the hot path moved on
to the single-sweep kernel below.

``fused_ell_sweep_pallas`` — the whole per-IRLS-iteration system in ONE
row-parallel sweep over the slot-major (ELL) edge data: reweight, the ELL
value fill (vals = −r), the L̃ diagonal (lane reduction + terminal
conductances) and the RHS (r_s) come out of a single read of
``vn/c_ell/c_s/c_t/v``.  The edge→slot scatter happens once per SOLVE
(core/laplacian.ell_edge_weights stages c into ``c_ell``); per iteration
there is no scatter at all — each undirected edge is evaluated once per
direction (z² is symmetric, both copies agree), trading ≤2× redundant FLOPs
for a race-free, perfectly regular (R, k) tile.  Replaces four separate
passes (reweight, fill_ell, diag segment_sum, rhs) of the unfused path.

Neither kernel gathers: Mosaic lowers no row gather from an n-long vector,
so the ops.py wrappers gather the voltages in XLA (``Δv = v[src] − v[dst]``
per edge, ``vn = v[cols]`` per ELL slot) and the kernels stream fixed tiles.
ε is a scalar in SMEM.

Tiling: ``edge_reweight`` grids over edge blocks (E = 4096 edges per step);
``fused_ell_sweep`` grids over row blocks (R = 1024 rows, like ell_spmv).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ell_spmv import ROWS_PER_BLOCK   # fused sweep shares the SpMV row tile

EDGES_PER_BLOCK = 4096


def _smem_scalar(eps, dtype) -> jax.Array:
    return jnp.reshape(jnp.asarray(eps, dtype), (1,))


def _edge_reweight_kernel(eps_ref, dv_ref, c_ref, r_ref):
    eps = eps_ref[0]
    c = c_ref[...]
    z = c * dv_ref[...]
    r_ref[...] = (c * c) * jax.lax.rsqrt(z * z + eps * eps)


@functools.partial(jax.jit, static_argnames=("interpret",))
def edge_reweight_pallas(dv: jax.Array, c: jax.Array, eps: jax.Array,
                         *, interpret: bool = False) -> jax.Array:
    """r_e = c² / sqrt((c·Δv)² + ε²) with ``dv = v[src] − v[dst]``
    gathered by the caller (see ref.edge_reweight_ref).

    m must be a multiple of EDGES_PER_BLOCK (the ops.py wrapper pads)."""
    m = dv.shape[0]
    assert m % EDGES_PER_BLOCK == 0, m
    edges = pl.BlockSpec((EDGES_PER_BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _edge_reweight_kernel,
        grid=(m // EDGES_PER_BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), edges, edges],
        out_specs=edges,
        out_shape=jax.ShapeDtypeStruct((m,), c.dtype),
        interpret=interpret,
    )(_smem_scalar(eps, c.dtype), dv, c)


def _fused_ell_sweep_kernel(eps_ref, vn_ref, ce_ref, cs_ref, ct_ref, v_ref,
                            vals_ref, diag_ref, rs_ref, rt_ref):
    eps = eps_ref[0]
    ce = ce_ref[...]                      # (R, k) slot-major edge weights
    rows = v_ref[...]                     # (R,) v[u]
    z = ce * (rows[:, None] - vn_ref[...])
    r = (ce * ce) * jax.lax.rsqrt(z * z + eps * eps)
    vals_ref[...] = -r
    cs = cs_ref[...]
    ct = ct_ref[...]
    z_s = cs * (1.0 - rows)
    z_t = ct * rows
    r_s = jnp.where(cs > 0, (cs * cs) * jax.lax.rsqrt(z_s * z_s + eps * eps),
                    0.0)
    r_t = jnp.where(ct > 0, (ct * ct) * jax.lax.rsqrt(z_t * z_t + eps * eps),
                    0.0)
    rs_ref[...] = r_s
    rt_ref[...] = r_t
    diag_ref[...] = jnp.sum(r, axis=1) + r_s + r_t


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_ell_sweep_pallas(vn: jax.Array, c_ell: jax.Array,
                           c_s: jax.Array, c_t: jax.Array, v: jax.Array,
                           eps: jax.Array, *, interpret: bool = False):
    """(vals, diag, r_s, r_t) = one sweep over the slot-major edge data
    (see ref.fused_ell_sweep_ref).  ``vn = v_full[cols]`` is gathered by the
    caller and ``v`` holds the rows' own voltages; n must be a multiple of
    ROWS_PER_BLOCK (the ops.py wrapper pads)."""
    n, k = vn.shape
    assert n % ROWS_PER_BLOCK == 0, n
    row = pl.BlockSpec((ROWS_PER_BLOCK,), lambda i: (i,))
    tile = pl.BlockSpec((ROWS_PER_BLOCK, k), lambda i: (i, 0))
    return pl.pallas_call(
        _fused_ell_sweep_kernel,
        grid=(n // ROWS_PER_BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # eps
                  tile, tile, row, row, row],              # vn c_ell cs ct v
        out_specs=[tile, row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), v.dtype),      # vals
            jax.ShapeDtypeStruct((n,), v.dtype),        # diag
            jax.ShapeDtypeStruct((n,), v.dtype),        # r_s
            jax.ShapeDtypeStruct((n,), v.dtype),        # r_t
        ],
        interpret=interpret,
    )(_smem_scalar(eps, v.dtype), vn, c_ell, c_s, c_t, v)
