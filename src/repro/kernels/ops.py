"""Jit'd dispatch wrappers around the Pallas kernels.

On TPU the kernels run compiled; on CPU they run in ``interpret=True`` mode,
which executes the kernel body in Python — the correctness contract the
tests enforce against ref.py.  Any other backend is an error: a kernel never
falls back in silence.  The wrappers own all padding so callers never see
the block-size requirements, and they do the voltage gathers in XLA (the
kernels stream fixed tiles; see ell_spmv.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .block_diag_matmul import block_diag_matvec_pallas
from .edge_reweight import (EDGES_PER_BLOCK, edge_reweight_pallas,
                            fused_ell_sweep_pallas)
from .ell_spmv import ROWS_PER_BLOCK, ell_spmv_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas path for backend {backend!r}: the "
                           f"kernels compile for TPU and interpret on CPU")
    return backend == "cpu"


def _pad_to(x: jax.Array, mult: int, axis: int = 0, value=0):
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=value)


def ell_spmv(cols: jax.Array, vals: jax.Array, diag: jax.Array,
             v: jax.Array) -> jax.Array:
    """ELLPACK SpMV (kernel on TPU / interpret on CPU).  Pads the row count
    to ROWS_PER_BLOCK; padded rows have diag=0, vals=0 → output 0."""
    n = v.shape[0]
    vn = _pad_to(v[cols], ROWS_PER_BLOCK)
    y = ell_spmv_pallas(vn, _pad_to(vals, ROWS_PER_BLOCK),
                        _pad_to(diag, ROWS_PER_BLOCK),
                        _pad_to(v, ROWS_PER_BLOCK), interpret=_interpret())
    return y[:n]


def edge_reweight_r(src: jax.Array, dst: jax.Array, c: jax.Array,
                    v: jax.Array, eps) -> jax.Array:
    """Fused reweighted conductances r_e (padded edges get c=0 → r=0)."""
    m = src.shape[0]
    dv = _pad_to(v[src] - v[dst], EDGES_PER_BLOCK)
    r = edge_reweight_pallas(dv, _pad_to(c, EDGES_PER_BLOCK),
                             jnp.asarray(eps, v.dtype),
                             interpret=_interpret())
    return r[:m]


def edge_reweight(g, v: jax.Array, eps):
    """Drop-in replacement for core.laplacian.reweight backed by the fused
    kernel: kernel computes r; terminal conductances + diagonal assembly
    (segment_sum scatters) stay in XLA."""
    from repro.core.laplacian import Reweighted

    r = edge_reweight_r(g.src, g.dst, g.c, v, eps)
    z_s = g.c_s * (1.0 - v)
    z_t = g.c_t * v
    r_s = jnp.where(g.c_s > 0,
                    (g.c_s * g.c_s) / jnp.sqrt(z_s * z_s + eps * eps), 0.0)
    r_t = jnp.where(g.c_t > 0,
                    (g.c_t * g.c_t) / jnp.sqrt(z_t * z_t + eps * eps), 0.0)
    deg = jax.ops.segment_sum(r, g.src, num_segments=g.n)
    deg = deg + jax.ops.segment_sum(r, g.dst, num_segments=g.n)
    return Reweighted(r=r, r_s=r_s, r_t=r_t, diag=deg + r_s + r_t)


def fused_ell_sweep(cols: jax.Array, c_ell: jax.Array, c_s: jax.Array,
                    c_t: jax.Array, v: jax.Array, eps):
    """Single-sweep IRLS system build (kernel on TPU / interpret on CPU):
    (vals, diag, r_s, r_t) from one pass over the slot-major edge data.
    Pads the row count to ROWS_PER_BLOCK; padded rows carry c_ell = c_s =
    c_t = 0 → all outputs 0 there, sliced off before returning.

    ``v`` may be longer than the row count (the halo-extended gather vector
    of the sharded solver — its first ``cols.shape[0]`` entries are the row
    voltages, and ``cols`` may gather from the tail)."""
    n = cols.shape[0]
    pad = lambda x: _pad_to(x, ROWS_PER_BLOCK)
    vals, diag, r_s, r_t = fused_ell_sweep_pallas(
        pad(v[cols]), pad(c_ell), pad(c_s), pad(c_t), pad(v[:n]),
        jnp.asarray(eps, v.dtype), interpret=_interpret())
    return vals[:n], diag[:n], r_s[:n], r_t[:n]


def block_diag_matvec(blocks: jax.Array, x: jax.Array) -> jax.Array:
    """Batched block-diagonal matvec; pads bs up to a 128 multiple so the
    MXU matmul dims are hardware-aligned."""
    p, bs, _ = blocks.shape
    target = max(128, -(-bs // 128) * 128)
    if target != bs:
        blocks = jnp.pad(blocks, ((0, 0), (0, target - bs), (0, target - bs)))
        x = jnp.pad(x, ((0, 0), (0, target - bs)))
    y = block_diag_matvec_pallas(blocks, x, interpret=_interpret())
    return y[:, :bs]


__all__ = ["ell_spmv", "edge_reweight", "edge_reweight_r",
           "fused_ell_sweep", "block_diag_matvec", "ref"]
