"""ELLPACK SpMV Pallas TPU kernel — the IRLS solver's PCG hot loop.

TPU adaptation of the paper's per-process CSR matvec (DESIGN.md §2): CSR has
ragged rows (branchy, serial on a vector unit), ELLPACK pads every row to a
fixed lane count ``k`` so the multiply-accumulate is perfectly regular: for
road networks k≈8, for 26-connected MRI grids k≈32.

The neighbour gather ``v[cols]`` is NOT in the kernel: Mosaic lowers only
2-D gathers within a vector register, not a row gather from an n-long
vector.  The ``ops.ell_spmv`` wrapper gathers in XLA and hands the kernel
the (n, k) neighbour values, so every operand streams through VMEM in row
tiles and no block grows with n.

Tiling scheme
-------------
grid = (n // ROWS_PER_BLOCK,)
  vn    block : (R, k)  f32   VMEM   neighbour voltages v[cols]
  vals  block : (R, k)  f32   VMEM
  diag  block : (R,)    f32   VMEM
  v     block : (R,)    f32   VMEM   the rows' own voltages
  out   block : (R,)    f32   VMEM

R = ROWS_PER_BLOCK = 1024: XLA tiles 1-D float32 arrays in 1024-element
tiles on TPU, and Mosaic refuses a 1-D block that does not match them.
VMEM per step (k ≤ 64, f32, double-buffered): ≤ 2·2·1024·64·4 B = 1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_BLOCK = 1024


def _ell_spmv_kernel(vn_ref, vals_ref, diag_ref, v_ref, out_ref):
    acc = jnp.sum(vals_ref[...] * vn_ref[...], axis=1)   # lane reduce
    out_ref[...] = diag_ref[...] * v_ref[...] + acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def ell_spmv_pallas(vn: jax.Array, vals: jax.Array, diag: jax.Array,
                    v: jax.Array, *, interpret: bool = False) -> jax.Array:
    """y = diag⊙v + Σ_lane vals⊙vn, with ``vn = v_full[cols]`` gathered by
    the caller (see ref.ell_spmv_ref).

    n must be a multiple of ROWS_PER_BLOCK (the ops.py wrapper pads).
    """
    n, k = vn.shape
    assert n % ROWS_PER_BLOCK == 0, n
    tile = pl.BlockSpec((ROWS_PER_BLOCK, k), lambda i: (i, 0))
    row = pl.BlockSpec((ROWS_PER_BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _ell_spmv_kernel,
        grid=(n // ROWS_PER_BLOCK,),
        in_specs=[tile, tile, row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((n,), v.dtype),
        interpret=interpret,
    )(vn, vals, diag, v)
