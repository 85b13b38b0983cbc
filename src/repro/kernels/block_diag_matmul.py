"""Block-Jacobi apply Pallas TPU kernel: batched block-diagonal matvec.

The paper applies its block-Jacobi preconditioner with per-block sparse
LU/ILU(0) triangular solves (UMFPACK / PETSc).  Triangular solves are
inherently sequential along the dependency chain — a poor fit for the MXU.
The TPU-native adaptation (DESIGN.md §2): form the explicit block inverses
once per IRLS iteration (batched Cholesky + batched solve against I, done by
XLA), then every PCG preconditioning step is

    y[p] = inv_blocks[p] @ x[p]        p = 0..P-1

— pure batched GEMM work that lives on the MXU.  One IRLS iteration runs
~50 PCG steps, so the (more expensive) explicit inversion amortizes exactly
like the paper's "symbolic factorization once, numeric refactor per
iteration" argument.

Tiling: grid over blocks; each step loads one (bs, bs) block and its
(1, bs) slice of x into VMEM and issues an MXU matvec.  x travels as
(P, 1, bs) so the block's last two dimensions equal the array's.  bs is
padded to a multiple of 128 by ops.py so the matmul dims are
hardware-aligned; typical bs = 128–512 ⇒ 64 KiB–1 MiB per block in f32,
well inside VMEM.  The solver runs in float32, so the matmul asks for
HIGHEST precision rather than the MXU's default bf16 passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _block_diag_matvec_kernel(a_ref, x_ref, y_ref):
    # y = a @ x as the row vector x @ aᵀ: (1, bs) · (bs, bs)ᵀ → (1, bs)
    y = jax.lax.dot_general(x_ref[0], a_ref[0], (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_diag_matvec_pallas(blocks: jax.Array, x: jax.Array,
                             *, interpret: bool = False) -> jax.Array:
    """y[p] = blocks[p] @ x[p]  (see ref.block_diag_matvec_ref).

    blocks: f[P, bs, bs], x: f[P, bs] → f[P, bs].
    """
    p, bs, bs2 = blocks.shape
    assert bs == bs2 and x.shape == (p, bs)
    vec = pl.BlockSpec((1, 1, bs), lambda i: (i, 0, 0))
    y = pl.pallas_call(
        _block_diag_matvec_kernel,
        grid=(p,),
        in_specs=[pl.BlockSpec((1, bs, bs), lambda i: (i, 0, 0)), vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((p, 1, bs), x.dtype),
        interpret=interpret,
    )(blocks, x.reshape(p, 1, bs))
    return y.reshape(p, bs)
