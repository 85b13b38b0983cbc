"""Preconditioned conjugate gradients on the reduced Laplacian (paper §3.1).

Implemented as a single ``jax.lax.while_loop`` so the whole solve stays on
device (one fused program; jit/shard_map friendly).  Supports:

* warm starts (paper §3.1: x0 = previous IRLS solution, ~20% fewer iters),
* relative-residual stopping criterion (paper: ‖r‖/‖b‖ ≤ 1e-3),
* hard iteration cap (paper: 50 at scale, 300 in the §5.2 study),
* a residual-history trace (fixed-length buffer) for the Fig-1 benchmark.

Residual bookkeeping is hoisted onto squared norms: the stopping test is
``‖r‖² ≤ tol²·‖b‖²`` so the no-history path pays exactly one extra
reduction per iteration (the ``r·r`` vdot) and zero square roots — the
sqrt only happens when a history entry is recorded or at the very end.

Three variants share the same update rule:

* ``pcg``             — tolerance + cap ``while_loop`` (host driver).
* ``pcg_masked``      — fixed-shape early exit with EXPLICITLY masked
  updates: once a lane converges its state stops changing, so under
  ``jax.vmap`` a batch stops paying for finished instances (the batch
  runs max-over-lanes iterations, not ``max_iters``) and per-lane results
  are bit-identical whether solved alone or co-batched.  ``tol`` may be a
  traced scalar — the adaptive IRLS driver feeds it per iteration.
* ``pcg_fixed_iters`` — static ``lax.scan`` schedule (the dry-run form);
  ``record_history=False`` drops the per-iteration norm reduction from
  the program entirely.

The matvec and the preconditioner are passed as closures so the same code
path serves the single-host (ELL / Pallas), the oracle (dense) and the
sharded (shard_map collective) implementations.  The INNER PRODUCTS are
closures too (``dot``/``dot2``): the default is a local ``vdot``, and
the sharded solver passes cross-shard psum reductions
(``distributed.collectives.psum_dots``) — so ``pcg_masked`` and
``pcg_fixed_iters`` ARE the distributed PCG, not templates for one.
``dot2(r, z) → (r·z, r·r)`` exists so the convergence bookkeeping can ride
the same reduction as the CG recurrence: distributed callers fuse both
scalars into ONE psum of a stacked pair, which is what keeps the masked
(early-exit) schedule at zero extra collectives per step over the fixed
one.  Every shard sees the same reduced scalars, so under ``shard_map`` the
``while_loop`` trip count — the early exit — agrees on all shards by
construction.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import named_scope


class PCGResult(NamedTuple):
    x: jax.Array          # solution
    iters: jax.Array      # iterations taken (i32 scalar)
    rel_res: jax.Array    # final relative residual
    history: jax.Array    # f[max_iters+1] residual norms (NaN-padded)


def vdot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Full-float32 inner product: a TPU dot defaults to bf16 passes, which
    would round CG's step lengths."""
    return jnp.vdot(a, b, precision=jax.lax.Precision.HIGHEST)


def _resolve_dots(dot, dot2):
    """Default inner products: local vdot; ``dot2`` from ``dot`` (two
    reductions — XLA fuses them locally; distributed callers supply a
    genuinely fused single-psum version)."""
    if dot is None:
        dot = vdot
    if dot2 is None:
        def dot2(r, z, _dot=dot):
            return _dot(r, z), _dot(r, r)
    return dot, dot2


@named_scope("irls.pcg")
def pcg(matvec: Callable[[jax.Array], jax.Array],
        b: jax.Array,
        x0: Optional[jax.Array] = None,
        precond: Optional[Callable[[jax.Array], jax.Array]] = None,
        tol: float = 1e-3,
        max_iters: int = 300,
        record_history: bool = False) -> PCGResult:
    """Solve ``A x = b`` with A SPD given through ``matvec``.

    ``precond`` applies M⁻¹ (identity when None).  ``x0`` enables warm starts.
    ``tol`` may be a traced scalar (adaptive inner tolerances).
    """
    if precond is None:
        precond = lambda r: r
    x = jnp.zeros_like(b) if x0 is None else x0

    bb = vdot(b, b)
    # guard: b == 0 ⇒ x = 0 is exact; avoid dividing by zero
    bb = jnp.where(bb > 0, bb, 1.0)
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * bb

    r = b - matvec(x)
    z = precond(r)
    p = z
    rz = vdot(r, z)
    rr = vdot(r, r)

    hist_len = max_iters + 1 if record_history else 1
    history = jnp.full((hist_len,), jnp.nan, dtype=b.dtype)
    history = history.at[0].set(jnp.sqrt(rr / bb))

    def cond(state):
        _, _, _, _, rr, it, _ = state
        return jnp.logical_and(rr > tol2, it < max_iters)

    def body(state):
        x, r, p, rz, rr, it, hist = state
        Ap = matvec(p)
        pAp = vdot(p, Ap)
        alpha = rz / jnp.where(pAp != 0, pAp, 1.0)
        x = x + alpha * p
        r = r - alpha * Ap
        z = precond(r)
        rz_new = vdot(r, z)
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        p = z + beta * p
        rr = vdot(r, r)
        it = it + 1
        if record_history:
            hist = hist.at[it].set(jnp.sqrt(rr / bb))
        return x, r, p, rz_new, rr, it, hist

    state = (x, r, p, rz, rr, jnp.asarray(0, jnp.int32), history)
    x, r, p, rz, rr, it, history = jax.lax.while_loop(cond, body, state)
    return PCGResult(x=x, iters=it, rel_res=jnp.sqrt(rr / bb),
                     history=history)


@named_scope("irls.pcg")
def pcg_masked(matvec, b, x0=None, precond=None, tol=1e-3,
               max_iters: int = 50, dot=None, dot2=None) -> PCGResult:
    """Fixed-shape masked-update PCG with early exit (no history buffer).

    Same update rule as ``pcg`` but every state update is explicitly gated
    on the lane's own ``active`` flag, so a converged instance's (x, r, p)
    are frozen rather than merely unread.  Under ``jax.vmap`` the
    ``while_loop`` runs until EVERY lane converged (or ``max_iters``) —
    finished lanes ride along as no-ops, which is what makes co-batched
    results bit-identical to solo solves.  ``tol`` may be a traced scalar.

    ``dot``/``dot2`` — inner-product closures (see module docstring).  With
    the sharded psum dots, every scalar the stopping test reads is the SAME
    all-reduce result on every shard, so the early exit is taken exactly
    when all shards agree — and the ``dot2`` fusion keeps the step at the
    fixed schedule's collective count.
    """
    if precond is None:
        precond = lambda r: r
    dot, dot2 = _resolve_dots(dot, dot2)
    x = jnp.zeros_like(b) if x0 is None else x0

    bb = dot(b, b)
    bb = jnp.where(bb > 0, bb, 1.0)
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * bb

    r = b - matvec(x)
    z = precond(r)
    p = z
    rz, rr = dot2(r, z)

    def cond(state):
        _, _, _, _, rr, it = state
        return jnp.logical_and(rr > tol2, it < max_iters)

    def body(state):
        x, r, p, rz, rr, it = state
        active = rr > tol2
        Ap = matvec(p)
        pAp = dot(p, Ap)
        alpha = jnp.where(active, rz / jnp.where(pAp != 0, pAp, 1.0), 0.0)
        x = x + alpha * p
        r = r - alpha * Ap
        z = precond(r)
        rz_new, rr_new = dot2(r, z)
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        p = jnp.where(active, z + beta * p, p)
        rz = jnp.where(active, rz_new, rz)
        rr = jnp.where(active, rr_new, rr)
        it = it + jnp.where(active, 1, 0).astype(jnp.int32)
        return x, r, p, rz, rr, it

    state = (x, r, p, rz, rr, jnp.asarray(0, jnp.int32))
    x, r, p, rz, rr, it = jax.lax.while_loop(cond, body, state)
    return PCGResult(x=x, iters=it, rel_res=jnp.sqrt(rr / bb),
                     history=jnp.zeros((1,), dtype=b.dtype))


@named_scope("irls.pcg")
def pcg_fixed_iters(matvec, b, x0=None, precond=None, n_iters: int = 50,
                    record_history: bool = True, dot=None, dot2=None):
    """PCG with a fixed iteration count via ``lax.scan`` — fully static
    control flow.  This is the variant the dry-run lowers (while_loop also
    compiles under pjit, but a static schedule gives a deterministic HLO for
    the roofline term extraction).  ``record_history=False`` removes the
    per-iteration residual-norm reduction from the program (the scanned
    IRLS driver only consumes the FINAL relative residual — and under the
    sharded psum dots that is what makes the step exactly one ``p·Ap`` plus
    one ``r·z`` reduction: squared-norm bookkeeping, sqrt only on exit)."""
    if precond is None:
        precond = lambda r: r
    dot, dot2 = _resolve_dots(dot, dot2)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - matvec(x)
    z = precond(r)
    p = z
    rz = dot(r, z)

    def step(carry, _):
        x, r, p, rz = carry
        Ap = matvec(p)
        pAp = dot(p, Ap)
        alpha = rz / jnp.where(pAp != 0, pAp, 1.0)
        x = x + alpha * p
        r = r - alpha * Ap
        z = precond(r)
        if record_history:
            rz_new, rr = dot2(r, z)
            y = jnp.sqrt(jnp.maximum(rr, 0.0))
        else:
            rz_new = dot(r, z)
            y = None
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        p = z + beta * p
        return (x, r, p, rz_new), y

    (x, r, p, rz), res_hist = jax.lax.scan(step, (x, r, p, rz), None,
                                           length=n_iters)
    bb = dot(b, b)
    b_norm = jnp.sqrt(jnp.maximum(bb, 0.0))
    b_norm = jnp.where(b_norm > 0, b_norm, 1.0)
    rr_fin = dot(r, r)
    history = (res_hist / b_norm if record_history
               else jnp.zeros((1,), dtype=b.dtype))
    return PCGResult(x=x, iters=jnp.asarray(n_iters, jnp.int32),
                     rel_res=jnp.sqrt(jnp.maximum(rr_fin, 0.0)) / b_norm,
                     history=history)
