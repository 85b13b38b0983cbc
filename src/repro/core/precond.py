"""Preconditioners for the reduced-Laplacian PCG (paper §3.1–3.2).

The paper's choice is block Jacobi: blocks come from a k-way partition of the
non-terminal graph, factorized once per IRLS iteration (LU / ILU(0)) and
applied in parallel.  Sparse triangular solves are sequential and branchy —
bad on TPU — so we ADAPT the insight to the MXU (DESIGN.md §2):

* the nodes are reordered so each part is contiguous and padded to a fixed
  block size ``bs``;
* each IRLS iteration the block diagonal of ``L̃`` is scattered into a batched
  dense tensor ``A[p, bs, bs]`` and factorized with one **batched Cholesky**;
* each PCG preconditioning step is then a **batched triangular solve** (or,
  optionally, a batched GEMM against the explicit inverse — pure MXU work,
  see kernels/block_diag_matmul.py).

This keeps the paper's structure exactly — "precondition with the
partition-local subsystem, refactor cheaply once per IRLS iteration" — in a
TPU-native dense-batched form.  A plain (point) Jacobi and a Chebyshev
polynomial preconditioner are provided as cheaper/collective-free options.

Strategies are looked up through ``REGISTRY`` (name → factory) so new
preconditioners plug into the IRLS drivers without touching them: register
with ``@register("name")`` a factory ``(rw, matvec, cfg, block_plan) →
apply_fn | None`` (None = unpreconditioned CG).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import named_scope
from .incidence import DeviceGraph
from .laplacian import Reweighted

# factory signature: (rw, matvec, cfg, block_plan) -> apply_fn | None
PrecondFactory = Callable[..., Optional[Callable[[jax.Array], jax.Array]]]

REGISTRY: Dict[str, PrecondFactory] = {}


def register(name: str):
    """Register a preconditioner factory under ``cfg.precond == name``."""
    def deco(fn: PrecondFactory) -> PrecondFactory:
        REGISTRY[name] = fn
        return fn
    return deco


@named_scope("irls.factor")
def make_preconditioner(name: str, rw: Reweighted, matvec, cfg,
                        block_plan: Optional["BlockPlan"] = None):
    """Resolve ``name`` through REGISTRY and build the per-iteration apply.

    Returns a callable ``x → M⁻¹x`` or None (identity).  Raises ValueError
    on unknown names, listing what is registered.  Construction (block
    assembly, Cholesky, explicit inverse) runs under the ``irls.factor``
    scope; the apply runs where PCG calls it, under ``irls.pcg``."""
    try:
        factory = REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown preconditioner {name!r}; "
                         f"registered: {sorted(REGISTRY)}") from None
    return factory(rw, matvec, cfg, block_plan)


class BlockPlan(NamedTuple):
    """Static block-Jacobi scatter plan (built once on host, like the paper's
    one-time symbolic factorization).

    node_block : int32[n]       block id of each (reordered) node
    node_slot  : int32[n]       position of each node inside its block
    intra_e    : int32[mi]      edge ids with both endpoints in one block
    intra_b    : int32[mi]      that block id
    intra_i/j  : int32[mi]      local slots of src/dst inside the block
    p, bs      : static ints    number of blocks / padded block size
    """

    node_block: jax.Array
    node_slot: jax.Array
    intra_e: jax.Array
    intra_b: jax.Array
    intra_i: jax.Array
    intra_j: jax.Array
    p: int
    bs: int


def build_block_plan(src, dst, labels, p: int, pad_to_multiple: int = 8) -> BlockPlan:
    """Host-side plan construction.  ``labels`` must already correspond to the
    *reordered* node ids (contiguous ranges per part)."""
    import numpy as np

    labels = np.asarray(labels, dtype=np.int64)
    n = labels.shape[0]
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    counts = np.bincount(labels, minlength=p)
    bs = int(counts.max()) if n else 1
    bs = max(8, -(-bs // pad_to_multiple) * pad_to_multiple)
    # slot within block = rank among same-label nodes (labels are sorted
    # contiguous after partition_order, so a simple offset works)
    starts = np.zeros(p + 1, dtype=np.int64)
    starts[1:] = np.cumsum(counts)
    slot = np.arange(n) - starts[labels]
    same = labels[src] == labels[dst]
    ie = np.nonzero(same)[0]
    return BlockPlan(
        node_block=jnp.asarray(labels, dtype=jnp.int32),
        node_slot=jnp.asarray(slot, dtype=jnp.int32),
        intra_e=jnp.asarray(ie, dtype=jnp.int32),
        intra_b=jnp.asarray(labels[src[ie]], dtype=jnp.int32),
        intra_i=jnp.asarray(slot[src[ie]], dtype=jnp.int32),
        intra_j=jnp.asarray(slot[dst[ie]], dtype=jnp.int32),
        p=int(p),
        bs=int(bs),
    )


def assemble_blocks(plan: BlockPlan, rw: Reweighted) -> jax.Array:
    """Scatter the block diagonal of L̃ into A[p, bs, bs].

    The diagonal uses the FULL L̃ diagonal (including cut-edge and terminal
    conductances), so every block is strictly diagonally dominant ⇒ SPD even
    with padding (pad slots get identity).
    """
    p, bs = plan.p, plan.bs
    A = jnp.zeros((p, bs, bs), dtype=rw.diag.dtype)
    r_in = rw.r[plan.intra_e]
    A = A.at[plan.intra_b, plan.intra_i, plan.intra_j].add(-r_in)
    A = A.at[plan.intra_b, plan.intra_j, plan.intra_i].add(-r_in)
    A = A.at[plan.node_block, plan.node_slot, plan.node_slot].add(rw.diag)
    # identity on padded slots keeps the batched Cholesky nonsingular
    occupied = jnp.zeros((p, bs), dtype=rw.diag.dtype)
    occupied = occupied.at[plan.node_block, plan.node_slot].set(1.0)
    eye = jnp.eye(bs, dtype=rw.diag.dtype)
    A = A + eye * (1.0 - occupied)[:, None, :]
    return A


class BlockJacobi(NamedTuple):
    """Factorized block-Jacobi preconditioner state (per IRLS iteration)."""

    chol: jax.Array          # [p, bs, bs] lower Cholesky factors
    inv: Optional[jax.Array]  # [p, bs, bs] explicit inverses (MXU apply path)
    plan: BlockPlan


def factorize_blocks(plan: BlockPlan, rw: Reweighted,
                     explicit_inverse: bool = False) -> BlockJacobi:
    A = assemble_blocks(plan, rw)
    chol = jnp.linalg.cholesky(A)
    inv = None
    if explicit_inverse:
        eye = jnp.broadcast_to(jnp.eye(plan.bs, dtype=A.dtype),
                               (plan.p, plan.bs, plan.bs))
        inv = jax.scipy.linalg.cho_solve((chol, True), eye)
    return BlockJacobi(chol=chol, inv=inv, plan=plan)


def gather_blocks(plan: BlockPlan, x: jax.Array) -> jax.Array:
    xb = jnp.zeros((plan.p, plan.bs), dtype=x.dtype)
    return xb.at[plan.node_block, plan.node_slot].set(x)


def scatter_blocks(plan: BlockPlan, xb: jax.Array) -> jax.Array:
    return xb[plan.node_block, plan.node_slot]


def apply_block_jacobi(M: BlockJacobi, x: jax.Array) -> jax.Array:
    """y = M⁻¹x via batched triangular solves (or batched GEMM when the
    explicit inverse was formed — see kernels/ops.block_diag_matmul)."""
    xb = gather_blocks(M.plan, x)  # [p, bs]
    if M.inv is not None:
        yb = jnp.einsum("pij,pj->pi", M.inv, xb,
                        precision=jax.lax.Precision.HIGHEST)
    else:
        yb = jax.scipy.linalg.cho_solve((M.chol, True), xb[..., None])[..., 0]
    return scatter_blocks(M.plan, yb)


# ---------------------------------------------------------------------------
# Point Jacobi + Chebyshev polynomial options
# ---------------------------------------------------------------------------

def jacobi_apply(diag: jax.Array, x: jax.Array) -> jax.Array:
    return x / diag


def make_chebyshev_apply(matvec: Callable[[jax.Array], jax.Array],
                         diag: jax.Array, degree: int = 4,
                         lam_max_scale: float = 1.1):
    """Chebyshev polynomial preconditioner for the Jacobi-scaled operator
    D^{-1/2} L̃ D^{-1/2} whose spectrum sits in (0, 2).

    Collective-free inner iterations: each application is ``degree`` extra
    matvecs and no factorization — the trade-off explored in §Perf.
    """
    dh = jnp.sqrt(diag)
    lam_max = 2.0 * lam_max_scale  # Gershgorin bound for scaled Laplacian
    lam_min = lam_max / 30.0
    theta = 0.5 * (lam_max + lam_min)
    delta = 0.5 * (lam_max - lam_min)

    def scaled_mv(y):
        return matvec(y / dh) / dh

    def apply(x):
        # Chebyshev semi-iteration (Saad, Iterative Methods §12.3) for the
        # symmetrically scaled system; z0 = 0.  Fixed polynomial ⇒ a valid
        # SPD preconditioner for CG.
        b = x / dh
        r = b
        d = r / theta
        z = d
        sigma = theta / delta
        rho = 1.0 / sigma
        for _ in range(degree - 1):
            r = b - scaled_mv(z)
            rho_next = 1.0 / (2.0 * sigma - rho)
            d = rho_next * rho * d + (2.0 * rho_next / delta) * r
            z = z + d
            rho = rho_next
        return z / dh

    return apply


# ---------------------------------------------------------------------------
# Registry entries (the former if/elif chain of the IRLS drivers)
# ---------------------------------------------------------------------------

@register("none")
def _make_none(rw, matvec, cfg, block_plan):
    return None


@register("jacobi")
def _make_jacobi(rw, matvec, cfg, block_plan):
    diag = rw.diag
    return lambda x: jacobi_apply(diag, x)


@register("chebyshev")
def _make_chebyshev(rw, matvec, cfg, block_plan):
    return make_chebyshev_apply(matvec, rw.diag, cfg.cheby_degree)


@register("block_jacobi")
def _make_block_jacobi(rw, matvec, cfg, block_plan):
    """Block Jacobi needs a partition plan; without one (e.g. a driver that
    skipped partitioning) it degrades to point Jacobi, matching the scanned
    driver's historical behaviour."""
    if block_plan is None:
        return _make_jacobi(rw, matvec, cfg, block_plan)
    M = factorize_blocks(block_plan, rw,
                         getattr(cfg, "explicit_block_inverse", False))
    if getattr(cfg, "use_pallas", False) and M.inv is not None:
        from repro.kernels import ops as kops
        return lambda x: scatter_blocks(
            M.plan, kops.block_diag_matvec(M.inv, gather_blocks(M.plan, x)))
    return lambda x: apply_block_jacobi(M, x)
