"""Sharded IRLS + PCG under ``jax.shard_map`` (the parallel PIRMCut of §3).

The whole IRLS(T) × PCG(K) nest runs as ONE jitted SPMD program over the
flattened device mesh.  Communication per PCG step:

  psum schedule : 1 × all-reduce(n)      (baseline)
  halo schedule : 1 × all-gather(p·b_sh) (partition-aware, b_sh ≪ n/p)

plus scalar psums for the CG dot products (squared-norm bookkeeping: one
``p·Ap`` reduction and one fused ``[r·z, r·r]`` pair reduction per step —
sqrt only on exit).  The block-Jacobi preconditioner is fully local to each
shard — its sub-blocks are nested inside the partition parts, so applying
it needs NO collectives (the paper's central argument for block Jacobi,
§4).

Both schedules run the SAME iteration core as the host/scanned backends:

* the PCG loops are ``core.pcg.pcg_fixed_iters`` / ``pcg_masked`` with the
  cross-shard inner products plugged in (``collectives.psum_dots``), and
* the adaptive early-exit schedule is ``core.adaptive`` — the convergence
  mask, patience counter and Eisenstat–Walker inner tolerance of PR 3,
  driven here by psum-reduced scalars: the fractional cut value is ONE
  extra scalar all-reduce per IRLS iteration, every shard reads identical
  reduced values, so all shards take the early exit in the same step and
  the masked PCG adds ZERO collectives per step over the fixed schedule.

Under ``cfg.fuse_edge_sweep`` (the default) the halo schedule restages the
local copy list into a per-shard ELL layout (``spmv.build_halo_ell``) and
builds each iteration's system — reweight → ELL values → diagonal → RHS —
in ONE pass over the local edges with the exported boundary values from
``halo_exchange`` (``core.laplacian.fused_ell_sweep``; the Pallas kernel
under ``cfg.use_pallas``).  The psum schedule's edge pass routes through
the same COO-flavored sweep (``spmv.coo_reweight``).

The same body is used (a) for numerical execution in the multi-device CPU
tests and (b) for the production-mesh dry-run (lower + compile only; the
abstract-plan path has no ELL staging and runs the unfused system build).
"""
from __future__ import annotations

import warnings
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import adaptive as sched
from repro.core import laplacian as lap
from repro.core.irls import IRLSConfig, eps_schedule_array
from repro.core.pcg import pcg_fixed_iters, pcg_masked, vdot
from repro.obs import trace
from repro.obs.metrics import get_registry
from .collectives import SOLVER_AXIS, flat_mesh, psum_dots
from .spmv import (HaloPlan, build_halo_ell, build_halo_plan,
                   build_psum_plan, coo_reweight, halo_exchange,
                   halo_l1_local, make_ell_halo_matvec, make_halo_matvec,
                   psum_matvec)


class Float32DivergenceWarning(UserWarning):
    """IRLS reweights ran into the float32 precision wall (see
    ``float32_divergence_threshold``)."""


def float32_divergence_threshold(eps: float) -> float:
    """Largest reweighted conductance float32 IRLS tolerates at this ε.

    The reweight r = c²/√((c·Δv)² + ε²) is bounded by max(c²)/ε, so a
    shrinking ε drives the conductance spread toward 1/ε.  In float32 the
    PCG quadratic forms lose ~εf32·κ of their value to rounding (εf32 ≈
    1.19e-7); once the spread reaches ~1/√(ε·εf32) the lost digits reach
    the residual scale √ε the stop test needs, and the iteration stalls or
    diverges (ROADMAP: ε = 1e-8 diverges in float32 while ε = 1e-6 is
    fine — thresholds ≈ 2.9e7 and 2.9e6 against reweights ~1e8 and ~1e6).
    """
    return 1.0 / float(np.sqrt(eps * np.finfo(np.float32).eps))


class HaloBlockPlan(NamedTuple):
    """Per-shard sub-block preconditioner plan (zero-collective apply).

    copy_b/copy_i/copy_j : i32[p, mc] sub-block / local slots of intra-block
                           directed copies (off-diagonal scatter targets)
    copy_id              : i32[p, mc] source copy index (into the ml axis)
    copy_valid           : f32[p, mc] 1 = real, 0 = padding
    node_b/node_s        : i32[p, nl] sub-block / slot of each local node
    nb, bs               : static — sub-blocks per shard / block size
    """

    copy_b: np.ndarray
    copy_i: np.ndarray
    copy_j: np.ndarray
    copy_id: np.ndarray
    copy_valid: np.ndarray
    node_b: np.ndarray
    node_s: np.ndarray
    nb: int
    bs: int


def build_halo_block_plan(plan: HaloPlan, target_bs: int = 128) -> HaloBlockPlan:
    """Split each shard's contiguous node range into fixed-size sub-blocks
    (node order already groups partition parts → sub-blocks inherit the
    partition locality the paper's preconditioner relies on)."""
    p, nl = plan.p, plan.nl
    bs = min(target_bs, nl)
    nb = -(-nl // bs)
    node_b = np.broadcast_to((np.arange(nl) // bs).astype(np.int32), (p, nl)).copy()
    node_s = np.broadcast_to((np.arange(nl) % bs).astype(np.int32), (p, nl)).copy()
    rows = []
    mc = 0
    for i in range(p):
        h, t, c = plan.heads[i], plan.tails_ext[i], plan.c[i]
        ok = (c > 0) & (t < nl) & ((h // bs) == (t // bs))
        ids = np.nonzero(ok)[0]
        rows.append(ids)
        mc = max(mc, len(ids))
    mc = max(8, -(-mc // 8) * 8)
    copy_b = np.zeros((p, mc), dtype=np.int32)
    copy_i = np.zeros((p, mc), dtype=np.int32)
    copy_j = np.zeros((p, mc), dtype=np.int32)
    copy_id = np.zeros((p, mc), dtype=np.int32)
    copy_valid = np.zeros((p, mc), dtype=np.float32)
    for i, ids in enumerate(rows):
        k = len(ids)
        h, t = plan.heads[i][ids], plan.tails_ext[i][ids]
        copy_b[i, :k] = (h // bs).astype(np.int32)
        copy_i[i, :k] = (h % bs).astype(np.int32)
        copy_j[i, :k] = (t % bs).astype(np.int32)
        copy_id[i, :k] = ids.astype(np.int32)
        copy_valid[i, :k] = 1.0
    return HaloBlockPlan(copy_b=copy_b, copy_i=copy_i, copy_j=copy_j,
                         copy_id=copy_id, copy_valid=copy_valid,
                         node_b=node_b, node_s=node_s, nb=nb, bs=bs)


def abstract_halo_plans(n: int, m: int, p: int, boundary_frac: float,
                        precond_bs: int = 128
                        ) -> Tuple["HaloPlan", "HaloBlockPlan"]:
    """Analytic plan SHAPES for dry-run lowering at scales where building a
    real instance on this host is pointless.  nl/ml/b_sh follow the same
    padding rules as build_halo_plan; boundary_frac comes from the real
    partitioner's measured cut fraction on small instances of the family.
    No ELL staging — the dry-run lowers the unfused system build."""
    pad8 = lambda x: max(8, -(-int(x) // 8) * 8)
    nl = pad8(-(-n // p))
    ml = pad8(2 * m / p * 1.05)
    b_sh = pad8(n * boundary_frac / p)
    sds = jax.ShapeDtypeStruct
    i32, f32, i64 = jnp.int32, jnp.float32, jnp.int64
    plan = HaloPlan(
        heads=sds((p, ml), i32), tails_ext=sds((p, ml), i32),
        c=sds((p, ml), f32), c_s=sds((p, nl), f32), c_t=sds((p, nl), f32),
        export=sds((p, b_sh), i32), node_valid=sds((p, nl), f32),
        perm=sds((n,), i64), n=n, nl=nl, b_sh=b_sh, p=p)
    bs = min(precond_bs, nl)
    nb = -(-nl // bs)
    mc = ml  # upper bound: every copy intra-block
    bplan = HaloBlockPlan(
        copy_b=sds((p, mc), i32), copy_i=sds((p, mc), i32),
        copy_j=sds((p, mc), i32), copy_id=sds((p, mc), i32),
        copy_valid=sds((p, mc), f32), node_b=sds((p, nl), i32),
        node_s=sds((p, nl), i32), nb=nb, bs=bs)
    return plan, bplan


class ShardedSolver:
    """Compiled sharded PIRMCut IRLS (halo or psum schedule).

    Runs the fixed ``n_irls × pcg_max_iters`` schedule by default, or the
    convergence-masked adaptive one when the config sets any of the
    early-exit knobs (``irls_tol`` / ``adaptive_tol`` — see
    core/adaptive.py); ``cfg.eps_schedule`` is honored (precomputed into
    the scan inputs, like the scanned backend).  ``solve`` returns
    ``(v, rels, iters)`` where ``iters`` is the PCG spend per IRLS
    iteration (parked at 0 once the adaptive mask froze the solve).
    """

    def __init__(self, instance, cfg: IRLSConfig, mesh: Optional[Mesh] = None,
                 schedule: str = "halo", labels: Optional[np.ndarray] = None,
                 precond_bs: int = 128, plans: Optional[tuple] = None,
                 halo_compression: Optional[str] = None):
        self.cfg = cfg
        self.halo_compression = halo_compression
        # kept for host-side diagnostics (the float32 divergence sentinel
        # reads the weights); None on the abstract-plans dry-run path
        self._instance = instance
        self._collectives: Optional[List[dict]] = None
        self._compiled = None  # cached AOT compile (collective stats + profiling)
        self.last_clamped = 0  # reweight-clamp hits of the latest solve()
        self.mesh = mesh if mesh is not None else flat_mesh()
        self.schedule = schedule
        self.p = int(np.prod(self.mesh.devices.shape))
        self._labels = labels
        self._precond_bs = precond_bs
        self.ell = None        # HaloEllPlan when the fused sweep is active
        # incremental refills: update_weights diffs the new weights against
        # the previous instance and, when the diff is sparse and support-
        # stable, patches the affected plan slots (halo c + ELL staging)
        # instead of re-running the host-side plan fills
        self.delta_stats = {"delta": 0, "rebuild": 0}
        self._copy_map = None  # directed copy -> (shard, ml slot), lazy
        if plans is not None:
            if schedule == "halo":
                if len(plans) == 3:
                    self.plan, self.block_plan, self.ell = plans
                else:
                    self.plan, self.block_plan = plans
            else:
                (self.plan,) = plans
        elif schedule == "halo":
            if labels is None:
                # partition here (not inside build_halo_plan) so the labels
                # survive for same-topology plan refills (update_weights)
                from repro.graphs import partition as gp
                self._labels = labels = gp.partition_kway(instance.graph, self.p)
            self.plan = build_halo_plan(instance, self.p, labels=labels)
            self.block_plan = build_halo_block_plan(self.plan, precond_bs)
            if cfg.fuse_edge_sweep:
                self.ell = build_halo_ell(self.plan)
        elif schedule == "psum":
            self.plan = build_psum_plan(instance, self.p)
        else:
            raise ValueError(schedule)
        self._fn = self._build_halo() if schedule == "halo" else self._build_psum()

    def update_weights(self, instance):
        """Refill the plan's weight arrays for a SAME-TOPOLOGY instance.

        The partition labels and the compiled SPMD program are reused — only
        the host-side plan fill (and the ELL weight restaging, when fused)
        is redone (identical shapes, so the jit cache hits).  The expensive
        phases (k-way partition, lowering, compile) are skipped entirely;
        this is the session API's sharded serving path.

        The refill itself is INCREMENTAL under weight drift: the new
        weights are diffed against the previous instance's, and a sparse
        support-stable diff (every changed edge stays positive, so the
        preconditioner's structural copy selection cannot move) patches
        only the affected halo-plan and ELL-staging slots — bit-equal to a
        full refill, since both write the same float32 values to the same
        slots.  Dense diffs, support flips and terminal-only topologies
        fall back to the full plan fill; ``delta_stats`` counts both paths.
        """
        if self.schedule == "halo" and self._try_delta_refill(instance):
            self._instance = instance
            self.delta_stats["delta"] += 1
            return
        self._instance = instance
        self.delta_stats["rebuild"] += 1
        if self.schedule == "halo":
            new_plan = build_halo_plan(instance, self.p, labels=self._labels)
            if (new_plan.nl, new_plan.b_sh, new_plan.heads.shape) != \
                    (self.plan.nl, self.plan.b_sh, self.plan.heads.shape):
                raise ValueError("update_weights requires the same topology "
                                 "(plan shapes changed)")
            self.plan = new_plan
            self.block_plan = build_halo_block_plan(new_plan, self._precond_bs)
            if self.ell is not None:
                new_ell = build_halo_ell(new_plan)
                if new_ell.cols.shape != self.ell.cols.shape:
                    raise ValueError("update_weights requires the same "
                                     "topology (ELL staging shapes changed)")
                self.ell = new_ell
        else:
            new_plan = build_psum_plan(instance, self.p)
            if (new_plan.n_pad, new_plan.src.shape) != \
                    (self.plan.n_pad, self.plan.src.shape):
                raise ValueError("update_weights requires the same topology "
                                 "(plan shapes changed)")
            self.plan = new_plan

    # refills stay incremental while the diff is this sparse; denser drift
    # amortizes better through the vectorized full plan fill
    DELTA_MAX_FRAC = 0.25

    def _directed_copy_slots(self):
        """Directed copy e ∈ [0, 2m) → (shard, ml slot) in the halo plan —
        the scatter targets of an incremental weight refill.  Replays the
        owner/selection order of ``build_halo_plan`` once per topology."""
        if self._copy_map is None:
            g = self._instance.graph
            perm, nl, p = self.plan.perm, self.plan.nl, self.p
            src = perm[np.asarray(g.src, dtype=np.int64)]
            dst = perm[np.asarray(g.dst, dtype=np.int64)]
            heads = np.concatenate([src, dst])
            h_own = np.minimum(heads // nl, p - 1)
            slot = np.empty(heads.shape[0], dtype=np.int64)
            for i in range(p):
                sel = np.nonzero(h_own == i)[0]
                slot[sel] = np.arange(sel.size)
            self._copy_map = (h_own.astype(np.int32),
                              slot.astype(np.int32))
        return self._copy_map

    def _try_delta_refill(self, instance) -> bool:
        """Patch the halo plan + ELL staging in place of a full refill.

        Applies when the edge-weight diff vs the previous instance is
        sparse AND support-stable (changed edges positive before and
        after — the block-preconditioner copy selection masks on c > 0, so
        a support flip changes plan STRUCTURE and needs the full path).
        Terminal weights are refreshed unconditionally (vectorized O(n),
        same expressions as the full fill).  Bit-equal to a full refill.
        """
        prev = self._instance
        if prev is None:
            return False
        plan = self.plan
        w_old = np.asarray(prev.graph.weight, dtype=np.float32)
        w_new = np.asarray(instance.graph.weight, dtype=np.float32)
        if w_old.shape != w_new.shape:
            return False
        m = w_new.shape[0]
        diff = np.flatnonzero(w_old != w_new)
        if diff.size > self.DELTA_MAX_FRAC * max(1, m):
            return False
        if diff.size and (np.any(w_old[diff] <= 0)
                          or np.any(w_new[diff] <= 0)):
            return False
        if diff.size:
            sh, sl = self._directed_copy_slots()
            idx = np.concatenate([diff, diff + m])
            vals = np.concatenate([w_new[diff], w_new[diff]])
            c = plan.c.copy()
            c[sh[idx], sl[idx]] = vals
            plan = plan._replace(c=c)
            if self.ell is not None:
                ce = self.ell.c_ell.copy()
                ce[sh[idx], self.ell.copy_row[sh[idx], sl[idx]],
                   self.ell.copy_lane[sh[idx], sl[idx]]] = vals
                self.ell = self.ell._replace(c_ell=ce)
        cs_new = np.asarray(instance.s_weight, dtype=np.float32)
        ct_new = np.asarray(instance.t_weight, dtype=np.float32)
        if (not np.array_equal(np.asarray(prev.s_weight, dtype=np.float32),
                               cs_new)
                or not np.array_equal(np.asarray(prev.t_weight,
                                                 dtype=np.float32),
                                      ct_new)):
            n, nl, p = plan.n, plan.nl, plan.p
            inv = np.empty_like(plan.perm)
            inv[plan.perm] = np.arange(n)
            cs = np.zeros(nl * p, dtype=np.float32)
            ct = np.zeros(nl * p, dtype=np.float32)
            cs[:n] = cs_new[inv]
            ct[:n] = ct_new[inv]
            plan = plan._replace(c_s=cs.reshape(p, nl),
                                 c_t=ct.reshape(p, nl))
        self.plan = plan
        return True

    # -- halo schedule --------------------------------------------------------
    def _build_halo(self):
        cfg = self.cfg
        axis = SOLVER_AXIS
        plan, bplan = self.plan, self.block_plan
        nl = plan.nl
        nb, bs = bplan.nb, bplan.bs
        use_block = cfg.precond in ("block_jacobi",)
        compression = self.halo_compression
        adaptive = sched.is_adaptive(cfg)
        fused = self.ell is not None
        use_pallas = cfg.use_pallas
        eps_np = eps_schedule_array(cfg)
        clamp = bool(cfg.reweight_clamp)
        eps_last = float(eps_np[-1]) if len(eps_np) else float(cfg.eps)
        n_base = 14

        def body(*args):
            loc = [a[0] for a in args]
            (heads, tails_ext, c, c_s, c_t, export, valid, copy_b, copy_i,
             copy_j, copy_id, copy_valid, node_b, node_s) = loc[:n_base]
            if fused:
                ell_cols, ell_c, copy_row, copy_lane = loc[n_base:]

            if clamp:
                # float32 mitigation: cap the reweights at the divergence
                # threshold cap = c_max·thresh(ε_last/c_max) =
                # √(c_max³/(ε_last·εf32)) so the conductance spread the PCG
                # quadratic forms see stays representable.  c_max is a
                # global reduce (one pmax, OUTSIDE the IRLS scan — weights
                # are loop constants), so every shard caps identically.
                eps_f32 = float(np.finfo(np.float32).eps)
                local_max = jnp.maximum(
                    jnp.max(c, initial=0.0),
                    jnp.maximum(jnp.max(c_s, initial=0.0),
                                jnp.max(c_t, initial=0.0)))
                c_max = jax.lax.pmax(local_max, axis)
                cap = jnp.sqrt(c_max ** 3 / (eps_last * eps_f32)).astype(
                    c.dtype)

            def local_dot(a, b_):
                return vdot(a * valid, b_ * valid)

            dot, dot2 = psum_dots(axis, local_dot)

            def exchange(x):
                return halo_exchange(x, export, axis, compression)

            def make_precond(r_copies, diag):
                if not use_block:
                    return lambda x: x / diag
                A = jnp.zeros((nb, bs, bs), dtype=diag.dtype)
                rvals = r_copies[copy_id] * copy_valid
                A = A.at[copy_b, copy_i, copy_j].add(-rvals)
                A = A.at[node_b, node_s, node_s].add(
                    jnp.where(valid > 0, diag, 0.0))
                occ = jnp.zeros((nb, bs), dtype=diag.dtype)
                occ = occ.at[node_b, node_s].max(valid)
                eye = jnp.eye(bs, dtype=diag.dtype)
                A = A + eye * (1.0 - occ)[:, None, :]
                chol = jnp.linalg.cholesky(A)

                def apply_M(x):
                    xb = jnp.zeros((nb, bs), dtype=x.dtype)
                    xb = xb.at[node_b, node_s].set(x * valid)
                    yb = jax.scipy.linalg.cho_solve((chol, True),
                                                    xb[..., None])[..., 0]
                    return yb[node_b, node_s] * valid
                return apply_M

            def system(v, eps, initial, ext):
                """One iteration's (matvec, b, per-copy r, diag).

                Fused: the whole build is ONE row-parallel sweep over the
                local ELL-staged edges with the halo-extended vector — the
                halo-aware fused edge sweep.  Unfused (dry-run/abstract
                plans, or ``fuse_edge_sweep=False``): the legacy per-copy
                passes.  ``ext`` is ``halo_exchange(v)`` (unused when
                ``initial`` — W⁰ = C needs no voltages).
                """
                nclamp = jnp.int32(0)
                if fused:
                    if initial:
                        r_s, r_t = c_s, c_t
                        vals = -ell_c
                        diag = jnp.sum(ell_c, axis=1) + r_s + r_t
                    else:
                        if use_pallas:
                            from repro.kernels import ops as kops
                            sweep = kops.fused_ell_sweep
                        else:
                            sweep = lap.fused_ell_sweep
                        vals, diag, r_s, r_t = sweep(ell_cols, ell_c, c_s,
                                                     c_t, ext, eps)
                        if clamp:
                            # ELL stores r negated (vals = −r); the sweep
                            # already folded r into diag, so subtract the
                            # excess back out instead of re-summing rows
                            excess = jnp.maximum(-vals - cap, 0.0)
                            vals = vals + excess
                            diag = diag - jnp.sum(excess, axis=1)
                            exc_s = jnp.maximum(r_s - cap, 0.0)
                            exc_t = jnp.maximum(r_t - cap, 0.0)
                            r_s, r_t = r_s - exc_s, r_t - exc_t
                            diag = diag - exc_s - exc_t
                            nclamp = (jnp.sum(excess > 0) + jnp.sum(exc_s > 0)
                                      + jnp.sum(exc_t > 0)).astype(jnp.int32)
                    diag = jnp.where(valid > 0, diag, 1.0)
                    # gather-back for the block-Jacobi assembly (one
                    # ml-element read against the sweep's 2m)
                    r_copies = -vals[copy_row, copy_lane]
                    mv_ell = make_ell_halo_matvec(ell_cols, vals, diag)

                    def mv(x):
                        return mv_ell(x, exchange(x))
                    return mv, r_s, r_copies, diag, nclamp
                if initial:
                    r, r_s, r_t = c, c_s, c_t
                else:
                    r = coo_reweight(heads, tails_ext, c, ext, eps,
                                     use_pallas)
                    r_s, r_t = lap.terminal_conductances(c_s, c_t,
                                                         ext[:nl], eps)
                    if clamp:
                        nclamp = (jnp.sum(r > cap) + jnp.sum(r_s > cap)
                                  + jnp.sum(r_t > cap)).astype(jnp.int32)
                        r = jnp.minimum(r, cap)
                        r_s = jnp.minimum(r_s, cap)
                        r_t = jnp.minimum(r_t, cap)
                deg = jax.ops.segment_sum(r, heads, num_segments=nl)
                diag = deg + r_s + r_t
                diag = jnp.where(valid > 0, diag, 1.0)
                mv_halo = make_halo_matvec(nl)

                def mv(x):
                    return mv_halo(exchange(x), heads, tails_ext, r, diag)
                return mv, r_s, r, diag, nclamp

            def solve_wls(v, eps, initial, x0, tol, ext):
                mv, b, r_copies, diag, nclamp = system(v, eps, initial, ext)
                M = make_precond(r_copies, diag)
                if adaptive:
                    res = pcg_masked(mv, b, x0=x0, precond=M, tol=tol,
                                     max_iters=cfg.pcg_max_iters,
                                     dot=dot, dot2=dot2)
                else:
                    res = pcg_fixed_iters(mv, b, x0=x0, precond=M,
                                          n_iters=cfg.pcg_max_iters,
                                          record_history=False,
                                          dot=dot, dot2=dot2)
                # clamp hits are a diagnostic: psum only when the clamp is
                # live so the default program keeps its collective census
                nc = (jax.lax.psum(nclamp, axis) if clamp
                      else jnp.int32(0))
                return res.x * valid, res.rel_res, res.iters, nc

            zeros = jnp.zeros((nl,), c.dtype)
            eps_sched = jnp.asarray(eps_np, c.dtype)
            tol0 = (sched.initial_tol(cfg, cfg.pcg_tight_tol) if adaptive
                    else cfg.pcg_tol)
            v0, _, _, _ = solve_wls(zeros, cfg.eps, True, zeros, tol0, None)

            if not adaptive:
                def scan_step(v, eps_l):
                    x0 = v if cfg.warm_start else jnp.zeros_like(v)
                    ext = exchange(v)
                    v2, rel, _, nc = solve_wls(v, eps_l, False, x0,
                                               cfg.pcg_tol, ext)
                    return v2, (rel, nc)

                v, (rels, nclamps) = jax.lax.scan(scan_step, v0, eps_sched)
                iters = jnp.full((cfg.n_irls,), cfg.pcg_max_iters, jnp.int32)
                return v[None], rels, iters, nclamps

            # adaptive: the state machine runs on psum-reduced scalars, so
            # every shard takes the SAME early-exit decision.  The exchange
            # of the post-iteration voltages powers BOTH the fractional-cut
            # reduction and the next iteration's system build — the early
            # exit adds one scalar psum per IRLS iteration and nothing per
            # PCG step.
            ext0 = exchange(v0)
            frac0 = jax.lax.psum(
                halo_l1_local(heads, tails_ext, c, c_s, c_t, v0, ext0), axis)
            st0 = sched.init_state(cfg, frac0, cfg.pcg_tight_tol, c.dtype)

            def scan_step(carry, eps_l):
                v, ext, st = carry
                tol_l = sched.inner_tol(st, c.dtype)
                x0 = v if cfg.warm_start else jnp.zeros_like(v)
                v2, rel, it, nc = solve_wls(v, eps_l, False, x0, tol_l, ext)
                # a done solve freezes: tol=∞ already parked its PCG at 0
                # iterations, the where guards the warm_start=False path
                v2 = jnp.where(st.done, v, v2)
                ext2 = exchange(v2)
                frac = jax.lax.psum(
                    halo_l1_local(heads, tails_ext, c, c_s, c_t, v2, ext2),
                    axis)
                spent = jnp.where(st.done, 0, it).astype(jnp.int32)
                nc = jnp.where(st.done, 0, nc).astype(jnp.int32)
                st2 = sched.advance(cfg, st, frac, rel, it,
                                    cfg.pcg_tight_tol)
                return (v2, ext2, st2), (rel, spent, nc)

            (v, _, _), (rels, iters, nclamps) = jax.lax.scan(scan_step,
                                                             (v0, ext0, st0),
                                                             eps_sched)
            return v[None], rels, iters, nclamps

        n_in = n_base + (4 if fused else 0)
        self._in_specs = (P(SOLVER_AXIS),) * n_in
        # replication checking off: the body mixes replicated scalars and
        # sharded arrays freely
        fn = jax.shard_map(body, mesh=self.mesh,
                           in_specs=self._in_specs,
                           out_specs=(P(SOLVER_AXIS), P(), P(), P()),
                           check_vma=False)
        self._raw_body = fn
        return jax.jit(fn)

    # -- psum schedule ----------------------------------------------------------
    def _build_psum(self):
        cfg = self.cfg
        plan = self.plan
        n_pad = plan.n_pad
        axis = SOLVER_AXIS
        adaptive = sched.is_adaptive(cfg)
        use_pallas = cfg.use_pallas
        eps_np = eps_schedule_array(cfg)
        clamp = bool(cfg.reweight_clamp)
        eps_last = float(eps_np[-1]) if len(eps_np) else float(cfg.eps)

        def body(src, dst, c, c_s, c_t):
            src, dst, c = src[0], dst[0], c[0]
            # v is REPLICATED here, so plain local dots already see the
            # whole vector — the only collective per PCG step is the
            # matvec's n-float all-reduce (psum_matvec)

            if clamp:
                # see _build_halo: cap = √(c_max³/(ε_last·εf32)), one pmax
                # outside the IRLS scan (c is sharded; terminals replicated)
                eps_f32 = float(np.finfo(np.float32).eps)
                local_max = jnp.maximum(
                    jnp.max(c, initial=0.0),
                    jnp.maximum(jnp.max(c_s, initial=0.0),
                                jnp.max(c_t, initial=0.0)))
                c_max = jax.lax.pmax(local_max, axis)
                cap = jnp.sqrt(c_max ** 3 / (eps_last * eps_f32)).astype(
                    c.dtype)

            def conductances(v, eps, initial):
                nclamp = jnp.int32(0)
                if initial:
                    r, r_s, r_t = c, c_s, c_t
                else:
                    r = coo_reweight(src, dst, c, v, eps, use_pallas)
                    r_s, r_t = lap.terminal_conductances(c_s, c_t, v, eps)
                    if clamp:
                        # edges are sharded (psum the count); terminals are
                        # REPLICATED — count them once, not once per shard
                        nclamp = (jax.lax.psum(
                            jnp.sum(r > cap).astype(jnp.int32), axis)
                            + jnp.sum(r_s > cap) + jnp.sum(r_t > cap)
                            ).astype(jnp.int32)
                        r = jnp.minimum(r, cap)
                        r_s = jnp.minimum(r_s, cap)
                        r_t = jnp.minimum(r_t, cap)
                deg = jax.ops.segment_sum(r, src, num_segments=n_pad)
                deg = deg + jax.ops.segment_sum(r, dst, num_segments=n_pad)
                deg = jax.lax.psum(deg, axis)
                diag = jnp.where(deg + r_s + r_t > 0, deg + r_s + r_t, 1.0)
                return r, r_s, r_t, diag, nclamp

            def solve_wls(v, eps, initial, x0, tol):
                r, r_s, r_t, diag, nclamp = conductances(v, eps, initial)
                mv = lambda x: psum_matvec(x, src, dst, r, r_s + r_t,
                                           n_pad, axis)
                M = lambda x: x / diag
                if adaptive:
                    res = pcg_masked(mv, r_s, x0=x0, precond=M, tol=tol,
                                     max_iters=cfg.pcg_max_iters)
                else:
                    res = pcg_fixed_iters(mv, r_s, x0=x0, precond=M,
                                          n_iters=cfg.pcg_max_iters,
                                          record_history=False)
                return res.x, res.rel_res, res.iters, nclamp

            zeros = jnp.zeros((n_pad,), c.dtype)
            eps_sched = jnp.asarray(eps_np, c.dtype)
            tol0 = (sched.initial_tol(cfg, cfg.pcg_tight_tol) if adaptive
                    else cfg.pcg_tol)
            v0, _, _, _ = solve_wls(zeros, cfg.eps, True, zeros, tol0)

            if not adaptive:
                def scan_step(v_, eps_l):
                    x0 = v_ if cfg.warm_start else jnp.zeros_like(v_)
                    v2, rel, _, nc = solve_wls(v_, eps_l, False, x0,
                                               cfg.pcg_tol)
                    return v2, (rel, nc)

                v, (rels, nclamps) = jax.lax.scan(scan_step, v0, eps_sched)
                iters = jnp.full((cfg.n_irls,), cfg.pcg_max_iters, jnp.int32)
                return v, rels, iters, nclamps

            def l1(v):
                # edges are sharded (one psum); terminals replicated
                z = c * (v[src] - v[dst])
                edge = jax.lax.psum(jnp.abs(z).sum(), axis)
                return (edge + jnp.abs(c_s * (1.0 - v)).sum()
                        + jnp.abs(c_t * v).sum())

            st0 = sched.init_state(cfg, l1(v0), cfg.pcg_tight_tol, c.dtype)

            def scan_step(carry, eps_l):
                v_, st = carry
                tol_l = sched.inner_tol(st, c.dtype)
                x0 = v_ if cfg.warm_start else jnp.zeros_like(v_)
                v2, rel, it, nc = solve_wls(v_, eps_l, False, x0, tol_l)
                v2 = jnp.where(st.done, v_, v2)
                spent = jnp.where(st.done, 0, it).astype(jnp.int32)
                nc = jnp.where(st.done, 0, nc).astype(jnp.int32)
                st2 = sched.advance(cfg, st, l1(v2), rel, it,
                                    cfg.pcg_tight_tol)
                return (v2, st2), (rel, spent, nc)

            (v, _), (rels, iters, nclamps) = jax.lax.scan(scan_step,
                                                          (v0, st0),
                                                          eps_sched)
            return v, rels, iters, nclamps

        self._in_specs = (P(SOLVER_AXIS), P(SOLVER_AXIS), P(SOLVER_AXIS),
                          P(), P())
        fn = jax.shard_map(body, mesh=self.mesh, in_specs=self._in_specs,
                           out_specs=(P(), P(), P(), P()),
                           check_vma=False)
        return jax.jit(fn)

    # -- execution --------------------------------------------------------------
    def arrays(self):
        if self.schedule == "halo":
            pl_, bp = self.plan, self.block_plan
            base = (pl_.heads, pl_.tails_ext, pl_.c, pl_.c_s, pl_.c_t,
                    pl_.export, pl_.node_valid, bp.copy_b, bp.copy_i,
                    bp.copy_j, bp.copy_id, bp.copy_valid, bp.node_b,
                    bp.node_s)
            if self.ell is not None:
                return base + (self.ell.cols, self.ell.c_ell,
                               self.ell.copy_row, self.ell.copy_lane)
            return base
        pl_ = self.plan
        return (pl_.src, pl_.dst, pl_.c, pl_.c_s, pl_.c_t)

    def abstract_inputs(self):
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in self.arrays())

    def lower(self):
        return self._fn.lower(*self.abstract_inputs())

    def compiled(self):
        """AOT-compiled solve program, cached.  The first call pays an AOT
        lower + compile; jax caches repeated AOT compiles of the same jitted
        object, so ``collective_stats`` and the continuous-profiling hook
        (``obs.perf.profile.compiled_costs``) share one compile."""
        if self._compiled is None:
            self._compiled = self.lower().compile()
        return self._compiled

    def collective_stats(self) -> List[dict]:
        """Per-while-loop direct collective counts of the compiled program
        (``launch.hlo_analysis.while_loop_collectives``), cached.  The
        first call pays an AOT lower + compile of the same program — the
        tracing layer therefore only records these gauges when a trace is
        actually enabled."""
        if self._collectives is None:
            from repro.launch.hlo_analysis import while_loop_collectives
            txt = self.compiled().as_text()
            self._collectives = while_loop_collectives(txt)
        return self._collectives

    def _record_collective_gauges(self) -> None:
        reg = get_registry()
        stats = self.collective_stats()
        reg.gauge(f"sharded_{self.schedule}_collective_loops").set(len(stats))
        if stats:
            reg.gauge(f"sharded_{self.schedule}_collectives_per_pcg_step").set(
                max(s["direct"] for s in stats if s["depth"] >= 2)
                if any(s["depth"] >= 2 for s in stats)
                else max(s["direct"] for s in stats))

    def check_float32_divergence(self, rels=None) -> Optional[float]:
        """Host-side sentinel: will the reweight ceiling c²/ε blow past the
        float32 stability threshold as the IRLS converges?

        The reweight r = c²/√((c·Δv)² + ε²) approaches c²/ε on settled
        edges (Δv → 0), so the conductance spread is set by ε RELATIVE to
        the weight scale: with ε_rel = ε / max(c) the normalized spread is
        1/ε_rel, and it crosses ``float32_divergence_threshold(ε_rel)``
        exactly when ε_rel < εf32 (float32 machine eps ≈ 1.19e-7) — the
        regime ROADMAP observed diverging (ε = 1e-8 at unit weights) while
        ε = 1e-6 stays safe.  Deterministic (weights + config only, no
        solved voltages needed); ``rels`` (per-IRLS final PCG relative
        residuals) is only consulted to name the first stalled iteration
        in the warning.  Returns the offending max conductance c²_max/ε
        when it breaches (after warning), else None.  No-op for float64
        configs or when the solver has no instance (abstract-plans dry
        run).
        """
        inst = self._instance
        if inst is None or jnp.dtype(self.cfg.dtype) != jnp.float32:
            return None
        eps_sched = eps_schedule_array(self.cfg)
        eps = float(eps_sched[-1]) if len(eps_sched) else float(self.cfg.eps)
        c_max = 0.0
        for arr in (inst.graph.weight, inst.s_weight, inst.t_weight):
            a = np.asarray(arr, dtype=np.float64)
            if a.size:
                c_max = max(c_max, float(np.max(a, initial=0.0)))
        if c_max <= 0:
            return None
        eps_rel = eps / c_max
        thresh = float32_divergence_threshold(eps_rel)
        if 1.0 / eps_rel <= thresh:
            return None
        r_max = c_max * c_max / eps
        stalled_iter = None
        if rels is not None:
            r = np.asarray(rels, dtype=np.float64)
            bad = np.nonzero(~np.isfinite(r) | (r > 1.0))[0]
            if bad.size:
                stalled_iter = int(bad[0])
        get_registry().counter("sharded_float32_divergence_total").inc()
        trace.event("sharded.float32_divergence", max_conductance=r_max,
                    threshold=thresh, eps=eps, eps_rel=eps_rel,
                    stalled_iter=stalled_iter, schedule=self.schedule,
                    clamped=bool(self.cfg.reweight_clamp))
        if self.cfg.reweight_clamp:
            # the mitigation is active: the reweights are capped AT the
            # threshold, so the spread the PCG sees stays representable —
            # keep the counter + trace event for the record, skip the
            # warning (nothing is about to diverge)
            return r_max
        at_iter = (f"; PCG stalled (rel residual > 1 or non-finite) first "
                   f"at IRLS iteration {stalled_iter}"
                   if stalled_iter is not None else "")
        warnings.warn(Float32DivergenceWarning(
            f"sharded IRLS reweights will reach ~{r_max:.3e} as edges "
            f"settle — past the float32 stability threshold "
            f"({thresh:.3e} at weight-relative eps {eps_rel:.3e}): the "
            f"PCG quadratic forms lose their significant digits at this "
            f"conductance spread and the iteration can stall or diverge"
            f"{at_iter}.  Raise cfg.eps (>= ~{c_max * 1.2e-7:.1e} at this "
            f"weight scale; 1e-6 is safe at unit weights) or switch "
            f"cfg.dtype to float64"), stacklevel=3)
        return r_max

    def solve(self):
        """Run the compiled SPMD program.

        Returns ``(v, rels, iters)``: voltages in ORIGINAL node order, the
        per-IRLS-iteration final PCG relative residual, and the PCG
        iterations actually spent per IRLS iteration (``pcg_max_iters``
        under the fixed schedule; drops to 0 once the adaptive mask froze
        the solve — the direct measure of what the early exit saved).
        """
        with trace.span("sharded.solve", schedule=self.schedule, p=self.p,
                        n=self.plan.n):
            # each shard's slice goes straight to its own device
            args = [jax.device_put(a, NamedSharding(self.mesh, spec))
                    for a, spec in zip(self.arrays(), self._in_specs)]
            out, rels, iters, nclamps = self._fn(*args)
            out = np.asarray(out).reshape(-1)
            if self.schedule == "halo":
                v = out[self.plan.perm]
            else:
                v = out[: self.plan.n]
            # total reweight-clamp hits across the IRLS sweep (always 0
            # when cfg.reweight_clamp is off); session telemetry reads it
            self.last_clamped = int(np.asarray(nclamps).sum())
            if self.last_clamped:
                get_registry().counter(
                    "sharded_clamped_reweights_total").inc(self.last_clamped)
            self.check_float32_divergence(rels=np.asarray(rels))
            if trace.enabled():
                self._record_collective_gauges()
        return v, np.asarray(rels), np.asarray(iters)
