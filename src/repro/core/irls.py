"""PIRMCut IRLS driver (paper Algorithm 1, eqs. 4–5).

The solver alternates

  Step 1 (reweight):  w_e = sqrt((CBx)_e² + ε²);  conductances r = c²/w
  Step 2 (WLS):       solve  L̃(r) v = b(r)  with PCG (warm-started)

starting from x⁰ = solution with W⁰ = C, for T iterations; the voltage
vector x^(T) then goes to a rounding procedure (core/rounding.py).

Two drivers are provided:

* ``solve`` — host-driven loop: each IRLS iteration is one jitted step, the
  preconditioner is refactorized between iterations (only in those whose
  warm start misses the PCG tolerance — ``pcg``'s lazy builder), and
  residual/objective diagnostics are collected.  This is the
  reference/production single-host path, and is what the paper measures
  per-phase (Table 2).
* ``solve_scanned`` — one jitted ``lax.scan`` over IRLS iterations — the form
  the distributed dry-run lowers and compiles, and the batched serving hot
  path (``jax.vmap`` over same-topology weight vectors).

The scanned driver runs one of two schedules:

* **fixed** (``irls_tol == 0`` and ``adaptive_tol == False``) — the paper's
  rigid ``n_irls × pcg_max_iters`` program, every instance pays the full
  budget (deterministic HLO; what the roofline/dry-run analyses consume).
* **adaptive** (any of the knobs below set) — a convergence-masked program
  that stays static-shape and jit/vmap-safe: the scan carries a per-instance
  ``done`` mask driven by the relative change of the fractional cut value
  (``irls_tol``), converged instances freeze (their PCG warm start is
  already below tolerance, so the masked inner loop exits immediately —
  vmapped batches stop paying for finished instances), and the inner PCG
  tolerance follows an Eisenstat–Walker-style schedule (``adaptive_tol``:
  loose early while the reweighting is far from fixed-point, tightening to
  ``pcg_tol`` as the outer iteration converges).

Both drivers build the per-iteration system through ONE dispatch helper
(``_iteration_system``): reweight→ELL-values→diagonal→RHS either as a fused
single sweep over the edge data (``fuse_edge_sweep``, kernels/edge_reweight
on TPU / the jnp fallback elsewhere) or as the legacy separate passes, with
``use_pallas`` honored uniformly (host and scanned alike).

Both are thin compatibility entry points over the session API
(core/session.py): ``Problem`` holds the one-time partition/plan setup and
``MinCutSession`` caches the compiled steppers, so repeated solves amortize
everything but the numerics.  See docs/API.md for the backend matrix.

Preconditioners resolve through ``precond.REGISTRY`` and rounding through
``rounding.REGISTRY`` — new strategies plug in without touching the drivers.

Beyond-paper options (documented in docs/API.md): ``eps_schedule``
(ε-continuation annealing) and ``precond="chebyshev"`` (collective-free
polynomial preconditioner).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace
from . import adaptive as sched
from . import laplacian as lap
from . import precond as pc
from .incidence import DeviceGraph, device_graph_from_instance, l1_objective, smoothed_objective
from .pcg import pcg, pcg_fixed_iters, pcg_masked


@dataclasses.dataclass(frozen=True)
class IRLSConfig:
    """All paper knobs (§5.4 defaults) + framework extensions."""

    eps: float = 1e-6                 # smoothing parameter ε
    n_irls: int = 50                  # T
    pcg_tol: float = 1e-3             # relative-residual stop
    pcg_max_iters: int = 50           # paper uses 50 at scale / 300 in §5.2
    warm_start: bool = True
    precond: str = "block_jacobi"     # jacobi | block_jacobi | chebyshev | none
    n_blocks: int = 16                # block-Jacobi part count ("processes" p)
    explicit_block_inverse: bool = False  # MXU GEMM apply path
    cheby_degree: int = 4
    eps_schedule: Optional[str] = None  # None | "anneal" (ε: 1e-2 → eps)
    layout: str = "coo"               # coo | ell  (matvec layout)
    dtype: str = "float32"
    use_pallas: bool = False          # route matvec/reweight through kernels/
    # -- adaptive early-exit hot path (see docs/API.md "Performance tuning").
    # All zero/False reproduces the fixed paper schedule exactly.
    irls_tol: float = 0.0             # rel. fractional-cut change that marks
                                      # an instance converged; 0 = run all T
    irls_patience: int = 2            # consecutive sub-irls_tol iterations
                                      # required before freezing (guards the
                                      # slow-convergence tail: one flat
                                      # reading is not convergence evidence)
    adaptive_tol: bool = False        # Eisenstat–Walker inner tolerance:
                                      # loose PCG early, tight late
                                      # (monotone non-increasing, so a
                                      # productive step can never loosen the
                                      # next one back into a no-op)
    pcg_loose_tol: float = 0.1        # loosest inner tolerance adaptive_tol
                                      # may use (first/far-from-fixed-point)
    pcg_tight_tol: float = 1e-6       # tight end of the adaptive SCANNED
                                      # schedule — matches the residual level
                                      # the fixed 50-iteration budget actually
                                      # reaches (the paper's 1e-3 is measured
                                      # against ‖b‖, which the ε-regularized
                                      # terminal conductances inflate)
    fuse_edge_sweep: bool = True      # build the per-iteration system in one
                                      # edge sweep (ELL layout only)
    reweight_clamp: bool = False      # sharded float32 mitigation: cap the
                                      # reweighted conductances at the
                                      # float32_divergence_threshold so the
                                      # Laplacian condition number stays
                                      # representable (opt-in; biases ε
                                      # upward on the clamped edges —
                                      # telemetry reports clamped_reweights)


@dataclasses.dataclass
class IRLSDiagnostics:
    pcg_iters: List[int]
    pcg_residuals: List[float]
    objective: List[float]            # smoothed S_ε(x^l)
    l1_objective: List[float]         # exact ‖CBx‖₁ (fractional cut value)
    voltages: Optional[List[np.ndarray]]  # per-iteration x (polarization study)
    setup_time: float = 0.0
    irls_time: float = 0.0
    # per iteration: was the preconditioner built?  False where the warm
    # start already met the PCG tolerance (0 PCG steps, factorization skipped)
    precond_built: List[bool] = dataclasses.field(default_factory=list)

    @property
    def precond_builds(self) -> int:
        """Preconditioner constructions in this solve."""
        return sum(self.precond_built)


def _eps_at(cfg: IRLSConfig, l: int) -> float:
    if cfg.eps_schedule == "anneal":
        # geometric continuation 1e-2 → eps over the first 60% of iterations
        hot, cold = 1e-2, cfg.eps
        frac = min(1.0, l / max(1, int(0.6 * cfg.n_irls)))
        return float(hot * (cold / hot) ** frac)
    return cfg.eps


def eps_schedule_array(cfg: IRLSConfig) -> np.ndarray:
    """ε for iterations 1..T as an array — the scanned driver consumes it as
    a scan input so host/scanned numerics agree under ``eps_schedule``."""
    return np.asarray([_eps_at(cfg, l) for l in range(1, cfg.n_irls + 1)])


def _adaptive(cfg: IRLSConfig) -> bool:
    """Does this config run the convergence-masked (early-exit) schedule?
    (Alias of ``adaptive.is_adaptive`` — the shared state machine lives in
    core/adaptive.py; host, scanned and sharded drivers all run it.)"""
    return sched.is_adaptive(cfg)


def _fused(cfg: IRLSConfig, ell_plan: Optional[lap.EllPlan]) -> bool:
    return cfg.fuse_edge_sweep and cfg.layout == "ell" and ell_plan is not None


def _ell_matvec(cfg: IRLSConfig, ell_plan: lap.EllPlan, vals, diag):
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return lambda v: kops.ell_spmv(ell_plan.cols, vals, diag, v)
    return lambda v: lap.matvec_ell(ell_plan.cols, vals, diag, v)


def _make_matvec(g: DeviceGraph, rw: lap.Reweighted, cfg: IRLSConfig,
                 ell_plan: Optional[lap.EllPlan]):
    if cfg.layout == "ell":
        vals, diag = lap.fill_ell(ell_plan, rw)
        return _ell_matvec(cfg, ell_plan, vals, diag)
    return lambda v: lap.matvec_coo(g, rw, v)


def _reweight(g: DeviceGraph, v, eps, cfg: IRLSConfig) -> lap.Reweighted:
    """THE reweight dispatch — every driver (host and scanned) routes here,
    so ``cfg.use_pallas`` means the same thing on every backend."""
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.edge_reweight(g, v, eps)
    return lap.reweight(g, v, eps)


@trace.named_scope("irls.system")
def _initial_system(g: DeviceGraph, cfg: IRLSConfig,
                    ell_plan: Optional[lap.EllPlan]):
    """The first WLS system, with W⁰ = C: returns ``(matvec, b, rw)``."""
    rw = lap.initial_weights(g)
    return _make_matvec(g, rw, cfg, ell_plan), lap.rhs(rw), rw


@trace.named_scope("irls.system")
def _iteration_system(g: DeviceGraph, cfg: IRLSConfig,
                      ell_plan: Optional[lap.EllPlan], c_ell, v, eps):
    """Build one IRLS iteration's system: returns ``(matvec, b, rw)``.

    Fused path (ELL layout + ``fuse_edge_sweep``): reweight → ELL value fill
    → diagonal → RHS in ONE sweep over the edge data (Pallas kernel under
    ``use_pallas``, the jnp fused fallback otherwise).  ``c_ell`` is the
    once-per-solve slot-major weight stage (``lap.ell_edge_weights``); pass
    None to build it here (host stepper — still one scatter per iteration,
    exactly what the legacy ``fill_ell`` cost).  The per-edge conductances
    are only gathered back when the preconditioner assembles blocks.

    Unfused path: the legacy separate passes (reweight, fill, rhs).
    """
    if not _fused(cfg, ell_plan):
        rw = _reweight(g, v, eps, cfg)
        return _make_matvec(g, rw, cfg, ell_plan), lap.rhs(rw), rw
    if c_ell is None:
        c_ell = lap.ell_edge_weights(ell_plan, g.c)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        vals, diag, r_s, r_t = kops.fused_ell_sweep(
            ell_plan.cols, c_ell, g.c_s, g.c_t, v, eps)
    else:
        vals, diag, r_s, r_t = lap.fused_ell_sweep(
            ell_plan.cols, c_ell, g.c_s, g.c_t, v, eps)
    # always recover the per-edge conductances: any REGISTRY preconditioner
    # may index rw.r (block_jacobi does), and the gather is one m-element
    # read against the sweep's 2m — not worth a name-based special case
    r = lap.edge_r_from_vals(ell_plan, vals)
    rw = lap.Reweighted(r=r, r_s=r_s, r_t=r_t, diag=diag)
    return _ell_matvec(cfg, ell_plan, vals, diag), r_s, rw


class _Stepper:
    """Jitted single-IRLS-iteration step factory (host-driven driver).

    The topology (src/dst and plans) is closed over as a compile-time
    constant; the edge/terminal weights are TRACED arguments, so one compiled
    stepper serves every same-topology weight vector — the plan-reuse
    property ``MinCutSession`` builds on.
    """

    def __init__(self, g: DeviceGraph, cfg: IRLSConfig,
                 block_plan: Optional[pc.BlockPlan],
                 ell_plan: Optional[lap.EllPlan]):
        self.g = g
        self.cfg = cfg
        self.block_plan = block_plan
        self.ell_plan = ell_plan
        self._jit_step = jax.jit(self._step_impl, static_argnames=("first",))

    def stage_edge_weights(self, weights=None):
        """Slot-major ELL weight stage for the fused sweep — computed ONCE
        per solve (the weights are fixed across the IRLS loop) and threaded
        through every step, so the per-iteration sweep stays scatter-free.
        None when the config doesn't run the fused path."""
        if not _fused(self.cfg, self.ell_plan):
            return None
        c = weights[0] if weights is not None else self.g.c
        return lap.ell_edge_weights(self.ell_plan, c)

    def _step(self, v, eps, *, first: bool, weights=None, tol=None,
              c_ell=None):
        c, c_s, c_t = (weights if weights is not None
                       else (self.g.c, self.g.c_s, self.g.c_t))
        tol = self.cfg.pcg_tol if tol is None else tol
        return self._jit_step(v, eps, tol, c, c_s, c_t, c_ell, first=first)

    def _step_impl(self, v, eps, tol, c, c_s, c_t, c_ell, *, first: bool):
        cfg = self.cfg
        g = DeviceGraph(src=self.g.src, dst=self.g.dst, c=c, c_s=c_s, c_t=c_t)
        if first:
            matvec, b, rw = _initial_system(g, cfg, self.ell_plan)
        else:
            matvec, b, rw = _iteration_system(g, cfg, self.ell_plan, c_ell,
                                              v, eps)
        x0 = v if (cfg.warm_start and not first) else jnp.zeros_like(v)
        # built only when x0 misses the tolerance (see ``pcg``)
        res = pcg(matvec, b, x0=x0, tol=tol, max_iters=cfg.pcg_max_iters,
                  record_history=True,
                  make_precond=lambda: pc.make_preconditioner(
                      cfg.precond, rw, matvec, cfg, self.block_plan))
        s_eps = smoothed_objective(g, res.x, eps)
        frac_cut = l1_objective(g, res.x)
        return res.x, res.iters, res.rel_res, s_eps, frac_cut, res.factored


def run_host_loop(stepper: _Stepper, cfg: IRLSConfig, n: int, dtype,
                  v0=None, collect_voltages: bool = False, weights=None,
                  c_ell=None):
    """Drive a prebuilt ``_Stepper`` through the IRLS loop.

    ``v0`` — optional warm-start voltages (REORDERED frame): when given, the
    cold initial WLS with W⁰ = C is skipped and reweighting starts from v0
    (the FlowImprove sequence regime).  ``weights`` — optional device
    ``(c, c_s, c_t)`` triple (REORDERED frame) overriding the stepper's
    baked-in weights.  ``c_ell`` — optional pre-staged slot-major ELL weight
    matrix (the session's delta-staging path under weight drift — see
    ``lap.ell_edge_weights_delta``); when absent the loop stages the weights
    itself, once.  Returns (device voltages, diag).

    Adaptive knobs (host flavor of the scanned early exit, driven by the
    SAME state machine — core/adaptive.py — run eagerly on the recorded
    diagnostics): ``irls_tol > 0`` breaks out of the loop once the
    fractional cut value's relative change stays below it; ``adaptive_tol``
    feeds a per-iteration inner tolerance (traced argument — no
    recompilation) to the stepper's PCG.

    Each iteration opens two spans (no-ops with tracing off):
    ``session.irls.dispatch`` around the step's launch (attribute ``l``, 0
    for the cold initial solve) and ``session.irls.readback`` around the
    device→host reads of its diagnostics and the adaptive state machine,
    with attribute ``factored`` (1 when the step built the preconditioner,
    0 when its warm start met the PCG tolerance and the build was skipped).
    """
    diag = IRLSDiagnostics(pcg_iters=[], pcg_residuals=[], objective=[],
                           l1_objective=[],
                           voltages=[] if collect_voltages else None)
    t1 = time.perf_counter()
    adaptive = _adaptive(cfg)
    tight = cfg.pcg_tol          # the host PCG stops on tolerance anyway
    tol_l = sched.initial_tol(cfg, tight) if adaptive else cfg.pcg_tol
    st = None                    # AdaptiveState, lazily seeded by the first
                                 # fractional-cut reading
    if c_ell is None:
        c_ell = stepper.stage_edge_weights(weights)  # one scatter per SOLVE
    if v0 is None:
        v = jnp.zeros((n,), dtype=dtype)
        # x⁰: WLS with W⁰ = C (cold start by definition)
        with trace.span("session.irls.dispatch", l=0):
            v, iters, rel, s_eps, frac, built = stepper._step(
                v, cfg.eps, first=True, weights=weights, tol=tol_l)
        with trace.span("session.irls.readback") as sp:
            _record(diag, sp, v, iters, rel, s_eps, frac, built,
                    collect_voltages)
            if adaptive:
                st = sched.init_state(cfg, float(frac), tight)
    else:
        v = jnp.asarray(v0, dtype=dtype)
    for l in range(1, cfg.n_irls + 1):
        eps_l = _eps_at(cfg, l)
        with trace.span("session.irls.dispatch", l=l):
            v, iters, rel, s_eps, frac, built = stepper._step(
                v, eps_l, first=False, weights=weights, tol=tol_l,
                c_ell=c_ell)
        with trace.span("session.irls.readback") as sp:
            _record(diag, sp, v, iters, rel, s_eps, frac, built,
                    collect_voltages)
            if not adaptive:
                continue
            if st is None:       # warm start: first reading seeds the state
                st = sched.init_state(cfg, float(frac), tight)
                continue
            st = sched.advance(cfg, st, float(frac), float(rel), int(iters),
                               tight)
            if cfg.adaptive_tol:
                tol_l = float(st.tol)
            if bool(st.done):
                break              # converged: stop paying for matvecs
    v.block_until_ready()
    diag.irls_time = time.perf_counter() - t1
    return v, diag


def solve(instance, cfg: IRLSConfig = IRLSConfig(),
          labels: Optional[np.ndarray] = None,
          collect_voltages: bool = False):
    """Run PIRMCut IRLS on a host STInstance (one-shot compatibility path).

    ``labels`` — optional precomputed partition labels over non-terminal
    nodes for the block-Jacobi preconditioner; computed with the multilevel
    partitioner when absent.  Returns (v, diagnostics).  For repeated solves
    build a ``Problem`` + ``MinCutSession`` instead (core/session.py) — this
    function rebuilds the partition, plans and jitted stepper every call.
    """
    from .session import Problem

    t0 = time.perf_counter()
    n_blocks = cfg.n_blocks if cfg.precond == "block_jacobi" else 1
    prob = Problem.build(instance, n_blocks=n_blocks, labels=labels)
    dtype = jnp.dtype(cfg.dtype)
    g = prob.device_graph(dtype)
    block_plan = prob.block_plan() if cfg.precond == "block_jacobi" else None
    ell_plan = prob.ell_plan() if cfg.layout == "ell" else None
    stepper = _Stepper(g, cfg, block_plan, ell_plan)
    setup_time = time.perf_counter() - t0

    v, diag = run_host_loop(stepper, cfg, g.n, dtype,
                            collect_voltages=collect_voltages)
    diag.setup_time = setup_time
    return prob.to_original(np.asarray(v)), diag


def _record(diag, span, v, iters, rel, s_eps, frac, built,
            collect_voltages):
    diag.pcg_iters.append(int(iters))
    diag.pcg_residuals.append(float(rel))
    diag.objective.append(float(s_eps))
    diag.l1_objective.append(float(frac))
    diag.precond_built.append(bool(built))
    span.set(factored=int(diag.precond_built[-1]))
    if collect_voltages and diag.voltages is not None:
        diag.voltages.append(np.asarray(v).copy())


# ---------------------------------------------------------------------------
# Fully-scanned variant (fixed or convergence-masked adaptive schedule)
# ---------------------------------------------------------------------------

def _scanned_precond(cfg: IRLSConfig, rw, matvec,
                     block_plan: Optional[pc.BlockPlan]):
    """Scanned drivers need a fixed-schedule preconditioner: resolve through
    the registry, falling back to point Jacobi when block Jacobi has no plan
    AND for "none" — the fixed iteration budget relies on at least diagonal
    scaling to converge, and this preserves the pre-registry scanned
    numerics exactly."""
    name = cfg.precond
    if name == "none" or (name == "block_jacobi" and block_plan is None):
        name = "jacobi"
    return pc.make_preconditioner(name, rw, matvec, cfg, block_plan)


def make_scanned_program(src, dst, cfg: IRLSConfig,
                         block_plan: Optional[pc.BlockPlan] = None,
                         ell_plan: Optional[lap.EllPlan] = None,
                         warm: bool = False, ext_stage: bool = False):
    """Build the weight-parameterized scanned IRLS program.

    Returns ``run(c, c_s, c_t) → (v, rels, iters)`` with the topology
    (src/dst and plans) closed over — one jit of ``run`` serves every
    same-topology weight vector, and ``jax.vmap(run)`` batches many
    instances (the ``MinCutSession.solve_batch`` serving path).  ``rels``
    and ``iters`` are the per-IRLS-iteration final PCG residual and the PCG
    iterations actually spent (masked to 0 once an instance is done).

    ``warm=True`` builds the warm-started variant ``run(c, c_s, c_t, v0)``:
    the cold initial WLS (W⁰ = C) is skipped and reweighting starts from
    the caller's voltages — same semantics as ``run_host_loop(v0=...)``,
    in scanned/vmappable form (the serving tier's drifting-weight re-solve
    path).  Under the adaptive schedule the convergence state is seeded
    from the first iteration's reading, exactly as the host loop does.

    ``ext_stage=True`` (fused ELL configs only) moves the once-per-solve
    slot-major weight staging OUT of the program: the caller passes the
    staged matrix as an extra traced argument right after the weights —
    ``run(c, c_s, c_t, c_ell[, v0])``.  This is the delta-staging serving
    path: under sparse weight drift the session patches the previous
    staging (``lap.ell_edge_weights_delta``) instead of rescattering all m
    edges inside the program.

    Static shapes end to end; control flow depends on the schedule:

    * fixed (default knobs): scan over T iterations × ``pcg_fixed_iters``
      (no residual history — one reduction per PCG step) — the
      deterministic-HLO form the dry-run/roofline consume.
    * adaptive (``irls_tol``/``adaptive_tol``): scan over T iterations
      carrying a per-instance ``done`` mask; each iteration runs
      ``pcg_masked`` (early exit, masked updates) under an
      Eisenstat–Walker inner tolerance.  A converged instance's voltages
      freeze, so its next warm-started PCG exits immediately — under
      ``vmap`` the batch stops paying for finished instances.

    The ε continuation (``cfg.eps_schedule``) is precomputed into a scan
    input array, so scanned and host numerics agree.
    """
    adaptive = _adaptive(cfg)
    if ext_stage and not _fused(cfg, ell_plan):
        raise ValueError("ext_stage requires the fused ELL path "
                         "(cfg.layout='ell' + fuse_edge_sweep + an ELL plan)")

    def _run(c, c_s, c_t, v_warm, c_ell_in):
        g = DeviceGraph(src=src, dst=dst, c=c, c_s=c_s, c_t=c_t)
        eps_sched = jnp.asarray(eps_schedule_array(cfg), dtype=c.dtype)
        # stage the edge weights slot-major ONCE per solve (unless the
        # caller staged them already — the delta path); every IRLS
        # iteration is then a scatter-free fused sweep
        if c_ell_in is not None:
            c_ell = c_ell_in
        else:
            c_ell = (lap.ell_edge_weights(ell_plan, c)
                     if _fused(cfg, ell_plan) else None)

        if warm:
            v0 = v_warm.astype(c.dtype)
        else:
            matvec0, b0, rw0 = _initial_system(g, cfg, ell_plan)
            apply_M0 = _scanned_precond(cfg, rw0, matvec0, block_plan)
            if adaptive:
                tol0 = sched.initial_tol(cfg, cfg.pcg_tight_tol)
                res0 = pcg_masked(matvec0, b0, precond=apply_M0, tol=tol0,
                                  max_iters=cfg.pcg_max_iters)
            else:
                res0 = pcg_fixed_iters(matvec0, b0, precond=apply_M0,
                                       n_iters=cfg.pcg_max_iters,
                                       record_history=False)
            v0 = res0.x

        if not adaptive:
            def irls_step(v, eps_l):
                matvec, b, rw = _iteration_system(g, cfg, ell_plan, c_ell,
                                                  v, eps_l)
                apply_M = _scanned_precond(cfg, rw, matvec, block_plan)
                x0 = v if cfg.warm_start else jnp.zeros_like(v)
                res = pcg_fixed_iters(matvec, b, x0=x0, precond=apply_M,
                                      n_iters=cfg.pcg_max_iters,
                                      record_history=False)
                return res.x, res.rel_res

            v, rels = jax.lax.scan(irls_step, v0, eps_sched)
            iters = jnp.full((cfg.n_irls,), cfg.pcg_max_iters, jnp.int32)
            return v, rels, iters

        def irls_step(carry, eps_l):
            v, st = carry
            matvec, b, rw = _iteration_system(g, cfg, ell_plan, c_ell,
                                              v, eps_l)
            apply_M = _scanned_precond(cfg, rw, matvec, block_plan)
            x0 = v if cfg.warm_start else jnp.zeros_like(v)
            # a done lane's PCG must be a no-op, not a discarded solve:
            # tol=∞ makes the masked loop exit at entry (0 iterations)
            tol_l = sched.inner_tol(st, c.dtype)
            res = pcg_masked(matvec, b, x0=x0, precond=apply_M, tol=tol_l,
                             max_iters=cfg.pcg_max_iters)
            # done lanes freeze: their state must not drift while other
            # instances of a vmapped batch keep iterating
            v_new = jnp.where(st.done, v, res.x)
            frac = l1_objective(g, v_new)
            spent = jnp.where(st.done, 0, res.iters).astype(jnp.int32)
            st_new = sched.advance(cfg, st, frac, res.rel_res, res.iters,
                                   cfg.pcg_tight_tol)
            return (v_new, st_new), (res.rel_res, spent)

        # Seeding the convergence state from v0's own fractional cut is
        # exactly the cold-start behaviour; under ``warm`` it lets an
        # already-converged warm start freeze after ``irls_patience``
        # iterations instead of re-running the full schedule.
        frac0 = l1_objective(g, v0)
        carry0 = (v0, sched.init_state(cfg, frac0, cfg.pcg_tight_tol,
                                       c.dtype))
        (v, _), (rels, iters) = jax.lax.scan(irls_step, carry0, eps_sched)
        return v, rels, iters

    if ext_stage and warm:
        def run(c, c_s, c_t, c_ell, v0):
            return _run(c, c_s, c_t, v0, c_ell)
    elif ext_stage:
        def run(c, c_s, c_t, c_ell):
            return _run(c, c_s, c_t, None, c_ell)
    elif warm:
        def run(c, c_s, c_t, v0):
            return _run(c, c_s, c_t, v0, None)
    else:
        def run(c, c_s, c_t):
            return _run(c, c_s, c_t, None, None)
    return run


def solve_scanned(g: DeviceGraph, cfg: IRLSConfig,
                  block_plan: Optional[pc.BlockPlan] = None,
                  ell_plan: Optional[lap.EllPlan] = None):
    """One jit-able program: scan over T IRLS iterations (compatibility
    wrapper over make_scanned_program; returns ``(v, rels)``)."""
    run = make_scanned_program(g.src, g.dst, cfg, block_plan, ell_plan)
    v, rels, _ = run(g.c, g.c_s, g.c_t)
    return v, rels
