"""Paper Figure 3: parallel scalability of the IRLS iterations.

Wall-clock strong scaling needs several chips; on one device this reports
the two quantities that DRIVE Fig 3, both derived structurally:

  (a) block-Jacobi WORK REDUCTION vs p — the paper's explanation for its
      superlinear speedups: total preconditioner flops drop as blocks
      shrink (dense-block model: Σ bs³ with bs ≈ n/p at fixed coverage);
      measured here by wall-clock of the single-host IRLS at varying
      n_blocks, and analytically from the block plans.
  (b) per-shard collective bytes vs p for the sharded halo solver (lower +
      HLO-walk at p = 2/4/8 over the process's own devices) — the
      communication curve that bends the scaling at high p (paper: N-D
      grids stop scaling at 64).

``run_sharded`` (repo-root ``BENCH_sharded.json``; CI gate via
``python -m benchmarks.scaling --smoke``) is the DISTRIBUTED ADAPTIVE
trajectory: over the process's devices (on the CPU, start Python with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) it solves grid
and random-regular families through ``MinCutSession(backend="sharded")``
under the fixed vs the convergence-masked adaptive schedule, asserting
equal cuts, recording the total-PCG-iteration reduction the early exit
buys, and checking — by counting all-reduce/all-gather ops in the lowered
HLO's PCG loop bodies — that the masked schedule adds ZERO collectives per
PCG step over the fixed baseline.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import IRLSConfig, MinCutSession

from .common import grid_instance, timer


def _mesh_of(p: int):
    """A p-device mesh of this process's devices.  The benchmarks run in
    one process, which holds the chip; for CPU emulation start Python with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    import jax

    from repro.distributed.collectives import flat_mesh

    devices = jax.devices()
    if len(devices) < p:
        raise RuntimeError(f"needs {p} devices; this process has "
                           f"{len(devices)}")
    return flat_mesh(devices[:p])


def _collective_bytes_at(p: int, side: int) -> dict:
    from repro.distributed.solver import ShardedSolver
    from repro.graphs import generators as gen
    from repro.launch import hlo_analysis as ha

    mesh = _mesh_of(p)
    g = gen.grid_2d(side, side, seed=11)
    inst = gen.segmentation_instance(g, (side, side), seed=12)
    s = ShardedSolver(inst, IRLSConfig(n_irls=5, pcg_max_iters=20),
                      mesh=mesh, schedule="halo", precond_bs=32)
    c = ha.analyze(s.lower().compile().as_text(), p)
    return {"collective": c.collective_bytes, "flops": c.flops,
            "hbm": c.hbm_bytes}


QUALITY_RTOL = 1e-3     # max rel. cut difference adaptive vs fixed sharded


def _sharded_payload_at(p: int, side: int, n_reg: int, n_irls: int,
                        pcg_iters: int) -> dict:
    """The sharded fixed-vs-adaptive comparison on a p-device mesh."""
    from repro.core import Problem
    from repro.distributed.solver import ShardedSolver
    from repro.graphs import generators as gen
    from repro.launch import hlo_analysis as ha

    mesh = _mesh_of(p)
    fixed = IRLSConfig(n_irls=n_irls, pcg_max_iters=pcg_iters)
    adapt = IRLSConfig(n_irls=n_irls, pcg_max_iters=pcg_iters,
                       irls_tol=1e-3, adaptive_tol=True)

    g = gen.grid_2d(side, side, seed=11)
    fams = [("grid", gen.segmentation_instance(g, (side, side), seed=12)),
            ("random_regular",
             gen.flow_improve_instance(gen.random_regular(n_reg, 4, seed=13),
                                       seed=14))]
    rows, solves = [], 0
    for name, inst in fams:
        sess = MinCutSession(Problem.build(inst, n_blocks=p), fixed,
                             backend="sharded", mesh=mesh, precond_bs=32)
        rf = sess.solve(cfg=fixed)          # first call pays compile
        t0 = time.perf_counter()
        rf = sess.solve(cfg=fixed)
        tf = time.perf_counter() - t0
        ra = sess.solve(cfg=adapt)
        t0 = time.perf_counter()
        ra = sess.solve(cfg=adapt)
        ta = time.perf_counter() - t0
        solves += 4
        itf, ita = int(rf.pcg_iters.sum()), int(ra.pcg_iters.sum())
        rel = (abs(ra.cut_value - rf.cut_value)
               / max(abs(rf.cut_value), 1e-30))
        rows.append(dict(
            family=name, n=int(inst.n), m=int(inst.graph.m),
            cut_fixed=float(rf.cut_value), cut_adaptive=float(ra.cut_value),
            cut_rel_diff=float(rel), quality_ok=bool(rel <= QUALITY_RTOL),
            pcg_iters_fixed=itf, pcg_iters_adaptive=ita,
            iter_reduction=float(itf) / max(ita, 1),
            converged_early=bool(int(ra.pcg_iters[-1]) == 0),
            s_per_solve_fixed=tf, s_per_solve_adaptive=ta))

    # collectives per PCG step (depth-2 while bodies of the lowered HLO),
    # fixed vs adaptive — must be IDENTICAL: the masked schedule rides the
    # same reductions
    small_f = IRLSConfig(n_irls=3, pcg_max_iters=8)
    small_a = IRLSConfig(n_irls=3, pcg_max_iters=8,
                         irls_tol=1e-3, adaptive_tol=True)
    counts = {}
    for tag, cfg in (("fixed", small_f), ("adaptive", small_a)):
        s = ShardedSolver(fams[0][1], cfg, mesh=mesh, schedule="halo",
                          precond_bs=32)
        body_rows = ha.while_loop_collectives(s.lower().compile().as_text())
        counts[tag] = sorted(r["direct"] for r in body_rows
                             if r["depth"] >= 2)
    return dict(families=rows, solves=solves, pcg_step_collectives=counts,
                zero_extra_collectives=bool(
                    counts["fixed"] == counts["adaptive"]))


def run_sharded(smoke: bool = False):
    """Sharded adaptive-early-exit trajectory (BENCH_sharded.json)."""
    if smoke:
        p, side, n_reg, n_irls, pcg_iters = 2, 10, 64, 10, 15
    else:
        p, side, n_reg, n_irls, pcg_iters = 4, 16, 200, 50, 50
    payload = _sharded_payload_at(p, side, n_reg, n_irls, pcg_iters)
    fams = payload["families"]
    derived = " ".join(
        f"{f['family']} {f['iter_reduction']:.1f}x"
        f"{'' if f['quality_ok'] else '(QUALITY MISS)'}"
        for f in fams)
    derived += (" PCG-iter reduction adaptive vs fixed, equal cut; "
                f"0 extra coll/step={payload['zero_extra_collectives']}")
    return {
        "name": "sharded",
        "us_per_call": 1e6 * float(np.mean(
            [f["s_per_solve_adaptive"] for f in fams])),
        "derived": derived,
        "solves": payload["solves"],
        "families": fams,
        "pcg_step_collectives": payload["pcg_step_collectives"],
        "zero_extra_collectives": payload["zero_extra_collectives"],
        "cfg": {"p": p, "n_irls": n_irls, "pcg_max_iters": pcg_iters,
                "smoke": smoke, "quality_rtol": QUALITY_RTOL},
    }


def run(side=48):
    inst = grid_instance(side)
    # (a) work reduction vs number of blocks (same solver, same tolerance)
    times = {}
    for nb in (2, 4, 8, 16, 32):
        cfg = IRLSConfig(n_irls=10, pcg_max_iters=100, n_blocks=nb)
        with timer() as t:
            MinCutSession(inst, cfg).solve(rounding=None)
        times[nb] = t.dt
    # (b) collective bytes per shard count
    comm = {p: _collective_bytes_at(p, side) for p in (2, 4, 8)}
    best = min(times, key=times.get)
    return {
        "name": "fig3_scaling",
        "n": inst.n, "irls_time_vs_blocks": times,
        "per_shard_costs_vs_p": comm,
        "us_per_call": times[best] * 1e6 / 10,
        "derived": f"best blocks={best} "
                   f"({times[2]/times[best]:.2f}x vs 2 blocks); "
                   f"coll bytes/shard p2→p8: "
                   f"{comm[2].get('collective', 0)/2**10:.0f}→"
                   f"{comm[8].get('collective', 0)/2**10:.0f} KiB; "
                   f"flops/shard {comm[2].get('flops', 0)/1e6:.1f}→"
                   f"{comm[8].get('flops', 0)/1e6:.1f} MF",
    }


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instances + short schedule (the CI gate); "
                         "still writes the repo-root BENCH_sharded.json "
                         "payload")
    args = ap.parse_args()

    from .run import write_payloads

    row = run_sharded(smoke=args.smoke)
    path = write_payloads(row)
    print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
    print(f"wrote {path}")
