"""End-to-end PIRMCut driver — Algorithm 1 on a real instance.

  python -m repro.launch.solve --family grid --side 64 --blocks 8
  python -m repro.launch.solve --family road --side 160 --backend sharded
  python -m repro.launch.solve --family grid --side 48 --repeat 3   # amortized

Pipeline (paper Algorithm 1), expressed through the session API: build/load
instance → ``Problem.build`` (k-way partition + reorder + plans, ONCE) →
``MinCutSession.solve`` (IRLS with warm-started block-Jacobi PCG → rounding)
→ report cut value, δ vs the exact serial solver, per-phase times (the
Table 2/3 readout).  ``--repeat`` re-solves on the cached session to show
the steady-state (plan/compile-amortized) time the paper's sequence
workloads run at.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def build_instance(family: str, side: int, seed: int):
    from repro.graphs import generators as gen

    if family == "road":
        g = gen.road_like(side, seed=seed)
        return gen.flow_improve_instance(g, seed=seed + 1)
    if family == "grid":
        g = gen.grid_2d(side, side, seed=seed)
        return gen.segmentation_instance(g, (side, side), seed=seed + 1)
    if family == "grid3d":
        g = gen.grid_3d(side, side, side, conn=26, seed=seed)
        return gen.segmentation_instance(g, (side, side, side), seed=seed + 1)
    raise ValueError(family)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="grid", choices=["road", "grid", "grid3d"])
    ap.add_argument("--side", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eps", type=float, default=1e-6)
    ap.add_argument("--irls", type=int, default=50)
    ap.add_argument("--pcg-iters", type=int, default=50)
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--precond", default="block_jacobi",
                    choices=["block_jacobi", "jacobi", "chebyshev", "none"])
    ap.add_argument("--rounding", default="two_level",
                    choices=["two_level", "sweep", "both"])
    ap.add_argument("--cold-start", action="store_true")
    ap.add_argument("--backend", default="host",
                    choices=["host", "scanned", "sharded"])
    ap.add_argument("--sharded", action="store_true",
                    help="alias for --backend sharded")
    ap.add_argument("--repeat", type=int, default=1,
                    help="re-solve on the cached session (amortized path)")
    ap.add_argument("--no-exact", action="store_true",
                    help="skip the exact serial baseline (large instances)")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    backend = "sharded" if args.sharded else args.backend

    from repro.launch import compile_cache
    compile_cache.enable()
    from repro.core import IRLSConfig, MinCutSession, Problem, max_flow
    from repro.core import rounding as rd

    t0 = time.time()
    inst = build_instance(args.family, args.side, args.seed)
    t_build = time.time() - t0
    print(f"instance: n={inst.n:,} m={inst.graph.m:,} ({t_build:.1f}s)")

    cfg = IRLSConfig(eps=args.eps, n_irls=args.irls,
                     pcg_max_iters=args.pcg_iters, n_blocks=args.blocks,
                     precond=args.precond, warm_start=not args.cold_start)

    t1 = time.time()
    n_blocks = args.blocks if args.precond == "block_jacobi" else 1
    prob = Problem.build(inst, n_blocks=n_blocks)
    t_problem = time.time() - t1
    sess = MinCutSession(prob, cfg, backend=backend)

    todo = ["two_level", "sweep"] if args.rounding == "both" else [args.rounding]
    res = sess.solve(rounding=todo[0])
    for _ in range(args.repeat - 1):
        res = sess.solve(rounding=todo[0])
    t_irls = res.timings["irls"]

    results = {"n": inst.n, "m": inst.graph.m, "t_build": t_build,
               "t_problem": t_problem, "t_irls": t_irls, "backend": backend,
               f"cut_{todo[0]}": res.cut_value,
               f"t_{todo[0]}": res.timings["rounding"]}
    print(f"problem setup (partition+reorder): {t_problem:.1f}s")
    print(f"IRLS [{backend}]: {t_irls:.1f}s"
          + (f" (stepper build {res.timings['setup']:.1f}s)"
             if res.timings.get("setup") else ""))
    print(f"{todo[0]}: cut={res.cut_value:.4f} "
          f"({res.timings['rounding']:.1f}s)"
          + (f" reduction {res.cut.meta['reduction']:.1f}x "
             f"(coarse n={res.cut.meta['coarse_n']})"
             if todo[0] == "two_level" else ""))
    for r in todo[1:]:
        t2 = time.time()
        extra = rd.round_voltages(r, inst, res.voltages)
        dt = time.time() - t2
        results[f"cut_{r}"] = extra.cut_value
        results[f"t_{r}"] = dt
        print(f"{r}: cut={extra.cut_value:.4f} ({dt:.1f}s)")

    if not args.no_exact:
        t3 = time.time()
        exact = max_flow(inst)
        t_exact = time.time() - t3
        results["cut_exact"] = exact.value
        results["t_exact"] = t_exact
        for r in todo:
            delta = (results[f"cut_{r}"] - exact.value) / exact.value
            results[f"delta_{r}"] = delta
            print(f"delta_{r} = {delta:.2e}")
        t_total = t_irls + results.get("t_two_level", 0)
        print(f"exact (serial Dinic): {exact.value:.4f} ({t_exact:.1f}s) "
              f"speedup_vs_serial={t_exact/max(t_total, 1e-9):.1f}x")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
