"""Bring-up smoke: the min-cut solve and serve paths, end to end, on a TPU.

    python3 chip_smoke.py                # one chip: solve + serve phases
    python3 chip_smoke.py --chips 4      # sharded solve on a 4-chip mesh
                                         # against the one-chip scanned solve

Refuses to run (nonzero exit, no result line) unless JAX's first device is
a TPU.  Every instance is generated from ``--seed``; nothing is read from
disk.  The phases:

* solve — a road instance (``repro.launch.solve.build_instance("road",
  side, seed)``) through ``Problem.build`` → ``MinCutSession.solve`` twice:
  the CLI's default host backend, and the scanned backend with the ELL
  layout routed through the Pallas kernels.  Each cut must be within
  ``REL_TOL`` of the exact Dinic cut, and the compiled scanned program must
  contain the kernels (``tpu_custom_call``).
* serve — ``MinCutServer()`` with its defaults answers 8 drifting-weight
  requests on one road topology; every request must complete, and one
  result must match ``MinCutSession.solve`` on the same weights within
  ``SERVE_PARITY``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check raises before it is printed.  Times printed on the way are
bring-up readings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

REL_TOL = 1e-3        # |cut − exact| / exact, as examples/quickstart.py
SERVE_PARITY = 1e-4   # served cut vs a session solve (tests/test_serve.py)
BLOCK_ROWS = 1024     # largest dense block-Jacobi block the solve accepts
SERVE_REQUESTS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    """The device list, or SystemExit when the first device is no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found: JAX's first device is "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    return devices


def rel_gap(cut: float, exact: float) -> float:
    return abs(cut - exact) / exact


def build_problem(inst, max_rows: int = BLOCK_ROWS):
    """``Problem.build`` with a block count whose largest part (the dense
    block-Jacobi block, ``bs``) holds at most ``max_rows`` nodes."""
    from repro.core import Problem

    p = max(2, math.ceil(1.5 * inst.n / max_rows))
    while True:
        prob = Problem.build(inst, n_blocks=p)
        if prob.block_plan().bs <= max_rows or p >= inst.n:
            return prob
        p = int(p * 1.25) + 1


def scanned_config(n_blocks: int):
    """The scanned backend's config: ELL layout, Pallas kernels, fused
    single-sweep system build; every other knob at its default."""
    from repro.core import IRLSConfig

    return IRLSConfig(n_blocks=n_blocks, layout="ell", use_pallas=True,
                      fuse_edge_sweep=True)


def checked_solves(tag: str, sess, exact: float) -> dict:
    """A first call (compile + solve) and a steady second call of
    ``sess.solve``; the cut must be within ``REL_TOL`` of ``exact``.
    ``solve`` returns host arrays, so each timed window closes only after
    the device has finished."""
    t0 = time.perf_counter()
    sess.solve(rounding="two_level")
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = sess.solve(rounding="two_level")
    t_steady = time.perf_counter() - t0
    gap = rel_gap(res.cut_value, exact)
    log(f"{tag}: cut={res.cut_value!r} rel_gap={gap:.3e} "
        f"first_call={t_first:.3f}s steady={t_steady:.3f}s "
        f"(irls={res.timings['irls']:.3f}s "
        f"rounding={res.timings['rounding']:.3f}s)")
    if not gap <= REL_TOL:
        raise AssertionError(f"{tag} cut {res.cut_value} is {gap:.3e} from "
                             f"the exact {exact}")
    return {"cut": res.cut_value, "rel_gap": gap, "t_first": t_first,
            "t_steady": t_steady}


def solve_phase(side: int, seed: int, check_kernels: bool = True,
                max_rows: int = BLOCK_ROWS) -> dict:
    """Both backends on one road instance, each checked against Dinic."""
    from repro.core import IRLSConfig, MinCutSession, max_flow
    from repro.launch.solve import build_instance

    t0 = time.perf_counter()
    inst = build_instance("road", side, seed)
    t_instance = time.perf_counter() - t0
    t0 = time.perf_counter()
    prob = build_problem(inst, max_rows)
    t_build = time.perf_counter() - t0
    bp = prob.block_plan()
    log(f"solve: road side={side} n={inst.n} m={inst.graph.m} "
        f"blocks p={bp.p} bs={bp.bs} instance={t_instance:.3f}s "
        f"problem_build={t_build:.3f}s")

    t0 = time.perf_counter()
    exact = float(max_flow(inst).value)
    log(f"solve: dinic exact={exact!r} ({time.perf_counter() - t0:.3f}s)")

    out = {"n": inst.n, "m": inst.graph.m, "p": bp.p, "bs": bp.bs,
           "exact": exact, "t_instance": t_instance, "t_build": t_build}
    sessions = {
        "host": MinCutSession(prob, IRLSConfig(n_blocks=prob.n_blocks),
                              backend="host"),
        "scanned": MinCutSession(prob, scanned_config(prob.n_blocks),
                                 backend="scanned")}
    for backend, sess in sessions.items():
        out[backend] = checked_solves(f"solve[{backend}]", sess, exact)

    # the very program the scanned session ran, compiled again only to read
    # its text; it is not executed again
    run, args = sessions["scanned"].scanned_program()
    kernels = run.lower(*args).compile().as_text().count("tpu_custom_call")
    log(f"solve[scanned]: tpu_custom_call x{kernels} in the compiled program")
    if check_kernels and kernels == 0:
        raise AssertionError("no Pallas kernel (tpu_custom_call) in the "
                             "compiled scanned program")
    out["scanned"]["kernels"] = kernels
    return out


def serve_phase(side: int, seed: int,
                n_requests: int = SERVE_REQUESTS) -> dict:
    """``MinCutServer()`` defaults on one road topology under drift."""
    import numpy as np

    from repro.core import MinCutSession, Problem, Weights, max_flow
    from repro.launch.solve import build_instance
    from repro.serve import MinCutServer

    inst = build_instance("road", side, seed)
    rng = np.random.default_rng(seed)
    scale, weights = 1.0, []
    for _ in range(n_requests):             # launch/mincut_serve.py's walk
        scale *= float(np.exp(rng.normal(0.0, 0.05)))
        weights.append(Weights(np.asarray(inst.graph.weight) * scale,
                               np.asarray(inst.s_weight),
                               np.asarray(inst.t_weight)))
    server = MinCutServer()
    try:
        key = server.register(inst)
        t0 = time.perf_counter()
        futures = [server.submit(key, w) for w in weights]
        # a failed request re-raises here; a rejected one raised at submit
        results = [f.result(timeout=600.0) for f in futures]
        t_wall = time.perf_counter() - t0
    finally:
        server.stop()
    m = server.metrics
    done = len(results)
    log(f"serve: road side={side} n={inst.n} m={inst.graph.m} "
        f"completed={m.completed}/{n_requests} failed={m.failed} "
        f"rejected={m.rejected} wall={t_wall:.3f}s (first batch compiles)")
    if not (done == m.completed == n_requests and m.failed == m.rejected == 0):
        raise AssertionError(f"serve: {m.completed}/{n_requests} completed")

    j = n_requests - 1
    n_blocks = server.cfg.n_blocks if server.cfg.precond == "block_jacobi" \
        else 1
    sess = MinCutSession(Problem.build(inst, n_blocks=n_blocks,
                                       seed=server.seed),
                         server.cfg, backend=server.backend)
    ref = sess.solve(weights=weights[j], rounding=server.rounding)
    parity = rel_gap(results[j].cut_value, ref.cut_value)
    exact = float(max_flow(sess.problem.instance_with(weights[j])).value)
    gap = rel_gap(results[j].cut_value, exact)
    log(f"serve: request {j} cut={results[j].cut_value!r} "
        f"session={ref.cut_value!r} parity={parity:.3e} "
        f"dinic={exact!r} rel_gap={gap:.3e}")
    if not parity <= SERVE_PARITY:
        raise AssertionError(f"serve parity {parity:.3e} > {SERVE_PARITY}")
    return {"n": inst.n, "m": inst.graph.m, "completed": done,
            "parity": parity, "rel_gap": gap, "t_wall": t_wall}


def sharded_phase(side: int, seed: int, n_chips: int) -> dict:
    """Sharded solve on an ``n_chips`` mesh and the one-chip scanned solve
    of the same instance, both checked against Dinic."""
    import jax

    from repro.core import IRLSConfig, MinCutSession, max_flow
    from repro.distributed.collectives import flat_mesh
    from repro.launch.solve import build_instance

    devices = jax.devices()
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"devices; JAX sees {len(devices)}")
    inst = build_instance("road", side, seed)
    t0 = time.perf_counter()
    prob = build_problem(inst)
    log(f"sharded: road side={side} n={inst.n} m={inst.graph.m} "
        f"p={prob.block_plan().p} problem_build="
        f"{time.perf_counter() - t0:.3f}s")
    exact = float(max_flow(inst).value)
    out = {"n": inst.n, "m": inst.graph.m, "exact": exact}
    runs = (("sharded", IRLSConfig(n_blocks=prob.n_blocks),
             flat_mesh(devices[:n_chips])),
            ("scanned", scanned_config(prob.n_blocks), None))
    for backend, cfg, mesh in runs:
        out[backend] = checked_solves(
            f"sharded[{backend}]",
            MinCutSession(prob, cfg, backend=backend, mesh=mesh), exact)
        if backend == "sharded":
            stats = [d.memory_stats() or {} for d in devices[:n_chips]]
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                out[key] = [st.get(key) for st in stats]
                log(f"sharded: {key} per device {out[key]}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded solve on a 4-chip mesh "
                         "and the one-chip scanned solve it is compared "
                         "with")
    ap.add_argument("--side", type=int, default=600,
                    help="road grid side of the solve instance "
                         "(n = side², m ≈ 1.27 n)")
    ap.add_argument("--serve-side", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu()
    from repro.launch import compile_cache
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{compile_cache.enable()}")
    if args.chips == 4:
        sharded_phase(args.side, args.seed, args.chips)
    else:
        solve_phase(args.side, args.seed)
        serve_phase(args.serve_side, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
