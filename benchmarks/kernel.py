"""Kernelization benchmark: exact presolve reductions vs plain solves.

Sparse pinned-pair instances on two kernelization-friendly families:

  road    — planar road proxy (``road_like``): long degree-2 corridors
            collapse to single weighted edges, dead-end streets merge
            into their junctions.
  social  — preferential-attachment proxy (``social_like``): the
            degree-1/2 fringe around the hub core is eliminated.

For each family the bench records the kernel size (nodes/edges and the
reduction ratios — the ISSUE gate is >= 2x node reduction on road) and
then, per backend (host, scanned, and sharded over four of the
process's devices), steady-state
seconds per solve for ``presolve=False`` vs ``presolve=True`` at ONE
shared config.  Parity is enforced, not assumed: both cuts must agree
with each other and with the Dinic oracle to ``PARITY_RTOL`` for the
speedup to count.  The config is deliberately strong (the plain path
needs the full schedule to reach the true min cut on road corridors —
the kernel path converges long before that), so the timing compares
equal-quality solves.

Dense-terminal instances (FlowImprove/segmentation) are NOT here on
purpose: every vertex carries a terminal edge, which blocks the degree
rules, so the kernel barely shrinks and the comparison degenerates to
noise.  The sparse pinned-pair regime is where kernelization bites.

  PYTHONPATH=src python -m benchmarks.kernel            # full
  PYTHONPATH=src python -m benchmarks.kernel --smoke    # CI gate
  PYTHONPATH=src python -m benchmarks.run kernel        # harness
"""
from __future__ import annotations

import time

import numpy as np

from .common import pinned_instance

BENCH_NAME = "kernel"

PARITY_RTOL = 1e-6      # max rel cut difference presolve vs plain vs oracle


def _strong_cfg(smoke: bool, n_blocks: int = 1):
    """One schedule for BOTH paths, strong enough that the plain path
    converges to the exact min cut (verified against the Dinic oracle).

    eps stays at 1e-6: edge reweights scale like 1/eps near the cut, and
    the sharded backend runs float32 — eps=1e-8 makes its PCG diverge on
    hub-heavy social kernels (parity would fail for numerical, not
    algorithmic, reasons)."""
    from repro.core import IRLSConfig

    if smoke:
        return IRLSConfig(n_irls=50, pcg_max_iters=150, precond="jacobi",
                          n_blocks=n_blocks, pcg_tol=1e-8, eps=1e-6)
    return IRLSConfig(n_irls=60, pcg_max_iters=200, precond="jacobi",
                      n_blocks=n_blocks, pcg_tol=1e-8, eps=1e-6)


def _topologies(smoke: bool, seed: int):
    """seed+1 on the full instances: the seed-0 road-20 pinned pair is a
    plateau instance where NO backend's plain path reaches the optimum at
    a sane schedule — parity there would measure stall luck, not the
    kernel."""
    if smoke:
        return [("road", "road", 12, seed), ("social", "social", 160, seed)]
    return [("road", "road", 20, seed + 1), ("social", "social", 600, seed + 1)]


def _kernel_stats(inst):
    from repro.presolve import kernelize

    t0 = time.perf_counter()
    k = kernelize(inst)
    t_kernelize = time.perf_counter() - t0
    return {
        "kernel_n": int(k.kernel_n), "kernel_m": int(k.kernel_m),
        "node_reduction": float(k.node_reduction),
        "edge_reduction": float(k.edge_reduction),
        "base": float(k.base), "rule_stats": {s: int(v)
                                              for s, v in k.stats.items()},
        "t_kernelize_s": t_kernelize,
    }


def _time_pair(sess, backend, repeat):
    """Steady-state (s_plain, s_presolve, cut_plain, cut_presolve)."""
    rp = sess.solve(backend=backend)               # compile + plans
    rk = sess.solve(backend=backend, presolve=True)
    tp, tk = [], []
    for _ in range(repeat):
        t0 = time.perf_counter()
        rp = sess.solve(backend=backend)
        tp.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        rk = sess.solve(backend=backend, presolve=True)
        tk.append(time.perf_counter() - t0)
    return min(tp), min(tk), float(rp.cut_value), float(rk.cut_value), rk


def _backend_row(backend, s_plain, s_pre, cut_plain, cut_pre, oracle):
    rel_pk = abs(cut_pre - cut_plain) / max(abs(cut_plain), 1e-30)
    rel_po = abs(cut_plain - oracle) / max(abs(oracle), 1e-30)
    return {
        "backend": backend,
        "s_per_solve_plain": s_plain, "s_per_solve_presolve": s_pre,
        "speedup": s_plain / max(s_pre, 1e-12),
        "cut_plain": cut_plain, "cut_presolve": cut_pre,
        "cut_rel_diff": float(rel_pk),
        "oracle_rel_diff": float(rel_po),
        "parity_ok": bool(rel_pk <= PARITY_RTOL and rel_po <= PARITY_RTOL),
    }


def _sharded_rows(topos, smoke: bool, repeat: int, p: int = 4):
    """Plain-vs-presolve sharded comparison on a p-device mesh of this
    process's devices (for CPU emulation, start Python with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
    import jax

    from repro.core import MinCutSession, Problem, max_flow
    from repro.distributed.collectives import flat_mesh

    devices = jax.devices()
    if len(devices) < p:
        raise RuntimeError(f"the sharded comparison needs {p} devices; this "
                           f"process has {len(devices)} (--no-sharded "
                           f"skips it)")
    mesh = flat_mesh(devices[:p])
    cfg = _strong_cfg(smoke, n_blocks=p)
    rows = []
    for name, kind, size, seed in topos:
        inst = pinned_instance(kind, size, seed=seed)
        oracle = float(max_flow(inst).value)
        sess = MinCutSession(Problem.build(inst, n_blocks=p), cfg,
                             backend="sharded", mesh=mesh)
        s_plain, s_pre, cut_plain, cut_pre, _ = _time_pair(sess, "sharded",
                                                            repeat)
        rows.append(dict(topology=name, oracle=oracle, s_plain=s_plain,
                         s_pre=s_pre, cut_plain=cut_plain, cut_pre=cut_pre))
    return rows


def run(smoke: bool = False, repeat: int = 3, seed: int = 0,
        sharded: bool = True):
    from repro.core import MinCutSession, Problem, max_flow

    if smoke:
        repeat, sharded = 1, False
    topos = _topologies(smoke, seed)
    cfg = _strong_cfg(smoke)
    backends = ("host", "scanned")

    rows, solves = [], 0
    for name, kind, size, tseed in topos:
        inst = pinned_instance(kind, size, seed=tseed)
        oracle = float(max_flow(inst).value)
        row = {"topology": name, "n": int(inst.n), "m": int(inst.graph.m),
               "oracle_cut": oracle, "kernel": _kernel_stats(inst),
               "backends": []}
        sess = MinCutSession(Problem.build(inst, n_blocks=1), cfg)
        for backend in backends:
            sp, sk, cp, ck, _ = _time_pair(sess, backend, repeat)
            row["backends"].append(_backend_row(backend, sp, sk, cp, ck,
                                                oracle))
            solves += 2 * (repeat + 1)
        rows.append(row)

    if sharded:
        for name_row, sh in zip(rows, _sharded_rows(topos, smoke, repeat)):
            name_row["backends"].append(_backend_row(
                "sharded", sh["s_plain"], sh["s_pre"], sh["cut_plain"],
                sh["cut_pre"], sh["oracle"]))
            solves += 2 * (repeat + 1)

    road = next(r for r in rows if r["topology"] == "road")
    scanned = [b for r in rows for b in r["backends"]
               if b["backend"] == "scanned"]
    derived = (f"road kernel {road['kernel']['node_reduction']:.1f}x smaller"
               f" ({road['n']}->{road['kernel']['kernel_n']} nodes); "
               + " ".join(f"{r['topology']}:"
                          + ",".join(f"{b['backend'][:2]} {b['speedup']:.1f}x"
                                     f"{'' if b['parity_ok'] else '(PARITY MISS)'}"
                                     for b in r["backends"])
                          for r in rows))
    return {
        "name": BENCH_NAME,
        "us_per_call": 1e6 * float(np.mean(
            [b["s_per_solve_presolve"] for b in scanned])),
        "derived": derived,
        "solves": solves,
        "parity_rtol": PARITY_RTOL,
        "topologies": rows,
        "cfg": {"n_irls": cfg.n_irls, "pcg_max_iters": cfg.pcg_max_iters,
                "pcg_tol": cfg.pcg_tol, "eps": cfg.eps, "repeat": repeat,
                "smoke": smoke, "sharded": sharded},
    }


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instances, host+scanned only (the CI gate); "
                         "still writes the repo-root BENCH_kernel.json")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the sharded comparison (needs 4 devices)")
    args = ap.parse_args()

    from .run import write_payloads

    row = run(smoke=args.smoke, sharded=not args.no_sharded)
    path = write_payloads(row)
    print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
    print(f"wrote {path}")
