import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input-shape) cell, builds the sharded program
against the production mesh — (16,16)=256 chips single-pod and
(2,16,16)=512 chips multi-pod — and proves it ``lower().compile()``s.
Records per cell:

  · compiled.memory_analysis()   (per-device bytes — proves it fits)
  · compiled.cost_analysis()     (XLA's own counters, body-once semantics)
  · the HLO-walker costs         (trip-count-exact flops / HBM bytes /
                                  collective wire bytes — §Roofline inputs)

Usage:
  python -m repro.launch.dryrun --arch all --mesh both --out experiments/dryrun
  python -m repro.launch.dryrun --arch minitron-4b --cell train_4k --mesh single

``--arch all`` re-execs itself one subprocess per cell (fresh XLA heap per
compile; a failed cell doesn't kill the sweep).
"""

import argparse
import json
import subprocess
import sys
import time
import traceback


def run_one(arch: str, cell: str, multi_pod: bool, out_dir: str) -> dict:
    import jax  # deferred: device count is locked at first jax use
    from repro.configs import registry
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh
    from repro.launch import hlo_analysis as ha
    from repro.obs.perf.peaks import V5E

    mesh_name = "multi" if multi_pod else "single"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    prog = build_cell(arch, cell, mesh)
    lowered = prog.lower()
    t_lower = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    rec = {
        "arch": arch, "cell": cell, "mesh": mesh_name, "n_chips": n_chips,
        "ok": True, "t_lower_s": t_lower, "t_compile_s": t_compile,
        "meta": {k: (v if isinstance(v, (int, float, str, bool, dict))
                     else str(v)) for k, v in prog.meta.items()},
    }
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        rec["xla_cost"] = {k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        rec["xla_cost"] = {"error": str(e)}
    try:
        txt = compiled.as_text()
        costs = ha.analyze(txt, n_shards_default=n_chips)
        terms = ha.roofline_terms(costs, V5E)
        rec["hlo_costs"] = {
            "flops_per_chip": costs.flops,
            "hbm_bytes_per_chip": costs.hbm_bytes,
            "collective_bytes_per_chip": costs.collective_bytes,
            "collective_counts": costs.collective_counts,
            "per_collective_bytes": costs.per_collective_bytes,
        }
        rec["roofline"] = terms
        mf = prog.meta.get("model_flops")
        if mf:
            total_hlo = costs.flops * n_chips
            rec["roofline"]["model_flops"] = mf
            rec["roofline"]["useful_ratio"] = mf / total_hlo if total_hlo else None
    except Exception as e:  # pragma: no cover
        rec["hlo_costs"] = {"error": str(e), "trace": traceback.format_exc()}

    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(out_dir, f"{arch}__{cell}__{mesh_name}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--include-solver", action="store_true",
                    help="also dry-run the paper's own solver cells")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.configs import registry  # light import (no jax devices)

    cells = []
    for aid, entry in registry.ARCHS.items():
        if args.arch not in ("all", aid):
            continue
        if entry.family == "solver" and not (args.include_solver
                                             or args.arch == "pirmcut"):
            continue
        for c in entry.cells:
            if args.cell in ("all", c):
                cells.append((aid, c))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if len(cells) == 1 and len(meshes) == 1:
        aid, c = cells[0]
        rec = run_one(aid, c, meshes[0], args.out)
        mem = rec.get("memory", {})
        print(f"[dryrun] OK {aid} × {c} × {rec['mesh']}: "
              f"compile {rec['t_compile_s']:.1f}s, "
              f"peak/device {mem.get('peak_estimate_bytes', 0)/2**30:.2f} GiB, "
              f"dominant={rec.get('roofline', {}).get('dominant')}")
        return

    # sweep mode: one subprocess per cell (isolated XLA heap, fail-soft)
    failures = []
    for multi in meshes:
        mesh_name = "multi" if multi else "single"
        for aid, c in cells:
            out_json = os.path.join(args.out, f"{aid}__{c}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(out_json):
                print(f"[dryrun] skip {aid} × {c} × {mesh_name} (exists)")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", aid, "--cell", c,
                   "--mesh", mesh_name, "--out", args.out]
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            dt = time.time() - t0
            if r.returncode == 0:
                print(f"[dryrun] OK   {aid:28s} {c:14s} {mesh_name:6s} "
                      f"({dt:6.1f}s)", flush=True)
            else:
                failures.append((aid, c, mesh_name))
                err = (r.stderr or "").strip().splitlines()
                print(f"[dryrun] FAIL {aid:28s} {c:14s} {mesh_name:6s} "
                      f"({dt:6.1f}s)\n  " + "\n  ".join(err[-12:]), flush=True)
                with open(out_json, "w") as f:
                    json.dump({"arch": aid, "cell": c, "mesh": mesh_name,
                               "ok": False, "stderr": err[-40:]}, f, indent=1)
    print(f"[dryrun] done: {len(cells)*len(meshes)-len(failures)} ok, "
          f"{len(failures)} failed")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
