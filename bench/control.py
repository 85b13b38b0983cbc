"""The control of the comparison: runs that must come out not correct.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 10]

The configurations state float32; the precision below is bfloat16.  Two
controls, each on every seed given:

* ``program`` — the cell run as the benchmark runs it (set-up, a window of
  ``--seconds``, the comparison), with the program's own bfloat16 path
  switched on (``IRLSConfig.dtype``).  A run that raises has failed, and
  sets no reading.  On the cells of ``BENCHMARK.json`` this path returns
  the exact cut (the rounding's coarse max flow absorbs the precision), so
  it sets no upper reading there; see PERF.md.
* ``reference`` — the exact reference in the program's place, on every
  capacity rounded to bfloat16: its cut, and the cut value it reports
  from its own rounded weights, for the first three answers the window
  would ask for.

Each prints one JSON line per seed with the numbers compared.  The
benchmark's own runs never run this; it needs a TPU for ``program``.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import compare, generator, harness, instances  # noqa: E402
from bench.reference.maxflow import Reference, cut_value  # noqa: E402

PRECISION = "bfloat16"


def bf16(a) -> np.ndarray:
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


def window_instances(cell, seed: int, count: int = 3):
    """The first ``count`` instances a run's window solves."""
    spec = cell.config["instance"]
    topo = instances.topology(spec)
    return generator.pool(spec, topo, cell.mix, seed)[:count]


def reference_control(cell, seed: int) -> dict:
    insts = window_instances(cell, seed)
    ref = Reference(insts[0].n, insts[0].src, insts[0].dst)
    answers = []
    for inst in insts:
        lw, ls, lt = bf16(inst.weight), bf16(inst.s_weight), bf16(inst.t_weight)
        low = ref.solve(lw, ls, lt)
        answers.append(((inst.weight, inst.s_weight, inst.t_weight),
                        low.in_source,
                        cut_value(inst.src, inst.dst, lw, ls, lt,
                                  low.in_source)))
    # the reference answers every request it is given
    return compare.compare(ref, answers, 0)


def program_control(cell, seed: int, seconds: float, devices) -> dict:
    cell = copy.deepcopy(cell)
    cell.config["solver"]["irls"]["dtype"] = PRECISION
    out = harness.run_cell(cell, seed, seconds, False, devices,
                           time.perf_counter())
    return {k: c["value"] for k, c in out["checks"].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--mode", choices=("program", "reference", "both"),
                    default="both")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    devices = None
    if args.mode != "reference":
        os.environ["REPRO_PROFILE"] = "0"
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print("control: the program control needs a TPU",
                  file=sys.stderr)
            return 2
        harness.enable_compile_cache(jax)
        devices = devices[:cell.chips]
    for seed in seeds:
        for mode in (("program", "reference") if args.mode == "both"
                     else (args.mode,)):
            row = {"workload": args.workload, "seed": seed, "mode": mode}
            try:
                row["numbers"] = (reference_control(cell, seed)
                                  if mode == "reference" else
                                  program_control(cell, seed, args.seconds,
                                                  devices))
            except Exception as e:   # a control that raises has failed
                row["error"] = f"{type(e).__name__}: {e}"[:2000]
                traceback.print_exc()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
