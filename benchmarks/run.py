"""Benchmark harness — one module per paper table/figure (+ serving, cut trees).

  PYTHONPATH=src python -m benchmarks.run           # all
  PYTHONPATH=src python -m benchmarks.run fig1 table3 serve cuttree

Prints ``name,us_per_call,derived`` CSV (one row per benchmark) and persists
every row through ONE writer (``write_payloads``): the full payload goes to
``experiments/bench/<name>.json`` (scratch detail, gitignored), a
timestamp-free copy to repo-root ``BENCH_<name>.json`` (deliberately
diffable commit to commit), and the flattened scalar metrics APPEND to
repo-root ``BENCH_HISTORY.jsonl`` — the cross-PR perf trajectory the
``repro.launch.bench_diff`` regression gate reads.  Bench modules return
their row; they never touch disk themselves.
"""
from __future__ import annotations

import json
import math
import os
import sys
import traceback

from . import (cuttree, drift, irls_hotpath, kernel, phases, polarization,
               quality, roofline, scaling, serve, speedup, warm_start)

BENCHES = {
    "fig1": warm_start.run,
    "fig2": polarization.run,
    "fig3": scaling.run,
    "table2": phases.run,
    "table3": speedup.run,
    "table4": quality.run,
    "roofline": roofline.run,
    "serve": serve.run,
    "irls": irls_hotpath.run,
    "cuttree": cuttree.run,
    "sharded": scaling.run_sharded,
    "kernel": kernel.run,
    "drift": drift.run,
}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.environ.get("BENCH_OUT", "experiments/bench")
_NON_TRAJECTORY_KEYS = ("timestamp", "date", "time")


def sanitize_json(obj):
    """Replace non-finite numbers (NaN/±inf) with ``None``, recursively.

    ``json.dump`` happily emits bare ``NaN``/``Infinity`` tokens, which are
    NOT JSON — any strict parser (and most non-Python tooling) chokes on
    the payload.  Benchmarks legitimately produce NaN for undefined stats
    (e.g. an early-exit rate with zero adaptive solves), so the writer
    converts them to ``null`` rather than rejecting the row.
    """
    if isinstance(obj, dict):
        return {k: sanitize_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [sanitize_json(v) for v in obj]
    if isinstance(obj, bool):       # bool is an int subclass: keep it
        return obj
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def write_payloads(row: dict, root: str = REPO_ROOT,
                   out_dir: str = OUT_DIR) -> str:
    """THE benchmark writer — the only place bench payloads touch disk.

    Writes ``row`` verbatim to ``<out_dir>/<name>.json`` (full scratch
    detail) and minus wall-clock timestamps to ``<root>/BENCH_<name>.json``
    so diffs between commits show only measurement changes (the timing
    fields themselves still vary run to run, like any measurement).
    Every payload carries the process-global observability snapshot
    (``repro.obs.bench_snapshot()``) under ``"obs"`` — registry counters
    plus span-path aggregates when the bench ran traced.  Non-finite
    numbers are rewritten to ``null`` (``sanitize_json``) and the dump
    runs with ``allow_nan=False``, so every written payload is strict
    JSON that round-trips through ``json.loads``.  Finally the payload's
    flattened scalar metrics append to ``<root>/BENCH_HISTORY.jsonl``
    (``repro.obs.perf.history``) — the append-only trajectory the
    ``bench_diff`` comparator estimates noise baselines from.  Returns
    the repo-root path.
    """
    if "obs" not in row:
        try:
            from repro.obs import bench_snapshot
            row["obs"] = bench_snapshot()
        except Exception:  # pragma: no cover - obs must never sink a bench
            row["obs"] = {}
    row = sanitize_json(row)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{row['name']}.json"), "w") as f:
        json.dump(row, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")
    payload = {k: v for k, v in row.items() if k not in _NON_TRAJECTORY_KEYS}
    path = os.path.join(root, f"BENCH_{row['name']}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")
    try:
        from repro.obs.perf import history as _history
        _history.append_history(payload, _history.history_path(root))
    except Exception:  # pragma: no cover - history must never sink a bench
        traceback.print_exc()
    return path


def main() -> None:
    # recorded payloads should carry the continuous-profiling figures
    # (achieved GFLOP/s per solve); sessions check this env at build time
    os.environ.setdefault("REPRO_PROFILE", "1")
    from repro.launch import compile_cache
    compile_cache.enable()
    names = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    failed = []
    for n in names:
        try:
            row = BENCHES[n]()
            print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"",
                  flush=True)
            write_payloads(row)
        except Exception as e:  # pragma: no cover
            failed.append(n)
            traceback.print_exc()
            print(f"{n},NaN,\"FAILED: {e}\"", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
