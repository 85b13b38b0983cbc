"""A copy of the benchmark's data files with every configuration cut to a
size a CPU test run can hold."""
from __future__ import annotations

import json
import os
import shutil

from bench import harness

SIDES = {"road": 24, "grid3d": 6}
MAX_BLOCK_ROWS = 64


def tiny_root(dst) -> str:
    dst = str(dst)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), dst)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(harness.ROOT, "bench", sub),
                        os.path.join(dst, "bench", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    cdir = os.path.join(dst, "bench", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["instance"]["side"] = SIDES[cfg["instance"]["family"]]
        cfg["solver"]["max_block_rows"] = MAX_BLOCK_ROWS
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dst


def cells(root=harness.ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def fake_trace(busy_s=0.5, window_s=1.0, collective_s=0.05) -> dict:
    return {"n_devices": 1, "window_s": window_s, "busy_s": busy_s,
            "collective_s": collective_s, "device_ops": [["fusion", busy_s]],
            "idle_gaps": [["session.rounding", window_s - busy_s]]}
