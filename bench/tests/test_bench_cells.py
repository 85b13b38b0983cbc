"""Cells are found by name from BENCHMARK.json, and a new cell needs new
files and a new ``workloads`` entry only."""
import json
import os
import re

import jax
import pytest

from bench import harness
from bench.tests.tiny import cells, fake_trace, tiny_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", cells())
def test_cell_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.name == name and cell.chips in (1, 4)
    assert cell.mix["backend"] == "host" and cell.mix["pool"] >= 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no_such.cell")


def test_benchmark_json_keeps_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    names = [c["name"] for c in b["configs"]]
    cell_names = [w["name"] for w in b["workloads"]]
    metrics = b["end_to_end"] + b["per_layer"]
    for n in names + cell_names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    assert len(set(names)) == len(names)
    assert len(set(cell_names)) == len(cell_names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert set(json.load(f)["reduced"]) == set(c["reduced"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                          "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(cell_names)


def test_new_cell_needs_only_new_files(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "configs", "road_ny.json")) as f:
        cfg = json.load(f)
    cfg["instance"]["seed"] = 5
    with open(os.path.join(bdir, "configs", "road_b.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "pair.json"), "w") as f:
        json.dump({"backend": "host", "pool": 2}, f)
    with open(os.path.join(bdir, "metrics", "solves_in_window.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.solves) or None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "road_b", "source": "x",
                             "file": "bench/configs/road_b.json",
                             "reduced": ["n", "m"], "why": "test"})
    bench["workloads"].append({"name": "road_b.pair", "config": "road_b",
                               "traffic": "pair", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "solve_s.road_ny":
            m["workloads"].append("road_b.pair")
    bench["per_layer"].append({"name": "solves_in_window", "unit": "solves",
                               "better": "higher", "source": "host_clock",
                               "layer": "session",
                               "moves": "solve_s.road_ny"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell("road_b.pair", root=root)
    assert "solves_in_window" in {m["name"] for m in cell.per_layer}
    dev = jax.devices()[:1]
    out = harness.run_cell(cell, 2**33 + 1, 0.5, False, dev, harness.clock(),
                           root=root)
    assert out["correct"]
    assert set(out["metrics"]) == {"setup_s", "solve_s.road_ny"}
    monkeypatch.setattr(harness, "_xplane", lambda d: d)
    monkeypatch.setattr(harness.trace_reduce, "load", lambda p: None)
    monkeypatch.setattr(harness.trace_reduce, "reduce",
                        lambda pd: fake_trace())
    out = harness.run_cell(cell, 2**33 + 1, 0.5, True, dev, harness.clock(),
                           root=root, trace_dir=str(tmp_path / "tr"))
    assert out["metrics"]["solves_in_window"]["value"] >= 1
    assert list(out)[-1] == "checks"


# the server's own deployment: its default solver (point Jacobi, adaptive
# early exit) on the scanned backend, where each batch bucket and the warm
# start compile programs of their own; and the host backend with tenants.
# The third item says whether the program serves exact cuts there: on the
# scanned backend a tenant's warm-started requests do not (PERF.md, open
# question 1), so that case checks only what the harness guarantees.
SERVER_DEFAULTS = {"n_irls": 20, "n_blocks": 1, "precond": "jacobi",
                   "irls_tol": 1e-3, "adaptive_tol": True}
SERVER_MIXES = {
    "scanned_tenants": ({"backend": "scanned", "clients": 2, "pool": 3,
                         "tenants": True}, SERVER_DEFAULTS, False),
    "scanned_batches": ({"backend": "scanned", "clients": 4, "pool": 3,
                         "n_workers": 1, "max_batch": 4,
                         "flush_policy": "deadline", "max_wait_ms": 50.0},
                        SERVER_DEFAULTS, True),
    "host_tenants": ({"backend": "host", "clients": 2, "pool": 2,
                      "tenants": True, "n_workers": 1, "max_batch": 2,
                      "flush_policy": "deadline"}, {"precond": "jacobi"},
                     True),
}


@pytest.mark.parametrize("mix", sorted(SERVER_MIXES))
def test_new_server_cell_needs_only_new_files(tmp_path, monkeypatch,
                                              capsys, mix):
    """A second cell on the server entry, with a configuration, server
    settings and tenants of its own: new files, and its name added to the
    served metrics.  Set-up runs every program the window uses."""
    traffic, irls, exact = SERVER_MIXES[mix]
    root = tiny_root(tmp_path)
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "configs", "road_ny.json")) as f:
        cfg = json.load(f)
    cfg["solver"]["irls"].update(irls)
    with open(os.path.join(bdir, "configs", "road_j.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "serve_pair.json"), "w") as f:
        json.dump({"entry": "server", **traffic}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "road_j", "source": "x",
                             "file": "bench/configs/road_j.json",
                             "reduced": ["n", "m"], "why": "test"})
    bench["workloads"].append({"name": "road_j.serve_pair",
                               "config": "road_j", "traffic": "serve_pair",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("served_per_s", "latency_p50_ms",
                         "queue_wait_p50_ms.road_ny_serve"):
            m["workloads"].append("road_j.serve_pair")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = harness.load_cell("road_j.serve_pair", root=root)
    dev = jax.devices()[:1]
    out = harness.run_cell(cell, 2**33 + 3, 1.0, False, dev, harness.clock(),
                           root=root)
    assert out["correct"] or not exact, out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["window_compiles"] == 0
    record = json.loads(capsys.readouterr().err.split("bench: ")[-1])
    assert record["setup"]["problem_build_s"] > 0
    if traffic.get("n_workers") == 1 and not traffic.get("tenants"):
        assert record["serve"]["max_batch_size"] >= 2
    assert set(out["metrics"]) == {"setup_s", "served_per_s",
                                   "latency_p50_ms"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    monkeypatch.setattr(harness, "_xplane", lambda d: d)
    monkeypatch.setattr(harness.trace_reduce, "load", lambda p: None)
    monkeypatch.setattr(harness.trace_reduce, "reduce",
                        lambda pd: fake_trace())
    out = harness.run_cell(cell, 2**33 + 3, 0.5, True, dev, harness.clock(),
                           root=root, trace_dir=str(tmp_path / "tr"))
    assert set(out["metrics"]) == {"queue_wait_p50_ms.road_ny_serve"}
    assert out["window_compiles"] == 0
