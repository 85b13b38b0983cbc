"""Each per-layer metric reader: its number from a run's records, and
nothing where there is nothing to read."""
import json
import os

import pytest

from bench import harness, peaks
from bench.tests.tiny import fake_trace


def _per_layer():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


def _solve_run():
    return harness.Run(
        setup={"problem_build_s": 9.5},
        solves=[{"irls_iters": 50, "pcg_iters": 10, "rounding_s": 0.05,
                 "wall_s": 3.0},
                {"irls_iters": 30, "pcg_iters": 30, "rounding_s": 0.07,
                 "wall_s": 3.2}],
        trace=fake_trace(busy_s=4.0, window_s=5.0, collective_s=0.4))


def _serve_run():
    from repro.serve import ServeMetrics

    serve = ServeMetrics()
    for q in (0.001, 0.003, 0.002):
        serve.record_request({"queue": q, "total": 2.0}, 0.0)
    return harness.Run(solves=_solve_run().solves, serve=serve)


EXPECTED = {
    "problem_build_s": (_solve_run, 9.5),
    "rounding_ms": (_solve_run, 60.0),
    "pcg_iters": (_solve_run, 20.0),
    "irls_step_ms": (_solve_run, 50.0),
    "device_idle_share": (_solve_run, 20.0),
    "queue_wait_p50_ms": (_serve_run, 2.0),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value(name):
    make, want = EXPECTED[name]
    assert harness.load_reader(name)(make()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_with_nothing_to_read(name):
    assert harness.load_reader(name)(harness.Run()) is None


def test_every_per_layer_metric_has_a_tested_reader():
    assert {harness.quantity(m) for m in _per_layer()} <= set(EXPECTED)
    for m in _per_layer():
        assert harness.load_reader(m) is not None
    mdir = os.path.join(harness.ROOT, "bench", "metrics")
    assert {f[:-3] for f in os.listdir(mdir) if f.endswith(".py")} \
        == set(EXPECTED)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.hbm_bytes_per_s == 819e9 and p.flops == 197e12
    assert peaks.peaks_for("cpu") is None and peaks.peaks_for(None) is None
