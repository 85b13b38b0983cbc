"""Perf-regression sentinel: schema, trajectory store, comparator, CLI,
and the continuous-profiling figures in SolveResult.telemetry."""
import json
import math
import os

import numpy as np
import pytest

from repro.obs.perf import history as hist
from repro.obs.perf import regress, schema


# ---------------------------------------------------------------------------
# schema: flatten + classify
# ---------------------------------------------------------------------------

PAYLOAD = {
    "name": "toy",
    "cfg": {"smoke": True, "n_irls": 50},          # config echo: skipped
    "derived": "text",                             # skipped
    "s_per_solve": 0.5,
    "solves_per_sec": 2.0,
    "speedup": 3.0,
    "pcg_iters": 120,
    "cut_value": 10.0,
    "quality_ok": True,
    "max_rel": 1e-6,
    "samples": [1.0, 2.0, 3.0],                    # scalar list: skipped
    "nan_metric": float("nan"),                    # dropped
    "topologies": [
        {"topology": "grid", "s_per_solve": 0.1},
        {"topology": "road", "s_per_solve": 0.2},
    ],
}


class TestSchema:
    def test_flatten_paths_and_values(self):
        ms = {m["metric"]: m for m in schema.extract_metrics(PAYLOAD)}
        assert ms["s_per_solve"]["kind"] == "time"
        assert ms["s_per_solve"]["direction"] == "lower"
        assert ms["solves_per_sec"]["kind"] == "throughput"
        assert ms["speedup"]["kind"] == "ratio"
        assert ms["pcg_iters"]["kind"] == "count"
        assert ms["cut_value"] == {"metric": "cut_value", "value": 10.0,
                                   "kind": "quality", "direction": "equal"}
        assert ms["max_rel"]["kind"] == "quality"
        # bools flatten to 0/1 with kind bool
        assert ms["quality_ok"]["value"] == 1.0
        assert ms["quality_ok"]["kind"] == "bool"
        # lists of dicts key by discriminator, not position
        assert ms["topologies[grid].s_per_solve"]["value"] == 0.1
        assert ms["topologies[road].s_per_solve"]["value"] == 0.2
        # config echo / text / raw samples / NaN never become metrics
        assert not any(m.startswith(("cfg", "derived", "samples")) for m in ms)
        assert "nan_metric" not in ms

    def test_info_rules_shadow_time_rules(self):
        # a config echo like max_wait_ms must NOT classify as wall-clock
        assert schema.classify("cfg_echo.max_wait_ms")[0] == "info"
        assert schema.classify("load_points[2.0].p99_ms")[0] == "time"
        # profiling figures: gflops gate as throughput, raw flops are info
        assert schema.classify("telemetry.mean_achieved_gflops")[0] == \
            "throughput"
        assert schema.classify("telemetry.total_flops")[0] == "info"
        assert schema.classify("unheard_of_metric")[0] == "info"

    def test_committed_bench_payloads_flatten(self):
        """Every committed BENCH_*.json yields classified, finite metrics."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        import glob
        files = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
        assert files, "no committed bench payloads found"
        for f in files:
            with open(f) as fh:
                payload = json.load(fh)
            ms = schema.extract_metrics(payload)
            assert ms, f
            for m in ms:
                assert m["kind"] in schema.KINDS
                assert not math.isnan(m["value"]), m


# ---------------------------------------------------------------------------
# history: append-only trajectory
# ---------------------------------------------------------------------------

class TestHistory:
    def test_roundtrip_and_run_numbering(self, tmp_path):
        path = str(tmp_path / "H.jsonl")
        r0 = hist.append_history(dict(PAYLOAD), path, sha="abc1234")
        r1 = hist.append_history(dict(PAYLOAD), path, sha="abc1234")
        assert {r["run"] for r in r0} == {0}
        assert {r["run"] for r in r1} == {1}
        recs = hist.read_history(path)
        assert len(recs) == len(r0) + len(r1)
        assert all(r["bench"] == "toy" and r["variant"] == "smoke"
                   and r["git_sha"] == "abc1234" for r in recs)

    def test_variants_number_independently(self, tmp_path):
        path = str(tmp_path / "H.jsonl")
        full = {k: v for k, v in PAYLOAD.items() if k != "cfg"}
        hist.append_history(dict(PAYLOAD), path, sha="s")      # smoke run 0
        recs = hist.append_history(full, path, sha="s")        # full run 0
        assert {r["variant"] for r in recs} == {"full"}
        assert {r["run"] for r in recs} == {0}

    def test_corrupt_lines_skipped(self, tmp_path):
        path = str(tmp_path / "H.jsonl")
        hist.append_history(dict(PAYLOAD), path, sha="s")
        n = len(hist.read_history(path))
        with open(path, "a") as fh:
            fh.write("{not json\n\n[1,2]\n")
        assert len(hist.read_history(path)) == n

    def test_missing_file_reads_empty(self, tmp_path):
        assert hist.read_history(str(tmp_path / "absent.jsonl")) == []


# ---------------------------------------------------------------------------
# comparator: median + MAD, direction-aware
# ---------------------------------------------------------------------------

class TestRegress:
    def test_direction_lower(self):
        base = [1.0, 1.0, 1.0]
        up = regress.classify_value("b", "m", "time", "lower", base, 2.0)
        down = regress.classify_value("b", "m", "time", "lower", base, 0.5)
        flat = regress.classify_value("b", "m", "time", "lower", base, 1.1)
        assert up.classification == "regressed"
        assert down.classification == "improved"
        assert flat.classification == "flat"    # within the 35% rtol

    def test_direction_higher(self):
        base = [10.0, 10.0, 10.0]
        v = regress.classify_value("b", "m", "throughput", "higher",
                                   base, 5.0)
        assert v.classification == "regressed"
        assert v.delta == pytest.approx(-5.0)

    def test_direction_equal_both_ways(self):
        base = [10.0] * 5
        for cur in (10.5, 9.5):
            v = regress.classify_value("b", "cut", "quality", "equal",
                                       base, cur)
            assert v.classification == "regressed", cur
        assert regress.classify_value("b", "cut", "quality", "equal",
                                      base, 10.001).classification == "flat"

    def test_noisy_baseline_widens_gate(self):
        # deterministic baseline: 10% count drift fires (rtol 5%)
        tight = regress.classify_value("b", "pcg_total", "count", "lower",
                                       [100.0] * 6, 110.0)
        assert tight.classification == "regressed"
        # same drift against a noisy baseline stays inside z·1.4826·MAD
        noisy = regress.classify_value("b", "pcg_total", "count", "lower",
                                       [90.0, 110.0, 95.0, 105.0, 100.0,
                                        108.0], 110.0)
        assert noisy.classification == "flat"
        assert noisy.threshold > tight.threshold

    def test_bool_flip_fires(self):
        v = regress.classify_value("b", "ok", "bool", "higher",
                                   [1.0, 1.0, 1.0], 0.0)
        assert v.classification == "regressed"

    def test_no_baseline_is_new_and_info_never_gates(self):
        assert regress.classify_value("b", "m", "time", "lower", [],
                                      1.0).classification == "new"
        assert regress.classify_value("b", "m", "info", "higher",
                                      [1.0], 99.0).classification == "flat"

    def test_compare_payload_filters_bench_and_variant(self, tmp_path):
        path = str(tmp_path / "H.jsonl")
        for _ in range(3):
            hist.append_history(dict(PAYLOAD), path, sha="s")
        # pollute with another bench and the full variant of the same bench
        other = dict(PAYLOAD, name="other", s_per_solve=99.0)
        full = {k: v for k, v in PAYLOAD.items() if k != "cfg"}
        full["s_per_solve"] = 99.0
        hist.append_history(other, path, sha="s")
        hist.append_history(full, path, sha="s")
        verdicts = regress.compare_payload(dict(PAYLOAD),
                                           hist.read_history(path))
        v = {x.metric: x for x in verdicts}["s_per_solve"]
        assert v.n_baseline == 3            # the polluters never matched
        assert v.baseline_median == pytest.approx(0.5)
        assert v.classification == "flat"

    def test_gate_kind_restriction(self):
        vs = [regress.classify_value("b", "t", "time", "lower",
                                     [1.0] * 3, 9.0),
              regress.classify_value("b", "c", "count", "lower",
                                     [100.0] * 3, 150.0)]
        assert {v.metric for v in regress.gate(vs)} == {"t", "c"}
        assert {v.metric for v in regress.gate(
            vs, kinds=("count", "quality", "bool"))} == {"c"}

    def test_render_table_mentions_regressions(self):
        vs = [regress.classify_value("toy", "s_per_solve", "time", "lower",
                                     [1.0] * 3, 9.0)]
        out = regress.render_table(vs, show="all")
        assert "regressed" in out and "s_per_solve" in out


# ---------------------------------------------------------------------------
# bench_diff CLI: record → diff → gate
# ---------------------------------------------------------------------------

class TestBenchDiffCLI:
    def _seed(self, tmp_path, n=3):
        path = str(tmp_path / "H.jsonl")
        for _ in range(n):
            hist.append_history(dict(PAYLOAD), path, sha="s")
        return path

    def _payload_file(self, tmp_path, payload, name="p.json"):
        f = str(tmp_path / name)
        with open(f, "w") as fh:
            json.dump(payload, fh)
        return f

    def test_synthetic_2x_slowdown_exits_nonzero(self, tmp_path, capsys):
        from repro.launch import bench_diff
        history = self._seed(tmp_path)
        slow = dict(PAYLOAD, s_per_solve=1.0)          # 2× the 0.5 baseline
        rc = bench_diff.main(["--from-payload",
                              self._payload_file(tmp_path, slow),
                              "--history", history])
        cap = capsys.readouterr()
        assert rc == 1
        assert "regressed" in cap.out
        assert "REGRESSED" in cap.err and "s_per_solve" in cap.err

    def test_unmodified_rerun_classifies_flat_across_repeats(self, tmp_path,
                                                             capsys):
        from repro.launch import bench_diff
        history = self._seed(tmp_path)
        f = self._payload_file(tmp_path, dict(PAYLOAD))
        for _ in range(3):                   # 3 repeats, growing baseline
            rc = bench_diff.main(["--from-payload", f,
                                  "--history", history])
            assert rc == 0
            assert "0 regressed" in capsys.readouterr().out
            hist.append_history(dict(PAYLOAD), history, sha="s")

    def test_gate_missing_baseline_exits_2(self, tmp_path, capsys):
        from repro.launch import bench_diff
        rc = bench_diff.main(["--gate", "--from-payload",
                              self._payload_file(tmp_path, dict(PAYLOAD)),
                              "--history", str(tmp_path / "empty.jsonl")])
        assert rc == 2
        assert "no committed baseline" in capsys.readouterr().err

    def test_gate_ignores_wallclock_regressions(self, tmp_path, capsys):
        from repro.launch import bench_diff
        history = self._seed(tmp_path)
        slow = dict(PAYLOAD, s_per_solve=1.0)          # time-kind only
        rc = bench_diff.main(["--gate", "--from-payload",
                              self._payload_file(tmp_path, slow),
                              "--history", history])
        capsys.readouterr()
        assert rc == 0                       # count/quality/bool unchanged
        bad = dict(PAYLOAD, pcg_iters=200)             # count-kind drift
        rc = bench_diff.main(["--gate", "--from-payload",
                              self._payload_file(tmp_path, bad, "q.json"),
                              "--history", history])
        capsys.readouterr()
        assert rc == 1

    def test_write_payloads_appends_history(self, tmp_path, monkeypatch):
        from benchmarks import run as bench_run
        row = dict(PAYLOAD, obs={})
        bench_run.write_payloads(dict(row), root=str(tmp_path),
                                 out_dir=str(tmp_path / "scratch"))
        bench_run.write_payloads(dict(row), root=str(tmp_path),
                                 out_dir=str(tmp_path / "scratch"))
        recs = hist.read_history(hist.history_path(str(tmp_path)))
        assert {r["run"] for r in recs} == {0, 1}
        assert os.path.exists(tmp_path / "BENCH_toy.json")


# ---------------------------------------------------------------------------
# continuous profiling: telemetry carries achieved GFLOP/s
# ---------------------------------------------------------------------------

class TestProfiling:
    @pytest.fixture(scope="class")
    def small_instance(self):
        from repro.graphs import generators as gen
        g = gen.grid_2d(8, 8, seed=3)
        return gen.segmentation_instance(g, (8, 8), seed=4)

    def test_host_and_scanned_telemetry_flops(self, small_instance):
        import jax
        from repro.core import IRLSConfig, MinCutSession
        from repro.obs.perf.peaks import peaks_for
        cfg = IRLSConfig(n_irls=4, pcg_max_iters=30)
        sess = MinCutSession(small_instance, cfg, profile=True)
        # the CPU has no entry in the peaks table: the counts are written,
        # the device rates and roofline share are not
        assert peaks_for(jax.devices()[0].device_kind) is None
        for backend in ("host", "scanned"):
            t = sess.solve(backend=backend).telemetry
            assert t["flops"] and t["flops"] > 0, backend
            assert t["hbm_bytes"] and t["hbm_bytes"] > 0, backend
            assert t["achieved_gflops"] is None, backend
            assert t["roofline_fraction"] is None, backend
        costs = sess.program_costs()
        assert {"host", "scanned/False"} <= set(costs)
        snap = sess.telemetry.snapshot()
        assert snap["total_flops"] > 0
        assert snap["profiled_solves"] == 2
        assert math.isnan(snap["mean_achieved_gflops"])

    def test_profile_off_leaves_telemetry_none(self, small_instance):
        from repro.core import IRLSConfig, MinCutSession
        sess = MinCutSession(small_instance,
                             IRLSConfig(n_irls=3, pcg_max_iters=20),
                             profile=False)
        t = sess.solve(backend="host").telemetry
        assert t["flops"] is None and t["achieved_gflops"] is None

    def test_profile_env_switch(self, monkeypatch):
        from repro.obs.perf import profile as perf_profile
        monkeypatch.setenv(perf_profile.PROFILE_ENV, "1")
        assert perf_profile.default_enabled()
        monkeypatch.setenv(perf_profile.PROFILE_ENV, "0")
        assert not perf_profile.default_enabled()

    def test_batch_solves_carry_costs(self, small_instance):
        from repro.core import IRLSConfig, MinCutSession, Weights
        cfg = IRLSConfig(n_irls=3, pcg_max_iters=20)
        sess = MinCutSession(small_instance, cfg, profile=True)
        w = Weights(np.asarray(small_instance.graph.weight),
                    np.asarray(small_instance.s_weight),
                    np.asarray(small_instance.t_weight))
        res = sess.solve_batch([w, w], cfg=cfg)
        assert len(res) == 2
        for r in res:
            assert r.telemetry["flops"] and r.telemetry["flops"] > 0
