"""HLO cost walker: while-loop collective census + trip-count correction.

Synthetic HLO keeps the parser tests instant; one real ``lax.scan``
program exercises the body-once correction the continuous profiler
(``repro.obs.perf.profile``) applies to ``compiled.cost_analysis()``.
"""
import numpy as np
import pytest

from repro.launch import hlo_analysis as ha

# a while-free module: one fusion + one top-level elementwise op
FLAT = """
HloModule flat

%fused (fa: f32[16], fb: f32[16]) -> f32[16] {
  %fa = f32[16] parameter(0)
  %fb = f32[16] parameter(1)
  ROOT %fm = f32[16] multiply(%fa, %fb)
}

ENTRY %main (a: f32[16], b: f32[16]) -> f32[16] {
  %a = f32[16] parameter(0)
  %b = f32[16] parameter(1)
  %s = f32[16] fusion(%a, %b), kind=kLoop, calls=%fused
  ROOT %r = f32[16] add(%s, %b)
}
"""

# nested whiles: outer (trip 3) holds an all-gather + collective-permute
# and an inner while (trip 5) holding ONE all-reduce; the inner COND is
# collective-free.  Exercises: per-loop direct counts that do NOT leak
# across the nesting boundary, depth annotation, trip multipliers.
NESTED = """
HloModule nested

%inner_cond (p: (f32[8], s32[])) -> pred[] {
  %p = (f32[8], s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=1
  %k = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%inner_body (p: (f32[8], s32[])) -> (f32[8], s32[]) {
  %p = (f32[8], s32[]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=0
  %ar = f32[8] all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %i = s32[] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (f32[8], s32[]) tuple(%ar, %i2)
}

%outer_cond (q: (f32[8], s32[])) -> pred[] {
  %q = (f32[8], s32[]) parameter(0)
  %j = s32[] get-tuple-element(%q), index=1
  %n = s32[] constant(3)
  ROOT %lt2 = pred[] compare(%j, %n), direction=LT
}

%outer_body (q: (f32[8], s32[])) -> (f32[8], s32[]) {
  %q = (f32[8], s32[]) parameter(0)
  %y = f32[8] get-tuple-element(%q), index=0
  %ag = f32[32] all-gather(%y), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = f32[8] collective-permute(%y), source_target_pairs={{0,1}}
  %z = f32[8] slice(%ag), slice={[0:8]}
  %zero = s32[] constant(0)
  %init = (f32[8], s32[]) tuple(%z, %zero)
  %w = (f32[8], s32[]) while(%init), condition=%inner_cond, body=%inner_body, backend_config={"known_trip_count":{"n":"5"}}
  %x2 = f32[8] get-tuple-element(%w), index=0
  %j = s32[] get-tuple-element(%q), index=1
  %one2 = s32[] constant(1)
  %j2 = s32[] add(%j, %one2)
  ROOT %t2 = (f32[8], s32[]) tuple(%x2, %j2)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %zero2 = s32[] constant(0)
  %init2 = (f32[8], s32[]) tuple(%a, %zero2)
  %w2 = (f32[8], s32[]) while(%init2), condition=%outer_cond, body=%outer_body, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %out = f32[8] get-tuple-element(%w2), index=0
}
"""

# an early-exit style loop whose only collective hides in the CONDITION
# (the stopping test's reduction) — the census must count it
COND_COLL = """
HloModule cond_coll

%cond (p: (f32[8], s32[])) -> pred[] {
  %p = (f32[8], s32[]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=0
  %ar = f32[8] all-reduce(%x), replica_groups={{0,1}}, to_apply=%sum
  %i = s32[] get-tuple-element(%p), index=1
  %k = s32[] constant(9)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (p: (f32[8], s32[])) -> (f32[8], s32[]) {
  %p = (f32[8], s32[]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=0
  %y = f32[8] add(%x, %x)
  %i = s32[] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (f32[8], s32[]) tuple(%y, %i2)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %zero = s32[] constant(0)
  %init = (f32[8], s32[]) tuple(%a, %zero)
  %w = (f32[8], s32[]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8] get-tuple-element(%w), index=0
}
"""


class TestWhileLoopCollectives:
    def test_no_while_returns_empty(self):
        assert ha.while_loop_collectives(FLAT) == []

    def test_garbage_and_empty_text(self):
        assert ha.while_loop_collectives("") == []
        assert ha.while_loop_collectives("not hlo at all\n{}") == []

    def test_nested_whiles_count_their_own(self):
        rows = {r["body"]: r for r in ha.while_loop_collectives(NESTED)}
        # outer: all-gather + collective-permute, NOT the inner all-reduce
        assert rows["outer_body"]["direct"] == 2
        assert rows["outer_body"]["depth"] == 1
        # inner: exactly its own all-reduce, at nesting depth 2
        assert rows["inner_body"]["direct"] == 1
        assert rows["inner_body"]["depth"] == 2

    def test_condition_collectives_counted(self):
        rows = ha.while_loop_collectives(COND_COLL)
        assert len(rows) == 1
        assert rows[0]["direct"] == 1      # the stopping test's all-reduce

    def test_counts_are_static_not_trip_multiplied(self):
        # trip counts 3 and 5 must not scale the census — a fixed-trip
        # scan and a dynamic while compare directly
        rows = {r["body"]: r for r in ha.while_loop_collectives(NESTED)}
        assert rows["inner_body"]["direct"] == 1  # not 5, not 15


class TestAnalyzeTripCounts:
    def test_trip_multipliers_compound(self):
        costs = ha.analyze(NESTED, n_shards_default=4)
        # inner all-reduce runs 3 × 5 times, outer collectives 3 times
        assert costs.collective_counts["all-reduce"] == pytest.approx(15.0)
        assert costs.collective_counts["all-gather"] == pytest.approx(3.0)
        assert costs.collective_counts["collective-permute"] == \
            pytest.approx(3.0)

    def test_masking_trip_count_yields_body_once(self):
        # the continuous profiler derives its while-trip correction from
        # exactly this ratio: analyze(text) / analyze(text with the
        # known_trip_count attribute masked)
        import re
        once = ha.analyze(re.sub(r"known_trip_count", "masked_trip_count",
                                 NESTED), n_shards_default=4)
        assert once.collective_counts["all-reduce"] == pytest.approx(1.0)
        full = ha.analyze(NESTED, n_shards_default=4)
        assert full.flops > once.flops

    def test_unknown_trip_while_counts_once(self):
        costs = ha.analyze(COND_COLL, n_shards_default=2)
        assert costs.collective_counts["all-reduce"] == pytest.approx(1.0)


class TestCostAnalysisCorrection:
    def test_scan_program_trip_scale(self):
        """cost_analysis counts a lax.scan body ONCE; the profiler's
        while-trip ratio recovers (approximately) the trip count."""
        import jax
        import jax.numpy as jnp
        from repro.obs.perf import profile as perf_profile

        trips = 7

        def step(c, _):
            return c * 1.5 + jnp.sum(c), None

        def prog(x):
            y, _ = jax.lax.scan(step, x, None, length=trips)
            return y

        cost = perf_profile.program_costs(jax.jit(prog),
                                          jnp.ones((256,), jnp.float32))
        assert cost is not None
        assert cost["cost_analysis_flops"] > 0
        # the ratio must recover most of the 7× the body-once count lost;
        # loop bookkeeping outside the body keeps it below the exact trip
        assert 2.0 < cost["while_trip_scale"] <= trips + 1
        assert cost["flops"] == pytest.approx(
            cost["cost_analysis_flops"] * cost["while_trip_scale"])

    def test_per_solve_cost_scaling(self):
        from repro.obs.perf import profile as perf_profile
        cost = {"flops": 1e9, "hbm_bytes": 4e9, "collective_bytes": 0.0,
                "cost_analysis_flops": 5e8, "while_trip_scale": 2.0}
        from repro.obs.perf.peaks import PEAKS, V5E
        per = perf_profile.per_solve_cost(cost, seconds=0.5, calls=3.0,
                                          device_kind=V5E)
        assert per["flops"] == pytest.approx(3e9)
        assert per["achieved_gflops"] == pytest.approx(3e9 / 0.5 / 1e9)
        assert per["achieved_gbps"] == pytest.approx(3 * 4e9 / 0.5 / 1e9)
        # roofline fraction: best-case time over measured time
        pk = PEAKS[V5E]
        best = max(3e9 / pk.flops, 3 * 4e9 / pk.hbm_bytes_per_s)
        assert per["roofline_fraction"] == pytest.approx(best / 0.5)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", None])
    def test_per_solve_cost_unknown_device_has_no_rates(self, kind):
        """A device missing from the peaks table gets counts, never rates
        or a roofline share borrowed from another chip."""
        from repro.obs.perf import profile as perf_profile
        cost = {"flops": 1e9, "hbm_bytes": 4e9, "collective_bytes": 0.0,
                "cost_analysis_flops": 1e9, "while_trip_scale": 1.0}
        if kind is None:     # default: the process's own device (the CPU)
            per = perf_profile.per_solve_cost(cost, seconds=0.5)
        else:
            per = perf_profile.per_solve_cost(cost, seconds=0.5,
                                              device_kind=kind)
        assert per["flops"] == pytest.approx(1e9)
        for key in ("achieved_gflops", "achieved_gbps", "roofline_fraction"):
            assert key not in per

    def test_per_solve_cost_handles_missing(self):
        from repro.obs.perf import profile as perf_profile
        assert perf_profile.per_solve_cost(None, 1.0) is None
        per = perf_profile.per_solve_cost(
            {"flops": 1e6, "hbm_bytes": 0.0, "collective_bytes": 0.0,
             "cost_analysis_flops": 1e6, "while_trip_scale": 1.0}, 0.0)
        assert per["flops"] == pytest.approx(1e6)
        assert per.get("achieved_gflops") is None
