"""Session API: backend parity, plan/stepper reuse, registries."""
import numpy as np
import pytest

from repro.core import (IRLSConfig, MinCutSession, Problem, Weights,
                        max_flow, pirmcut, solve, two_level)
from repro.core import precond as pc
from repro.core import rounding as rd


CFG = IRLSConfig(n_irls=15, n_blocks=4, pcg_max_iters=80)


def _weights_of(inst, scale=1.0):
    return Weights(np.asarray(inst.graph.weight) * scale,
                   np.asarray(inst.s_weight), np.asarray(inst.t_weight))


# ---------------------------------------------------------------------------
# parity: solve vs solve_scanned vs session backends
# ---------------------------------------------------------------------------

def test_solve_vs_scanned_voltage_objective_parity(grid_instance):
    """Host driver and scanned driver agree on voltages and on the achieved
    (fractional) objective for a fixed schedule on a small grid."""
    from repro.core import solve_scanned
    from repro.core.incidence import (device_graph_from_instance,
                                      l1_objective)

    # fixed schedule so the two drivers run the same numerics: host driver
    # with tol=0 runs pcg to the iteration cap like the scanned one
    cfg = IRLSConfig(n_irls=10, pcg_max_iters=40, pcg_tol=0.0,
                     precond="jacobi")
    v_host, _ = solve(grid_instance, cfg)
    g = device_graph_from_instance(grid_instance)
    v_scan, _ = solve_scanned(g, cfg)
    v_scan = np.asarray(v_scan)
    np.testing.assert_allclose(v_host, v_scan, atol=5e-5)
    f_host = float(l1_objective(g, v_host))
    f_scan = float(l1_objective(g, v_scan))
    assert f_host == pytest.approx(f_scan, rel=1e-4)


def test_session_backends_match_legacy_solve(grid_instance):
    """Host and scanned session backends land within 1e-4 relative delta of
    the legacy core.solve path's cut (the sharded backend is covered in
    test_distributed.py — it needs a multi-device subprocess)."""
    v_ref, _ = solve(grid_instance, CFG)
    cut_ref = two_level(grid_instance, v_ref).cut_value

    sess = MinCutSession(Problem.build(grid_instance, n_blocks=CFG.n_blocks),
                         CFG)
    for backend in ("host", "scanned"):
        res = sess.solve(backend=backend)
        assert res.cut_value == pytest.approx(cut_ref, rel=1e-4), backend


def test_session_backends_match_legacy_solve_road(road_instance):
    v_ref, _ = solve(road_instance, CFG)
    cut_ref = two_level(road_instance, v_ref).cut_value
    sess = MinCutSession(road_instance, CFG)
    for backend in ("host", "scanned"):
        res = sess.solve(backend=backend)
        assert res.cut_value == pytest.approx(cut_ref, rel=1e-4), backend


def test_host_solve_skips_factorization_when_warm_start_meets_tol(
        road_instance, monkeypatch):
    """The host stepper builds the block-Jacobi preconditioner only in the
    IRLS iterations whose warm start misses ``pcg_tol``; the cut is that of
    building it every iteration (the apply form of ``pcg``)."""
    from repro.core import irls
    from repro.core.pcg import pcg as pcg_fn
    cfg = IRLSConfig(n_irls=15, n_blocks=4, pcg_max_iters=80, pcg_tol=1e-3)
    res = MinCutSession(road_instance, cfg).solve()
    diag = res.diagnostics
    assert len(diag.precond_built) == len(diag.pcg_iters) == cfg.n_irls + 1
    # built exactly where PCG stepped: the cold solve plus the warm
    # iterations whose x0 missed the tolerance
    assert diag.precond_built == [k > 0 for k in diag.pcg_iters]
    assert diag.precond_built[0]
    assert diag.precond_builds == 1 + sum(k > 0 for k in diag.pcg_iters[1:])
    assert diag.precond_builds < cfg.n_irls + 1

    def eager(*a, make_precond, **kw):
        return pcg_fn(*a, precond=make_precond(), **kw)

    monkeypatch.setattr(irls, "pcg", eager)
    ref = MinCutSession(road_instance, cfg).solve()
    assert ref.diagnostics.pcg_iters == diag.pcg_iters
    assert res.cut_value == ref.cut_value
    np.testing.assert_array_equal(res.cut.in_source, ref.cut.in_source)


def test_pirmcut_wrapper_matches_session(grid_instance):
    res, v, diag = pirmcut(grid_instance, CFG)
    sess_res = MinCutSession(grid_instance, CFG).solve()
    assert res.cut_value == pytest.approx(sess_res.cut_value, rel=1e-6)
    np.testing.assert_allclose(v, sess_res.voltages, atol=1e-6)
    assert diag.pcg_iters  # host diagnostics present


# ---------------------------------------------------------------------------
# plan / stepper reuse
# ---------------------------------------------------------------------------

def test_second_solve_skips_partition_and_plans(grid_instance, monkeypatch):
    from repro.graphs import partition as gp

    calls = {"kway": 0}
    real = gp.partition_kway

    def counting(*a, **kw):
        calls["kway"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(gp, "partition_kway", counting)
    prob = Problem.build(grid_instance, n_blocks=4)
    assert calls["kway"] == 1
    sess = MinCutSession(prob, CFG)
    r1 = sess.solve()
    r2 = sess.solve()
    # partition ran exactly once (at Problem.build), never inside solve
    assert calls["kway"] == 1
    # one compiled stepper serves both solves; the second pays zero setup
    assert len(sess._steppers) == 1
    assert r1.timings["setup"] > 0.0
    assert r2.timings["setup"] == 0.0
    assert r1.cut_value == pytest.approx(r2.cut_value, rel=1e-9)
    # and the steady-state solve is strictly cheaper than the cold one
    assert r2.timings["total"] < r1.timings["total"]


def test_weight_update_reuses_stepper(grid_instance):
    sess = MinCutSession(grid_instance, CFG)
    r1 = sess.solve()
    w2 = _weights_of(grid_instance, scale=1.5)
    r2 = sess.solve(weights=w2)
    assert len(sess._steppers) == 1            # same compiled stepper
    # scaling all internal edges by 1.5 changes the optimum
    assert r2.cut_value != pytest.approx(r1.cut_value, rel=1e-6)
    # cross-check against a from-scratch solve on the scaled instance
    inst2 = sess.problem.instance_with(w2)
    exact2 = max_flow(inst2).value
    assert r2.cut_value == pytest.approx(exact2, rel=1e-3)


def test_warm_from_previous_result(road_instance):
    sess = MinCutSession(road_instance, CFG)
    r1 = sess.solve()
    r2 = sess.solve(warm_from=r1)
    # warm continuation stays at the converged cut and spends (far) fewer
    # PCG iterations than the cold solve
    assert r2.cut_value == pytest.approx(r1.cut_value, rel=1e-4)
    assert sum(r2.diagnostics.pcg_iters) <= sum(r1.diagnostics.pcg_iters)
    # the scanned backend runs a warm-started program too (serving path)
    r3 = sess.solve(warm_from=r1, backend="scanned")
    assert r3.cut_value == pytest.approx(r1.cut_value, rel=1e-4)
    # sharded still runs a fixed cold schedule only
    with pytest.raises(ValueError):
        sess.solve(warm_from=r1, backend="sharded")


def test_solve_batch_matches_individual(grid_instance):
    cfg = IRLSConfig(n_irls=10, n_blocks=4, pcg_max_iters=50)
    sess = MinCutSession(grid_instance, cfg)
    ws = [_weights_of(grid_instance, s) for s in (1.0, 1.3, 0.7)]
    batch = sess.solve_batch(ws, cfg=cfg)
    assert len(batch) == 3
    for w, res in zip(ws, batch):
        single = sess.solve(weights=w, backend="scanned", cfg=cfg)
        assert res.cut_value == pytest.approx(single.cut_value, rel=1e-4)
        np.testing.assert_allclose(res.voltages, single.voltages, atol=1e-4)


def test_solve_batch_empty_fast_path(grid_instance):
    sess = MinCutSession(grid_instance, CFG)
    assert sess.solve_batch([]) == []
    assert sess._steppers == {}            # no program compiled for nothing


def test_solve_batch_padded_bucket_returns_only_real_results(grid_instance):
    cfg = IRLSConfig(n_irls=10, n_blocks=4, pcg_max_iters=50)
    sess = MinCutSession(grid_instance, cfg)
    ws = [_weights_of(grid_instance, s) for s in (1.0, 1.4, 0.8)]
    padded = sess.solve_batch(ws, cfg=cfg, pad_to=4)
    assert len(padded) == 3                # pad results are dropped
    unpadded = sess.solve_batch(ws, cfg=cfg)
    for a, b in zip(padded, unpadded):
        assert a.cut_value == pytest.approx(b.cut_value, rel=1e-6)
        np.testing.assert_allclose(a.voltages, b.voltages, atol=1e-6)
    with pytest.raises(ValueError, match="pad_to"):
        sess.solve_batch(ws, cfg=cfg, pad_to=2)


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

def test_precond_registry_complete():
    for name in ("none", "jacobi", "block_jacobi", "chebyshev"):
        assert name in pc.REGISTRY
    with pytest.raises(ValueError, match="unknown preconditioner"):
        pc.make_preconditioner("nope", None, None, None)


def test_rounding_registry_pluggable(grid_instance):
    assert set(rd.REGISTRY) >= {"sweep", "two_level"}
    with pytest.raises(ValueError, match="unknown rounding"):
        rd.round_voltages("nope", grid_instance, np.zeros(grid_instance.n))

    @rd.register("_all_source")
    def _all_source(instance, v):
        ind = np.ones(instance.n, dtype=bool)
        return rd.RoundingResult(ind, instance.cut_value(ind),
                                 {"method": "_all_source"})

    try:
        res = MinCutSession(grid_instance, CFG).solve(rounding="_all_source")
        assert res.cut.meta["method"] == "_all_source"
    finally:
        del rd.REGISTRY["_all_source"]


def test_mismatched_n_blocks_rejected(grid_instance):
    """A cfg asking for a different block count than the Problem's partition
    must refuse instead of silently running the wrong preconditioner."""
    sess = MinCutSession(Problem.build(grid_instance, n_blocks=4), CFG)
    with pytest.raises(ValueError, match="n_blocks"):
        sess.solve(cfg=IRLSConfig(n_irls=3, n_blocks=8))


def test_unknown_backend_rejected(grid_instance):
    with pytest.raises(ValueError, match="unknown backend"):
        MinCutSession(grid_instance, CFG, backend="gpu-cluster")
    sess = MinCutSession(grid_instance, CFG)
    with pytest.raises(ValueError, match="unknown backend"):
        sess.solve(backend="nope")


# ---------------------------------------------------------------------------
# weight validation + terminal rebinding
# ---------------------------------------------------------------------------

def test_zero_terminal_weights_rejected(grid_instance):
    """All-zero c_s / c_t makes the reduced Laplacian singular — reject with
    a clear ValueError at check_weights time instead of an opaque NaN deep
    inside PCG."""
    prob = Problem.build(grid_instance, n_blocks=1)
    good = _weights_of(grid_instance)
    n = grid_instance.n
    with pytest.raises(ValueError, match="c_s has no positive entry"):
        prob.check_weights(Weights(good.c, np.zeros(n), good.c_t))
    with pytest.raises(ValueError, match="c_t has no positive entry"):
        prob.check_weights(Weights(good.c, good.c_s, np.zeros(n)))
    # the same gate guards every solve path that takes a weight override
    sess = MinCutSession(prob, IRLSConfig(n_irls=2, n_blocks=1,
                                          precond="jacobi"),
                         backend="scanned")
    with pytest.raises(ValueError, match="no positive entry"):
        sess.solve(weights=Weights(good.c, np.zeros(n), good.c_t))
    with pytest.raises(ValueError, match="no positive entry"):
        sess.solve_batch([good, Weights(good.c, good.c_s, np.zeros(n))])


def test_rebind_terminals_one_hot(grid_instance):
    """rebind_terminals pins the pair as the ONLY terminal edges, at a
    strength that upper-bounds the pair's min cut, and passes validation."""
    from repro.core import rebind_terminals

    prob = Problem.build(grid_instance, n_blocks=1)
    w = prob.rebind_terminals(3, 17)
    assert np.count_nonzero(w.c_s) == 1 and w.c_s[3] > 0
    assert np.count_nonzero(w.c_t) == 1 and w.c_t[17] > 0
    deg = grid_instance.graph.weighted_degrees()
    assert w.c_s[3] == pytest.approx(1.0 + min(deg[3], deg[17]))
    assert w.c_t[17] == w.c_s[3]
    prob.check_weights(w)                      # passes the terminal gate
    with pytest.raises(ValueError, match="distinct"):
        prob.rebind_terminals(3, 3)
    with pytest.raises(ValueError, match="out of range"):
        rebind_terminals(grid_instance, 0, grid_instance.n)
