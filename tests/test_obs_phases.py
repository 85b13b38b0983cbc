"""Phase instrumentation of the solve path: the device phase scopes in the
compiled programs' metadata, and the host spans of the IRLS loop, the
two-level rounding and ``Problem.build``."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

PHASES = ("irls.system", "irls.factor", "irls.pcg", "irls.objective")


@pytest.fixture(scope="module")
def instance():
    from repro.graphs import generators as gen
    g = gen.grid_2d(10, 10, seed=3)
    return gen.segmentation_instance(g, (10, 10), seed=4)


@pytest.fixture
def traced():
    from repro.obs import trace
    trace.clear()
    trace.configure(enabled=True)
    yield trace
    trace.configure(enabled=False)
    trace.clear()


def _cfg(**kw):
    from repro.core import IRLSConfig
    return IRLSConfig(n_irls=3, pcg_max_iters=20, n_blocks=4, **kw)


def _session(instance, **kw):
    from repro.core import MinCutSession, Problem
    cfg = _cfg(**kw)
    return MinCutSession(Problem.build(instance, n_blocks=cfg.n_blocks), cfg,
                         profile=False)


def _scopes(hlo: str):
    return {p for p in PHASES if f"/{p}/" in hlo}


@pytest.mark.parametrize("first", [False, True])
def test_host_step_carries_every_phase(instance, first):
    from repro.core.irls import _Stepper
    sess = _session(instance)
    block_plan, ell_plan = sess._plans_for(sess.cfg)
    st = _Stepper(sess.problem.device_graph(jnp.float32), sess.cfg,
                  block_plan, ell_plan)
    g = st.g
    hlo = st._jit_step.lower(jnp.zeros_like(g.c_s), 1e-6, 1e-3, g.c, g.c_s,
                             g.c_t, None, first=first).compile().as_text()
    assert _scopes(hlo) == set(PHASES)


def _computations(hlo: str):
    """``{name: instruction lines}`` of an HLO module's text, and the
    entry computation's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%(\S+) \(.*\{$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _reachable(comps, roots, into_conditionals: bool):
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            if not into_conditionals and " conditional(" in line:
                continue
            todo += [c for c in re.findall(r"%([\w.\-]+)", line)
                     if c in comps]
    return seen


@pytest.mark.parametrize("first", [False, True])
def test_host_step_gates_the_factorization(instance, first):
    """The preconditioner's construction is traced inside the branch of a
    ``conditional`` on the initial residual: gated, not hoisted out."""
    from repro.core.irls import _Stepper
    sess = _session(instance)
    block_plan, ell_plan = sess._plans_for(sess.cfg)
    st = _Stepper(sess.problem.device_graph(jnp.float32), sess.cfg,
                  block_plan, ell_plan)
    g = st.g
    hlo = st._jit_step.lower(jnp.zeros_like(g.c_s), 1e-6, 1e-3, g.c, g.c_s,
                             g.c_t, None, first=first).compile().as_text()
    comps, entry = _computations(hlo)
    conds = [line for line in comps[entry] if " conditional(" in line]
    assert len(conds) == 1
    branches = re.search(r"branch_computations=\{([^}]*)\}", conds[0])
    branches = [b.strip().lstrip("%") for b in branches.group(1).split(",")]
    inside = _reachable(comps, branches, into_conditionals=True)
    outside = _reachable(comps, [entry], into_conditionals=False)
    factor = lambda names: [line for c in names for line in comps[c]
                            if "/irls.factor/" in line]
    assert factor(inside)
    assert not factor(outside)
    assert any("Cholesky" in line or "potrf" in line
               for c in inside for line in comps[c])


@pytest.mark.parametrize("layout", ["coo", "ell"])
def test_scanned_programs_carry_every_phase(instance, layout):
    """The adaptive schedule reads the fractional cut every iteration; the
    fixed one computes no objective, so it carries the other three."""
    for kw, want in (({"irls_tol": 1e-3}, set(PHASES)),
                     ({}, set(PHASES) - {"irls.objective"})):
        run, args = _session(instance, layout=layout,
                             **kw).scanned_program()
        hlo = run.lower(*args).compile().as_text()
        assert _scopes(hlo) == want, kw


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_host_loop_spans_per_iteration(instance, traced):
    sess = _session(instance)
    res = sess.solve(rounding=None)
    spans = _by_name(traced.spans())
    iters = len(res.diagnostics.pcg_iters)
    assert iters == sess.cfg.n_irls + 1
    dispatch = spans["session.irls.dispatch"]
    assert [s.attrs["l"] for s in dispatch] == list(range(iters))
    assert len(spans["session.irls.readback"]) == iters
    assert ([s.attrs["factored"] for s in spans["session.irls.readback"]]
            == [int(b) for b in res.diagnostics.precond_built])
    irls = spans["session.irls"][0]
    for s in dispatch + spans["session.irls.readback"]:
        assert s.parent_id == irls.span_id
        assert irls.t0 <= s.t0 <= s.t1 <= irls.t1


def test_adaptive_host_loop_spans_stop_with_the_loop(instance, traced):
    sess = _session(instance, irls_tol=0.5, irls_patience=1)
    res = sess.solve(rounding=None)
    spans = _by_name(traced.spans())
    iters = len(res.diagnostics.pcg_iters)
    assert iters < sess.cfg.n_irls + 1
    assert len(spans["session.irls.dispatch"]) == iters
    assert len(spans["session.irls.readback"]) == iters


def test_two_level_rounding_spans(instance, traced):
    sess = _session(instance)
    res = sess.solve(rounding="two_level")
    assert res.cut.meta["coarse_n"] > 0
    spans = _by_name(traced.spans())
    outer = spans["session.rounding"][0]
    steps = [spans[f"session.rounding.{p}"][0]
             for p in ("thresholds", "coarsen", "maxflow", "lift")]
    for s in steps:
        assert s.parent_id == outer.span_id
    assert [s.t0 for s in steps] == sorted(s.t0 for s in steps)
    flow = steps[2].attrs
    assert flow["coarse_n"] == res.cut.meta["coarse_n"]
    assert flow["coarse_m"] == res.cut.meta["coarse_m"]
    assert sum(s.dur_s for s in steps) <= outer.dur_s


def test_problem_build_spans(instance, traced):
    from repro.core import Problem
    Problem.build(instance, n_blocks=4)
    spans = _by_name(traced.spans())
    assert spans["session.problem.partition"][0].attrs == {"n_blocks": 4}
    assert len(spans["session.problem.reorder"]) == 1
    # given labels: nothing to partition, the reorder still runs
    traced.clear()
    Problem.build(instance, n_blocks=2,
                  labels=np.arange(instance.n) % 2)
    spans = _by_name(traced.spans())
    assert "session.problem.partition" not in spans
    assert len(spans["session.problem.reorder"]) == 1


def test_tracing_off_records_nothing(instance):
    from repro.core import Problem
    from repro.obs import trace
    assert not trace.enabled()
    trace.clear()
    sess = _session(instance)
    sess.solve(rounding="two_level")
    Problem.build(instance, n_blocks=4)
    assert trace.spans() == []


def test_tracing_on_compiles_no_cost_program(instance, traced, monkeypatch):
    from repro.core import MinCutSession
    from repro.obs.perf import profile as perf_profile
    monkeypatch.delenv(perf_profile.PROFILE_ENV, raising=False)
    assert not perf_profile.default_enabled()
    sess = MinCutSession(instance, _cfg())
    for backend in ("host", "scanned"):
        t = sess.solve(backend=backend, rounding=None).telemetry
        assert t["flops"] is None, backend
    assert sess.program_costs() == {}


def test_named_scope_is_fresh_per_call():
    """Nested calls of one decorated function each get their own scope."""
    from repro.obs.trace import named_scope

    @named_scope("irls.outer")
    def f(x, depth):
        return f(x, depth - 1) * 2 if depth else jnp.sin(x)

    hlo = jax.jit(lambda x: f(x, 1) + jnp.cos(x)).lower(1.0).as_text(
        debug_info=True)
    assert "irls.outer/irls.outer/sin" in hlo
    assert "irls.outer/cos" not in hlo
