"""The split of device time by named scope: the trace metadata reader on a
synthetic ``XSpace`` and on a trace recorded on a TPU v5e, and the
per-phase numbers from known events and spans."""
import gzip
import os
from collections import defaultdict
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce, trace_scopes
from bench.tests.test_bench_trace_reduce import CHOLESKY, FUSION, WHILE

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_v5e_small.xplane.pb.gz")


# -- a minimal protobuf writer for synthetic XSpaces --------------------------

def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, value: bytes) -> bytes:
    return _f(1, key) + _f(2, value)


def _plane(name, stats, events, lines=b""):
    """``stats``: {id: name}; ``events``: {id: (name, [(stat id, field,
    value)])}."""
    out = _f(2, name) + _f(3, lines)
    for k, (ev, st) in events.items():
        meta = _f(1, k) + _f(2, ev) + b"".join(
            _f(5, _f(1, sid) + _f(field, v)) for sid, field, v in st)
        out += _f(4, _entry(k, meta))
    for k, s in stats.items():
        out += _f(5, _entry(k, _f(1, k) + _f(2, s)))
    return out


PATH_F = "jit(_step_impl)/irls.system/add"
PATH_C = "jit(_step_impl)/irls.factor/jit(cholesky)/cholesky"


def _space():
    stats = {3: "flops", 7: "tf_op", 9: PATH_C}
    dev = _plane("/device:TPU:0", stats, {
        1: (FUSION, [(3, 3, 12), (7, 5, PATH_F)]),
        2: (CHOLESKY, [(7, 7, 9)]),               # interned: ref_value
        4: (WHILE, [(3, 3, 1)]),                  # no tf_op
    }, lines=b"\x08\x01" * 64)
    host = _plane("/host:CPU", {1: "tf_op"}, {1: ("x", [(1, 5, "irls.pcg")])})
    return _f(1, host) + _f(1, dev) + _f(1, _plane("/device:TPU:1", {}, {}))


def test_tf_ops_reads_str_and_interned_values():
    assert trace_scopes.tf_ops(_space()) == {
        "/device:TPU:0": {FUSION: PATH_F, CHOLESKY: PATH_C},
        "/device:TPU:1": {}}


@pytest.mark.parametrize("path,want", [
    (PATH_C, "irls.factor"),
    ("jit(_step_impl)/irls.pcg/while/body/jit(_cho_solve)/triangular_solve",
     "irls.pcg"),
    ("jit(run)/while/body/irls.system/irls.pcg/mul", "irls.system"),
    ("jit(_step_impl)/jit(cholesky)/cholesky", "(none)"),
    ("jit(f)/birls.pcg/add", "(none)"),
    (None, "(none)"),
])
def test_phase(path, want):
    assert trace_scopes.phase(path) == want


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _pd():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("session.solve", 10, 900)])])
    other = "%fusion.7 = f32[4]{0} fusion(f32[4]{0} %p.2), kind=kLoop"
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev(FUSION, 100, 200), _ev(WHILE, 50, 900),
                                   _ev(CHOLESKY, 400, 300),
                                   _ev(other, 900, 200)]),
        NS(name="XLA Modules", events=[_ev("jit_step(1)", 50, 900)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev(FUSION, 0, 100)])])
    return NS(planes=[host, dev0, dev1])


def test_scope_s_synthetic():
    ops = {"/device:TPU:0": {FUSION: PATH_F, CHOLESKY: PATH_C},
           "/device:TPU:1": {FUSION: PATH_F}}
    got = trace_scopes.scope_s(_pd(), ops)
    # the while is a container; the last fusion is clipped to the window
    assert got == pytest.approx({"irls.system": (200 + 100) / 2 * 1e-9,
                                 "irls.factor": 300 / 2 * 1e-9,
                                 "(none)": 100 / 2 * 1e-9})
    # without metadata every op falls under (none)
    assert trace_scopes.scope_s(_pd(), {}) == pytest.approx(
        {"(none)": 700 / 2 * 1e-9})


def test_phase_ops():
    """Scoped time by opcode; unscoped time by opcode and shape."""
    ops = {"/device:TPU:0": {FUSION: PATH_F}}
    got = trace_scopes.phase_ops(_pd(), ops)
    assert dict(got["irls.system"]) == pytest.approx({"fusion": 200 / 2
                                                     * 1e-9})
    none = got["(none)"]
    assert none[0][0] == "custom-call:Cholesky f32[4,8,8]"
    assert dict(none) == pytest.approx({
        "custom-call:Cholesky f32[4,8,8]": 300 / 2 * 1e-9,
        "fusion f32[4]": 100 / 2 * 1e-9,
        "fusion f32[8]": 100 / 2 * 1e-9})
    assert trace_scopes.phase_ops(_pd(), ops, top=1)["(none)"] == none[:1]


def test_scope_s_needs_a_window():
    pd = _pd()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace_scopes.scope_s(pd, {})


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rb") as f:
        raw = f.read()
    return raw, trace_reduce.load(data=raw)


def _op_time_by_path(recorded):
    raw, pd = recorded
    paths = trace_scopes.tf_ops(raw)["/device:TPU:0"]
    by = defaultdict(float)
    for p in pd.planes:
        if p.name != "/device:TPU:0":
            continue
        for line in p.lines:
            if line.name != trace_reduce.OP_LINE:
                continue
            for e in line.events:
                op = trace_reduce.opcode(e.name)
                if op not in trace_reduce.CONTAINERS:
                    by[(op, paths.get(e.name))] += e.duration_ns * 1e-6
    return by


def test_recorded_trace_metadata(recorded):
    """The recorded trace predates the scopes: its tf_op paths name the
    jitted functions only.  The triangular-solve custom call splits between
    the factor (the Cholesky's expansion) and the preconditioner's applies
    before and inside the PCG loop."""
    by = _op_time_by_path(recorded)
    chol = {path for (op, path) in by if op == "custom-call:Cholesky"}
    assert chol == {"jit(_step_impl)/jit(cholesky)/cholesky"}
    inv = {path: ms for (op, path), ms in by.items()
           if op == "custom-call:InvertDiagBlocksLowerTriangular"}
    assert inv == pytest.approx({
        "jit(_step_impl)/jit(cholesky)/cholesky": 1.85,
        "jit(_step_impl)/jit(_cho_solve)/triangular_solve": 3.70,
        "jit(_step_impl)/while/body/jit(_cho_solve)/triangular_solve": 1.14,
    }, abs=0.005)
    covered = sum(ms for (_, path), ms in by.items() if path)
    assert covered / sum(by.values()) == pytest.approx(0.945, abs=0.005)


def test_recorded_trace_scope_s(recorded):
    """No op of the recorded trace carries an ``irls.*`` scope; the phases
    still add up to the window's op time."""
    raw, pd = recorded
    got = trace_scopes.scope_s(pd, trace_scopes.tf_ops(raw))
    assert set(got) == {"(none)"}
    r = trace_reduce.reduce(pd)
    assert got["(none)"] <= r["busy_s"] * 1.001
    assert got["(none)"] >= sum(v for _, v in r["device_ops"])


def _span(name, t0, t1, **attrs):
    return NS(name=name, t0=t0, t1=t1, dur_s=t1 - t0, attrs=attrs)


def test_phase_report():
    spans = [_span("session.problem.partition", 0.0, 2.0, n_blocks=4),
             _span("session.problem.reorder", 2.0, 2.5),
             _span("session.solve", 3.0, 4.0),             # warm-up
             _span("session.irls.dispatch", 3.1, 3.2, l=0),
             _span("session.rounding.maxflow", 3.5, 3.6, coarse_n=99)]
    for k, t in enumerate((10.0, 20.0)):
        spans += [_span("session.irls.dispatch", t, t + 0.1, l=0),
                  _span("session.irls.readback", t + 0.1, t + 0.2),
                  _span("session.irls.dispatch", t + 0.2, t + 0.3, l=1),
                  _span("session.irls.readback", t + 0.3, t + 0.4),
                  _span("session.rounding.thresholds", t + 1, t + 1.1),
                  _span("session.rounding.coarsen", t + 1.1, t + 1.3),
                  _span("session.rounding.maxflow", t + 1.3, t + 2.3,
                        coarse_n=10 * (k + 1)),
                  _span("session.rounding.lift", t + 2.3, t + 2.4),
                  _span("session.rounding", t + 1, t + 2.5),
                  _span("session.solve", t, t + 3)]
    r = trace_scopes.phase_report({"irls.pcg": 0.4, "(none)": 0.04}, spans)
    assert r["solves"] == 2 and r["irls_iters"] == 4
    assert r["scope_ms_per_iter"] == pytest.approx({"irls.pcg": 100.0,
                                                    "(none)": 10.0})
    assert r["irls_readback_ms_per_iter"] == pytest.approx(100.0)
    assert r["rounding_ms"] == pytest.approx(1500.0)
    assert r["rounding_step_ms"] == pytest.approx(
        {"thresholds": 100.0, "coarsen": 200.0, "maxflow": 1000.0,
         "lift": 100.0})
    assert r["coarse_n"] == [10, 20] and r["coarse_nodes"] == 15.0
    assert r["solve_wall_s"] == pytest.approx([3.0, 3.0])
    assert r["partition_s"] == 2.0 and r["reorder_s"] == 0.5


def test_phase_report_without_spans():
    r = trace_scopes.phase_report({"(none)": 1.0}, [])
    assert r["solves"] == 0 and r["irls_iters"] == 0
    assert r["scope_ms_per_iter"] == {} and r["coarse_nodes"] is None
    assert r["partition_s"] == 0.0
