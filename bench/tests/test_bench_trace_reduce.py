"""The trace reduction, on a synthetic trace whose numbers are known and
on a small trace recorded on a TPU v5e."""
import gzip
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import trace_reduce

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_v5e_small.xplane.pb.gz")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines])


FUSION = "%fusion.1 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p.1), kind=kLoop"
WHILE = ("%while.2 = (s32[]{:T(128)}, f32[8]{0:T(1024)S(1)}) "
         "while((s32[]{:T(128)}, f32[8]{0}) %tuple.3), condition=%c, body=%b")
ALL_REDUCE = "%all-reduce.3 = f32[2]{0} all-reduce(f32[2]{0} %x), to_apply=%s"
PERMUTE = ("%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) "
           "collective-permute-start(f32[8]{0} %y), source_target_pairs={}")
CHOLESKY = ("%custom-call.9 = f32[4,8,8]{2,1,0:T(8,128)} custom-call(f32[4,8,8]"
            "{2,1,0} %s.1), custom_call_target=\"Cholesky\"")


def test_opcode():
    assert trace_reduce.opcode(FUSION) == "fusion"
    assert trace_reduce.opcode(WHILE) == "while"
    assert trace_reduce.opcode(CHOLESKY) == "custom-call:Cholesky"
    assert trace_reduce.opcode(PERMUTE) == "collective-permute-start"


def _synthetic():
    host = _plane("/host:CPU", [
        ("python", [_ev("bench.window", 0, 1000),
                    _ev("session.solve", 60, 890),
                    _ev("session.rounding", 700, 200),
                    _ev("$profiler start", 0, 5)]),
        ("worker", [_ev("PjitFunction(step)", 100, 10)])])
    dev0 = _plane("/device:TPU:0", [
        ("XLA Ops", [_ev(FUSION, 100, 200), _ev(WHILE, 100, 250),
                     _ev(FUSION, 250, 100), _ev(ALL_REDUCE, 400, 100),
                     _ev(FUSION, 1200, 100)]),
        ("XLA Modules", [_ev("jit_step(123)", 100, 400)])])
    dev1 = _plane("/device:TPU:1", [
        ("XLA Ops", [_ev(PERMUTE, 0, 300), _ev(CHOLESKY, 600, 100)]),
        ("XLA Modules", [_ev("jit_step(123)", 0, 300),
                         _ev("jit_run(9)", 600, 100)])])
    return NS(planes=[host, dev0, dev1, _plane("/host:metadata", [])])


def test_synthetic_trace_numbers():
    r = trace_reduce.reduce(_synthetic())
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy [100, 350) + [400, 500) = 350; chip 1 [0, 300) + [600, 700)
    assert r["busy_s"] == pytest.approx((350 + 400) / 2 * 1e-9)
    assert r["collective_s"] == pytest.approx((100 + 300) / 2 * 1e-9)
    ops = dict(r["device_ops"])
    # the while spans its body and is not counted; the third fusion lies
    # outside the window
    assert ops == pytest.approx({
        "jit_step:fusion": 300 / 2 * 1e-9,
        "jit_step:all-reduce": 100 / 2 * 1e-9,
        "jit_step:collective-permute-start": 300 / 2 * 1e-9,
        "jit_run:custom-call:Cholesky": 100 / 2 * 1e-9})
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(1000e-9 - r["busy_s"])
    # chip 0 idles [0, 100) before any span, [500, 1000) in rounding
    assert gaps["session.rounding"] > 0 and "session.solve" in gaps
    assert "(none)" in gaps


def test_no_window_or_no_device_is_an_error():
    t = _synthetic()
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(t)
    t = _synthetic()
    t.planes = t.planes[:1]
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(t)


def test_union_merges_overlaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], float)
    assert trace_reduce._union(iv).tolist() == [[0, 3], [5, 9], [10, 11]]


def _recorded():
    with gzip.open(RECORDED, "rb") as f:
        return trace_reduce.load(data=f.read())


def test_recorded_tpu_trace():
    """A short traced window on one TPU v5e (a warm and a cold host-backend
    solve and one served request on a small road graph): the reduction
    agrees with a plain walk over the same events."""
    pd = _recorded()
    window = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in pd.planes if p.name == "/host:CPU"
              for line in p.lines for e in line.events
              if e.name == "bench.window"]
    assert len(window) == 1
    w0, w1 = window[0]
    ops = sorted((max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
                 for p in pd.planes if p.name == "/device:TPU:0"
                 for line in p.lines if line.name == "XLA Ops"
                 for e in line.events)
    busy, end = 0.0, w0
    for s, e in ops:
        if e > end:
            busy += e - max(s, end)
            end = e
    r = trace_reduce.reduce(pd)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0.0
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert set(gaps) <= {"session.solve", "session.irls", "session.rounding",
                         "session.solve_batch", "serve.batch",
                         "serve.assembly", "(none)"}
    ops = dict(r["device_ops"])
    assert "jit__step_impl:custom-call:Cholesky" in ops
    assert not any(k.endswith(":while") for k in ops)
    assert len(r["device_ops"]) <= trace_reduce.TOP
