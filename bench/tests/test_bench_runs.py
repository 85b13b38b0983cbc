"""Every cell, run end to end on the CPU at a tiny size: correct, with
its end-to-end metrics untraced and its per-layer metrics traced; and
with the timed path broken underneath, not correct."""
import threading

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import cells, fake_trace, tiny_root
from repro.core import rounding
from repro.serve import MinCutServer

SEED = 2**31 + 77
CELLS = cells()
SERVER_CELLS = [c for c in CELLS
                if harness.load_cell(c).mix.get("entry") == "server"]


def _run(tmp_path, name, trace=False, monkeypatch=None, seconds=0.5):
    root = tiny_root(tmp_path)
    cell = harness.load_cell(name, root=root)
    if trace:
        monkeypatch.setattr(harness, "_xplane", lambda d: d)
        monkeypatch.setattr(harness.trace_reduce, "load", lambda p: None)
        monkeypatch.setattr(harness.trace_reduce, "reduce",
                            lambda pd: fake_trace())
    out = harness.run_cell(cell, SEED, seconds, trace, jax.devices()[:1],
                           harness.clock(), root=root,
                           trace_dir=str(tmp_path / "trace"))
    return cell, out


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(tmp_path, name):
    cell, out = _run(tmp_path, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["window_compiles"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_traced_reports_every_per_layer_metric(tmp_path, monkeypatch,
                                                    name):
    cell, out = _run(tmp_path, name, trace=True, monkeypatch=monkeypatch)
    assert out["correct"]
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _altered(fault):
    real = rounding.REGISTRY["two_level"]

    def rounder(instance, v, **kw):
        res = real(instance, v, **kw)
        if fault == "partition":
            side = np.asarray(res.in_source).copy()
            side[0] = not side[0]
            return res._replace(in_source=side)
        return res._replace(cut_value=res.cut_value * 1.001)
    return rounder


@pytest.mark.parametrize("fault", ["partition", "value"])
@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(tmp_path, monkeypatch, name, fault):
    monkeypatch.setitem(rounding.REGISTRY, "two_level", _altered(fault))
    _, out = _run(tmp_path, name)
    assert not out["correct"]
    assert out["checks"]["cut_gap"]["value"] > out["checks"]["cut_gap"]["limit"]



def _unanswered(fault, monkeypatch):
    """Leave the requests of the window's first batch without an answer:
    its rounding raises, or the batch is dropped and its futures never
    resolve.  Returns a list that gets the batch's size."""
    size = []
    window = threading.Event()
    here = threading.local()
    real_reset, real_execute = (MinCutServer.reset_measurement,
                                MinCutServer._execute)

    def reset_measurement(self):
        window.set()
        return real_reset(self)

    def execute(self, batch, wid):
        with lock:
            first = window.is_set() and not size
            if first:
                size.append(len(batch.requests))
        if not first:
            return real_execute(self, batch, wid)
        if fault == "raises":
            here.planted = True
            try:
                return real_execute(self, batch, wid)
            finally:
                here.planted = False
        for _ in batch.requests:
            self.admission.release()

    lock = threading.Lock()
    if fault == "raises":
        real = rounding.REGISTRY["two_level"]

        def rounder(instance, v, **kw):
            if getattr(here, "planted", False):
                raise FloatingPointError("planted")
            return real(instance, v, **kw)

        monkeypatch.setitem(rounding.REGISTRY, "two_level", rounder)
    else:
        monkeypatch.setattr(harness, "LATE_S", 1.0)
    monkeypatch.setattr(MinCutServer, "reset_measurement", reset_measurement)
    monkeypatch.setattr(MinCutServer, "_execute", execute)
    return size


@pytest.mark.parametrize("fault", ["raises", "dropped"])
@pytest.mark.parametrize("name", SERVER_CELLS)
def test_unanswered_request_is_counted_failed(tmp_path, monkeypatch, name,
                                              fault):
    """A served request that never returns an answer is counted in
    ``failed`` and fails the run; the others are still answered and
    compared."""
    size = _unanswered(fault, monkeypatch)
    _, out = _run(tmp_path, name)
    assert out["failed"] == size[0] and out["attempted"] > size[0]
    assert not out["correct"]
    assert out["checks"]["failed"] == {"value": size[0], "limit": 0}
    assert out["checks"]["cut_gap"]["value"] <= \
        out["checks"]["cut_gap"]["limit"]
