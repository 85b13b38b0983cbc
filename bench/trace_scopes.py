"""Split a JAX profiler trace's device time by the program's named scopes.

The IRLS step names its phases with ``jax.named_scope``: ``irls.system``,
``irls.factor``, ``irls.pcg`` and ``irls.objective`` (``core/irls.py``,
``core/precond.py``, ``core/pcg.py``, ``core/incidence.py``).  XLA keeps a
scope in each instruction's ``op_name`` metadata, and a TPU trace carries it
as the ``tf_op`` stat of the instruction's event metadata, for example
``jit(_step_impl)/irls.factor/jit(cholesky)/cholesky:`` (the op's name, a
colon, and its type, which XLA leaves empty).  ``ProfileData``
gives an ``XLA Ops`` event only its own stats, so this module reads the
planes' event metadata from the serialized ``XSpace`` with a small
protobuf wire-format reader (no protobuf package).  An event metadata's
``name`` is the instruction text that ``ProfileData`` gives as the event's
``name``; the two are joined on it.

Field numbers (``tsl/profiler/protobuf/xplane.proto``): ``XSpace.planes``
1; ``XPlane.name`` 2, ``event_metadata`` 4 (map entry: key 1, value 2),
``stat_metadata`` 5; ``XEventMetadata.name`` 2, ``stats`` 5;
``XStatMetadata.name`` 2; ``XStat.metadata_id`` 1, ``str_value`` 5,
``ref_value`` 7 (a string interned as a stat metadata's name).  A plane's
``lines`` (field 3), which hold the events, are skipped by length.

Run as a script, it makes one traced run of a cell and prints its
per-phase numbers as the last line (``phase_report``):

    python3 bench/trace_scopes.py --workload road_ny.solve --seed 7 --seconds 45

That is ``bench/harness.py``'s ``--trace 1`` run with the program's span
tracer on from the start of set-up and the trace kept for this reader.
"""
from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

if not __package__:  # run as a script: put the checkout and src/ on the path
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace_reduce  # noqa: E402

PHASE = re.compile(r"(?:^|/)(irls\.[A-Za-z0-9_]+)")
NONE = "(none)"
TF_OP = "tf_op"
ROUNDING_STEPS = ("thresholds", "coarsen", "maxflow", "lift")


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint or
    fixed field, a ``memoryview`` (no copy) for a length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _field(buf, number: int, default=None):
    """The last value of field ``number`` in one message."""
    out = default
    for f, v in _fields(buf):
        if f == number:
            out = v
    return out


def _text(v) -> str:
    return str(v, "utf-8")


def _plane_tf_ops(plane) -> Dict[str, str]:
    """``{event metadata name: tf_op}`` of one ``XPlane``, over the event
    metadata that carry one."""
    events, stat_names = [], {}
    for f, v in _fields(plane):
        if f == 4:
            events.append(_field(v, 2, b""))
        elif f == 5:
            stat_names[_field(v, 1, 0)] = _text(_field(_field(v, 2, b""), 2,
                                                       b""))
    tf_op_id = next((k for k, s in stat_names.items() if s == TF_OP), None)
    out: Dict[str, str] = {}
    if tf_op_id is None:
        return out
    for meta in events:
        name, path = None, None
        for f, v in _fields(meta):
            if f == 2:
                name = _text(v)
            elif f == 5:
                stat = dict(_fields(v))
                if stat.get(1) == tf_op_id:
                    path = (_text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7)))
        if name is not None and path:
            # ``<op_name>:<op_type>``, the type empty for XLA's ops
            out[name] = path.rpartition(":")[0] or path
    return out


def tf_ops(raw: bytes) -> Dict[str, Dict[str, str]]:
    """``{device plane name: {instruction text: tf_op}}`` of a serialized
    ``XSpace``."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        name = _text(_field(plane, 2, b""))
        if trace_reduce.DEVICE_PLANE.match(name):
            out[name] = _plane_tf_ops(plane)
    return out


def phase(path: Optional[str]) -> str:
    """The first ``irls.*`` component of a ``tf_op`` path, or ``(none)``."""
    m = PHASE.search(path or "")
    return m.group(1) if m else NONE


def phase_ops(pd, ops: Dict[str, Dict[str, str]],
              top: Optional[int] = trace_reduce.TOP
              ) -> Dict[str, List[List[object]]]:
    """Device seconds over the traced window (the host span ``bench.window``)
    by phase, then by opcode (a custom call by its target), largest first,
    at most ``top`` per phase (all with None); mean over the chips that ran
    an op.  Every non-container ``XLA Ops`` event counts, clipped to the
    window, under its ``tf_op``'s phase.  Under ``(none)`` (no ``irls.*``
    scope, or no ``tf_op``: XLA drops the metadata of some instructions it
    creates) the key adds the result shape (``fusion f32[516096]``), which
    says whose they are.  ``ops`` is ``tf_ops`` of the same trace."""
    windows = [(s, e) for name, s, e in trace_reduce._host_spans(pd)
               if name == trace_reduce.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW_SPAN!r} span "
                         f"in the trace, found {len(windows)}")
    w0, w1 = windows[0]
    acc: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    chips = 0
    for plane in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        paths = ops.get(plane.name, {})
        events = [ev for line in plane.lines
                  if line.name == trace_reduce.OP_LINE
                  for ev in trace_reduce._events(line)]
        chips += bool(events)
        for name, s, e in events:
            d = min(e, w1) - max(s, w0)
            op = trace_reduce.opcode(name)
            if d <= 0 or op in trace_reduce.CONTAINERS:
                continue
            p = phase(paths.get(name))
            if p == NONE:
                op += " " + name.split(" = ", 1)[-1].split("{", 1)[0].split()[0]
            acc[p][op] += d
    if chips == 0:
        raise ValueError("no device operation in the traced window")
    return {p: [[k, v / chips * 1e-9] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:top]]
            for p, by in sorted(acc.items())}


def scope_s(pd, ops: Dict[str, Dict[str, str]]) -> Dict[str, float]:
    """Device seconds per phase over the traced window (``phase_ops``
    summed)."""
    return {p: sum(v for _, v in rows)
            for p, rows in phase_ops(pd, ops, top=None).items()}


def phase_report(scopes: Dict[str, float], spans: List) -> dict:
    """Per-phase numbers of one traced run.  ``scopes``: ``scope_s`` of its
    window; ``spans``: the program tracer's records (``SpanRecord``) of its
    set-up and window.  Set-up runs one solve, the warm-up; the window's
    solves are the later ``session.solve`` spans."""
    solves = [s for s in spans if s.name == "session.solve"][1:]
    t0 = solves[0].t0 if solves else float("inf")
    n = max(1, len(solves))

    def window(name):
        return [s for s in spans if s.name == name and s.t0 >= t0]

    def ms_per_solve(name):
        return 1e3 * sum(s.dur_s for s in window(name)) / n

    def setup_s(name):
        return sum(s.dur_s for s in spans if s.name == name and s.t0 < t0)

    iters = len(window("session.irls.dispatch"))
    flows = window("session.rounding.maxflow")
    coarse = [s.attrs.get("coarse_n") for s in flows]
    return {
        "solves": len(solves),
        "irls_iters": iters,
        "scope_s": scopes,
        "scope_ms_per_iter": ({p: 1e3 * v / iters for p, v in scopes.items()}
                              if iters else {}),
        "irls_readback_ms_per_iter": (ms_per_solve("session.irls.readback")
                                      * n / iters if iters else None),
        "rounding_ms": ms_per_solve("session.rounding"),
        "rounding_step_ms": {p: ms_per_solve(f"session.rounding.{p}")
                             for p in ROUNDING_STEPS},
        "coarse_n": coarse,
        "coarse_nodes": sum(coarse) / len(coarse) if coarse else None,
        "solve_wall_s": [s.dur_s for s in solves],
        "partition_s": setup_s("session.problem.partition"),
        "reorder_s": setup_s("session.problem.reorder"),
    }


def main(argv=None) -> int:
    from bench import harness
    from repro.obs import trace

    t_start = harness.clock()
    trace.configure(enabled=True)
    kept = {}
    load = trace_reduce.load

    def keep(path=None, data=None):
        with open(path, "rb") as f:
            kept["raw"] = f.read()
        kept["pd"] = load(data=kept["raw"])
        return kept["pd"]

    trace_reduce.load = keep
    try:
        rc = harness.main(list(sys.argv[1:] if argv is None else argv)
                          + ["--trace", "1"], t_start=t_start)
    finally:
        trace_reduce.load = load
    if rc != 0 or "pd" not in kept:
        return rc
    ops = tf_ops(kept["raw"])
    report = phase_report(scope_s(kept["pd"], ops), trace.spans())
    report["phase_ops"] = phase_ops(kept["pd"], ops)
    print(json.dumps({"phases": report}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
