"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

What a TPU v5e trace holds (read by hand from a recorded one): planes
named ``/device:TPU:<k>`` are the chips.  On each, the line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
whole text (``%fusion.84 = f32[2304]{0:T(1024)S(1)} fusion(...)``,
``... custom-call(...), custom_call_target="Cholesky"``); a ``while``
event spans the events of its body.  The line ``XLA Modules`` holds one
event per executed program (``jit__step_impl(<hash>)``), and ``Async XLA
Ops`` the asynchronous copies.  The host plane (``/host:CPU``) holds one
line per thread; among its events are the program's spans as
``jax.profiler.TraceAnnotation`` events (``session.solve``,
``serve.batch``, ...) and the harness's own ``bench.window``, whose
interval is the traced window.  Host and device events share one clock.

Per chip, over the window: ``busy`` is the union of the ``XLA Ops``
intervals, ``idle`` the rest of the window, and ``collective`` the union
of the intervals of collective instructions (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute, and their -start/-done
halves).  Op time is summed per program and opcode (a custom call by its
target), leaving out the containers (``while``, ``conditional``,
``call``) whose bodies are counted already.  Each idle gap is charged to
the innermost host span open at its midpoint (``(none)`` where none is).
Numbers are averaged over the chips that ran any operation.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# the program's span names (repro.obs.trace) and the harness's window
SPAN = re.compile(r"^(bench|session|serve|sharded|presolve|cuttree)\.")
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast)")
CONTAINERS = ("while", "conditional", "call")
OPCODE = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
TOP = 10


def opcode(text: str) -> str:
    """The opcode of an HLO instruction's text; a custom call as
    ``custom-call:<target>``."""
    m = OPCODE.search(text)
    if m is None:
        return text.split(" ", 1)[0]
    op = m.group(1)
    if op == "custom-call":
        t = TARGET.search(text)
        if t:
            op += ":" + t.group(1)
    return op


def profile_options():
    """Profiler options for the traced window: the Python function tracer
    off (it would record every Python call of the program and the
    harness); the host's TraceMe events, the spans among them, on."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load(path: Optional[str] = None, data: Optional[bytes] = None):
    from jax.profiler import ProfileData

    if data is not None:
        return ProfileData.from_serialized_xspace(data)
    return ProfileData.from_file(path)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``[k, 2]`` intervals (start, end) into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(new)
    stop = np.append(last[1:], len(iv)) - 1
    return np.stack([starts, ends[stop]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if iv.size else 0.0


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _host_spans(pd) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans.extend(ev for ev in _events(line) if SPAN.match(ev[0]))
    return spans


def reduce(pd) -> Dict[str, object]:
    """Device busy, idle and collective time over the traced window, the
    operations that took most time, and the idle time by open host span.
    Seconds throughout; ``n_devices`` counts the chips that ran an op."""
    spans = _host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                         f"found {len(windows)}")
    w0, w1 = windows[0]
    inner = sorted((s, e, name) for name, s, e in spans
                   if name != WINDOW_SPAN and e > w0 and s < w1)
    starts = np.asarray([s for s, _, _ in inner], dtype=np.float64)
    reach = np.maximum.accumulate([e for _, e, _ in inner]) if inner else []
    busy, coll = [], []
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(_events(line)) for line in plane.lines}
        ops = lines.get(OP_LINE, [])
        if not ops:
            continue
        iv = _clip(np.asarray([(s, e) for _, s, e in ops], dtype=np.float64),
                   w0, w1)
        u = _union(iv)
        busy.append(_length(u))
        mods = sorted((s, e, name.split("(", 1)[0])
                      for name, s, e in lines.get(MODULE_LINE, []))
        mod_starts = np.asarray([m[0] for m in mods], dtype=np.float64)
        civ = []
        for name, s, e in ops:
            op = opcode(name)
            if COLLECTIVE.match(op):
                civ.append((s, e))
            d = min(e, w1) - max(s, w0)
            if d <= 0 or op in CONTAINERS:
                continue
            i = int(np.searchsorted(mod_starts, s, side="right")) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            op_time[f"{mod}:{op}"] += d
        coll.append(_length(_union(_clip(np.asarray(civ, dtype=np.float64)
                                         .reshape(-1, 2), w0, w1))))
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        for g0, g1 in edges[edges[:, 1] > edges[:, 0]]:
            gap_time[_open_span(inner, starts, reach, 0.5 * (g0 + g1))] += g1 - g0
    k = len(busy)
    if k == 0:
        raise ValueError("no device operation in the traced window")
    ns = 1e-9
    return {
        "n_devices": k,
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / k * ns,
        "collective_s": sum(coll) / k * ns,
        "device_ops": _top(op_time, k),
        "idle_gaps": _top(gap_time, k),
    }


def _open_span(spans, starts: np.ndarray, reach, t: float) -> str:
    """Innermost (latest-starting) host span open at ``t``; ``reach[i]``
    is the latest end among spans ``0..i``."""
    for i in range(int(np.searchsorted(starts, t, side="right")) - 1, -1, -1):
        if reach[i] < t:
            break
        if spans[i][1] >= t:
            return spans[i][2]
    return "(none)"


def _top(acc: Dict[str, float], k: int) -> List[List[object]]:
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, v / k * 1e-9] for name, v in rows]
