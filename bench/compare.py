"""How ``correct`` is decided: the program's cuts against the exact
reference (``bench/reference/maxflow.py``), on the very weights the timed
path was given.

Numbers compared, each the worst over the checked answers:

* ``cut_gap`` — how far the answer lies above the minimum cut, as a share
  of the reference's certified lower bound L: the larger of (value of the
  program's partition − L) and |cut value the program reported − L|, over
  L.  Both values are summed in float64 on the benchmark's own copy of
  the weights.  The configurations promise an exact minimum cut; a sound
  answer reads the certificate's own slack, under 1e-8 at the cells'
  sizes.
* ``failed`` — the requests of the window that never returned an answer:
  a future that raised, or one still open a minute past the close.  The
  harness counts them (``entry.failed()``); the limit is 0.  A session
  solve that raises ends the run instead, which then prints no result.

``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

from bench.reference.maxflow import Reference, cut_value

LIMITS = {
    "cut_gap": 5e-8,
    "failed": 0,
}

# (edge weights, source weights, sink weights), partition, reported value
Answer = Tuple[Tuple[np.ndarray, np.ndarray, np.ndarray], np.ndarray, float]


def compare(ref: Reference, answers: Iterable[Answer],
            failed: int) -> Dict[str, float]:
    """The numbers compared: the worst over ``answers``, and the count of
    requests that ``failed`` to return one."""
    cut_gap = 0.0
    for (w, s_w, t_w), in_source, reported in answers:
        side = np.asarray(in_source)
        if side.shape != (ref.n,) or side.dtype != bool:
            cut_gap = float("inf")
            continue
        low = ref.solve(w, s_w, t_w).lower
        value = cut_value(ref.src, ref.dst, w, s_w, t_w, side)
        gap = max(value - low, abs(float(reported) - low)) / low
        cut_gap = max(cut_gap, gap if np.isfinite(gap) else float("inf"))
    return {"cut_gap": cut_gap, "failed": int(failed)}


def judge(numbers: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """Whether every number is within its limit, and each beside it; a
    number left out raises."""
    checks = {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
