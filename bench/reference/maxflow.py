"""Exact s-t min cut with a certificate, for the benchmark's comparison.

The plain reference of the system under test: the min cut of an
undirected instance with terminal edges (s = n, t = n + 1), from a
combinatorial max flow and nothing of the program.  It stands in for the
program's own Dinic (``repro.core.maxflow``), which is pure Python and
takes minutes at the MRI block's 1.4M edges.

Floating capacities are scaled by a power of two ``scale`` (the largest
that keeps every arc below 2**31) and rounded down to integers; SciPy's
Dinic (``scipy.sparse.csgraph.maximum_flow``) then gives an exact integer
max flow F.  Every flow under the rounded capacities is a flow under the
true ones, so ``lower = F / scale`` is a certified lower bound on the true
min cut, and it falls short by less than (arcs in the cut) / ``scale``.
The residual graph's source side is a cut; its true value in float64 is
``upper``.  The true min cut lies in ``[lower, upper]``, and any cut of
value ``c`` is at most ``(c - lower) / lower`` above the minimum.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order, maximum_flow

INT_CAP = 2 ** 31 - 1


class MinCut(NamedTuple):
    lower: float          # certified lower bound on the min cut value
    upper: float          # true value of the reference's own cut
    in_source: np.ndarray  # bool[n]: the reference's cut, True = s side


def cut_value(src, dst, w, s_w, t_w, in_source) -> float:
    """Value of the cut ``in_source`` (True = s side), summed in float64."""
    side = np.asarray(in_source, dtype=bool)
    return float(np.sum(np.asarray(w, np.float64)[side[src] != side[dst]])
                 + np.sum(np.asarray(s_w, np.float64)[~side])
                 + np.sum(np.asarray(t_w, np.float64)[side]))


class Reference:
    """Exact min cuts on one topology under many weight vectors: the arc
    structure is laid out once; each call only fills capacities."""

    def __init__(self, n: int, src, dst):
        self.n = int(n)
        self.src = np.asarray(src, dtype=np.int64)
        self.dst = np.asarray(dst, dtype=np.int64)
        nodes = np.arange(self.n, dtype=np.int64)
        s, t = self.n, self.n + 1
        # arcs: u→v and v→u per edge (capacity c each), s→u and u→t per node
        self._rows = np.concatenate([self.src, self.dst,
                                     np.full(self.n, s), nodes])
        self._cols = np.concatenate([self.dst, self.src, nodes,
                                     np.full(self.n, t)])

    def solve(self, w, s_w, t_w) -> MinCut:
        n, s, t = self.n, self.n, self.n + 1
        caps = np.concatenate([w, w, s_w, t_w]).astype(np.float64)
        if not np.all(np.isfinite(caps)) or np.any(caps < 0):
            raise ValueError("capacities must be finite and non-negative")
        scale = float(2.0 ** np.floor(np.log2(INT_CAP / caps.max())))
        icap = np.floor(caps * scale).astype(np.int32)
        live = icap > 0
        a = csr_matrix((icap[live], (self._rows[live], self._cols[live])),
                       shape=(n + 2, n + 2))
        res = maximum_flow(a, s, t, method="dinic")
        resid = (a - res.flow).tocsr()
        resid.data = (resid.data > 0).astype(np.int8)
        resid.eliminate_zeros()
        reach = breadth_first_order(resid, s, directed=True,
                                    return_predecessors=False)
        side = np.zeros(n + 2, dtype=bool)
        side[reach] = True
        if side[t]:
            raise RuntimeError("residual graph still reaches t: the flow "
                               "is not maximal")
        in_source = side[:n]
        return MinCut(lower=int(res.flow_value) / scale,
                      upper=cut_value(self.src, self.dst, w, s_w, t_w,
                                      in_source),
                      in_source=in_source)
