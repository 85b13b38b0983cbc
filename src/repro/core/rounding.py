"""Rounding procedures: sweep cut and the two-level procedure (paper §3.4).

* ``sweep_cut`` — the standard spectral-style rounding: sort nodes by
  voltage, evaluate every prefix cut with difference arrays (fully
  vectorized, O(m + n log n)), return the best threshold.  Runs in JAX.

* ``two_level`` — the paper's contribution: exploit *node voltage
  polarization*.  K-means (2 centers, init 0.1/0.9) on x^(T) picks
  γ₀ = c₀ + 0.05 and γ₁ = c₁ − 0.05; nodes with x ≤ γ₀ are contracted into
  the sink, x ≥ γ₁ into the source; the SMALL coarsened graph is solved
  exactly (core/maxflow.py = the paper's B-K step) and the cut is lifted
  back.  Prop 3.1 gives the exactness condition.

Both return a boolean indicator over non-terminal nodes (True = source side)
plus the achieved cut value.

Procedures are looked up through ``REGISTRY`` (name → rounder) so new
strategies plug into the solver drivers without touching them: register with
``@register("name")`` a callable ``(instance, voltages, **kw) →
RoundingResult``.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import numpy as np

try:
    import jax
    import jax.numpy as jnp
except Exception:  # pragma: no cover
    jax = None

from repro.graphs.structures import EdgeList, STInstance
from repro.obs import trace


class RoundingResult(NamedTuple):
    in_source: np.ndarray   # bool[n]
    cut_value: float
    meta: dict


# rounder signature: (instance, voltages, **kw) -> RoundingResult
Rounder = Callable[..., "RoundingResult"]

REGISTRY: Dict[str, Rounder] = {}


def register(name: str):
    """Register a rounding procedure under ``rounding == name``."""
    def deco(fn: Rounder) -> Rounder:
        REGISTRY[name] = fn
        return fn
    return deco


def round_voltages(name: str, instance, v, **kw) -> "RoundingResult":
    """Resolve ``name`` through REGISTRY and round the voltage vector."""
    try:
        rounder = REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown rounding {name!r}; "
                         f"registered: {sorted(REGISTRY)}") from None
    return rounder(instance, v, **kw)


# ---------------------------------------------------------------------------
# Sweep cut
# ---------------------------------------------------------------------------

def sweep_cut_jax(src, dst, w, s_w, t_w, v):
    """All-prefix cut evaluation, device-side.

    Sort nodes by voltage DESCENDING; prefix i (1..n) puts the top-i nodes on
    the source side.  An internal edge (u,x) crosses iff exactly one endpoint
    is inside the prefix: contributes for i in [min(r_u,r_x)+1, max(..)].
    Terminal s-edge (s,u) crosses while u is OUTSIDE: i in [0, r_u];
    terminal t-edge (u,t) crosses while u is INSIDE: i in [r_u+1, n].
    Difference arrays + cumsum give cut(i) for every i in one pass.
    """
    n = v.shape[0]
    order = jnp.argsort(-v)            # order[i] = node at rank i (0-based)
    rank = jnp.zeros((n,), dtype=jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    ru = rank[src]
    rx = rank[dst]
    lo = jnp.minimum(ru, rx)
    hi = jnp.maximum(ru, rx)
    # diff over prefix index i in [1..n]; array slot j holds cut at i = j+1
    d = jnp.zeros((n + 1,), dtype=v.dtype)
    d = d.at[lo].add(w)        # starts crossing at i = lo+1  (slot lo)
    d = d.at[hi].add(-w)       # stops crossing at i = hi+1   (slot hi)
    # s-edges cross for i ≤ r_u, i.e. slots [0, r_u-1]... careful: at i=0
    # nothing is on the source side except s itself; prefix i covers slots
    # j = i-1. s-edge crosses while u outside: i in [0..r_u] → slots start
    # at -1; fold the i=0 constant in `base`.
    base = jnp.sum(s_w)        # cut at i = 0: all s-edges cross
    d = d.at[rank].add(-s_w)   # u enters at i = rank+1 → s-edge stops
    d = d.at[rank].add(t_w)    # u enters → its t-edge starts crossing
    cuts = base + jnp.cumsum(d)[:n]    # cuts[j] = cut at prefix i = j+1
    # an s-t cut may place all non-terminals on one side, so every prefix
    # i ∈ [0, n] is valid (i=0 handled via `base` below)
    best = jnp.argmin(cuts)
    best_val = cuts[best]
    i0_val = base  # prefix 0: every non-terminal on sink side
    use0 = i0_val < best_val
    in_source = rank <= jnp.where(use0, -1, best)
    return in_source, jnp.where(use0, i0_val, best_val)


@register("sweep")
def sweep_cut(instance: STInstance, v: np.ndarray) -> RoundingResult:
    g = instance.graph
    ind, val = jax.jit(sweep_cut_jax)(
        jnp.asarray(np.asarray(g.src), jnp.int32),
        jnp.asarray(np.asarray(g.dst), jnp.int32),
        jnp.asarray(np.asarray(g.weight), jnp.float32),
        jnp.asarray(np.asarray(instance.s_weight), jnp.float32),
        jnp.asarray(np.asarray(instance.t_weight), jnp.float32),
        jnp.asarray(np.asarray(v), jnp.float32),
    )
    ind = np.asarray(ind)
    exact = instance.cut_value(ind)   # recompute in f64 on host
    return RoundingResult(in_source=ind, cut_value=exact,
                          meta={"method": "sweep"})


# ---------------------------------------------------------------------------
# Two-level rounding
# ---------------------------------------------------------------------------

def kmeans_thresholds(v: np.ndarray, n_iters: int = 25,
                      margin: float = 0.05) -> Tuple[float, float]:
    """2-means on the voltages, centers initialized at 0.1 / 0.9 (paper
    §3.4); γ₀ = c₀ + margin, γ₁ = c₁ − margin."""
    c0, c1 = 0.1, 0.9
    for _ in range(n_iters):
        assign1 = np.abs(v - c1) < np.abs(v - c0)
        if assign1.any():
            c1 = float(v[assign1].mean())
        if (~assign1).any():
            c0 = float(v[~assign1].mean())
    if c0 > c1:
        c0, c1 = c1, c0
    return c0 + margin, c1 - margin


def coarsen(instance: STInstance, v: np.ndarray, gamma0: float,
            gamma1: float) -> Tuple[STInstance, np.ndarray, np.ndarray, float]:
    """Contract S₀ = {x ≤ γ₀} into the sink and S₁ = {x ≥ γ₁} into the
    source (paper §3.4 edge-weight rules).  Returns the coarse instance, the
    label array (0 = sink-merged, 1 = source-merged, 2+k = contour node k)
    and the contour node ids."""
    g = instance.graph
    v = np.asarray(v)
    in_s0 = v <= gamma0
    in_s1 = v >= gamma1
    contour = ~(in_s0 | in_s1)
    contour_ids = np.nonzero(contour)[0]
    nc = len(contour_ids)
    # map original node -> coarse id (contour nodes are 0..nc-1 in coarse)
    cmap = np.full(g.n, -1, dtype=np.int64)
    cmap[contour_ids] = np.arange(nc)

    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.weight, dtype=np.float64)
    cs = np.zeros(nc, dtype=np.float64)  # coarse source-terminal weights
    ct = np.zeros(nc, dtype=np.float64)

    # original terminal edges of contour nodes survive
    cs += np.asarray(instance.s_weight, dtype=np.float64)[contour_ids]
    ct += np.asarray(instance.t_weight, dtype=np.float64)[contour_ids]

    a_s0 = in_s0[src]; a_s1 = in_s1[src]; a_c = contour[src]
    b_s0 = in_s0[dst]; b_s1 = in_s1[dst]; b_c = contour[dst]

    # contour-contour edges survive
    cc = a_c & b_c
    c_src = cmap[src[cc]]
    c_dst = cmap[dst[cc]]
    c_w = w[cc]

    # contour-S1 edges become source-terminal; contour-S0 become sink-terminal
    for a, b in ((src, dst), (dst, src)):
        am = contour[a]
        sel = am & in_s1[b]
        np.add.at(cs, cmap[a[sel]], w[sel])
        sel = am & in_s0[b]
        np.add.at(ct, cmap[a[sel]], w[sel])

    # S0/S1 internal or s_c—t_c edges: constant offset (never part of the
    # optimization).  s_c—t_c edges DO count toward the final cut value.
    st_cross = float(w[(a_s1 & b_s0) | (a_s0 & b_s1)].sum())
    # original terminal edges absorbed by contraction:
    #   s—u for u ∈ S0 is an s_c—t_c edge; u—t for u ∈ S1 likewise
    st_cross += float(np.asarray(instance.s_weight, dtype=np.float64)[in_s0].sum())
    st_cross += float(np.asarray(instance.t_weight, dtype=np.float64)[in_s1].sum())

    coarse = STInstance(
        graph=EdgeList(src=c_src.astype(np.int32), dst=c_dst.astype(np.int32),
                       weight=c_w, n=nc),
        s_weight=cs, t_weight=ct,
    )
    labels = np.where(in_s1, 1, np.where(in_s0, 0, 2))
    return coarse, labels, contour_ids, st_cross


@register("two_level")
def two_level(instance: STInstance, v: np.ndarray,
              margin: float = 0.05) -> RoundingResult:
    """The paper's two-level rounding: coarsen by polarization, solve the
    coarse instance EXACTLY, lift.  Each step is a span (no-op with tracing
    off): ``session.rounding.thresholds``, ``.coarsen``, ``.maxflow``
    (attributes ``coarse_n``, ``coarse_m``) and ``.lift``."""
    with trace.span("session.rounding.thresholds"):
        gamma0, gamma1 = kmeans_thresholds(np.asarray(v), margin=margin)
    with trace.span("session.rounding.coarsen"):
        coarse, labels, contour_ids, st_cross = coarsen(instance, v, gamma0,
                                                        gamma1)
    from .maxflow import max_flow
    if coarse.n == 0:
        # degenerate coarsening (fully polarized voltages): the threshold
        # assignment IS the cut; fall back to the better of it and sweep
        with trace.span("session.rounding.lift"):
            in_source = labels == 1
            thr = RoundingResult(in_source=in_source,
                                 cut_value=instance.cut_value(in_source),
                                 meta={"method": "two_level",
                                       "gamma0": gamma0, "gamma1": gamma1,
                                       "coarse_n": 0,
                                       "reduction": float(instance.n + 2)})
            sw = sweep_cut(instance, v)
        return thr if thr.cut_value <= sw.cut_value else \
            RoundingResult(in_source=sw.in_source, cut_value=sw.cut_value,
                           meta=dict(thr.meta, fallback="sweep"))
    with trace.span("session.rounding.maxflow", coarse_n=int(coarse.n),
                    coarse_m=int(coarse.graph.m)):
        res = max_flow(coarse)
    with trace.span("session.rounding.lift"):
        in_source = labels == 1
        in_source[contour_ids] = res.in_source[: coarse.n]
        exact = instance.cut_value(in_source)
    meta = {
        "method": "two_level", "gamma0": gamma0, "gamma1": gamma1,
        "coarse_n": int(coarse.n), "coarse_m": int(coarse.graph.m),
        "reduction": (instance.n + 2) / max(1, coarse.n + 2),
        "coarse_flow": float(res.value), "st_cross": st_cross,
    }
    return RoundingResult(in_source=in_source, cut_value=exact, meta=meta)
