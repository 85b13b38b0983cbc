"""Continuous profiling: device-side FLOP/byte estimates per solve.

``compiled.cost_analysis()`` is the authoritative XLA flop/byte count —
but it counts every while-loop body exactly ONCE, so a scanned IRLS
program (T-trip ``lax.scan``) under-reports by the trip count.
``launch.hlo_analysis.analyze`` already propagates ``known_trip_count``
multipliers down the HLO call graph; :func:`compiled_costs` reuses that
correction as a RATIO — walker-with-trips over walker-body-once —
applied to XLA's own numbers:

    flops ≈ cost_analysis.flops × (analyze(hlo).flops /
                                   analyze(hlo minus trip counts).flops)

Dynamic-trip whiles (the masked PCG inner loop, host early-exit loops)
carry no ``known_trip_count`` and stay counted once — the estimate is a
LOWER BOUND under adaptive schedules, which is the honest direction for
an achieved-GFLOP/s figure.

Profiling pays one extra AOT ``lower().compile()`` per compiled-program
cache key (≈0.2–1 s), so it is OFF for plain solves and ON when the
obs tracing layer is enabled or ``REPRO_PROFILE=1`` — the bench harness
and the ``bench_diff`` CLI set the env var, so every recorded bench
payload carries achieved GFLOP/s without taxing the unit-test hot path.
"""
from __future__ import annotations

import os
import re
from typing import Any, Dict, Optional

from .peaks import peaks_for

__all__ = ["default_enabled", "compiled_costs", "program_costs",
           "per_solve_cost", "PROFILE_ENV"]

PROFILE_ENV = "REPRO_PROFILE"

_TRIP_MASK = re.compile(r"known_trip_count")


def default_enabled() -> bool:
    """Profile by default?  ``REPRO_PROFILE`` (1/0) wins; otherwise
    follow the tracing switch — a traced run wants the device-side
    counters, an untraced unit test wants the compile time back."""
    env = os.environ.get(PROFILE_ENV, "").strip().lower()
    if env in ("1", "true", "on", "yes"):
        return True
    if env in ("0", "false", "off", "no"):
        return False
    from repro.obs import trace
    return trace.enabled()


def compiled_costs(compiled) -> Dict[str, float]:
    """FLOP/byte estimates of one compiled XLA program (per execution).

    ``compiled`` — a ``jax.stages.Compiled`` (from ``.lower().compile()``).
    Returns ``{"flops", "hbm_bytes", "collective_bytes",
    "cost_analysis_flops", "while_trip_scale"}`` — see module docstring
    for the trip-count correction.
    """
    text = compiled.as_text()
    ca = compiled.cost_analysis() or {}
    raw_flops = float(ca.get("flops", 0.0) or 0.0)
    raw_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)

    from repro.launch.hlo_analysis import analyze
    with_trips = analyze(text)
    body_once = analyze(_TRIP_MASK.sub("masked_trip_count", text))
    fscale = (with_trips.flops / body_once.flops
              if body_once.flops > 0 else 1.0)
    bscale = (with_trips.hbm_bytes / body_once.hbm_bytes
              if body_once.hbm_bytes > 0 else 1.0)
    flops = raw_flops * fscale if raw_flops > 0 else with_trips.flops
    hbm = raw_bytes * bscale if raw_bytes > 0 else with_trips.hbm_bytes
    return {"flops": float(flops), "hbm_bytes": float(hbm),
            "collective_bytes": float(with_trips.collective_bytes),
            "cost_analysis_flops": raw_flops,
            "while_trip_scale": float(fscale)}


def program_costs(jitted, *example_args, **example_kwargs
                  ) -> Dict[str, float]:
    """AOT lower + compile ``jitted`` at the example arguments (concrete
    arrays or ``ShapeDtypeStruct``s) and extract its costs.  A program
    that does not compile raises: the solve it profiles would fail too."""
    compiled = jitted.lower(*example_args, **example_kwargs).compile()
    return compiled_costs(compiled)


def per_solve_cost(cost: Optional[Dict[str, float]], seconds: float,
                   calls: float = 1.0, device_kind: Optional[str] = None
                   ) -> Optional[Dict[str, Any]]:
    """Scale a per-execution cost record to one solve and derive rates.

    ``calls`` — program executions this solve ran (the host backend runs
    its compiled step once per IRLS iteration; scanned/sharded programs
    are whole-solve, calls=1).  ``seconds`` — the solve's IRLS wall.
    ``device_kind`` — the chip the solve ran on (default: the first JAX
    device).  Rates and the roofline fraction (the time the chip's
    published peaks say the flops/bytes NEED, over the wall) are written
    only for a device in ``peaks.PEAKS``; on any other device the record
    carries the counts alone.
    """
    if cost is None:
        return None
    flops = cost["flops"] * calls
    hbm = cost["hbm_bytes"] * calls
    coll = cost.get("collective_bytes", 0.0) * calls
    out: Dict[str, Any] = {
        "flops": flops, "hbm_bytes": hbm, "collective_bytes": coll,
        "program_calls": float(calls),
        "while_trip_scale": cost.get("while_trip_scale", 1.0),
    }
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    pk = peaks_for(device_kind)
    if pk is not None and seconds and seconds > 0:
        out["achieved_gflops"] = flops / seconds / 1e9
        out["achieved_gbps"] = hbm / seconds / 1e9
        t_roof = max(flops / pk.flops, hbm / pk.hbm_bytes_per_s,
                     coll / pk.ici_bytes_per_s)
        out["roofline_fraction"] = t_roof / seconds
    return out
