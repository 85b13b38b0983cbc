"""End-to-end observability: tracing, metrics, solver telemetry.

Zero-dependency (numpy only; jax imported lazily for fences) substrate
shared by every subsystem:

    trace      — thread-safe nested span tracer: in-memory ring +
                 optional JSONL sink + ``jax.profiler`` passthrough;
                 free when disabled (``trace.configure(enabled=True)``)
    metrics    — Counter/Gauge/Histogram (bounded reservoir) registry
                 with JSON + Prometheus-text exposition
    telemetry  — the ``SolveResult.telemetry`` schema and its
                 per-session / per-server aggregation
    dashboard  — JSONL sink reader + flamegraph-style text rendering
                 (driven by ``python -m repro.launch.obs``)

Instrumented span names by subsystem (the CI obs smoke asserts one of
each appears in a traced serve replay; docs/API.md "Observability" has
the full schema):

    serve.*     engine queue/assembly/dispatch/flush  (serve/engine.py)
    session.*   solve / solve_batch / presolve / irls / rounding phases;
                irls.dispatch / irls.readback per host IRLS iteration
                (core/irls.py); rounding.thresholds / .coarsen / .maxflow /
                .lift (core/rounding.py); problem.partition / .reorder
                (Problem.build)
    presolve.*  kernelization fixpoint                (presolve/contract.py)
    cuttree.*   build / wave / speculation            (cuttree/gusfield.py)
    sharded.*   SPMD solve + collective gauges        (distributed/solver.py)

Device phases (``jax.named_scope`` via ``trace.named_scope``; HLO
metadata, so a profiler trace's ``tf_op`` paths carry them):
``irls.system`` (system build), ``irls.factor`` (preconditioner
construction), ``irls.pcg`` (the PCG solve), ``irls.objective``.
"""
from . import dashboard, metrics, telemetry, trace
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, Reservoir,
                      get_registry, parse_prometheus_text)
from .telemetry import TelemetryAggregator, build_solve_telemetry
from .trace import Tracer, configure, enabled, event, fence, get_tracer, span


def bench_snapshot() -> dict:
    """Observability snapshot for ``BENCH_*.json`` payloads.

    Always includes the global metrics registry; includes a span-path
    summary only when tracing ran (the payload stays small and
    deterministic-ish otherwise).
    """
    out = {"metrics": get_registry().snapshot()}
    spans = trace.spans()
    if spans:
        agg = dashboard.aggregate([s.to_dict() for s in spans])
        out["span_paths"] = {
            path: {"count": int(d["count"]),
                   "total_s": d["total_s"], "self_s": d["self_s"]}
            for path, d in sorted(agg.items())}
    return out
