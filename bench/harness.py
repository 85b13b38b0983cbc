"""Run one benchmark cell once and report it as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration (its
file under ``bench/configs/``) and a traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is read by
``bench/metrics/<metric>.py``.  The traffic file's ``entry`` chooses how
the program is driven: ``session`` (the default), back-to-back
``MinCutSession.solve`` calls, or ``server``, closed-loop clients of
``MinCutServer.submit``.  The harness finds all of them by name, so a new
cell on either entry needs new files and new entries, and no change here.

A metric named ``<quantity>.<part>`` is the quantity ``<quantity>``, split
so that cells whose runs spread differently get bounds, and per-layer
metrics a ``moves``, of their own: ``solve_s.road_ny`` is ``solve_s`` in
the cells it lists, and a per-layer metric without a reader of its own
name is read by ``bench/metrics/<quantity>.py``.

One run:

1. set-up, timed from the start of the process: the topology from the
   configuration and the traffic's instances (``bench/generator.py``),
   ``Problem.build`` (the server's own, in its session cache), the
   session or the server, and a warm-up that runs every program the
   window uses: one solve or request, and on the server entry a tenant's
   warm second request or the scanned backend's batch buckets;
2. the window: the traffic's instances taken in turn for ``--seconds``,
   each solved cold, or warm from a tenant's last answer (a solve or
   request under way at the close runs to its end and counts); the
   result line's ``window_compiles`` counts the programs compiled in it;
3. with ``--trace 1`` the window runs under the JAX profiler, and the
   per-layer metrics are read from the trace and the program's counters;
   with ``--trace 0`` the end-to-end metrics are reported;
4. once the window has closed and the device's peak memory is read, the
   answers are compared with the exact reference (``bench/compare.py``),
   and the entry's failed requests are counted against the limit 0.

The last line of standard output is the result; the numbers compared,
each beside its limit, are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the harness exits with
code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import importlib.util
import inspect
import json
import os
import re
import shutil
import statistics
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import compare, generator, instances, trace_reduce
from bench.reference.maxflow import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# inside the checkout, at a fixed path: the path is part of the compile
# cache's key, so only the first run of a cell in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
ROUNDING = "two_level"
# how long past the window's close a client waits for its last answer
LATE_S = 60.0
clock = time.perf_counter


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic mix and metrics."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    wl = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a ``workloads`` list is reported wherever
    # the end-to-end metric it moves is
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, int(wl["chips"]), config,
                generator.load(root, wl["traffic"]), e2e, per_layer)


def quantity(metric: str) -> str:
    """What a metric measures: its name up to the first ``.``."""
    return metric.split(".")[0]


def load_reader(metric: str, root: str = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``, else of
    ``bench/metrics/<quantity>.py``."""
    mdir = os.path.join(root, "bench", "metrics")
    path = os.path.join(mdir, f"{metric}.py")
    if not os.path.exists(path):
        path = os.path.join(mdir, f"{quantity(metric)}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a run observed, for the metric readers.  ``solves`` holds one
    record per window solve or answered request; ``trace`` is
    ``trace_reduce.reduce``'s output (traced runs only); ``serve`` is the
    server's ``ServeMetrics`` over the window (server entry only)."""
    setup: Dict[str, float] = dataclasses.field(default_factory=dict)
    solves: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None
    serve: Optional[object] = None


def program_instance(inst: instances.Instance):
    from repro.graphs.structures import EdgeList, STInstance

    return STInstance(graph=EdgeList(src=inst.src, dst=inst.dst,
                                     weight=inst.weight, n=inst.n),
                      s_weight=inst.s_weight, t_weight=inst.t_weight)


def build_problem(st, max_rows: int, build: Optional[Callable] = None):
    """The Problem with the fewest blocks whose largest (the dense
    block-Jacobi block) holds at most ``max_rows`` nodes: start from 1.5 n
    / ``max_rows`` and grow by a quarter until it fits.  ``build(p)`` makes
    the Problem for ``p`` blocks (default ``Problem.build``)."""
    from repro.core import Problem

    if build is None:
        def build(p):
            return Problem.build(st, n_blocks=p)
    p = max(2, int(np.ceil(1.5 * st.n / max_rows)))
    while True:
        prob = build(p)
        if prob.block_plan().bs <= max_rows or p >= st.n:
            return prob
        p = int(p * 1.25) + 1


def draw(cell: Cell, seed: int, run: Run):
    """The configuration's topology and the traffic's instances from
    ``seed``: the window's pool, and one more for the warm-up."""
    t = clock()
    topo = instances.topology(cell.config["instance"])
    *pool, warm = generator.pool(cell.config["instance"], topo, cell.mix,
                                 seed)
    run.setup["instance_s"] = clock() - t
    return pool, warm


def weights(inst: instances.Instance):
    return inst.weight, inst.s_weight, inst.t_weight


def pcg_steps(res):
    """IRLS iterations and PCG steps a solve took: the host loop's
    per-iteration counts, or the scanned program's per-iteration spend (an
    iteration the adaptive mask froze spends 0 and is not counted)."""
    if res.diagnostics is not None:
        iters = np.asarray(res.diagnostics.pcg_iters)
        return len(iters), int(iters.sum())
    iters = np.asarray(res.pcg_iters)
    return int(np.count_nonzero(iters)), int(iters.sum())


class SessionEntry:
    """Back-to-back cold ``MinCutSession.solve`` calls on one topology."""

    def __init__(self, cell: Cell, seed: int, run: Run):
        self.cell, self.seed, self.run = cell, seed, run
        self.records: List[dict] = []

    def setup(self) -> None:
        from repro.core import IRLSConfig, MinCutSession

        cfg, mix = self.cell.config, self.cell.mix
        self.pool, warm = draw(self.cell, self.seed, self.run)
        t = clock()
        prob = build_problem(program_instance(warm),
                             int(cfg["solver"]["max_block_rows"]))
        self.run.setup["problem_build_s"] = clock() - t
        self.sess = MinCutSession(
            prob, IRLSConfig(**cfg["solver"]["irls"], n_blocks=prob.n_blocks),
            backend=mix["backend"], profile=False)
        t = clock()
        self._solve(warm)
        self.run.setup["warmup_s"] = clock() - t

    def _solve(self, inst: instances.Instance):
        return self.sess.solve(weights=weights(inst), rounding=ROUNDING)

    def window(self, seconds: float) -> Dict[str, float]:
        t0 = clock()
        while True:
            k = len(self.records) % len(self.pool)
            res = self._solve(self.pool[k])
            t1 = clock()
            self.records.append({"k": k, "in_source": res.cut.in_source,
                                 "value": res.cut_value})
            irls, pcg = pcg_steps(res)
            self.run.solves.append({"irls_iters": irls, "pcg_iters": pcg,
                                    "rounding_s": res.timings["rounding"],
                                    "wall_s": res.timings["total"]})
            if t1 - t0 >= seconds:
                break
        return {"solve_s": (t1 - t0) / len(self.records)}

    def attempted(self) -> int:
        return len(self.records)

    def failed(self) -> int:
        """A solve that raises ends the run, so none is left uncounted."""
        return 0

    def close(self) -> None:
        self.sess = None

    def answers(self):
        for r in self.records:
            yield weights(self.pool[r["k"]]), r["in_source"], r["value"]


class ServerEntry:
    """Closed-loop clients of ``MinCutServer.submit`` on one registered
    topology: ``clients`` threads, each sending its next request once the
    last one's future has resolved, the instances taken in turn from one
    shared counter.  With ``"tenants": true`` each client submits as a
    tenant of its own, so its requests warm-start from its last answer.
    The server runs the configuration's solver block on the traffic's
    ``backend``; of its other settings, those the traffic file names
    (``SETTINGS``) are set, the rest keep their defaults."""

    SETTINGS = ("n_workers", "max_batch", "max_wait_ms", "flush_policy")

    def __init__(self, cell: Cell, seed: int, run: Run):
        self.cell, self.seed, self.run = cell, seed, run
        self.records: List[dict] = []
        self.failures: List[str] = []
        self.hung = False
        self.server = None
        self._lock = threading.Lock()
        self._next = 0

    def setup(self) -> None:
        from repro.core import IRLSConfig

        cfg = self.cell.config
        self.pool, warm = draw(self.cell, self.seed, self.run)
        st = program_instance(warm)
        irls = IRLSConfig(**cfg["solver"]["irls"])
        # the server's own session build (partition, reorder), timed as the
        # session entry times Problem.build; block-Jacobi finds its block
        # count by build_problem's rule, each try a server of its own
        t = clock()
        if irls.precond == "block_jacobi":
            build_problem(st, int(cfg["solver"]["max_block_rows"]),
                          lambda p: self._start(
                              dataclasses.replace(irls, n_blocks=p), st))
        else:
            self._start(irls, st)
        self.run.setup["problem_build_s"] = clock() - t
        t = clock()
        self._warm_up(warm)
        self.run.setup["warmup_s"] = clock() - t

    def _setting(self, name: str):
        """The traffic's value of a server setting, else the default."""
        from repro.serve import MinCutServer

        default = inspect.signature(MinCutServer).parameters[name].default
        return self.cell.mix.get(name, default)

    def _start(self, irls, st):
        """A server on ``irls`` with ``st`` registered and its session
        built (in place of the last try's); that session's Problem."""
        from repro.serve import MinCutServer

        if self.server is not None:
            self.server.stop()
        self.server = MinCutServer(
            cfg=irls, backend=self.cell.mix["backend"], rounding=ROUNDING,
            **{k: self.cell.mix[k] for k in self.SETTINGS
               if k in self.cell.mix})
        self.key = self.server.register(st)
        return self.server.cache.get(self.key).problem

    def _warm_up(self, warm: instances.Instance) -> None:
        """Run every program the window can: one cold request; with tenants
        a tenant's second request too, which starts warm (a tenant's batch
        holds its one request in flight); without, on the scanned backend,
        which compiles a vmapped program per batch bucket, one batch of
        each bucket that ``clients`` requests in flight can fill, run as
        the server runs a batch."""
        from repro.serve import bucket_size

        mix = self.cell.mix
        tenant = "warm-up" if mix.get("tenants") else None
        self.server.submit(self.key, weights(warm), tenant=tenant).result()
        if tenant is not None:
            self.server.submit(self.key, weights(self.pool[0]),
                               tenant=tenant).result()
        elif self.server.backend == "scanned":
            sess = self.server.cache.get(self.key)
            max_batch = self._setting("max_batch")
            most = min(int(mix["clients"]), max_batch)
            for b in sorted({bucket_size(k, max_batch)
                             for k in range(2, most + 1)}):
                sess.solve_batch([weights(warm)] * b, rounding=ROUNDING,
                                 cfg=self.server.cfg, pad_to=b)

    def _client(self, seconds: float, tenant: Optional[str]) -> None:
        while True:
            with self._lock:
                if clock() - self.t0 >= seconds:
                    return
                k = self._next % len(self.pool)
                self._next += 1
            t_submit = clock()
            try:
                fut = self.server.submit(self.key, weights(self.pool[k]),
                                         tenant=tenant)
                res = fut.result(
                    timeout=max(0.0, self.t0 + seconds + LATE_S - clock()))
            except concurrent.futures.TimeoutError:
                self._fail(k, f"no answer {LATE_S} s after the close")
                self.hung = True
                return
            except Exception as e:   # a request that raises is failed
                self._fail(k, repr(e))
                continue
            t_done = clock()
            irls, pcg = pcg_steps(res)
            with self._lock:
                self.records.append({"k": k, "in_source": res.cut.in_source,
                                     "value": res.cut_value,
                                     "latency_s": t_done - t_submit,
                                     "t_done": t_done})
                self.run.solves.append({"irls_iters": irls, "pcg_iters": pcg,
                                        "rounding_s": res.timings["rounding"],
                                        "wall_s": t_done - t_submit})

    def _fail(self, k: int, why: str) -> None:
        with self._lock:
            self.failures.append(why)
        print(f"bench: request for instance {k} failed: {why}",
              file=sys.stderr)

    def window(self, seconds: float) -> Dict[str, float]:
        self.server.reset_measurement()
        self.t0 = clock()
        tenants = bool(self.cell.mix.get("tenants", False))
        clients = [threading.Thread(target=self._client, args=(
            seconds, f"client{i}" if tenants else None))
            for i in range(int(self.cell.mix["clients"]))]
        for c in clients:
            c.start()
        for c in clients:
            c.join()
        self.run.serve = self.server.metrics
        if not self.records:
            raise RuntimeError(f"no request of the window was answered; "
                               f"{len(self.failures)} failed")
        last = max(r["t_done"] for r in self.records)
        return {"served_per_s": len(self.records) / (last - self.t0),
                "latency_p50_ms": 1e3 * statistics.median(
                    r["latency_s"] for r in self.records)}

    def attempted(self) -> int:
        return len(self.records) + len(self.failures)

    def failed(self) -> int:
        return len(self.failures)

    def close(self) -> None:
        # a worker that never answered would hold stop(wait=True) forever
        self.server.stop(wait=not self.hung)
        self.server = None

    def answers(self):
        for r in self.records:
            yield weights(self.pool[r["k"]]), r["in_source"], r["value"]


ENTRIES = {"session": SessionEntry, "server": ServerEntry}

# one event per program JAX compiles, or loads from its persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def compiles():
    """Each program compiled or loaded inside the block, as its function's
    name and seconds, in a list that the block fills."""
    import jax

    seen: List[list] = []

    def listen(event, secs, fun_name="", **kw):
        if event == COMPILE_EVENT:
            seen.append([fun_name, secs])

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, root: str = ROOT,
             trace_dir: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    from repro.obs import trace as spans

    run = Run()
    entry = ENTRIES[cell.mix.get("entry", "session")](cell, seed, run)
    entry.setup()
    setup_s = clock() - t_start
    if trace:
        trace_dir = trace_dir or os.path.join(CACHE_DIR, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans.configure(enabled=True, profiler=True)
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profile_options())
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN), \
                compiles() as window_compiles:
            e2e = entry.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            spans.configure(enabled=False, profiler=False)
    device = device_info(devices)
    entry.close()
    if trace:
        run.trace = trace_reduce.reduce(trace_reduce.load(_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    inst = entry.pool[0]
    numbers = compare.compare(Reference(inst.n, inst.src, inst.dst),
                              entry.answers(), entry.failed())
    correct, checks = compare.judge(numbers)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[quantity(m["name"])],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    out = {"correct": correct, "attempted": entry.attempted(),
           "failed": entry.failed(), "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    # set-up runs every program the window uses, so this reads 0
    out["window_compiles"] = len(window_compiles)
    out["checks"] = checks
    record = {"setup": run.setup, "window_compiles": window_compiles,
              "solves": run.solves}
    if run.serve is not None:
        record["serve"] = run.serve.snapshot()
    print("bench: " + json.dumps(record), file=sys.stderr)
    return out


def _xplane(trace_dir: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def enable_compile_cache(jax) -> None:
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE_DIR, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = clock() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import repro  # noqa: F401  -- the system under test, beside the benchmark
    # the program's own cost profiling compiles extra programs whenever its
    # tracer is on; the traced run keeps it off, as the untraced run does
    os.environ["REPRO_PROFILE"] = "0"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    enable_compile_cache(jax)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices[:cell.chips], t_start)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
