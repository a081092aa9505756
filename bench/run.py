#!/usr/bin/env python3
"""One benchmark cell of the NDV statistics service, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
(``configs/<name>.json``: a TPC catalog at a scale factor, its refresh
functions, the limits of its correctness check) and a traffic mix
(``traffic/<name>.json``: loop, rates, clients). One process holds the chip:

  1. fails unless JAX's backend is a TPU with the chips the cell asks for;
  2. keeps JAX's compile cache at ``<checkout>/.jax_cache``;
  3. synthesizes the catalog's footers from ``--seed`` (`lake`);
  4. serves every table as a dataset of one replica behind the program's
     router (`repro.launch.serve_fleet.make_router`), over loopback HTTP,
     with the benchmark's own `MetadataSource` and no background refresh;
  5. has the load generator (`loadgen.py`, a child process without JAX)
     prime: every graph is costed once and every table's stats fetched, so
     every (B, R) bucket and planner shape compiles before the window;
  6. drives the window: probes (``POST /cost``) and, for maintenance
     traffic, a writer that commits a staged snapshot through the control
     endpoint, ``POST /{ns}/{ds}/refresh``es it and probes until the plan
     is fresh, then reads the table's ``/tablestats``;
  7. checks what the window served against the plain reference
     (`reference`) and prints the result as its last line.

``--trace 1`` runs the same window under the profiler and reports the
cell's per-layer metrics instead of its end-to-end ones. ``--control 1``
also computes the readings of the control (the reference in bfloat16 in
the program's place), which the benchmark's own runs never need.
"""
from __future__ import annotations

import time

START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import checks  # noqa: E402
import lake  # noqa: E402
from loadgen import GRACE_S  # noqa: E402


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def graph_request(namespace: str, template: dict, mode: str,
                  max_plans: Optional[int] = None) -> dict:
    """A query template as the body of ``POST /cost``; without ``max_plans``
    the request leaves the plan budget to the program's default."""
    body = {
        "graph": {
            "tables": [{"name": a, "namespace": namespace, "dataset": t,
                        "filter_selectivity": s}
                       for a, t, s in template["tables"]],
            "edges": [{"left": l, "left_column": lc, "right": r,
                       "right_column": rc}
                      for l, lc, r, rc in template["edges"]],
        },
        "mode": mode,
    }
    if max_plans is not None:
        body["max_plans"] = max_plans
    return body


class Recorder:
    """Wraps calls of the program for the window's bookkeeping.

    EstimationEngine.estimate   the shape of every pack dispatched
    AsyncIngestor.refresh       the host time of every refresh
    StatsService.table_stats    which state every /tablestats ETag names

    `annotate_layers` (trace runs) also runs each of these, the router's
    HTTP handler, ``Fleet.cost``, the planner's ``compute_cost`` and the
    packer inside a `jax.profiler.TraceAnnotation` named as in
    `tracing.ANNOTATIONS`, which puts the layers on the device trace's clock.
    """

    def __init__(self):
        self.dispatches: List[tuple] = []
        self.refreshes: List[tuple] = []
        self.tablestats: Dict[str, tuple] = {}
        self._undo = []

    def _patch(self, owner, name, make):
        original = getattr(owner, name)
        self._undo.append((owner, name, original))
        setattr(owner, name, make(original))

    def _span(self, owner, name, label):
        import jax

        def make(original):
            def wrapped(*args, **kwargs):
                with jax.profiler.TraceAnnotation(label):
                    return original(*args, **kwargs)
            return wrapped
        self._patch(owner, name, make)

    def install(self) -> "Recorder":
        from repro.engine import EstimationEngine
        from repro.service.ingest import AsyncIngestor
        from repro.service.service import StatsService

        rec = self

        def estimate(original):
            def wrapped(self, batch, schema_bound=None, *, mode="paper"):
                out = original(self, batch, schema_bound, mode=mode)
                rec.dispatches.append((time.monotonic(), batch.batch,
                                       batch.max_groups, batch.n_groups))
                return out
            return wrapped

        def refresh(original):
            def wrapped(self):
                t0 = time.monotonic()
                summary = original(self)
                rec.refreshes.append((t0, time.monotonic() - t0,
                                      summary.changed))
                return summary
            return wrapped

        def table_stats(original):
            def wrapped(self, **kw):
                resp = original(self, **kw)
                if resp.status == 200:
                    rec.tablestats[resp.etag] = (self.name.split("#")[0],
                                                 resp.body["generation"])
                return resp
            return wrapped

        self._patch(EstimationEngine, "estimate", estimate)
        self._patch(AsyncIngestor, "refresh", refresh)
        self._patch(StatsService, "table_stats", table_stats)
        return self

    def annotate_layers(self) -> None:
        import repro.fleet.router as router_mod
        from repro.catalog.packer import BatchPacker
        from repro.engine import EstimationEngine
        from repro.service.http import JSONResponseHandler
        from repro.service.ingest import AsyncIngestor
        from repro.service.service import StatsService

        self._span(JSONResponseHandler, "_serve", "http.request")
        self._span(router_mod.Fleet, "cost", "router.cost")
        self._span(router_mod, "compute_cost", "planner.compute_cost")
        self._span(StatsService, "table_stats", "service.tablestats")
        self._span(AsyncIngestor, "refresh", "ingest.refresh")
        self._span(BatchPacker, "pack", "catalog.pack")
        self._span(EstimationEngine, "estimate", "engine.estimate")

    def uninstall(self) -> None:
        for owner, name, original in reversed(self._undo):
            setattr(owner, name, original)
        self._undo.clear()


class CompileMonitor:
    """Backend compiles and persistent-cache hits, from JAX's events.

    JAX reports a backend compile for every program it builds, a program
    read back from the persistent cache included; ``hits`` counts those.
    """

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.hits = 0
        self.seconds = 0.0

        def duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        def count(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(count)


def registry_values() -> dict:
    """Every cell of the program's metrics registry: ``{(name, labels):
    value}``, a histogram's value being ``(sum, count)``."""
    from repro.obs import registry

    out = {}
    for name, metric in list(registry()._metrics.items()):
        for labels, cell in metric.snapshot():
            key = (name, tuple(labels))
            if hasattr(cell, "count"):
                out[key] = (cell.sum, cell.count)
            else:
                out[key] = getattr(cell, "value", 0.0)
    return out


def series_delta(before: dict, after: dict) -> dict:
    """What the window added to each registry series: ``{name: [(labels,
    delta)]}`` with ``labels`` a dict; a histogram's delta is a dict of
    ``sum`` and ``count``."""
    out: Dict[str, list] = {}
    for (name, labels), v in after.items():
        b = before.get((name, labels))
        if isinstance(v, tuple):
            b = b or (0.0, 0)
            d = {"sum": v[0] - b[0], "count": v[1] - b[1]}
        else:
            d = v - (b or 0.0)
        out.setdefault(name, []).append((dict(labels), d))
    return out


def series_total(series: dict, name: str) -> float:
    return sum(d for _, d in series.get(name, ()))


class ControlServer:
    """``POST /commit {"index": k}``: apply staged commit k to its table."""

    def __init__(self, namespace: str, sources: dict, commits, footers):
        lock = threading.Lock()
        ns = namespace

        def apply(k: int) -> dict:
            c = commits[k]
            with lock:
                src = sources[c.table]
                live = src.commit(footers[k], c.remove)
                return {"dataset": f"{ns}/{c.table}",
                        "generation": len(src.history), "files": len(live)}

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                body = json.loads(self.rfile.read(n))
                payload = json.dumps(apply(int(body["index"]))).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = "http://127.0.0.1:%d" % self.httpd.server_address[1]
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)
        self.thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.thread.join(timeout=10)
        self.httpd.server_close()


def load_readers(names):
    """The per-layer metrics' readers, ``metrics/<name>.py`` each."""
    out = {}
    for name in names:
        path = os.path.join(HERE, "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod.read
    return out


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The end-to-end metrics of a cell, or with ``trace`` its per-layer ones."""
    def applies(m, reported):
        if "workloads" in m:
            return cell["name"] in m["workloads"]
        return m.get("moves") in reported if trace else True

    e2e = [m for m in bench["end_to_end"] if applies(m, ())]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"] if applies(m, reported)]


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else math.inf


def run_cell(cell: dict, bench: dict, seed: int, seconds: float,
             trace: bool = False, control: bool = False,
             scale: float = 1.0, config_override: Optional[dict] = None
             ) -> dict:
    """Run one cell; returns the result object (last line of output)."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.fleet import DatasetRegistry
    from repro.launch import serve_fleet

    enable_compile_cache()
    monitor = CompileMonitor()
    config = config_override or lake.load_json("configs",
                                               cell["config"] + ".json")
    traffic = lake.load_json("traffic", cell["traffic"] + ".json")
    queries = lake.load_json("queries", config["queries"] + ".json")
    ns = config["namespace"]
    mode, max_plans = traffic["mode"], traffic.get("max_plans")

    world = lake.Lake(config, seed, scale=scale)
    Source = lake.make_source_class()
    spill = tempfile.TemporaryDirectory()
    sources = {}
    for name, files in world.files.items():
        root = os.path.join(spill.name, name)
        os.makedirs(root)
        sources[name] = Source(root)
        sources[name].commit({fid: lake.footer(world.tables[name], fd)
                              for fid, fd in files.items()})
    file_data = {name: dict(files) for name, files in world.files.items()}

    # The writer's staged snapshots, in the refresh run's order.
    commit_rate = float(traffic.get("commit_rate", 0.0))
    staged, footers = [], []
    if commit_rate > 0:
        count = int(commit_rate * seconds) + 2
        staged = world.stage_commits(count)
        for c in staged:
            footers.append({fid: lake.footer(world.tables[c.table], fd)
                            for fid, fd in c.add.items()})
            file_data[c.table].update(c.add)

    templates = {t["id"]: t for t in queries["templates"]}
    graphs = {gid: {"body": graph_request(ns, t, mode, max_plans)}
              for gid, t in templates.items()}
    # After a commit the writer re-plans one query on that table, the same
    # for every seed: the first template (in file order) that reads it.
    first_graph = {}
    for t in queries["templates"]:
        for _, table, _ in t["tables"]:
            first_graph.setdefault(table, t["id"])
    writer = [{"index": c.index, "dataset": f"{ns}/{c.table}",
               "graph": first_graph[c.table]} for c in staged]

    if traffic["loop"] == "open":
        probe_rate = traffic.get("probe_rate")
        if probe_rate is None:
            m = config["maintenance"]
            probe_rate = commit_rate * m["queries_per_run"] / len(m["run"])
    else:
        probe_rate = None

    args = serve_fleet.build_parser().parse_args(
        ["--port", "0", "--replicas", str(int(traffic.get("replicas", 1))),
         "--refresh-interval", "0",
         "--probe-interval", "0", "--max-batch", "auto"])
    registry = DatasetRegistry()
    for name, src in sources.items():
        registry.add(ns, name, src, engine_config=serve_fleet.engine_config(args))

    peaks = lake.load_json("peaks.json")
    kind = jax.devices()[0].device_kind
    if trace and kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    recorder = Recorder().install()
    router = control_srv = child = None
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    try:
        router = serve_fleet.make_router(args, registry).start()
        if staged:
            control_srv = ControlServer(ns, sources, staged, footers)
        plan = {
            "router": router.url,
            "control": control_srv.url if control_srv else None,
            "seconds": seconds,
            "schedule_seed": int(traffic.get("schedule_seed", 0)),
            "loop": traffic["loop"],
            "streams": traffic.get("streams", 0),
            "senders": traffic.get("senders", 0),
            "clients": traffic.get("clients", traffic.get("streams", 1)),
            "prime": bool(traffic.get("prime", True)),
            "probe_rate": probe_rate,
            "templates": traffic.get("templates", {"dist": "uniform"}),
            "bursts": traffic.get("bursts"),
            "commit_rate": commit_rate,
            "graphs": graphs,
            "probe_graphs": sorted(graphs),
            "commits": writer,
            "tablestats": [f"{ns}/{t}" for t in sorted(world.tables)],
        }
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        child.stdin.write(json.dumps(plan) + "\n")
        child.stdin.flush()
        ready = _event(child, "ready")
        if trace:
            # After priming: a jitted function traced under the wrappers
            # would key the compile cache apart from the untraced runs.
            recorder.annotate_layers()
        compiles_before = monitor.count
        before = registry_values()
        recorder.dispatches.clear()
        recorder.refreshes.clear()
        if trace:
            # No Python tracer: it slows the host several times over and
            # would measure a different window than the untraced runs.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir.name, profiler_options=options)
        t_go = time.monotonic()
        setup_s = t_go - START
        child.stdin.write("go\n")
        child.stdin.flush()
        _event(child, "closed")
        t_close = time.monotonic()
        after = registry_values()
        if trace:
            jax.profiler.stop_trace()
        window_dispatches = [d for d in recorder.dispatches if d[0] <= t_close]
        window_refreshes = [r for r in recorder.refreshes if r[0] <= t_close]
        compiles_in_window = monitor.count - compiles_before
        result = _event(child, "result")
        child.wait(timeout=60)
        served = {
            name: [(tuple(r.service.catalog.files), r.service.ingestor.generation)
                   for r in rset.replicas]
            for name, rset in ((k.split("/", 1)[1], v)
                               for k, v in router.fleet.sets.items())
        }
        devices = jax.devices()[:cell["chips"]]
        mem_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices]
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if control_srv is not None:
            control_srv.stop()
        if router is not None:
            router.stop()
        recorder.uninstall()
        spill.cleanup()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(max(mem_peaks))}

    # -- end-to-end metrics, from the generator's records --------------------
    window = float(result["window_s"])
    records = result["records"]
    writes = result["writes"]
    ok = (200, 304)
    # A probe or commit that failed, or was never answered, counts as one
    # that waited the whole window and the grace after it.
    never = (window + GRACE_S) * 1e3
    latencies = [(r[2] - r[0]) * 1e3 if r[3] in ok else never
                 for r in records]
    scheduled = result.get("scheduled", len(records))
    latencies += [never] * max(scheduled - len(records), 0)
    fresh = [(w["t_fresh"] - w["t_sched"]) * 1e3 if "t_fresh" in w
             else never for w in writes]
    values = {
        "probe_p50_ms": percentile(latencies, 50),
        "probe_p95_ms": percentile(latencies, 95),
        "probes_per_s": sum(1 for r in records
                            if r[3] in ok and r[2] <= window) / window,
        "fresh_mean_ms": float(np.mean(fresh)) if fresh else math.inf,
        "setup_s": setup_s,
    }
    attempted = len(latencies) + len(writes)
    failed = sum(1 for r in records if r[3] not in ok) + \
        max(scheduled - len(records), 0) + \
        sum(1 for w in writes if "t_fresh" not in w)

    lateness = [r[1] - r[0] for r in records]
    quarters = [[lat for r, lat in zip(records, latencies)
                 if int(4 * r[0] / window) == q] for q in range(4)]
    print(f"generator: {len(records)} probes, {scheduled} scheduled, "
          f"{len(writes)} commits; lateness p50 "
          f"{percentile(lateness, 50) * 1e3:.3f} ms p99 "
          f"{percentile(lateness, 99) * 1e3:.3f} ms max "
          f"{max(lateness, default=0) * 1e3:.3f} ms; probe p50 by quarter "
          f"{' / '.join(f'{percentile(q, 50):.1f}' for q in quarters)} ms; "
          f"statuses "
          f"{sorted(collections.Counter(r[3] for r in records).items())}",
          flush=True)
    if writes:
        late = [w["t_start"] - w["t_sched"] for w in writes]
        q = max(len(late) // 4, 1)
        print(f"writer: {len(writes)} commits at {commit_rate:g}/s; lateness "
              f"first quarter {np.mean(late[:q]) * 1e3:.1f} ms, last quarter "
              f"{np.mean(late[-q:]) * 1e3:.1f} ms; fresh mean "
              f"{values['fresh_mean_ms']:.1f} ms, p50 "
              f"{percentile(fresh, 50):.1f} ms, p90 "
              f"{percentile(fresh, 90):.1f} ms, max {max(fresh):.1f} ms",
              flush=True)
    series = series_delta(before, after)
    print(f"window: "
          f"{series_total(series, 'ndv_engine_dispatches_total'):.0f} engine "
          f"dispatches, "
          f"{series_total(series, 'planner_dispatches_total'):.0f} planner "
          f"dispatches, {compiles_in_window} backend compiles, "
          f"{len(window_refreshes)} refreshes; set-up {setup_s:.3f} s, "
          f"compile {monitor.seconds:.3f} s in {monitor.count} programs, "
          f"{monitor.hits} from the persistent cache", flush=True)

    # -- correctness ---------------------------------------------------------
    t_check = time.monotonic()
    verdict = checks.check_window(
        config=config, world=world, sources=sources, file_data=file_data,
        ns=ns, seed=seed, ready=ready, result=result, served=served,
        tablestats_etags=recorder.tablestats, templates=templates,
        max_plans=max_plans, control=control)
    print(f"checks: {verdict['states_checked']} table states, "
          f"{verdict['plans_checked']} /cost bodies "
          f"({verdict['excess_checked']} against the whole plan space) in "
          f"{time.monotonic() - t_check:.1f} s", flush=True)

    metrics_out = {} if trace else {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in cell_metrics(bench, cell, False)}
    breakdown = None
    if trace:
        import tracing

        reduced = tracing.reduce(trace_dir.name)
        trace_dir.cleanup()
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = t_close - t_go
        breakdown = tracing.breakdown(reduced)
        # What a reader (``metrics/<name>.py``) gets: the traced window's
        # length, the trace's reduction (`tracing.reduce`), the peaks of
        # this device kind, the harness's records of the window (packs
        # dispatched, refreshes, commits) and what the window added to
        # every series of the program's metrics registry.
        ctx = {
            "window_s": device["window_s"], "trace": reduced,
            "device_kind": device["kind"], "peaks": peaks,
            "dispatches": window_dispatches, "refreshes": window_refreshes,
            "commits": len(writes), "series": series,
        }
        per_layer = cell_metrics(bench, cell, True)
        readers = load_readers(m["name"] for m in per_layer)
        for m in per_layer:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": verdict["correct"], "attempted": attempted,
           "failed": failed, "metrics": metrics_out, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control"] = verdict["control"]
    out["checks"] = verdict["checks"]
    return out


def finite(obj):
    """``obj`` with every infinite float capped at +-1e308 and NaN as None,
    so the result line is strict JSON."""
    if isinstance(obj, float):
        if math.isnan(obj):
            return None
        return max(min(obj, 1e308), -1e308)
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


def _event(child, name: str) -> dict:
    while True:
        line = child.stdout.readline()
        if not line:
            raise RuntimeError(f"load generator exited before {name!r} "
                               f"(code {child.poll()})")
        msg = json.loads(line)
        if msg.get("event") == name:
            return msg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (reference in bfloat16)")
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = find_cell(bench, args.workload)

    # The compile cache lives in the checkout, at a fixed path, and keeps
    # every entry: its least-recently-used eviction reads a per-entry access
    # file that concurrent writers can leave missing, and a failed write
    # there costs the next run a compile.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"run.py: needs a TPU, but JAX's backend is {backend!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    out = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace),
                   bool(args.control))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(out), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
