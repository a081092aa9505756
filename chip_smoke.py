#!/usr/bin/env python3
"""Bring-up check: the served NDV path on a TPU, end to end, in one process.

    python chip_smoke.py [--seed N]     # one chip, both phases below
    python chip_smoke.py --chips 4      # four chips: strategy parity only

One chip:
  1. Small dataset, full HTTP surface. A `StatsServer`, built as
     `repro.launch.serve_stats` builds it with the default engine, serves a
     PQLite dataset written from ``--seed``: `/estimate` with 304
     revalidation, `/batch`, `/tablestats`, `/cost` with 304, `?explain=1`,
     and one synchronous audit pass.
  2. Wide catalog at the engine's real width. A router, built as
     `repro.launch.serve_fleet` builds it with ``max_batch="auto"``, serves
     footers synthesized in memory (`repro.columnar.lakehouse`): 128 tables
     x 32 columns, each column over 16 files x 16 row groups of 2^20 rows.
     One `/batch` over every table runs as one super-pack engine call of
     B = 4096 lanes at R = 256; a re-send must answer 304 per tuple.

Checks: JAX's backend is a TPU before any work; the program dispatched for
the widest pack contains the compiled kernel (`tpu_custom_call`); every
estimate matches a ``backend="ref"`` engine on the same packed batch
(route and layout exactly, NDV to rtol 1e-5); `/cost` matches the planner's
`reference_cost` fold bit for bit; ingestion recorded no error and every
tuple answered 200 or 304.

``--chips 4`` runs only the multi-chip path on the wide catalog: the
sharded strategy at the auto budget and the composed strategy at a budget
that forces several super-chunks, each bit-identical to local on one chip.

Any failed check exits 1; the last line of standard output is then not the
result. On success it is ``{"ok": true, "device": {...}}``. JAX's
persistent compile cache goes where `repro.compile_cache` puts it.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MODES = ("paper", "improved")
# The kernel-vs-reference float tolerance the fused-kernel tests use.
RTOL, ATOL = 1e-5, 1e-6


class Checks:
    """Named pass/fail checks; explicit, so ``python -O`` cannot strip them."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        status = "ok" if ok else "FAIL"
        print(f"[{status}] {name}" + (f": {detail}" if detail else ""),
              flush=True)
        if not ok:
            self.failed.append(name)
        return bool(ok)


class CompileMonitor:
    """Backend compile time and persistent-cache traffic, from JAX events."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1


@contextlib.contextmanager
def recorded_dispatches():
    """Every `EstimationEngine.estimate` call: (engine, batch, bound, mode)."""
    from repro.engine import EstimationEngine

    seen = []
    original = EstimationEngine.estimate

    def estimate(self, batch, schema_bound=None, *, mode="paper"):
        seen.append((self, batch, schema_bound, mode))
        return original(self, batch, schema_bound, mode=mode)

    EstimationEngine.estimate = estimate
    try:
        yield seen
    finally:
        EstimationEngine.estimate = original


def compiled_text(engine, batch, schema_bound, mode) -> str:
    """The optimized program a local engine dispatches for this batch."""
    from repro.core.ndv.estimator import estimate_batch

    return estimate_batch.lower(
        batch, schema_bound, mode=mode,
        backend=engine.config.backend, fuse=engine.config.fuse,
    ).compile().as_text()


def compare_to_ref(checks, label, out, ref, lanes) -> float:
    """Route and layout exact, NDV within tolerance; returns max rel diff."""
    import numpy as np

    got = {f: np.asarray(getattr(out, f))[:lanes] for f in out._fields}
    want = {f: np.asarray(getattr(ref, f))[:lanes] for f in ref._fields}
    for field in ("route", "layout"):
        same = np.array_equal(got[field], want[field])
        checks(f"{label} {field} == ref", same,
               "" if same else f"{int(np.sum(got[field] != want[field]))} "
               f"of {lanes} lanes differ")
    a, b = got["ndv"].astype(np.float64), want["ndv"].astype(np.float64)
    rels = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    close = bool(np.all(np.abs(a - b) <= ATOL + RTOL * np.abs(b)))
    checks(f"{label} ndv == ref within rtol {RTOL}", close,
           f"max relative difference {float(np.max(rels)):.3g}, "
           f"{int(np.sum(rels > RTOL))} of {lanes} lanes over rtol")
    if not close:
        shown = ("ndv", "ndv_dict", "ndv_minmax", "dict_iterations",
                 "coupon_iterations", "dict_residual", "route", "layout")
        for i in np.argsort(-rels)[:6]:
            print(f"  lane {i}: " + ", ".join(
                f"{f} {got[f][i]!r}/{want[f][i]!r}" for f in shown), flush=True)
    return float(np.max(rels))


def _small_dataset(root: str, seed: int) -> None:
    import numpy as np

    from repro.columnar import generator as gen
    from repro.columnar.writer import WriterOptions, write_file

    rng = np.random.default_rng(seed)
    rows = 4096
    cols = {
        "tok": gen.uniform_column(gen.int_domain(300, seed=seed), rows, seed)[0],
        "ts": gen.sorted_column(gen.int_domain(2000, seed=seed + 1), rows,
                                seed + 1)[0],
        "region": gen.partitioned_column(gen.int_domain(500, seed=seed + 2),
                                         rows, seed=seed + 2)[0],
        "name": gen.clustered_column(gen.string_domain(400, seed=seed + 3),
                                     rows, seed=seed + 3)[0],
        "price": np.round(rng.uniform(0, 1000, rows), 2),
    }
    for i in range(4):
        part = slice(i * rows // 4, (i + 1) * rows // 4)
        write_file(os.path.join(root, f"part-{i:03d}"),
                   {k: v[part] for k, v in cols.items()},
                   options=WriterOptions(row_group_size=256))


def small_dataset_phase(seed: int, checks: Checks) -> None:
    """A `StatsServer` over a small PQLite dataset, every HTTP endpoint."""
    import numpy as np

    from repro.core.ndv.estimator import provenance_from_batch
    from repro.engine import EngineConfig, EstimationEngine
    from repro.launch import serve_stats
    from repro.planner.api import TableStats, ColumnStats, sequential_reference
    from repro.planner.cost import best_plan_index, reference_cost
    from repro.service.http import parse_cost_request
    from repro.wire import ConnectionPool, fetch

    print("== phase 1: small dataset, HTTP surface", flush=True)
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "ds")
        _small_dataset(root, seed)
        args = serve_stats.build_parser().parse_args(
            ["--port", "0", "--refresh-interval", "0", "--audit",
             "--audit-columns", "5"]
        )
        pool = ConnectionPool(timeout=600.0)
        with serve_stats.make_server(args, root) as server:
            base, service = server.url, server.service
            checks("default engine config", service.engine.config
                   == EngineConfig(), repr(service.engine.config))
            bodies, etags = {}, {}
            for mode in MODES:
                url = f"{base}/estimate?mode={mode}"
                st, etag, body = fetch(url, pool=pool, binary=False)
                checks(f"/estimate {mode} 200", st == 200 and bool(etag), str(st))
                st2, etag2, _ = fetch(url, pool=pool, etag=etag, binary=False)
                checks(f"/estimate {mode} 304", st2 == 304 and etag2 == etag,
                       str(st2))
                bodies[mode], etags[mode] = body, etag

            st, _, env = fetch(
                base + "/batch", pool=pool, method="POST", binary=False,
                payload={"tuples": [
                    {"mode": "paper"},
                    {"mode": "improved", "if_none_match": etags["improved"]},
                ]},
            )
            statuses = [e["status"] for e in env["responses"]]
            checks("/batch per-tuple 200 + 304",
                   st == 200 and statuses == [200, 304], str(statuses))

            st, etag_x, explained = fetch(
                base + "/estimate?mode=improved&explain=1", pool=pool,
                binary=False,
            )
            checks("?explain=1 keeps the ETag",
                   st == 200 and etag_x == etags["improved"], str(st))

            # The served estimates against a reference engine on the same
            # packed batch; routes from the explained body.
            batch = service.catalog.packed_batch()
            names = service.catalog.column_names
            ref = EstimationEngine(EngineConfig(backend="ref"))
            for mode in MODES:
                ref_out = ref.estimate(batch, mode=mode)
                served = bodies[mode]["estimates"]
                ndv = np.array([served[n]["ndv"] for n in names])
                layout = np.array([served[n]["layout"] for n in names])
                want = np.asarray(ref_out.ndv)[:len(names)].astype(np.float64)
                checks(f"/estimate {mode} layout == ref", np.array_equal(
                    layout, np.asarray(ref_out.layout)[:len(names)]))
                checks(f"/estimate {mode} ndv == ref within rtol {RTOL}",
                       bool(np.all(np.abs(ndv - want)
                                   <= ATOL + RTOL * np.abs(want))),
                       f"max relative difference "
                       f"{float(np.max(np.abs(ndv - want) / want)):.3g}")
                if mode == "improved":
                    provs = provenance_from_batch(ref_out, batch, names)
                    routes = {p.column_name: p.route for p in provs}
                    served_routes = {
                        n: p["route"] for n, p in explained["provenance"].items()
                    }
                    checks("?explain=1 routes == ref", served_routes == routes,
                           f"{served_routes} vs {routes}")

            st, etag_t, tstats = fetch(
                base + "/tablestats?mode=paper&columns=tok,region", pool=pool,
                binary=False,
            )
            checks("/tablestats 200", st == 200 and bool(etag_t), str(st))

            cost_payload = {"graph": {
                "tables": [{"name": "a"}, {"name": "b"}, {"name": "c"}],
                "edges": [
                    {"left": "a", "left_column": "tok",
                     "right": "b", "right_column": "tok"},
                    {"left": "b", "left_column": "region",
                     "right": "c", "right_column": "region"},
                ],
            }}
            st, etag_c, cost = fetch(base + "/cost", pool=pool,
                                     payload=cost_payload, binary=False)
            checks("/cost 200", st == 200 and bool(etag_c), str(st))
            st2, etag_c2, _ = fetch(base + "/cost", pool=pool,
                                    payload=cost_payload, etag=etag_c,
                                    binary=False)
            checks("/cost 304", st2 == 304 and etag_c2 == etag_c, str(st2))

            # /cost against the pure-Python float32 fold over the same stats.
            graph, _, max_plans = parse_cost_request(cost_payload)
            stats = {
                t.name: TableStats(
                    rows=float(tstats["rows"]),
                    columns={c: ColumnStats(ndv=tstats["columns"][c]["ndv"],
                                            non_null=0)
                             for c in ("tok", "region")},
                )
                for t in graph.tables
            }
            costs, plans = sequential_reference(graph, stats,
                                                max_plans=max_plans)
            best = best_plan_index(plans, costs)
            order = [graph.names[i] for i in plans[best]]
            base_rows = np.full(len(graph.names), np.float32(tstats["rows"]),
                                np.float32)
            index = {n: i for i, n in enumerate(graph.names)}
            factors = [
                (index[e.left], index[e.right], float(np.float32(1.0) / np.float32(
                    max(1.0, tstats["columns"][e.left_column]["ndv"],
                        tstats["columns"][e.right_column]["ndv"]))))
                for e in graph.edges
            ]
            total, cards = reference_cost([int(x) for x in plans[best]],
                                          base_rows, factors)
            same = (cost["best_order"] == order
                    and cost["total_cost"] == total
                    and [j["cardinality"] for j in cost["joins"]] == cards)
            checks("/cost == reference_cost, bit for bit", same,
                   f"served {cost['best_order']} {cost['total_cost']!r} vs "
                   f"reference {order} {total!r}")

            audit = service.run_audit()
            checks("run_audit() sampled columns",
                   bool(audit) and all(np.isfinite(r.qerror) for r in audit),
                   ", ".join(f"{r.column}:{r.qerror:.2f}" for r in audit))
            checks("ingest recorded no error",
                   service.ingestor.stats.last_error is None,
                   str(service.ingestor.stats.last_error))


def wide_catalog_phase(seed: int, checks: Checks, monitor: CompileMonitor,
                       shape=None):
    """A router over a lakehouse-scale in-memory catalog, one wide /batch."""
    import jax
    import numpy as np

    from repro.catalog.source import InMemoryMetadataSource
    from repro.columnar.lakehouse import (
        LakehouseShape, synthesize_lakehouse, table_columns,
    )
    from repro.engine import EngineConfig, EstimationEngine
    from repro.fleet import DatasetRegistry
    from repro.launch import serve_fleet
    from repro.wire import ConnectionPool, fetch

    print("== phase 2: wide catalog through the router", flush=True)
    shape = shape or LakehouseShape()
    t0 = time.perf_counter()
    footers, _, _ = synthesize_lakehouse(seed, shape)
    print(f"synthesized {len(footers)} footers, "
          f"{shape.tables * shape.columns_per_table} columns x "
          f"{shape.row_groups} row groups in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    args = serve_fleet.build_parser().parse_args(
        ["--port", "0", "--replicas", "1", "--refresh-interval", "0",
         "--probe-interval", "0", "--max-batch", "auto"]
    )
    with tempfile.TemporaryDirectory() as spill:
        registry = DatasetRegistry()
        registry.add("lake", "catalog", InMemoryMetadataSource(footers, spill),
                     engine_config=serve_fleet.engine_config(args))
        t0 = time.perf_counter()
        with serve_fleet.make_router(args, registry) as router:
            print(f"router up, footers ingested in "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
            service = router.fleet.sets["lake/catalog"].replicas[0].service
            engine = service.engine
            print(f"engine max_batch auto -> {engine.resolve_max_batch()} "
                  f"lanes", flush=True)
            pool = ConnectionPool(timeout=900.0)
            tuples = [
                {"namespace": "lake", "dataset": "catalog", "mode": mode,
                 "columns": table_columns(shape, t)}
                for mode in MODES for t in range(shape.tables)
            ]
            compile_before = monitor.seconds
            t0 = time.perf_counter()
            with recorded_dispatches() as seen:
                st, _, env = fetch(router.url + "/batch", pool=pool,
                                   method="POST", payload={"tuples": tuples})
            cold_s = time.perf_counter() - t0
            compile_s = monitor.seconds - compile_before
            entries = env["responses"] if st == 200 else []
            checks("/batch over every table: all 200",
                   len(entries) == len(tuples)
                   and all(e["status"] == 200 for e in entries),
                   f"status {st}, {collections.Counter(e['status'] for e in entries)}")
            checks("one engine call per mode", len(seen) == len(MODES),
                   f"{len(seen)} dispatches")

            again = [dict(t, if_none_match=e["etag"])
                     for t, e in zip(tuples, entries)]
            st, _, env = fetch(router.url + "/batch", pool=pool,
                               method="POST", payload={"tuples": again})
            statuses = [e["status"] for e in env["responses"]] if st == 200 else []
            checks("re-sent /batch: per-tuple 304",
                   len(statuses) == len(tuples)
                   and all(s == 304 for s in statuses),
                   str(collections.Counter(statuses)))
            checks("ingest recorded no error",
                   service.ingestor.stats.last_error is None,
                   str(service.ingestor.stats.last_error))

            if not seen:
                return
            eng, batch, sb, _ = max(
                seen, key=lambda s: (s[1].batch, s[1].max_groups)
            )
            b, r = batch.batch, batch.max_groups
            print(f"widest pack dispatched: B={b} R={r} "
                  f"(strategy {eng.resolve_strategy(b)})", flush=True)
            checks("widest pack B >= 4096 at R = 256", b >= 4096 and r == 256,
                   f"B={b} R={r}")
            checks("widest pack runs local on one chip",
                   eng.resolve_strategy(b) == "local",
                   eng.resolve_strategy(b))
            checks("dispatched program holds the compiled kernel",
                   "tpu_custom_call" in compiled_text(eng, batch, sb, "paper"))

            # Served bodies came from the kernel; the kernel agrees with
            # the reference engine on the same packed batch.
            names = service.catalog.column_names
            ref = EstimationEngine(EngineConfig(backend="ref",
                                                max_batch="auto"))
            served = {}
            for t, e in zip(tuples, entries):
                for n, est in e["body"]["estimates"].items():
                    served[(t["mode"], n)] = est
            rel_max = 0.0
            for _, mode_batch, mode_sb, mode in seen:
                out = jax.block_until_ready(
                    eng.estimate(mode_batch, mode_sb, mode=mode))
                t0 = time.perf_counter()
                jax.block_until_ready(eng.estimate(mode_batch, mode_sb,
                                                   mode=mode))
                warm_s = time.perf_counter() - t0
                print(f"{mode}: warm dispatch of B={mode_batch.batch} "
                      f"R={mode_batch.max_groups}: {warm_s:.4f}s", flush=True)
                ndv = np.asarray(out.ndv)
                checks(f"{mode}: served bodies are the kernel's output",
                       all(served[(mode, n)]["ndv"] == float(ndv[i])
                           for i, n in enumerate(names)))
                ref_out = ref.estimate(mode_batch, mode_sb, mode=mode)
                rel_max = max(rel_max, compare_to_ref(
                    checks, f"{mode} B={mode_batch.batch}", out, ref_out,
                    len(names)))
            print(f"cold /batch (pack, transfer, compile, run): {cold_s:.2f}s; "
                  f"backend compile inside it: {compile_s:.2f}s", flush=True)
            print(f"largest relative NDV difference from ref: {rel_max:.3g}",
                  flush=True)


def four_chip_parity(seed: int, checks: Checks, shape=None) -> None:
    """sharded and composed vs local on one chip, bit for bit."""
    import jax
    import numpy as np

    from repro.catalog import FileEntry, StatsCatalog
    from repro.catalog.source import InMemoryMetadataSource
    from repro.columnar.lakehouse import LakehouseShape, synthesize_lakehouse
    from repro.engine import EngineConfig, EstimationEngine
    from repro.engine.engine import _sharded_fn, composed_plan

    print("== four chips: strategy parity on the wide catalog", flush=True)
    footers, _, _ = synthesize_lakehouse(seed, shape or LakehouseShape())
    local = EstimationEngine(EngineConfig(strategy="local", max_batch="auto"))
    catalog = StatsCatalog(InMemoryMetadataSource(footers), engine=local)
    # Commit the footers directly (the ingestion seam under `update()`):
    # this phase is about the chips, not about fingerprinting footers.
    catalog.apply_footers(
        [FileEntry(fid, fid, f) for fid, f in footers.items()],
        live_ids=sorted(footers),
    )
    batch = catalog.packed_batch()
    b = batch.batch
    sharded = EstimationEngine(EngineConfig(strategy="sharded",
                                            max_batch="auto"))
    composed = EstimationEngine(EngineConfig(strategy="composed",
                                             max_batch=512))
    n = composed.shard_count
    spans = composed_plan(b, n, composed.per_shard_budget())[1]
    print(f"B={b} R={batch.max_groups}, {n} shards; sharded budget "
          f"{sharded.per_shard_budget()}/shard; composed "
          f"{composed.per_shard_budget()}/shard in {len(spans)} super-chunks",
          flush=True)
    checks("four devices visible", n == 4, str(n))
    checks("composed streams several super-chunks", len(spans) > 1,
           str(len(spans)))
    sb = jax.numpy.full((b,), np.inf, np.float32)
    text = _sharded_fn(tuple(jax.devices()[:n]), "paper", "auto",
                       "auto").lower(batch, sb).compile().as_text()
    checks("sharded program holds the compiled kernel",
           "tpu_custom_call" in text)
    for mode in MODES:
        want = jax.block_until_ready(local.estimate(batch, mode=mode))
        for name, eng in (("sharded", sharded), ("composed", composed)):
            got = jax.block_until_ready(eng.estimate(batch, mode=mode))
            diff = [f for f in want._fields if not np.array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)))]
            checks(f"{mode}: {name} bit-identical to local", not diff,
                   f"fields differ: {diff}" if diff else "all 16 fields")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the datasets and footers")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded/composed parity across chips")
    args = ap.parse_args(argv)

    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's backend is {platform!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    monitor = CompileMonitor()
    print(f"device_kind {devices[0].device_kind!r}, {len(devices)} "
          f"device(s), compile cache {cache_dir}", flush=True)

    checks = Checks()
    if args.chips == 4:
        four_chip_parity(args.seed, checks)
    else:
        small_dataset_phase(args.seed, checks)
        wide_catalog_phase(args.seed, checks, monitor)
    print(f"backend compile {monitor.seconds:.2f}s in all; compile cache "
          f"{dict(monitor.events)}", flush=True)
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
