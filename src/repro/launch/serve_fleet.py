"""Fleet launcher: the replicated multi-dataset router over HTTP.

    PYTHONPATH=src python -m repro.launch.serve_fleet \\
        --dataset wh/lineitem=/data/lineitem \\
        --dataset wh/orders=/data/orders \\
        --replicas 3 --port 8090 --refresh-interval 30

    # self-contained smoke (CI): router + 2 replicas x 2 temp datasets,
    # estimate, kill a replica, re-estimate through failover, assert 304
    # revalidation and zero-pack warm start from the shared spill, then a
    # binary POST /batch spanning both datasets (per-tuple 304s asserted
    # through a second mid-batch replica kill, one pooled connection) and
    # a cross-dataset POST /cost (combined ETag stable on the degraded
    # fleet, 304 revalidation, batch-tuple parity)
    PYTHONPATH=src python -m repro.launch.serve_fleet --smoke

A planner then addresses the whole namespace through one endpoint:

    curl -s http://host:8090/datasets
    curl -s 'http://host:8090/wh/lineitem/estimate?mode=improved'
    curl -s -H 'If-None-Match: <etag>' 'http://host:8090/wh/lineitem/estimate?mode=improved'
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time
import urllib.error

from repro.compile_cache import enable_compile_cache
from repro.engine import EngineConfig
from repro.fleet import (
    DatasetRegistry,
    Fleet,
    LocalReplica,
    StatsRequest,
    StatsRouter,
    parse_spec,
)
from repro.service import fetch_json
from repro.wire import ConnectionPool, fetch


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dataset", action="append", default=[],
                    metavar="NS/NAME=ROOT",
                    help="serve ROOT as namespace/dataset (repeatable)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8090,
                    help="0 binds an ephemeral port")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replicas per dataset")
    ap.add_argument("--refresh-interval", type=float, default=30.0,
                    help="per-replica ingestion poll seconds; 0 disables")
    ap.add_argument("--probe-interval", type=float, default=5.0,
                    help="replica health-probe seconds; 0 disables")
    ap.add_argument("--strategy", default="auto",
                    help="engine strategy (auto/local/sharded/chunked/composed)")
    ap.add_argument("--backend", default="auto",
                    help="kernel backend (auto/pallas/ref)")
    ap.add_argument("--max-batch", default="auto",
                    help='chunk budget: a power of two, or "auto" to derive '
                         "it from device memory")
    ap.add_argument("--slow-request-ms", type=float, default=None,
                    help="log one structured line per request slower than "
                         "this many milliseconds (default: off)")
    ap.add_argument("--audit", action="store_true",
                    help="per-replica accuracy auditor: sample columns each "
                         "refresh, sketch a reference NDV, record q-error "
                         "into /metrics (ndv_audit_qerror)")
    ap.add_argument("--audit-columns", type=int, default=4,
                    help="columns sampled per audit generation")
    ap.add_argument("--smoke", action="store_true",
                    help="boot 2 replicas x 2 temp datasets on an ephemeral "
                         "port, run the scripted failover client, exit")
    return ap


def engine_config(args: argparse.Namespace) -> EngineConfig:
    mb = args.max_batch
    return EngineConfig(
        strategy=args.strategy,
        backend=args.backend,
        max_batch=mb if mb == "auto" else int(mb),
    )


def make_router(args: argparse.Namespace, registry: DatasetRegistry) -> StatsRouter:
    fleet = Fleet(
        registry,
        replicas_per_dataset=args.replicas,
        probe_interval=args.probe_interval or None,
        poll_interval=args.refresh_interval or None,
        audit=args.audit,
        audit_columns=args.audit_columns,
    )
    return StatsRouter(
        fleet,
        host=args.host,
        port=args.port,
        slow_request_ms=args.slow_request_ms,
    )


def _smoke_dataset(root: str, seed: int) -> str:
    import numpy as np

    from repro.columnar.writer import WriterOptions, write_file

    rng = np.random.default_rng(seed)
    for i in range(2):
        write_file(
            os.path.join(root, f"shard_{i:03d}"),
            {
                "tok": rng.integers(0, 100 + 40 * seed, 768).astype(np.int64),
                "val": np.round(rng.uniform(0, 50, 768), 1),
            },
            options=WriterOptions(row_group_size=256),
        )
    return root


def run_smoke(args: argparse.Namespace) -> int:
    args = argparse.Namespace(**{
        **vars(args),
        "port": 0, "replicas": 2,
        "refresh_interval": 0.0, "probe_interval": 0.0,
        "audit": True, "audit_columns": 2,
    })
    base = tempfile.mkdtemp()
    registry = DatasetRegistry()
    cfg = engine_config(args)
    for name, seed in (("alpha", 1), ("beta", 2)):
        root = _smoke_dataset(os.path.join(base, name), seed)
        registry.add("smoke", name, root, engine_config=cfg)

    with make_router(args, registry) as router:
        base_url = router.url
        # both datasets serve through one endpoint
        etags = {}
        for name in ("alpha", "beta"):
            url = router.url_for("smoke", name, "estimate") + "?mode=improved"
            status, etag, body = fetch_json(url)
            assert status == 200 and etag and body["estimates"], (status, body)
            etags[name] = (etag, body)
        status, _, listing = fetch_json(base_url + "/datasets")
        assert status == 200 and len(listing["datasets"]) == 2, listing

        # kill the replica that owns alpha's estimate placement mid-run
        fleet = router.fleet
        rset = fleet.sets["smoke/alpha"]
        identity = StatsRequest("estimate", "improved").identity
        victim = rset.rank(identity)[0]
        victim.kill()

        # the request survives (failover retries), body is byte-identical,
        # and the pre-kill ETag still revalidates as 304 on the survivor
        url = router.url_for("smoke", "alpha", "estimate") + "?mode=improved"
        status, etag, body = fetch_json(url)
        assert status == 200, status
        assert etag == etags["alpha"][0], (etag, etags["alpha"][0])
        assert body == etags["alpha"][1], "failover changed the body"
        status, etag304, _ = fetch_json(url, etag=etags["alpha"][0])
        assert status == 304 and etag304 == etags["alpha"][0], (status, etag304)
        assert rset.failovers >= 1 and rset.health[victim.name].healthy is False

        # a freshly started replica warms from the shared spill:
        # first estimate is a cache hit — zero engine packs
        fresh = LocalReplica(
            "smoke/alpha#fresh", registry.get("smoke", "alpha").root,
            engine_config=cfg,
        ).start()
        try:
            resp = fresh.handle(StatsRequest("estimate", "improved"))
            assert resp.status == 200 and resp.etag == etags["alpha"][0]
            packs = fresh.service.catalog.stats.packs
            assert packs == 0, f"fresh replica packed {packs}x despite spill"
        finally:
            fresh.stop()

        # -- batched RPC: one binary /batch frame spanning both datasets --
        pool = ConnectionPool()
        tuples = [
            {"namespace": "smoke", "dataset": "alpha", "mode": "improved"},
            {"namespace": "smoke", "dataset": "beta", "mode": "improved"},
            {"namespace": "smoke", "dataset": "beta"},
            {"namespace": "smoke", "dataset": "ghost"},
        ]
        status, _, env = fetch(base_url + "/batch", pool=pool,
                               method="POST", payload={"tuples": tuples})
        entries = env["responses"]
        assert status == 200, status
        assert [e["status"] for e in entries] == [200, 200, 200, 404], entries
        # tuple bodies/ETags match the singleton routed endpoint exactly
        assert entries[0]["etag"] == etags["alpha"][0], entries[0]
        assert entries[0]["body"] == etags["alpha"][1]
        assert entries[1]["etag"] == etags["beta"][0]

        # kill a second replica mid-batch: the sub-batch requeues whole
        # onto the survivor and every per-tuple 304 stays valid
        beta_set = fleet.sets["smoke/beta"]
        beta_victim = beta_set.rank(
            StatsRequest("estimate", "improved").identity
        )[0]
        beta_victim.kill()
        revalidate = [dict(t) for t in tuples[:3]]
        for t, e in zip(revalidate, entries):
            t["if_none_match"] = e["etag"]
        status, _, env = fetch(base_url + "/batch", pool=pool,
                               method="POST",
                               payload={"tuples": revalidate})
        statuses = [e["status"] for e in env["responses"]]
        assert status == 200 and statuses == [304, 304, 304], statuses
        assert beta_set.failovers >= 1, beta_set.health_view()
        assert pool.stats.snapshot()["opened"] == 1, pool.stats.snapshot()

        status, _, health = fetch_json(base_url + "/health")
        assert status == 200 and health["status"] == "serving", health

        # -- planner tier: cross-dataset /cost through the router ---------
        # Both replica sets have had a kill above, so the combined ETag
        # (a hash of per-dataset /tablestats tags, themselves state-derived)
        # is exercised on the degraded fleet: the tag must not depend on
        # which replica served each tablestats fetch.
        cost_payload = {"graph": {
            "tables": [
                {"name": "a", "namespace": "smoke", "dataset": "alpha"},
                {"name": "b", "namespace": "smoke", "dataset": "beta"},
            ],
            "edges": [{"left": "a", "left_column": "tok",
                       "right": "b", "right_column": "tok"}],
        }}
        status, cost_etag, cost = fetch(
            base_url + "/cost", pool=pool, payload=cost_payload, binary=False
        )
        assert status == 200 and cost_etag, (status, cost)
        assert sorted(cost["best_order"]) == ["a", "b"], cost
        assert set(cost["sources"]) == {"smoke/alpha", "smoke/beta"}, cost
        status, etag2_, _ = fetch(
            base_url + "/cost", pool=pool, payload=cost_payload,
            etag=cost_etag, binary=False,
        )
        assert status == 304 and etag2_ == cost_etag, (status, etag2_)
        # a cost tuple rides /batch with the identical ETag
        status, _, env = fetch(
            base_url + "/batch", pool=pool, method="POST",
            payload={"tuples": [{"cost": cost_payload}]},
        )
        entry = env["responses"][0]
        assert status == 200 and entry["status"] == 200, env
        assert entry["etag"] == cost_etag, (entry["etag"], cost_etag)

        # -- quality observability: explain round-trip + audited q-error --
        url = router.url_for("smoke", "beta", "estimate") \
            + "?mode=improved&explain=1"
        status, etag, explained = fetch_json(url)
        assert status == 200 and etag == etags["beta"][0], (status, etag)
        assert explained["provenance"].keys() \
            == etags["beta"][1]["estimates"].keys()
        assert {k: v for k, v in explained.items() if k != "provenance"} \
            == etags["beta"][1], "explain must not perturb the body"
        # one deterministic audit pass per live replica (the background
        # auditor is commit-driven; the smoke drives it synchronously)
        for rset_ in fleet.sets.values():
            for rep in rset_.replicas:
                if rep.probe():
                    rep.service.run_audit()

        # -- telemetry: /metrics key series + the batch's own trace --
        import json as _json
        import urllib.request as _req

        with _req.urlopen(base_url + "/metrics") as r:
            metrics = r.read().decode()
        for series in ("ndv_http_requests_total", "ndv_service_responses_304",
                       "ndv_service_engine_runs", "ndv_pool_opened",
                       "ndv_fleet_batches", "ndv_engine_dispatches_total",
                       "ndv_route_total", "ndv_audit_qerror"):
            assert series in metrics, f"/metrics missing {series}"
        with _req.urlopen(base_url + "/debug/traces?limit=10") as r:
            traces = _json.load(r)["traces"]
        batch_traces = [t for t in traces if t["name"] == "router.batch"]
        assert batch_traces, [t["name"] for t in traces]

        def _names(node, acc):
            acc.add(node["name"])
            for c in node["children"]:
                _names(c, acc)
            return acc

        span_names = _names(batch_traces[-1], set())
        assert "replica.sub_batch" in span_names, span_names
        print(f"[serve_fleet --smoke] ok: 2 datasets x 2 replicas, "
              f"failover after kill ({rset.failovers} failovers), ETag "
              f"stable across replicas, 304 revalidation on survivor, "
              f"fresh replica warm from spill (0 packs), binary /batch "
              f"across both datasets with per-tuple 304s through a "
              f"mid-batch kill on one keep-alive connection, cross-dataset "
              f"/cost with a combined ETag stable on the degraded fleet "
              f"(304 + batch-tuple parity), ?explain=1 provenance with "
              f"stable ETag, audited q-error in /metrics, /debug/traces "
              f"scraped")
    # context exit shut everything down; a second connect must now fail
    try:
        fetch_json(base_url + "/health")
    except (urllib.error.URLError, ConnectionError):
        print("[serve_fleet --smoke] clean shutdown verified")
        return 0
    print("[serve_fleet --smoke] ERROR: router still answering after stop()",
          file=sys.stderr)
    return 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        return run_smoke(args)
    if not args.dataset:
        print("error: at least one --dataset NS/NAME=ROOT is required "
              "(or use --smoke)", file=sys.stderr)
        return 2
    registry = DatasetRegistry()
    cfg = engine_config(args)
    for spec in args.dataset:
        ns, ds, root = parse_spec(spec)
        registry.add(ns, ds, root, engine_config=cfg)
    with make_router(args, registry) as router:
        print(f"[serve_fleet] routing {len(registry)} datasets x "
              f"{args.replicas} replicas at {router.url}")
        for key in registry.keys():
            print(f"[serve_fleet]   {router.url}/{key}/estimate")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            print("\n[serve_fleet] shutting down")
    return 0


if __name__ == "__main__":
    sys.exit(main())
