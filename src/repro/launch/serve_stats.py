"""Stats-serving launcher: the `repro.service` endpoint over one dataset.

    PYTHONPATH=src python -m repro.launch.serve_stats --root /data/ds \
        --port 8080 --refresh-interval 30

    # self-contained smoke (CI): temp dataset, ephemeral port, scripted
    # client asserting estimate / 304 / plan / health, binary-negotiated
    # estimate parity, a per-tuple 200+304 /batch frame, a /cost join
    # order with 304 revalidation, clean shutdown
    PYTHONPATH=src python -m repro.launch.serve_stats --smoke

Query planners then pull estimates without local footer access:

    curl -s 'http://host:8080/estimate?mode=improved'
    curl -s -H 'If-None-Match: <etag>' 'http://host:8080/estimate?mode=improved'
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time
import urllib.error

from repro.compile_cache import enable_compile_cache
from repro.engine import EngineConfig, EstimationEngine
from repro.service import StatsServer, StatsService, fetch_json
from repro.wire import ConnectionPool, fetch


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", help="dataset root directory (PQLite files)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 binds an ephemeral port")
    ap.add_argument("--refresh-interval", type=float, default=30.0,
                    help="seconds between background refreshes; 0 disables")
    ap.add_argument("--workers", type=int, default=8,
                    help="ingestion scatter-gather thread-pool width")
    ap.add_argument("--strategy", default="auto",
                    help="engine strategy (auto/local/sharded/chunked/composed)")
    ap.add_argument("--backend", default="auto",
                    help="kernel backend (auto/pallas/ref)")
    ap.add_argument("--auto-load-cache", action="store_true",
                    help="restore the dataset's estimate-cache spill on boot")
    ap.add_argument("--save-cache-on-commit", action="store_true",
                    help="spill the compacted estimate cache on each commit")
    ap.add_argument("--slow-request-ms", type=float, default=None,
                    help="log one structured line per request slower than "
                         "this many milliseconds (default: off)")
    ap.add_argument("--audit", action="store_true",
                    help="run the background accuracy auditor: sample columns "
                         "each refresh, sketch a reference NDV, record "
                         "q-error into /metrics (ndv_audit_qerror)")
    ap.add_argument("--audit-columns", type=int, default=4,
                    help="columns sampled per audit generation")
    ap.add_argument("--smoke", action="store_true",
                    help="boot on a temp dataset + ephemeral port, run a "
                         "scripted client, exit (asserts clean shutdown)")
    return ap


def make_server(args: argparse.Namespace, root: str) -> StatsServer:
    engine = EstimationEngine(
        EngineConfig(strategy=args.strategy, backend=args.backend)
    )
    service = StatsService(
        root,
        engine=engine,
        max_workers=args.workers,
        poll_interval=args.refresh_interval or None,
        auto_load_cache=args.auto_load_cache,
        save_cache_on_commit=args.save_cache_on_commit,
        audit=args.audit,
        audit_columns=args.audit_columns,
    )
    return StatsServer(
        service,
        host=args.host,
        port=args.port,
        slow_request_ms=args.slow_request_ms,
    )


def _smoke_dataset() -> str:
    import numpy as np

    from repro.columnar.writer import WriterOptions, write_file

    root = os.path.join(tempfile.mkdtemp(), "smoke_ds")
    rng = np.random.default_rng(0)
    for i in range(3):
        write_file(
            os.path.join(root, f"shard_{i:03d}"),
            {
                "tok": rng.integers(0, 128, 1024).astype(np.int64),
                "val": np.round(rng.uniform(0, 50, 1024), 1),
            },
            options=WriterOptions(row_group_size=256),
        )
    return root


def run_smoke(args: argparse.Namespace) -> int:
    args = argparse.Namespace(**{**vars(args), "port": 0,
                                 "refresh_interval": 0.0, "audit": True})
    root = args.root or _smoke_dataset()
    with make_server(args, root) as server:
        base = server.url
        status, etag, body = fetch_json(base + "/estimate?mode=improved")
        assert status == 200 and etag and body["estimates"], (status, body)
        status2, etag2, _ = fetch_json(base + "/estimate?mode=improved", etag=etag)
        assert status2 == 304 and etag2 == etag, (status2, etag2)
        status3, _, plans = fetch_json(base + "/plan?mode=improved")
        assert status3 == 200 and plans["plans"].keys() == body["estimates"].keys()
        status4, _, health = fetch_json(base + "/health")
        assert status4 == 200 and health["status"] == "serving"
        assert health["service"]["responses_304"] == 1, health["service"]
        # binary negotiation decodes bit-identically with the same ETag,
        # and a batched frame answers per-tuple (200 + 304 in one trip)
        pool = ConnectionPool()
        statusb, etagb, bodyb = fetch(
            base + "/estimate?mode=improved", pool=pool, binary=True
        )
        assert (statusb, etagb, bodyb) == (200, etag, body), statusb
        statusb, _, env = fetch(
            base + "/batch", pool=pool, method="POST",
            payload={"tuples": [{"mode": "paper"},
                                {"mode": "improved", "if_none_match": etag}]},
        )
        tuple_statuses = [e["status"] for e in env["responses"]]
        assert statusb == 200 and tuple_statuses == [200, 304], env
        # explain round-trip: provenance attaches without rotating the ETag
        # and the stripped body is byte-identical to the plain response
        # (quality-observability acceptance, ISSUE 9)
        statuse, etage, explained = fetch_json(
            base + "/estimate?mode=improved&explain=1"
        )
        assert statuse == 200 and etage == etag, (statuse, etage)
        assert explained["provenance"].keys() == body["estimates"].keys()
        assert {k: v for k, v in explained.items() if k != "provenance"} \
            == body, "explain must not perturb the response body"
        # planner tier: a self-join /cost over the served dataset answers
        # with a join order, and revalidates 304 on the same state-derived
        # ETag (cacheable POST acceptance, ISSUE 10)
        cost_payload = {"graph": {
            "tables": [{"name": "a"}, {"name": "b"}, {"name": "c"}],
            "edges": [
                {"left": "a", "left_column": "tok",
                 "right": "b", "right_column": "tok"},
                {"left": "b", "left_column": "tok",
                 "right": "c", "right_column": "tok"},
            ],
        }}
        statusc, etagc, cost = fetch(
            base + "/cost", pool=pool, payload=cost_payload, binary=False
        )
        assert statusc == 200 and etagc, (statusc, cost)
        assert sorted(cost["best_order"]) == ["a", "b", "c"], cost
        assert len(cost["joins"]) == 2 and cost["total_cost"] > 0, cost
        statusc2, etagc2, _ = fetch(
            base + "/cost", pool=pool, payload=cost_payload,
            etag=etagc, binary=False,
        )
        assert statusc2 == 304 and etagc2 == etagc, (statusc2, etagc2)
        # one synchronous audit pass (the background thread is event-driven;
        # the smoke drives it deterministically) feeds the q-error series
        server.service.run_audit()
        # /metrics serves the key series and /debug/traces recorded the
        # smoke's own batch (telemetry acceptance, ISSUE 8)
        import json as _json
        import urllib.request as _req

        with _req.urlopen(base + "/metrics") as r:
            metrics = r.read().decode()
        for series in ("ndv_http_requests_total", "ndv_service_responses_304",
                       "ndv_service_engine_runs", "ndv_batch_tuples",
                       "ndv_engine_dispatches_total", "ndv_route_total",
                       "ndv_audit_qerror", "planner_plans_scored_total",
                       "planner_dispatches_total"):
            assert series in metrics, f"/metrics missing {series}"
        with _req.urlopen(base + "/debug/traces?limit=10") as r:
            traces = _json.load(r)["traces"]
        assert any(t["name"] == "service.batch" for t in traces), \
            [t["name"] for t in traces]
        print(f"[serve_stats --smoke] ok: {len(body['estimates'])} columns, "
              f"etag {etag[:10]}..., 304 revalidation, "
              f"{health['ingest']['footers_read']} footers read async, "
              f"binary /estimate bit-identical, /batch per-tuple 200+304, "
              f"/cost join order with 304 revalidation, "
              f"?explain=1 provenance with stable ETag, audited q-error in "
              f"/metrics, /debug/traces scraped")
    # context exit shut the server down; a second connect must now fail
    try:
        fetch_json(base + "/health")
    except (urllib.error.URLError, ConnectionError):
        print("[serve_stats --smoke] clean shutdown verified")
        return 0
    print("[serve_stats --smoke] ERROR: server still answering after stop()",
          file=sys.stderr)
    return 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        return run_smoke(args)
    if not args.root:
        print("error: --root is required (or use --smoke)", file=sys.stderr)
        return 2
    with make_server(args, args.root) as server:
        print(f"[serve_stats] serving {args.root} at {server.url} "
              f"(engine {server.service.engine.cache_token}, "
              f"refresh every {args.refresh_interval or 'never'}s)")
        print(f"[serve_stats] try: curl -s {server.url}/estimate")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            print("\n[serve_stats] shutting down")
    return 0


if __name__ == "__main__":
    sys.exit(main())
