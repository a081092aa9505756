"""`Fleet` + `StatsRouter`: one HTTP endpoint over many replicated datasets.

`Fleet` is the transport-agnostic core: it owns a `DatasetRegistry`, one
`ReplicaSet` per registered dataset (built by a pluggable `replica_factory`
— process-local `StatsService` replicas by default, `RemoteReplica` HTTP
proxies for out-of-process deployments), an optional background health
prober, and the routing counters. `StatsRouter` is the stdlib HTTP shell
over it, the same shape as `repro.service.StatsServer`:

  GET  /datasets                              registry + replica health
  GET  /health                                router + per-dataset health
                                              (incl. connection-pool stats)
  GET  /metrics                               Prometheus exposition, router +
                                              remote replicas (`replica` label)
  GET  /debug/traces?limit=N                  recent traces, JSON span trees
  GET  /debug/explain?dataset=&namespace=     provenance caches + audit
                                              samples, aggregated per replica
                                              (local queried in-process,
                                              remote scraped best-effort)
  POST /refresh                               broadcast refresh, all datasets
  POST /batch                                 estimate + cost tuples, one frame
  POST /cost?explain=                         join ordering over registered
                                              datasets        [combined ETag]
  GET  /{ns}/{ds}/columns                     routed        [ETag passthrough]
  GET  /{ns}/{ds}/estimate?mode=&bounds=      routed        [ETag passthrough]
  GET  /{ns}/{ds}/plan?mode=                  routed        [ETag passthrough]
  GET  /{ns}/{ds}/tablestats?mode=&columns=   routed        [ETag passthrough]
  GET  /{ns}/{ds}/health                      routed (any healthy replica)
  POST /{ns}/{ds}/refresh                     broadcast refresh, one dataset

`POST /batch` tuples carry `namespace`/`dataset` alongside the per-dataset
batch fields (`repro.service.parse_query_tuple` shape) and may span any
mix of registered datasets: the router groups tuples by their
rendezvous-chosen replica and forwards one sub-batch RPC per replica
(`ReplicaSet.call_batch`), each of which executes its cold tuples as one
cross-dataset super-pack on the serving side. Content negotiation
(`Accept: application/x-ndv-wire`) applies to the envelope exactly as to
single requests.

The router adds nothing to response bodies and nothing to ETags: a tag
minted by any replica validates on any other, because tags are derived from
(dataset fingerprint set, engine cache token, request identity) and the
registry pins one engine config per dataset. That is the whole failover
story — clients keep their `If-None-Match` caches across replica deaths,
router restarts, and replica cold starts.

`POST /cost` is the fleet's planner entry point (`repro.planner`): a join
graph whose tables name registered datasets (`namespace`/`dataset` on every
table) is costed in the router process. The router fetches one
`/tablestats` body per referenced dataset from that dataset's replica set
(restricted to the join columns the graph actually uses), scores the plan
space with `compute_cost`, and mints a combined ETag hashed over (graph
identity, mode, max_plans, the sorted per-dataset `/tablestats` ETags) —
so `/cost` answers 304 exactly when *every* input dataset's stats are
unchanged, and the tag is identical no matter which replica served each
`/tablestats`, because those tags are state-derived. Cost tuples (dicts
carrying a `"cost"` key) ride `POST /batch` next to estimate tuples.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
from http.server import ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union
from urllib.parse import parse_qs, urlsplit

import jax

from repro.fleet.registry import DatasetRegistry, DatasetSpec
from repro.fleet.replica import (
    LocalReplica,
    NoReplicaAvailable,
    ReplicaSet,
    StatsRequest,
)
from repro.obs import WIDTH_BUCKETS, registry as obs_registry
from repro.obs.metrics import add_label_to_exposition
from repro.planner import (
    ColumnStats,
    DEFAULT_MAX_PLANS,
    JoinGraph,
    TableStats,
    compute_cost,
)
from repro.planner.api import provenance_block
from repro.service import (
    CostQuery,
    Response,
    batch_envelope,
    etag_matches,
    parse_bounds,
    parse_columns,
    parse_cost_request,
    parse_explain,
    parse_query_tuple,
)
from repro.service.http import JSONResponseHandler

ROUTED_KINDS = ("columns", "estimate", "plan", "tablestats", "health")

# Same metric family the service tier observes — the `tier` label keeps
# router envelopes and replica sub-batches distinguishable.
_BATCH_WIDTH = obs_registry().histogram(
    "ndv_batch_tuples",
    "Estimate tuples carried per /batch request",
    WIDTH_BUCKETS,
)


def replica_devices(index: int, replicas: int, devices: Sequence) -> tuple:
    """Replica `index`'s share of the host's `devices`.

    With at least as many replicas as devices, replica i gets device
    i mod n; with fewer, it gets every device j with j mod replicas = i, so
    a single replica keeps them all (and its engine shards as before).
    Replica i of every dataset lands on the same share.
    """
    n = len(devices)
    if replicas >= n:
        return (devices[index % n],)
    return tuple(d for j, d in enumerate(devices) if j % replicas == index)


def default_replica_factory(
    spec: DatasetSpec, index: int, **replica_kwargs
) -> LocalReplica:
    """Process-local replicas sharing the dataset's estimate-cache spill,
    each on the devices `Fleet` hands it (`devices=`)."""
    return LocalReplica(
        f"{spec.key}#{index}",
        spec.root,
        engine_config=spec.engine_config,
        **replica_kwargs,
    )


@dataclasses.dataclass
class FleetStats:
    """Router-side counters (per-replica health lives on the sets)."""

    requests: int = 0
    routed: int = 0
    retried: int = 0          # requests that needed >1 replica attempt
    unavailable: int = 0      # 503s: every replica of a set failed
    not_found: int = 0        # 404s: unregistered dataset or bad path
    batches: int = 0          # /batch envelopes handled
    batch_tuples: int = 0     # tuples carried inside those envelopes


class Fleet:
    """Replica sets for every registered dataset, behind one routing seam."""

    def __init__(
        self,
        registry: DatasetRegistry,
        *,
        replicas_per_dataset: int = 2,
        probe_interval: Optional[float] = None,
        replica_factory: Optional[Callable] = None,
        **replica_kwargs,
    ):
        if replicas_per_dataset < 1:
            raise ValueError("replicas_per_dataset must be >= 1")
        if len(registry) == 0:
            raise ValueError("fleet needs at least one registered dataset")
        self.registry = registry
        self.probe_interval = probe_interval
        self.stats = FleetStats()
        # ThreadingHTTPServer handles requests on concurrent threads; bare
        # `+=` on the counters would lose increments under load.
        self._stats_mu = threading.Lock()
        factory = replica_factory or default_replica_factory
        # Placement follows from what the host has: replica i of every
        # dataset runs on the same share of the local devices.
        local = jax.local_devices()
        shares = [
            replica_devices(i, replicas_per_dataset, local)
            for i in range(replicas_per_dataset)
        ]
        self.sets: Dict[str, ReplicaSet] = {
            spec.key: ReplicaSet(
                spec.key,
                [
                    factory(spec, i, devices=shares[i], **replica_kwargs)
                    for i in range(replicas_per_dataset)
                ],
            )
            for spec in registry
        }
        self._stop = threading.Event()
        self._prober: Optional[threading.Thread] = None
        obs_registry().register_stats_view("ndv_fleet", {}, self.stats)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "Fleet":
        for rset in self.sets.values():
            rset.start()
        if self.probe_interval:
            self._stop.clear()
            self._prober = threading.Thread(
                target=self._probe_loop, name="ndv-fleet-probe", daemon=True
            )
            self._prober.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._prober is not None:
            self._prober.join(timeout=10.0)
            self._prober = None
        for rset in self.sets.values():
            rset.stop()

    def __enter__(self) -> "Fleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _probe_loop(self) -> None:
        while not self._stop.wait(self.probe_interval):
            self.probe_all()

    def probe_all(self) -> Dict[str, Dict[str, bool]]:
        """One probe sweep: ejected replicas that answer rejoin service."""
        return {key: rset.probe_all() for key, rset in self.sets.items()}

    def _bump(self, **fields: int) -> None:
        with self._stats_mu:
            for name, delta in fields.items():
                setattr(self.stats, name, getattr(self.stats, name) + delta)

    # -- endpoints -----------------------------------------------------------

    def route(self, namespace: str, dataset: str, req: StatsRequest) -> Response:
        """Place one request on the dataset's replica set, with failover."""
        self._bump(requests=1)
        try:
            rset = self.sets[self.registry.get(namespace, dataset).key]
        except KeyError as e:
            self._bump(not_found=1)
            return Response(404, {"error": str(e)}, None)
        try:
            resp, replica_name, attempts = rset.call(req)
        except NoReplicaAvailable as e:
            self._bump(unavailable=1)
            return Response(503, {"error": str(e)}, None)
        self._bump(routed=1, retried=int(attempts > 1))
        return resp

    @staticmethod
    def _cost_etag(
        graph: JoinGraph, mode: str, max_plans: int,
        source_etags: Dict[str, str],
    ) -> str:
        """Combined planner tag: rotates iff any input dataset's stats did.

        Hashes the request identity (graph identity is order-insensitive,
        so listing the same tables/edges in a different order revalidates)
        plus every referenced dataset's `/tablestats` ETag in sorted key
        order. Those tags are state-derived and replica-independent, so
        this one is too.
        """
        h = hashlib.sha1()
        h.update(
            f"cost|{mode}|{graph.identity()!r}|{int(max_plans)}".encode()
        )
        for key in sorted(source_etags):
            h.update(f"|{key}={source_etags[key]}".encode())
        return f'"{h.hexdigest()}"'

    def cost(
        self,
        *,
        graph: JoinGraph,
        mode: str = "paper",
        max_plans: int = DEFAULT_MAX_PLANS,
        if_none_match: Optional[str] = None,
        explain: bool = False,
    ) -> Response:
        """Cost a cross-dataset join graph; the fleet's `POST /cost`.

        Every graph table must carry `namespace`/`dataset` naming a
        registered dataset (404 otherwise). Per referenced dataset, one
        `/tablestats` request — restricted to the join columns the graph
        uses on that dataset — goes through the replica set with the usual
        rendezvous placement and failover; scoring happens here in the
        router process. The 304 check runs after the (warm, cheap)
        tablestats fetches but before any plan enumeration or scoring.
        """
        self._bump(requests=1)
        needed = graph.columns_by_table()
        key_by_alias: Dict[str, str] = {}
        cols_by_key: Dict[str, set] = {}
        for t in graph.tables:
            if t.dataset_key is None:
                return Response(
                    400,
                    {"error": f"table {t.name!r} must name a registered "
                              f"dataset (namespace/dataset)"},
                    None,
                )
            try:
                key = self.registry.get(t.namespace, t.dataset).key
            except KeyError as e:
                self._bump(not_found=1)
                return Response(404, {"error": str(e)}, None)
            key_by_alias[t.name] = key
            cols_by_key.setdefault(key, set()).update(needed[t.name])
        bodies: Dict[str, dict] = {}
        source_etags: Dict[str, str] = {}
        for key in sorted(cols_by_key):
            req = StatsRequest(
                kind="tablestats",
                mode=mode,
                columns=tuple(sorted(cols_by_key[key])) or None,
            )
            try:
                resp, _name, attempts = self.sets[key].call(req)
            except NoReplicaAvailable as e:
                self._bump(unavailable=1)
                return Response(503, {"error": str(e)}, None)
            self._bump(routed=1, retried=int(attempts > 1))
            if resp.status != 200:
                err = (resp.body or {}).get("error", f"status {resp.status}")
                return Response(
                    resp.status,
                    {"error": f"tablestats for dataset {key!r}: {err}"},
                    None,
                )
            bodies[key] = resp.body
            source_etags[key] = resp.body["etag"]
        etag = self._cost_etag(graph, mode, max_plans, source_etags)
        if if_none_match is not None and etag_matches(if_none_match, etag):
            return Response(304, None, etag)
        stats: Dict[str, TableStats] = {}
        for t in graph.tables:
            body = bodies[key_by_alias[t.name]]
            columns: Dict[str, ColumnStats] = {}
            for col in needed[t.name]:
                cs = body["columns"].get(col)
                if cs is None:
                    # The replica validated the column list, so this only
                    # fires on a body-shape drift — still a client-visible
                    # 400, not a 500.
                    return Response(
                        400,
                        {"error": f"dataset {key_by_alias[t.name]!r} has "
                                  f"no column {col!r}"},
                        None,
                    )
                columns[col] = ColumnStats(
                    ndv=float(cs["ndv"]),
                    non_null=int(cs["non_null"]),
                    confidence=cs.get("confidence"),
                    route=cs.get("route"),
                )
            stats[t.name] = TableStats(
                rows=float(body["rows"]), columns=columns
            )
        try:
            cost_body = compute_cost(
                graph, stats, mode=mode, max_plans=max_plans
            )
        except ValueError as e:
            return Response(400, {"error": str(e)}, None)
        out = {
            "etag": etag,
            "sources": dict(sorted(source_etags.items())),
            **cost_body,
        }
        if explain:
            out["provenance"] = provenance_block(graph, stats)
        return Response(200, out, etag)

    def batch(
        self, items: Sequence[Tuple[str, str, StatsRequest]]
    ) -> List[Response]:
        """Route `(namespace, dataset, estimate request)` tuples in bulk.

        Tuples are grouped per registered dataset and each group forwards
        through its replica set's `call_batch` — rendezvous placement,
        sub-batch failover, and the serving-side super-pack all happen
        there. Per-tuple errors answer in place (404 unknown dataset, 400
        non-estimate kind, 503 when every replica of a set failed); the
        envelope itself only fails on transport-level problems.
        """
        self._bump(requests=1, batches=1, batch_tuples=len(items))
        responses: List[Optional[Response]] = [None] * len(items)
        groups: Dict[str, List[int]] = {}
        for i, (ns, ds, req) in enumerate(items):
            if req.kind != "estimate":
                responses[i] = Response(
                    400,
                    {"error": f"batch tuples must be estimates, "
                              f"got kind {req.kind!r}"},
                    None,
                )
                continue
            try:
                key = self.registry.get(ns, ds).key
            except KeyError as e:
                self._bump(not_found=1)
                responses[i] = Response(404, {"error": str(e)}, None)
                continue
            groups.setdefault(key, []).append(i)
        for key, indices in groups.items():
            answers, _ = self.sets[key].call_batch(
                [items[i][2] for i in indices]
            )
            served = sum(1 for r in answers if r.status != 503)
            self._bump(routed=served, unavailable=len(answers) - served)
            for i, resp in zip(indices, answers):
                responses[i] = resp
        return list(responses)

    def refresh(
        self, namespace: Optional[str] = None, dataset: Optional[str] = None
    ) -> Response:
        """Broadcast a refresh to one dataset's replicas, or to all."""
        self._bump(requests=1)
        if namespace is not None:
            try:
                keys = [self.registry.get(namespace, dataset).key]
            except KeyError as e:
                self._bump(not_found=1)
                return Response(404, {"error": str(e)}, None)
        else:
            keys = list(self.sets)
        body: Dict[str, dict] = {}
        for key in keys:
            results = self.sets[key].refresh_all()
            body[key] = {
                name: (resp.body if resp is not None else None)
                for name, resp in results
            }
        return Response(200, {"refreshed": body}, None)

    def datasets(self) -> Response:
        self._bump(requests=1)
        body = {
            "datasets": [
                {
                    "key": spec.key,
                    "namespace": spec.namespace,
                    "dataset": spec.dataset,
                    "root": spec.root,
                    "engine": dataclasses.asdict(spec.engine_config),
                    **self.sets[spec.key].health_view(),
                }
                for spec in self.registry
            ]
        }
        return Response(200, body, None)

    def metrics_text(self) -> str:
        """Aggregate exposition: this process's registry plus every
        REMOTE replica's `/metrics` scrape re-emitted under a
        `replica="<name>"` label.

        Local replicas are deliberately not scraped — they already write
        this process's registry, so re-emitting them would double-count.
        Remote sample lines are appended comment-free (the aggregate is a
        concatenation; duplicate TYPE headers would be invalid), and an
        unreachable replica contributes nothing rather than failing the
        scrape.
        """
        parts = [obs_registry().exposition()]
        for rset in self.sets.values():
            for replica in rset.replicas:
                scrape = getattr(replica, "scrape_metrics", None)
                if scrape is None:
                    continue
                text = scrape()
                if text:
                    parts.append(
                        add_label_to_exposition(text, {"replica": replica.name})
                    )
        return "".join(parts)

    def explain_view(self, dataset_key: Optional[str] = None) -> Response:
        """Router-aggregated `/debug/explain`, patterned on `metrics_text`.

        Local replicas are queried in-process (their service owns the
        provenance cache); remote replicas are scraped best-effort — an
        unreachable replica contributes nothing rather than failing the
        view. `dataset_key` narrows to one registered dataset (404 when
        unknown); None aggregates all of them.
        """
        self._bump(requests=1)
        if dataset_key is not None:
            if dataset_key not in self.sets:
                self._bump(not_found=1)
                return Response(
                    404, {"error": f"unknown dataset {dataset_key!r}"}, None
                )
            keys = [dataset_key]
        else:
            keys = list(self.sets)
        datasets: Dict[str, dict] = {}
        for key in keys:
            per_replica: Dict[str, dict] = {}
            for replica in self.sets[key].replicas:
                service = getattr(replica, "service", None)
                if service is not None:
                    per_replica[replica.name] = service.debug_explain().body
                    continue
                scrape = getattr(replica, "scrape_explain", None)
                payload = scrape() if scrape is not None else None
                if payload is not None:
                    per_replica[replica.name] = payload
            datasets[key] = per_replica
        return Response(200, {"datasets": datasets}, None)

    def health(self) -> Response:
        self._bump(requests=1)
        views = {key: rset.health_view() for key, rset in self.sets.items()}
        all_up = all(v["healthy"] > 0 for v in views.values())
        with self._stats_mu:
            router_stats = dataclasses.asdict(self.stats)
        return Response(200, {
            "status": "serving" if all_up else "degraded",
            "datasets": views,
            "router": router_stats,
        }, None)


# -- HTTP shell ---------------------------------------------------------------


class _RouterHandler(JSONResponseHandler):
    """Routes one request onto the shared `Fleet`."""

    fleet: Fleet  # injected by make_router_handler
    server_version = "ndv-stats-router"
    tier = "router"

    _TOP_ROUTES = frozenset({"datasets", "health", "refresh", "batch", "cost"})

    def _route_label(self, path: str) -> str:
        # `/{ns}/{ds}/{kind}` collapses to the kind — dataset names must
        # not mint unbounded label values.
        parts = [p for p in path.split("/") if p]
        if len(parts) == 1 and parts[0] in self._TOP_ROUTES:
            return parts[0]
        if len(parts) == 3 and parts[2] in ROUTED_KINDS + ("refresh",):
            return parts[2]
        return "other"

    def _metrics_text(self) -> str:
        return self.fleet.metrics_text()

    def _explain_body(self, query) -> Response:
        # /debug/* params are validated here, not trusted: junk answers a
        # 400 JSON error (raised ValueError), never an unhandled 500.
        ds = query.get("dataset", [None])[0]
        ns = query.get("namespace", [None])[0]
        if ds is not None and not ds.strip():
            raise ValueError("dataset must be a non-empty dataset key")
        if ns is not None:
            if not ns.strip():
                raise ValueError("namespace must be a non-empty string")
            if ds is None:
                raise ValueError("namespace requires a dataset")
            try:
                ds = self.fleet.registry.get(ns, ds).key
            except KeyError as e:
                self.fleet._bump(not_found=1)
                return Response(404, {"error": str(e)}, None)
        return self.fleet.explain_view(ds)

    def _split(self) -> Tuple[List[str], dict]:
        url = urlsplit(self.path)
        parts = [p for p in url.path.split("/") if p]
        return parts, parse_qs(url.query)

    @staticmethod
    def _parse_batch(
        payload,
    ) -> List[Union[Tuple[str, str, StatsRequest], CostQuery]]:
        """Router `/batch` body -> routable items (ValueError on junk).

        Estimate tuples carry `namespace`/`dataset` alongside the service
        tuple fields. Cost tuples (a `"cost"` key) carry no top-level
        dataset fields — every table inside the graph names its own —
        and come back as `CostQuery` for `Fleet.cost`.
        """
        if not isinstance(payload, dict) or not isinstance(
            payload.get("tuples"), list
        ):
            raise ValueError(
                "batch body must be an object with a 'tuples' list"
            )
        items: List[Union[Tuple[str, str, StatsRequest], CostQuery]] = []
        for t in payload["tuples"]:
            if isinstance(t, dict) and "cost" in t:
                unknown = set(t) - {"cost", "if_none_match", "explain"}
                if unknown:
                    raise ValueError(
                        f"unknown cost tuple fields: {sorted(unknown)}"
                    )
                graph, mode, max_plans = parse_cost_request(
                    t["cost"], require_datasets=True
                )
                inm = t.get("if_none_match")
                if inm is not None and not isinstance(inm, str):
                    raise ValueError("if_none_match must be a string")
                items.append(CostQuery(
                    graph=graph,
                    mode=mode,
                    max_plans=max_plans,
                    if_none_match=inm,
                    explain=bool(t.get("explain", False)),
                ))
                continue
            query = parse_query_tuple(t)
            ns, ds = t.get("namespace"), t.get("dataset")
            if not isinstance(ns, str) or not isinstance(ds, str):
                raise ValueError(
                    "router batch tuples need string 'namespace' and "
                    "'dataset' fields"
                )
            items.append((ns, ds, StatsRequest.from_query(query)))
        return items

    def handle_get(self, url) -> None:
        parts, query = self._split()
        try:
            if parts == ["datasets"]:
                return self._send(self.fleet.datasets())
            if parts == ["health"]:
                return self._send(self.fleet.health())
            if len(parts) == 3 and parts[2] in ROUTED_KINDS:
                ns, ds, kind = parts
                bounds = None
                if "bounds" in query:
                    try:
                        bounds = tuple(sorted(
                            parse_bounds(query["bounds"][0]).items()
                        ))
                    except ValueError as e:
                        return self._error(400, str(e))
                try:
                    explain = parse_explain(query)
                except ValueError as e:
                    return self._error(400, str(e))
                columns = None
                if kind == "tablestats" and "columns" in query:
                    try:
                        columns = parse_columns(query["columns"][0])
                    except ValueError as e:
                        return self._error(400, str(e))
                req = StatsRequest(
                    kind=kind,
                    mode=query.get("mode", ["paper"])[0],
                    schema_bounds=bounds,
                    if_none_match=self.headers.get("If-None-Match"),
                    columns=columns,
                    explain=explain,
                )
                return self._send(self.fleet.route(ns, ds, req))
            self.fleet._bump(not_found=1)
            self._error(404, f"no such route: {self.path}")
        except Exception as e:
            self._error(500, f"{type(e).__name__}: {e}")

    def handle_post(self, url) -> None:
        parts, _ = self._split()
        try:
            if parts == ["refresh"]:
                return self._send(self.fleet.refresh())
            if parts == ["cost"]:
                try:
                    explain = parse_explain(
                        parse_qs(urlsplit(self.path).query,
                                 keep_blank_values=True)
                    )
                    graph, mode, max_plans = parse_cost_request(
                        self._read_body(), require_datasets=True
                    )
                except ValueError as e:
                    return self._error(400, str(e))
                return self._send(self.fleet.cost(
                    graph=graph,
                    mode=mode,
                    max_plans=max_plans,
                    if_none_match=self.headers.get("If-None-Match"),
                    explain=explain,
                ))
            if parts == ["batch"]:
                try:
                    items = self._parse_batch(self._read_body())
                except ValueError as e:
                    return self._error(400, str(e))
                _BATCH_WIDTH.observe(len(items), tier=self.tier)
                responses: List[Optional[Response]] = [None] * len(items)
                est_items: List[Tuple[str, str, StatsRequest]] = []
                est_idx: List[int] = []
                for i, item in enumerate(items):
                    if isinstance(item, CostQuery):
                        responses[i] = self.fleet.cost(
                            graph=item.graph,
                            mode=item.mode,
                            max_plans=item.max_plans,
                            if_none_match=item.if_none_match,
                            explain=item.explain,
                        )
                    else:
                        est_idx.append(i)
                        est_items.append(item)
                if est_items:
                    for i, resp in zip(
                        est_idx, self.fleet.batch(est_items)
                    ):
                        responses[i] = resp
                return self._send(batch_envelope(responses))
            if len(parts) == 3 and parts[2] == "refresh":
                return self._send(self.fleet.refresh(parts[0], parts[1]))
            self.fleet._bump(not_found=1)
            self._error(404, f"no such route: {self.path}")
        except Exception as e:
            self._error(500, f"{type(e).__name__}: {e}")


def make_router_handler(fleet: Fleet, *, slow_request_ms: Optional[float] = None):
    return type(
        "BoundRouterHandler",
        (_RouterHandler,),
        {"fleet": fleet, "slow_request_ms": slow_request_ms},
    )


class StatsRouter:
    """Owns a `ThreadingHTTPServer` fronting one `Fleet`.

    Same lifecycle contract as `repro.service.StatsServer`: port 0 binds an
    ephemeral port, `start()` runs the accept loop on a daemon thread,
    `stop()` shuts down the HTTP loop and then the fleet (replica sets and
    the health prober). Usable as a context manager.
    """

    def __init__(
        self,
        fleet: Fleet,
        host: str = "127.0.0.1",
        port: int = 0,
        slow_request_ms: Optional[float] = None,
    ):
        self.fleet = fleet
        self.httpd = ThreadingHTTPServer(
            (host, port),
            make_router_handler(fleet, slow_request_ms=slow_request_ms),
        )
        self.host, self.port = self.httpd.server_address[:2]
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def url_for(self, namespace: str, dataset: str, kind: str) -> str:
        return f"{self.url}/{namespace}/{dataset}/{kind}"

    def start(self) -> "StatsRouter":
        self.fleet.start()
        self._thread = threading.Thread(
            target=self.httpd.serve_forever,
            name="ndv-stats-router-http",
            daemon=True,
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        # shutdown() blocks on an event only serve_forever() sets — guard
        # against a start() that never reached the accept loop.
        if self._thread is not None:
            self.httpd.shutdown()
            self._thread.join(timeout=10.0)
            self._thread = None
        self.httpd.server_close()
        self.fleet.stop()

    def __enter__(self) -> "StatsRouter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def serve_fleet(
    registry: DatasetRegistry,
    *,
    host: str = "127.0.0.1",
    port: int = 0,
    **fleet_kwargs,
) -> StatsRouter:
    """One-call convenience: build a `Fleet` and start routing it."""
    return StatsRouter(Fleet(registry, **fleet_kwargs), host=host, port=port).start()
