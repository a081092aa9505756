"""Replicas and `ReplicaSet`: health-checked request placement per dataset.

A replica is anything that can answer the stats-serving contract —
`StatsRequest` in, `repro.service.Response` out — plus a cheap liveness
probe. Two implementations:

  `LocalReplica`   a process-local `StatsService` in shared-spill mode: it
                   warms from, and contributes to, the dataset's on-disk
                   estimate-cache spill, so any replica of the set can
                   serve any entry a sibling has computed. `kill()` is the
                   fault-injection hook (smoke test, failover benchmark):
                   the replica starts refusing requests and failing probes,
                   exactly like a crashed process behind a proxy.
  `RemoteReplica`  an HTTP proxy to a `StatsServer` owned elsewhere; the
                   probe is `GET /health`, requests forward with their
                   `If-None-Match` intact.

`ReplicaSet` places requests with rendezvous (highest-random-weight)
hashing over (dataset, request identity): identical requests always land on
the same healthy replica — maximizing that replica's estimate-cache hit
rate — while distinct (mode, bounds, endpoint) identities spread across the
set. When a replica is ejected, only the keys it owned move (classic
rendezvous property); everything else keeps its placement. Failover is
retry-down-the-preference-order: a replica that raises is marked down and
the request continues to the next candidate, so one crash loses no
requests. Ejected replicas rejoin when `probe_all()` sees them healthy —
correct because ETags are derived from dataset state, not server identity,
so a rejoining (or brand-new) replica validates the same client tags
byte-for-byte.
"""
from __future__ import annotations

import dataclasses
import hashlib
import http.client
import json
import threading
import time
from typing import Dict, List, Optional, Tuple
from urllib.parse import urlencode

from repro.engine import EngineConfig, EstimationEngine
from repro.obs import span as _obs_span
from repro.service import (
    EstimateQuery,
    Response,
    StatsService,
    format_bounds,
    format_columns,
)
from repro.wire import ConnectionPool, WireError, fetch


@dataclasses.dataclass(frozen=True)
class StatsRequest:
    """One transport-agnostic routed request (the router's unit of work)."""

    kind: str  # "columns" | "estimate" | "plan" | "tablestats" | "health"
               # | "refresh"
    mode: str = "paper"
    schema_bounds: Optional[Tuple[Tuple[str, float], ...]] = None
    if_none_match: Optional[str] = None
    # Batched-estimate column filter. None = every column; a tuple narrows
    # the body and extends the identity/ETag (a filtered response is a
    # different cacheable thing than the full one).
    columns: Optional[Tuple[str, ...]] = None
    # Diagnostics request: attach per-column provenance to the body. Like
    # `if_none_match`, NOT part of `identity` — an explained request must
    # land on the same replica (same warm caches) as its plain twin.
    explain: bool = False

    @property
    def identity(self) -> tuple:
        """The placement key: everything that names the cached response —
        and nothing that does not (`if_none_match` must not move a request
        between replicas, or revalidations would land cold; `explain`
        must not either, or diagnostics would probe a cold sibling)."""
        base = (self.kind, self.mode, self.schema_bounds or ())
        # Appended only when present, so pre-existing identities (and the
        # rendezvous placement derived from them) are unchanged.
        return base if self.columns is None else base + (self.columns,)

    @property
    def bounds_dict(self) -> Optional[Dict[str, float]]:
        if not self.schema_bounds:
            return None
        return dict(self.schema_bounds)

    def to_query(self) -> EstimateQuery:
        """The service-level batch tuple this request maps onto."""
        return EstimateQuery(
            columns=self.columns,
            mode=self.mode,
            schema_bounds=self.bounds_dict,
            if_none_match=self.if_none_match,
            explain=self.explain,
        )

    @classmethod
    def from_query(cls, q: EstimateQuery) -> "StatsRequest":
        """Inverse of `to_query` for estimate tuples (router `/batch`)."""
        sb = (
            tuple(sorted(q.schema_bounds.items()))
            if q.schema_bounds else None
        )
        return cls(
            kind="estimate",
            mode=q.mode,
            schema_bounds=sb,
            if_none_match=q.if_none_match,
            columns=q.columns,
            explain=q.explain,
        )

    def to_wire(self) -> dict:
        """The `/batch` tuple dict (absent fields elided, compact frames)."""
        d: dict = {}
        if self.columns is not None:
            d["columns"] = list(self.columns)
        if self.mode != "paper":
            d["mode"] = self.mode
        if self.schema_bounds:
            d["bounds"] = self.bounds_dict
        if self.if_none_match is not None:
            d["if_none_match"] = self.if_none_match
        if self.explain:
            # Elided when false: explain-off frames are byte-identical to
            # pre-provenance peers' frames (and those peers never see the
            # field at all).
            d["explain"] = True
        return d


class ReplicaError(ConnectionError):
    """A replica refused or failed a request (triggers failover)."""


class NoReplicaAvailable(RuntimeError):
    """Every replica of the set failed the request."""


# What ejects a replica: transport-shaped failures only (`ReplicaError` is
# a `ConnectionError` is an `OSError`). Anything else — a ValueError from a
# schema-mismatched dataset, a bug — is request- or dataset-scoped: every
# replica would fail it identically, so ejecting (let alone cascading
# through the whole set) would turn one poison request into a fleet-wide
# "degraded" for no benefit. Those propagate to the HTTP layer's 500
# instead, leaving health state untouched.
FAILOVER_ERRORS = (OSError, TimeoutError)


class LocalReplica:
    """One process-local `StatsService` replica in shared-spill mode."""

    def __init__(
        self,
        name: str,
        root: str,
        *,
        engine_config: Optional[EngineConfig] = None,
        devices=None,
        poll_interval: Optional[float] = None,
        max_workers: int = 8,
        audit: bool = False,
        audit_columns: int = 4,
    ):
        self.name = name
        self.service = StatsService(
            root,
            engine=EstimationEngine(
                engine_config or EngineConfig(), devices=devices
            ),
            poll_interval=poll_interval,
            max_workers=max_workers,
            shared_spill=True,
            name=name,  # /metrics series labeled {service="<replica name>"}
            audit=audit,
            audit_columns=audit_columns,
        )
        self._killed = False

    def start(self) -> "LocalReplica":
        self.service.start()
        # The default mode's program for this dataset's bucket, compiled on
        # this replica's own devices before the first request needs it.
        self.service.catalog.warm()
        return self

    def stop(self) -> None:
        self.service.stop()

    def kill(self) -> None:
        """Simulate a crash: refuse all requests and fail probes until
        `revive()`. The underlying ingestion loop is stopped too."""
        self._killed = True
        self.service.stop()

    def revive(self) -> None:
        self._killed = False
        self.service.start()

    def probe(self) -> bool:
        return not self._killed and self.service.probe()

    def handle(self, req: StatsRequest) -> Response:
        if self._killed:
            raise ReplicaError(f"replica {self.name} is down")
        if req.kind == "columns":
            return self.service.columns(if_none_match=req.if_none_match)
        if req.kind == "estimate":
            return self.service.estimate(
                mode=req.mode,
                schema_bounds=req.bounds_dict,
                if_none_match=req.if_none_match,
                explain=req.explain,
            )
        if req.kind == "plan":
            return self.service.plan(
                mode=req.mode, if_none_match=req.if_none_match
            )
        if req.kind == "tablestats":
            return self.service.table_stats(
                mode=req.mode,
                columns=req.columns,
                if_none_match=req.if_none_match,
            )
        if req.kind == "health":
            return self.service.health()
        if req.kind == "refresh":
            return self.service.refresh()
        return Response(400, {"error": f"unknown kind {req.kind!r}"}, None)

    def handle_batch(self, reqs: List[StatsRequest]) -> List[Response]:
        """One sub-batch: all cold tuples share one super-pack engine call."""
        if self._killed:
            raise ReplicaError(f"replica {self.name} is down")
        return self.service.batch([r.to_query() for r in reqs])


class RemoteReplica:
    """HTTP proxy to a `StatsServer` whose lifecycle is owned elsewhere.

    The hop runs over a keep-alive `ConnectionPool` (one TCP connection
    serves the replica's whole request stream, stale sockets retried once
    on a fresh connection — `repro.wire.client`) and negotiates the binary
    wire encoding; both are transparent to callers because the two
    encodings decode to bit-identical bodies with the same ETags.
    """

    def __init__(
        self,
        name: str,
        base_url: str,
        *,
        timeout: float = 30.0,
        pool: Optional[ConnectionPool] = None,
        binary: bool = True,
    ):
        self.name = name
        self.base_url = base_url.rstrip("/")
        self.timeout = timeout
        self.binary = binary
        self._own_pool = pool is None
        self.pool = pool or ConnectionPool(timeout=timeout, name=name)

    def start(self) -> "RemoteReplica":
        return self

    def stop(self) -> None:
        if self._own_pool:
            self.pool.close()

    def probe(self) -> bool:
        try:
            status, _, body = self._fetch(self.base_url + "/health")
        except ReplicaError:
            return False
        return status == 200 and (body or {}).get("status") == "serving"

    def _fetch(
        self, url: str, *, etag=None, method: str = "GET", payload=None
    ) -> Tuple[int, Optional[str], Optional[dict]]:
        """Pooled fetch with replica-shaped error wrapping."""
        try:
            return fetch(
                url,
                pool=self.pool,
                etag=etag,
                method=method,
                payload=payload,
                binary=self.binary,
            )
        except (OSError, http.client.HTTPException, WireError,
                json.JSONDecodeError) as e:
            # unreachable, hung, or answering garbage: all replica-shaped
            raise ReplicaError(
                f"replica {self.name} at {self.base_url}: {e}"
            ) from e

    def handle(self, req: StatsRequest) -> Response:
        path, method = f"/{req.kind}", "GET"
        if req.kind == "refresh":
            method = "POST"
        params = {}
        if req.kind in ("estimate", "plan", "tablestats"):
            params["mode"] = req.mode
        if req.kind == "tablestats" and req.columns:
            params["columns"] = format_columns(req.columns)
        if req.kind == "estimate" and req.schema_bounds:
            # Percent-escaped per side: a column name containing ':' or ','
            # survives the trip (parse_bounds unescapes after splitting).
            params["bounds"] = format_bounds(req.schema_bounds)
        if req.kind == "estimate" and req.explain:
            params["explain"] = "1"
        url = self.base_url + path + (
            "?" + urlencode(params) if params else ""
        )
        status, etag, body = self._fetch(
            url, etag=req.if_none_match, method=method
        )
        # A 5xx passes through as a response, NOT as a ReplicaError: the
        # upstream _Handler turns application errors (e.g. a ValueError
        # from a schema-mismatched dataset) into 500s, and those would
        # fail identically on every replica — same contract as a
        # LocalReplica propagating the exception (see FAILOVER_ERRORS).
        # Replica-local sickness is the probe loop's job to catch.
        return Response(status, body, etag)

    def scrape_metrics(self) -> Optional[str]:
        """This replica's `/metrics` exposition text, or None if unreachable.

        Only REMOTE replicas are scraped by the router's aggregate —
        local replicas already write the router process's own registry,
        so re-scraping them would double-count every series.
        """
        try:
            status, _, raw = self.pool.request(self.base_url + "/metrics")
        except Exception:
            return None
        if status != 200:
            return None
        try:
            return raw.decode("utf-8")
        except UnicodeDecodeError:
            return None

    def scrape_explain(self) -> Optional[dict]:
        """This replica's `/debug/explain` body, or None if unreachable.

        Mirrors `scrape_metrics`: best-effort, remote replicas only (a
        local replica's service is queried directly by the router)."""
        try:
            status, _, body = self._fetch(self.base_url + "/debug/explain")
        except Exception:
            return None
        if status != 200 or not isinstance(body, dict):
            return None
        return body

    def handle_batch(self, reqs: List[StatsRequest]) -> List[Response]:
        """Forward one sub-batch as a single binary `POST /batch` frame."""
        payload = {"tuples": [r.to_wire() for r in reqs]}
        status, _, body = self._fetch(
            self.base_url + "/batch", method="POST", payload=payload
        )
        entries = (body or {}).get("responses")
        if status != 200 or not isinstance(entries, list) \
                or len(entries) != len(reqs):
            # A replica that cannot answer the batch shape is as failed as
            # an unreachable one — the caller retries the sub-batch whole.
            raise ReplicaError(
                f"replica {self.name} at {self.base_url}: bad /batch "
                f"answer (status {status})"
            )
        return [
            Response(e.get("status", 500), e.get("body"), e.get("etag"))
            for e in entries
        ]


@dataclasses.dataclass
class ReplicaHealth:
    """Mutable health record the set keeps per replica."""

    healthy: bool = True
    consecutive_failures: int = 0
    last_error: Optional[str] = None
    last_change_monotonic: float = 0.0
    ejections: int = 0


class ReplicaSet:
    """N interchangeable replicas of one dataset behind rendezvous hashing."""

    def __init__(self, dataset_key: str, replicas: List):
        if not replicas:
            raise ValueError(f"replica set {dataset_key!r} needs >= 1 replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names in set: {names}")
        self.dataset_key = dataset_key
        self.replicas = list(replicas)
        self.health: Dict[str, ReplicaHealth] = {
            r.name: ReplicaHealth() for r in replicas
        }
        self.failovers = 0
        self._mu = threading.Lock()

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ReplicaSet":
        for r in self.replicas:
            r.start()
        return self

    def stop(self) -> None:
        for r in self.replicas:
            r.stop()

    # -- placement -----------------------------------------------------------

    def rank(self, identity: tuple) -> List:
        """All replicas, best placement first (rendezvous hashing).

        Weight = SHA-1(dataset key, request identity, replica name): stable
        across processes and restarts, so a router restart or an
        independently-built second router places identically.
        """
        def weight(replica) -> str:
            h = hashlib.sha1(
                f"{self.dataset_key}|{identity!r}|{replica.name}".encode()
            )
            return h.hexdigest()

        return sorted(self.replicas, key=weight, reverse=True)

    def _candidates(self, identity: tuple) -> List:
        """Healthy replicas in rank order, then ejected ones as last
        resorts — an all-down set still attempts every replica (and a
        successful hail-mary resurrects the one that answered)."""
        ranked = self.rank(identity)
        with self._mu:
            up = [r for r in ranked if self.health[r.name].healthy]
            down = [r for r in ranked if not self.health[r.name].healthy]
        return up + down

    def _mark(self, name: str, healthy: bool, error: Optional[str]) -> None:
        with self._mu:
            rec = self.health[name]
            if healthy:
                rec.consecutive_failures = 0
                rec.last_error = None
            else:
                rec.consecutive_failures += 1
                rec.last_error = error
                if rec.healthy:
                    rec.ejections += 1
            if rec.healthy != healthy:
                rec.healthy = healthy
                rec.last_change_monotonic = time.monotonic()

    # -- serving -------------------------------------------------------------

    def call(self, req: StatsRequest) -> Tuple[Response, str, int]:
        """Route one request; returns (response, replica name, attempts).

        A replica that fails transport-shaped (`FAILOVER_ERRORS`) is
        ejected and the request retries on the next candidate — the caller
        sees a failure only when every replica failed
        (`NoReplicaAvailable`, carrying each replica's error). Any other
        exception is request/dataset-scoped and propagates immediately,
        with no ejection: every replica would fail it the same way.
        """
        errors: List[str] = []
        for attempt, replica in enumerate(self._candidates(req.identity), 1):
            try:
                # Each attempt gets its own span, parented to the CURRENT
                # (router) span — so a failed attempt's retry shows up as
                # a re-parented sibling, never an orphan of the dead span.
                with _obs_span(
                    "replica.call",
                    replica=replica.name, kind=req.kind, attempt=attempt,
                ):
                    resp = replica.handle(req)
            except FAILOVER_ERRORS as e:
                self._mark(replica.name, False, f"{type(e).__name__}: {e}")
                errors.append(f"{replica.name}: {type(e).__name__}: {e}")
                with self._mu:
                    self.failovers += 1
                continue
            self._mark(replica.name, True, None)
            return resp, replica.name, attempt
        raise NoReplicaAvailable(
            f"all {len(self.replicas)} replicas of {self.dataset_key!r} "
            f"failed: {'; '.join(errors)}"
        )

    def call_batch(
        self, reqs: List[StatsRequest]
    ) -> Tuple[List[Response], int]:
        """Route a batch of estimate tuples; returns (responses aligned
        with `reqs`, sub-batch dispatches performed).

        Tuples are grouped by their rendezvous-chosen replica — one
        `handle_batch` RPC per distinct placement, so every tuple still
        lands where its singleton `/estimate` would (same cache locality),
        while the common case (all tuples share a placement) is a single
        RPC. A failed dispatch (`FAILOVER_ERRORS`) ejects the replica and
        requeues its whole sub-batch for the next pass, where
        `_candidates` re-ranks around the ejection; passes are bounded by
        the replica count, and tuples that outlive every pass answer 503
        in place (the batch envelope itself never fails partway).
        """
        responses: List[Optional[Response]] = [None] * len(reqs)
        pending = list(range(len(reqs)))
        dispatches = 0
        for _ in range(len(self.replicas)):
            if not pending:
                break
            groups: Dict[str, List[int]] = {}
            chosen: Dict[str, object] = {}
            for i in pending:
                replica = self._candidates(reqs[i].identity)[0]
                chosen[replica.name] = replica
                groups.setdefault(replica.name, []).append(i)
            requeued: List[int] = []
            for name, indices in groups.items():
                replica = chosen[name]
                dispatches += 1
                try:
                    # One span per dispatch attempt, parented to the
                    # current (router) span: a requeued sub-batch's retry
                    # span is a SIBLING of the failed attempt's span (its
                    # `error` attribute marks the failure), not a child of
                    # it — failover re-parents instead of orphaning.
                    with _obs_span(
                        "replica.sub_batch",
                        replica=name, tuples=len(indices),
                    ):
                        answers = replica.handle_batch(
                            [reqs[i] for i in indices]
                        )
                except FAILOVER_ERRORS as e:
                    self._mark(name, False, f"{type(e).__name__}: {e}")
                    with self._mu:
                        self.failovers += 1
                    requeued.extend(indices)
                    continue
                self._mark(name, True, None)
                for i, resp in zip(indices, answers):
                    responses[i] = resp
            pending = requeued
        for i in pending:
            responses[i] = Response(
                503,
                {
                    "error": f"all {len(self.replicas)} replicas of "
                    f"{self.dataset_key!r} failed"
                },
                None,
            )
        return list(responses), dispatches

    def refresh_all(self) -> List[Tuple[str, Optional[Response]]]:
        """Broadcast a refresh to every replica, one after another (each
        replica ingests independently; all must see a dataset change for
        their ETags to agree, and the refresh is acknowledged only once
        they have). Transport failures eject, as in `call()`; a
        dataset-scoped refresh error (e.g. a schema-mismatched new file —
        every replica rejects it identically, last-good state keeps
        serving) is reported as a failed entry without ejecting anyone."""
        out: List[Tuple[str, Optional[Response]]] = []
        refresh = StatsRequest("refresh")
        with _obs_span(
            "fleet.refresh_broadcast",
            dataset=self.dataset_key, replicas=len(self.replicas),
        ):
            for replica in self.replicas:
                try:
                    with _obs_span(
                        "replica.call",
                        replica=replica.name, kind="refresh", attempt=1,
                    ):
                        resp = replica.handle(refresh)
                except Exception as e:
                    if isinstance(e, FAILOVER_ERRORS):
                        self._mark(
                            replica.name, False, f"{type(e).__name__}: {e}"
                        )
                    out.append((replica.name, None))
                    continue
                self._mark(replica.name, True, None)
                out.append((replica.name, resp))
        return out

    # -- health --------------------------------------------------------------

    def probe_all(self) -> Dict[str, bool]:
        """Probe every replica; ejected replicas that pass rejoin."""
        results: Dict[str, bool] = {}
        for replica in self.replicas:
            try:
                ok = bool(replica.probe())
            except Exception as e:
                ok = False
                self._mark(replica.name, False, f"{type(e).__name__}: {e}")
            else:
                self._mark(replica.name, ok, None if ok else "probe failed")
            results[replica.name] = ok
        return results

    def health_view(self) -> dict:
        # Connection-pool counters (opened/reused/retried_stale) per
        # replica that carries its own pool (remote hops) — collected
        # since PR 7 but previously never exposed over HTTP.
        pools = {
            r.name: r.pool.stats.snapshot()
            for r in self.replicas
            if getattr(r, "pool", None) is not None
        }
        with self._mu:
            view = {
                "replicas": {
                    name: {
                        "healthy": rec.healthy,
                        "consecutive_failures": rec.consecutive_failures,
                        "ejections": rec.ejections,
                        "last_error": rec.last_error,
                    }
                    for name, rec in self.health.items()
                },
                "healthy": sum(r.healthy for r in self.health.values()),
                "total": len(self.replicas),
                "failovers": self.failovers,
            }
        if pools:
            view["pools"] = pools
        return view
