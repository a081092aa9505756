"""`StatsService`: the facade joining async ingestion and stat serving.

One object owns a `StatsCatalog`, its `AsyncIngestor`, the shared lock, and
the request-side machinery (ETags, single-flight). The HTTP layer
(`repro.service.http`) is a thin translation onto this class — every
endpoint method here is synchronous, HTTP-agnostic, and returns a
`Response(status, body, etag)`, which keeps the whole serving contract
testable without sockets.

Coherence model (see the package docstring for the client-facing contract):

  * Every cacheable response carries an ETag = SHA-1 over the catalog's
    fingerprint set, the engine's `cache_token` (the resolved backend —
    the only numerics-bearing knob; execution strategy is neutral, so
    tags survive strategy changes), and the request identity (endpoint
    kind, mode, schema bounds). Any file add/remove/rewrite changes the
    fingerprint set and therefore rotates every ETag; an unchanged
    dataset validates forever.
  * An `If-None-Match` hit is answered before any catalog work: zero packs,
    zero engine executions, zero merges, and no lock — the fingerprint-set
    digest is precomputed at each commit (`_state_token`), so revalidation
    traffic never queues behind an in-flight cold computation.
  * Concurrent identical cold requests are coalesced (single-flight): one
    leader computes, everyone else waits on its result. With the catalog's
    own estimate cache this bounds work to one engine execution per
    (dataset state, engine config, mode, bounds) no matter the fan-in.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from typing import (
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
    Union,
)

from repro.catalog import (
    StatsCatalog,
    SuperpackJob,
    estimate_to_json,
    superpack_estimate,
)
from repro.catalog.source import MetadataSource
from repro.core.ndv.estimator import provenance_to_json
from repro.obs import registry, span, timed_acquire, timed_span
from repro.obs.metrics import QERROR_BUCKETS
from repro.planner import (
    ColumnStats,
    DEFAULT_MAX_PLANS,
    JoinGraph,
    TableStats,
    compute_cost,
)
from repro.planner.api import provenance_block
from repro.service.ingest import AsyncIngestor

MODES = ("paper", "improved")


class EstimateQuery(NamedTuple):
    """One tuple of a batched estimate request (`StatsService.batch`).

    `columns=None` means every column (identical identity — and therefore
    ETag — to a plain `/estimate` call, so 304 caches are shared between
    the batched and unbatched paths); a tuple of names restricts the body
    to those columns and extends the ETag identity accordingly.
    """

    columns: Optional[Tuple[str, ...]] = None
    mode: str = "paper"
    schema_bounds: Optional[Dict[str, float]] = None
    if_none_match: Optional[str] = None
    # Diagnostics-only: excluded from the ETag identity and the
    # single-flight key, so explain-on and explain-off tuples coalesce and
    # revalidate against each other; provenance attaches to a COPY of the
    # published body, never to the shared single-flight result.
    explain: bool = False


class CostQuery(NamedTuple):
    """One `/cost` tuple of a batched request (`StatsService.batch`).

    Same identity rules as the standalone endpoint: `if_none_match` and
    `explain` are excluded from the ETag identity, so batched cost tuples
    revalidate against standalone `/cost` responses byte-for-byte.
    """

    graph: JoinGraph
    mode: str = "paper"
    max_plans: int = DEFAULT_MAX_PLANS
    if_none_match: Optional[str] = None
    explain: bool = False


class AuditResult(NamedTuple):
    """One sketch-audited column: dataset estimate vs a sampled reference.

    The reference is a HyperLogLog count (`repro.kernels.hll`) over ONE
    row group per file — a zero-ish-cost sample, not a full scan — so the
    q-error is a drift signal (route misfires, systematic bias), not a
    full-accuracy statement. `row_group` is the sampled index.
    """

    column: str
    route: str
    estimate: float
    reference: float
    qerror: float
    generation: int
    row_group: int


class Response(NamedTuple):
    """Transport-agnostic endpoint result."""

    status: int                 # 200 | 304 | 400
    body: Optional[dict]        # JSON-ready payload; None for 304
    etag: Optional[str]         # quoted ETag; None where caching is invalid


@dataclasses.dataclass
class ServiceStats:
    """Request-side counters (ingestion counters live on the ingestor)."""

    requests: int = 0
    responses_200: int = 0
    responses_304: int = 0
    engine_runs: int = 0            # estimate-cache misses served (executions)
    single_flight_leaders: int = 0  # cold computations actually performed
    coalesced_waits: int = 0        # requests that rode a leader's result
    spill_reloads: int = 0          # shared-spill rechecks that found entries


class _Call:
    __slots__ = ("event", "result", "error")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None


class SingleFlight:
    """Duplicate-call suppression: one in-flight computation per key.

    Two APIs over one mechanism: `do()` is the classic run-once wrapper;
    `claim()` / `finish()` / `wait()` expose the leadership handshake so a
    BATCH of keys can be claimed up front, computed jointly (one super-pack
    engine call), and published per key — the per-tuple granularity the
    `/batch` endpoint needs. Keys are shared with the single-request path,
    so a concurrent `/estimate` coalesces onto a batch's leader and vice
    versa.
    """

    def __init__(self):
        self._mu = threading.Lock()
        self._calls: Dict[tuple, _Call] = {}

    def claim(self, key: tuple) -> Tuple[_Call, bool]:
        """Claim leadership of `key`; returns (call, is_leader).

        A leader MUST eventually `finish()` the call (success or error),
        or every follower blocks forever. A follower `wait()`s on it.
        """
        with self._mu:
            call = self._calls.get(key)
            leader = call is None
            if leader:
                call = _Call()
                self._calls[key] = call
        return call, leader

    def finish(
        self,
        key: tuple,
        call: _Call,
        *,
        result: object = None,
        error: Optional[BaseException] = None,
    ) -> None:
        """Publish a claimed call's outcome and release the key."""
        call.result = result
        call.error = error
        with self._mu:
            self._calls.pop(key, None)
        call.event.set()

    @staticmethod
    def wait(call: _Call) -> object:
        """Block on a follower's call; re-raises the leader's exception."""
        with timed_span("service.flight_wait"):
            call.event.wait()
        if call.error is not None:
            raise call.error
        return call.result

    def do(self, key: tuple, fn: Callable[[], object]) -> Tuple[object, bool]:
        """Run `fn` once per concurrent burst of `key`; returns (result,
        was_leader). Followers re-raise the leader's exception."""
        call, leader = self.claim(key)
        if leader:
            result, error = None, None
            try:
                result = fn()
            except BaseException as e:
                error = e
            self.finish(key, call, result=result, error=error)
            if error is not None:
                raise error
            return result, True
        return self.wait(call), False


def etag_matches(if_none_match: str, etag: str) -> bool:
    """RFC 7232 weak comparison of an If-None-Match header against one ETag."""
    for candidate in if_none_match.split(","):
        candidate = candidate.strip()
        if candidate == "*":
            return True
        if candidate.startswith("W/"):
            candidate = candidate[2:]
        if candidate == etag:
            return True
    return False


class StatsService:
    """Async-ingesting, ETag-serving stats facade over one catalog.

    Args:
      source: a `StatsCatalog`, a `MetadataSource`, or a dataset root path.
      engine: optional injected `EstimationEngine` (used only when `source`
        is not already a catalog; a catalog brings its own).
      max_workers: ingestion scatter width.
      poll_interval: seconds between background refreshes under `start()`;
        None serves whatever `refresh()` is called manually.
      auto_load_cache: thread the catalog's mtime-guarded cache auto-load.
      save_cache_on_commit: keep the on-disk estimate-cache spill current —
        rewritten (compacted) after each committed refresh that changed the
        dataset, and again whenever a cold request populates a new entry,
        so a restarted server serves the newest state warm.
      shared_spill: run this service as one replica of a set sharing the
        dataset's on-disk estimate-cache spill. Implies `auto_load_cache`
        and `save_cache_on_commit`, and additionally re-checks the spill
        (mtime-guarded, one stat when nothing changed) before every cold
        computation — so a request this replica never computed is served
        from a sibling replica's spill instead of re-running the engine.
        Spill writes are merge-not-clobber and atomic under concurrent
        replicas (see `StatsCatalog.save_cache`).
      health_hook: optional callable polled by `probe()`; returning False
        marks this replica unhealthy to replica managers (the fleet tier's
        ejection signal) without affecting direct request serving.
      name: telemetry label for this service's stats views in `/metrics`
        (`{service="<name>"}`) — distinguishes replicas sharing a process.
      audit: opt-in background accuracy auditor. After every committed
        refresh (and once at start) a daemon thread samples
        `audit_columns` columns — a rotating, generation-keyed window over
        the sorted column list — computes a reference NDV with the HLL
        sketch kernel over one row group per file, and records
        `max(est/ref, ref/est)` into the `ndv_audit_qerror{route=}`
        histogram. Results surface per column in `?explain=1` bodies and
        `/debug/explain`. Requires a filesystem-backed source (the sketch
        reads raw values); columns whose data cannot be read are skipped.
      audit_columns: sample width K per audit pass.
    """

    def __init__(
        self,
        source: Union[StatsCatalog, MetadataSource, str],
        *,
        engine=None,
        max_workers: int = 8,
        poll_interval: Optional[float] = None,
        auto_load_cache: bool = False,
        save_cache_on_commit: bool = False,
        shared_spill: bool = False,
        health_hook: Optional[Callable[[], bool]] = None,
        name: str = "stats",
        audit: bool = False,
        audit_columns: int = 4,
    ):
        if shared_spill:
            auto_load_cache = True
            save_cache_on_commit = True
        if isinstance(source, StatsCatalog):
            self.catalog = source
        else:
            self.catalog = StatsCatalog(
                source, engine=engine, auto_load_cache=auto_load_cache
            )
        self.engine = self.catalog.engine
        self.lock = threading.RLock()
        self.save_cache_on_commit = save_cache_on_commit
        self.shared_spill = shared_spill
        self.health_hook = health_hook
        self.closed = False
        self.ingestor = AsyncIngestor(
            self.catalog,
            max_workers=max_workers,
            poll_interval=poll_interval,
            lock=self.lock,
            on_commit=self._on_commit,
        )
        self.stats = ServiceStats()
        self._flight = SingleFlight()
        self._state_token: Optional[str] = None
        self._started_at = time.monotonic()
        self.audit_enabled = audit
        self.audit_columns = audit_columns
        self._audit_results: Dict[str, AuditResult] = {}
        self._audit_wake = threading.Event()
        self._audit_thread: Optional[threading.Thread] = None
        # Serialized explained payloads (wire frames / JSON bytes), keyed
        # by (etag, wire, audit_version) — see `_Handler._encode_payload`.
        # `audit_version` bumps whenever the audit sidecar changes, so a
        # new audit pass orphans stale entries instead of serving them.
        self.audit_version = 0
        self._explain_payloads: "OrderedDict[tuple, bytes]" = OrderedDict()
        # The pre-existing stats objects stay the single source of truth;
        # /metrics reads them live through weakref views (repro.obs).
        self.name = name
        labels = {"service": name}
        reg = registry()
        reg.register_stats_view("ndv_service", labels, self.stats)
        reg.register_stats_view("ndv_ingest", labels, self.ingestor.stats)
        reg.register_stats_view("ndv_catalog", labels, self.catalog.stats)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        """Initial synchronous refresh, then the polling loop (if any)."""
        self.closed = False
        self.refresh()
        if self.ingestor.poll_interval:
            self.ingestor.start()
        if self.audit_enabled and self._audit_thread is None:
            self._audit_wake.set()  # audit the initial state too
            self._audit_thread = threading.Thread(
                target=self._audit_loop, name="ndv-audit", daemon=True
            )
            self._audit_thread.start()

    def stop(self) -> None:
        self.ingestor.stop()
        self.closed = True
        if self._audit_thread is not None:
            self._audit_wake.set()  # wake the loop so it observes `closed`
            self._audit_thread.join(timeout=10.0)
            self._audit_thread = None

    def probe(self) -> bool:
        """Replica-manager liveness probe (the fleet tier's health signal).

        True while the service can serve: not stopped, and the optional
        `health_hook` (fault injection, external circuit breakers) agrees.
        Deliberately cheap — no catalog work, no lock — so a prober can
        hammer it.
        """
        if self.closed:
            return False
        if self.health_hook is not None and not self.health_hook():
            return False
        return True

    def __enter__(self) -> "StatsService":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def _on_commit(self, summary) -> None:
        # Runs under self.lock, after a committed refresh changed the state:
        # stale-fingerprint cache lines can never be requested again, and
        # the precomputed state token must rotate with the fingerprint set.
        self.catalog.compact_caches()
        self._state_token = self._compute_state_token()
        if self.save_cache_on_commit:
            self.catalog.save_cache()
        if self.audit_enabled:
            self._audit_wake.set()  # new generation: schedule an audit pass

    def _ensure_ready(self) -> None:
        if not self.catalog.scanned:
            self.ingestor.refresh()

    # -- ETags ---------------------------------------------------------------

    def _compute_state_token(self) -> str:
        """Digest of (fingerprint set, engine config). Call under the lock."""
        h = hashlib.sha1()
        for part in sorted(self.catalog.fingerprint_key()):
            h.update(part.encode())
            h.update(b"\x00")
        h.update(self.engine.cache_token.encode())
        return h.hexdigest()

    def _current_state_token(self) -> str:
        # Reading the attribute is atomic and the token only changes inside
        # a commit, so the hot path (every 304) takes no lock at all.
        token = self._state_token
        if token is None:
            with self.lock:
                token = self._state_token = self._compute_state_token()
        return token

    def _etag(
        self,
        kind: str,
        mode: str = "",
        bounds_key: tuple = (),
        columns: Optional[Tuple[str, ...]] = None,
    ) -> str:
        h = hashlib.sha1(self._current_state_token().encode())
        h.update(f"|{kind}|{mode}|{bounds_key!r}".encode())
        if columns is not None:
            # Appended ONLY when a filter is present, so unfiltered batch
            # tuples share tags byte-for-byte with plain /estimate calls.
            h.update(f"|cols={columns!r}".encode())
        return f'"{h.hexdigest()}"'

    # -- endpoints -----------------------------------------------------------

    def health(self) -> Response:
        """Liveness + counters. Never cached (no ETag, never 304)."""
        with self.lock:
            scanned = self.catalog.scanned
            body = {
                "status": "serving" if scanned else "starting",
                "generation": self.ingestor.generation,
                "files": len(self.catalog.entry_fingerprints()),
                "columns": len(self.catalog.column_names) if scanned else 0,
                "engine": self.engine.cache_token,
                "ingestor_running": self.ingestor.running,
                "uptime_s": time.monotonic() - self._started_at,
                "service": dataclasses.asdict(self.stats),
                "ingest": dataclasses.asdict(self.ingestor.stats),
                "catalog": dataclasses.asdict(self.catalog.stats),
            }
        return Response(200, body, None)

    def refresh(self) -> Response:
        """Force one scatter-gather refresh; returns the update summary."""
        summary = self.ingestor.refresh()
        return Response(200, {
            "generation": self.ingestor.generation,
            "added": summary.added,
            "updated": summary.updated,
            "removed": summary.removed,
            "total": summary.total,
            "changed": summary.changed,
        }, None)

    def columns(self, *, if_none_match: Optional[str] = None) -> Response:
        """Merged per-column summary of the dataset view."""
        self.stats.requests += 1
        self._ensure_ready()
        with self.lock:
            etag = self._etag("columns")
            if if_none_match is not None and etag_matches(if_none_match, etag):
                self.stats.responses_304 += 1
                return Response(304, None, etag)
            merged = self.catalog.merged_metadata()
            body = {
                "etag": etag,
                "generation": self.ingestor.generation,
                "files": self.catalog.num_files,
                "columns": {
                    name: {
                        "non_null": m.non_null,
                        "num_row_groups": m.num_row_groups,
                        "physical_type": int(m.physical_type),
                    }
                    for name, m in merged.items()
                },
            }
        self.stats.responses_200 += 1
        return Response(200, body, etag)

    def estimate(
        self,
        *,
        mode: str = "paper",
        schema_bounds: Optional[Dict[str, float]] = None,
        if_none_match: Optional[str] = None,
        explain: bool = False,
    ) -> Response:
        """Dataset-level NDV estimates, bit-identical to
        `StatsCatalog.estimate()` under the same engine config.

        `explain=True` attaches per-column provenance (route, margins,
        Newton diagnostics, clamps — plus the latest audit sample when the
        auditor has one) under a "provenance" key, on a COPY of the body:
        the ETag, the single-flight result, and every explain-off response
        stay byte-identical to the explain-free server.
        """
        resp = self._cached_endpoint(
            "estimate", mode, schema_bounds, if_none_match,
            lambda etag, gen: {
                "etag": etag,
                "generation": gen,
                "mode": mode,
                "schema_bounds": schema_bounds,
                "estimates": {
                    name: estimate_to_json(e)
                    for name, e in self.catalog.estimate(
                        mode=mode, schema_bounds=schema_bounds
                    ).items()
                },
            },
        )
        if explain:
            resp = self._attach_provenance(resp, mode, schema_bounds)
        return resp

    def plan(
        self,
        *,
        mode: str = "paper",
        if_none_match: Optional[str] = None,
    ) -> Response:
        """Per-column memory plans via the default `NDVPlanner`.

        Deliberately no planner override: the ETag/single-flight key has no
        planner component, so differently-configured planners would
        validate and coalesce against each other. Custom planners belong on
        the library path (`catalog.plan(planner)`), not the cached one.
        """
        return self._cached_endpoint(
            "plan", mode, None, if_none_match,
            lambda etag, gen: {
                "etag": etag,
                "generation": gen,
                "mode": mode,
                "plans": {
                    name: dataclasses.asdict(p)
                    for name, p in self.catalog.plan(mode=mode).items()
                },
            },
        )

    def table_stats(
        self,
        *,
        mode: str = "paper",
        columns: Optional[Tuple[str, ...]] = None,
        if_none_match: Optional[str] = None,
    ) -> Response:
        """Planner-shaped table statistics: row count + per-column NDV.

        The fleet router's `/cost` input: one small cacheable body per
        dataset carrying everything the join-cardinality formula needs —
        total rows (footer sums), per-column NDV, non-null count, and the
        PR 9 quality signals (route, confidence). `columns=None` serves
        every column; a filter restricts the body AND extends the ETag
        identity (same rule as filtered batch tuples). Unknown columns
        are a request error (400).
        """
        if columns is not None:
            self._ensure_ready()
            unknown = [
                c for c in columns if c not in set(self.catalog.column_names)
            ]
            if unknown:
                self.stats.requests += 1
                return Response(
                    400, {"error": f"unknown columns {unknown}"}, None
                )

        def build(etag: str, gen: int) -> dict:
            ests = self.catalog.estimate(mode=mode)
            provs = self.catalog.provenance(mode=mode, engine=self.engine)
            merged = self.catalog.merged_metadata()
            names = columns if columns is not None else sorted(ests)
            return {
                "etag": etag,
                "generation": gen,
                "mode": mode,
                "rows": self.catalog.total_rows(),
                "columns": {
                    name: {
                        "ndv": float(ests[name].ndv),
                        "non_null": int(merged[name].non_null),
                        "confidence": float(ests[name].confidence),
                        "route": (
                            provs[name].route if name in provs else None
                        ),
                    }
                    for name in names
                },
            }

        return self._cached_response(
            "tablestats", mode, (), if_none_match, build, columns
        )

    def cost(
        self,
        *,
        graph: JoinGraph,
        mode: str = "paper",
        max_plans: int = DEFAULT_MAX_PLANS,
        if_none_match: Optional[str] = None,
        explain: bool = False,
    ) -> Response:
        """Cheapest join order + per-join cardinalities for a join graph.

        Tables read THIS service's dataset (aliases make self-join graphs;
        cross-dataset graphs are the fleet router's `/cost`). The ETag
        hashes (state token, graph identity, max_plans): a plan 304s
        exactly while the dataset's stats are unchanged, and rotates with
        any file add/remove/rewrite. `explain=True` attaches the
        per-column NDV/route/confidence provenance that fed each
        cardinality, on a copy — identity-neutral like `/estimate`'s.
        """
        ident_key = (repr(graph.identity()), int(max_plans))

        def build(etag: str, gen: int) -> dict:
            stats_map = self._planner_stats(graph, mode)
            body = compute_cost(
                graph, stats_map, mode=mode, max_plans=max_plans
            )
            return {"etag": etag, "generation": gen, **body}

        try:
            resp = self._cached_response(
                "cost", mode, ident_key, if_none_match, build
            )
        except ValueError as e:
            # Graph references a column this dataset doesn't have.
            return Response(400, {"error": str(e)}, None)
        if explain and resp.status == 200 and resp.body is not None:
            with self.lock:
                stats_map = self._planner_stats(graph, mode)
            body = dict(resp.body)
            body["provenance"] = provenance_block(graph, stats_map)
            resp = Response(resp.status, body, resp.etag)
        return resp

    def _planner_stats(self, graph: JoinGraph, mode: str):
        """Per-table `TableStats` for `compute_cost`, from this catalog.

        Every graph alias reads the served dataset, so tables share the
        row count and column estimates. Call under the lock (the cost
        build does). Raises ValueError for unknown join columns -> 400.
        """
        ests = self.catalog.estimate(mode=mode)
        provs = self.catalog.provenance(mode=mode, engine=self.engine)
        merged = self.catalog.merged_metadata()
        rows = float(self.catalog.total_rows())
        needed = graph.columns_by_table()
        unknown = sorted(
            {c for cols in needed.values() for c in cols} - set(ests)
        )
        if unknown:
            raise ValueError(f"unknown join columns {unknown}")
        stats_map: Dict[str, TableStats] = {}
        for name, cols in needed.items():
            stats_map[name] = TableStats(
                rows=rows,
                columns={
                    c: ColumnStats(
                        ndv=float(ests[c].ndv),
                        non_null=int(merged[c].non_null),
                        confidence=float(ests[c].confidence),
                        route=provs[c].route if c in provs else None,
                    )
                    for c in cols
                },
            )
        return stats_map

    def batch(
        self, queries: Sequence[Union[EstimateQuery, "CostQuery"]]
    ) -> List[Response]:
        """Many estimate tuples, one engine dispatch per cold mode group.

        Per-tuple semantics are exactly `estimate()`'s: the same ETags
        (unfiltered tuples share tags byte-for-byte with `/estimate`),
        per-tuple 304s, per-tuple 400s for bad modes or unknown columns,
        and bodies bit-identical to the sequential path (the super-pack
        exactness contract, `repro.catalog.superpack`).

        Cold tuples extend single-flight to per-tuple granularity: each
        cold tuple's ("estimate", etag) key is claimed up front — keys
        already in flight (a concurrent `/estimate`, another batch, or a
        duplicate within this one) ride that leader — and all claimed
        tuples execute as ONE `superpack_estimate` call under the lock,
        publishing each tuple's body to its own followers.

        `CostQuery` tuples ride the same envelope: each runs the standalone
        `cost()` path (its own single-flight key and 304 semantics — a
        cost tuple's ETag matches the standalone endpoint's byte-for-byte).
        The batched plan scorer is already one dispatch per graph, so cost
        tuples don't super-pack across graphs the way estimate tuples do.
        """
        n = len(queries)
        responses: List[Optional[Response]] = [None] * n
        if n == 0:
            return []
        for i, q in enumerate(queries):
            if isinstance(q, CostQuery):
                try:
                    responses[i] = self.cost(
                        graph=q.graph, mode=q.mode, max_plans=q.max_plans,
                        if_none_match=q.if_none_match, explain=q.explain,
                    )
                except Exception as e:
                    responses[i] = Response(
                        500, {"error": f"{type(e).__name__}: {e}"}, None
                    )
        est_count = sum(
            1 for q in queries if not isinstance(q, CostQuery)
        )
        self.stats.requests += est_count
        if est_count == 0:
            return responses
        self._ensure_ready()
        known = set(self.catalog.column_names)

        claimed: List[tuple] = []   # (index, query, key, call)
        in_batch: List[Tuple[int, int]] = []   # (follower idx, leader idx)
        waiting: List[tuple] = []   # (index, call) — led by another thread
        leader_for: Dict[tuple, int] = {}
        for i, q in enumerate(queries):
            if isinstance(q, CostQuery):
                continue
            if q.mode not in MODES:
                responses[i] = Response(
                    400, {"error": f"mode {q.mode!r} not in {list(MODES)}"},
                    None,
                )
                continue
            if q.columns is not None:
                unknown = [c for c in q.columns if c not in known]
                if unknown:
                    responses[i] = Response(
                        400, {"error": f"unknown columns {unknown}"}, None
                    )
                    continue
            bounds_key = (
                tuple(sorted(q.schema_bounds.items()))
                if q.schema_bounds else ()
            )
            etag = self._etag("estimate", q.mode, bounds_key, q.columns)
            if q.if_none_match is not None and etag_matches(
                q.if_none_match, etag
            ):
                self.stats.responses_304 += 1
                responses[i] = Response(304, None, etag)
                continue
            key = ("estimate", etag)
            if key in leader_for:
                in_batch.append((i, leader_for[key]))
                continue
            call, is_leader = self._flight.claim(key)
            if is_leader:
                leader_for[key] = i
                claimed.append((i, q, key, call))
            else:
                waiting.append((i, call))

        if claimed:
            self._batch_compute(claimed, responses)
        for i, leader_idx in in_batch:
            self.stats.coalesced_waits += 1
            r = responses[leader_idx]
            if r.status == 200:
                self.stats.responses_200 += 1
            responses[i] = r
        for i, call in waiting:
            self.stats.coalesced_waits += 1
            try:
                body = SingleFlight.wait(call)
            except Exception as e:
                responses[i] = Response(
                    500, {"error": f"{type(e).__name__}: {e}"}, None
                )
                continue
            self.stats.responses_200 += 1
            responses[i] = Response(200, body, body["etag"])
        for i, q in enumerate(queries):
            # After publication: provenance attaches to per-tuple COPIES,
            # so coalesced tuples sharing a leader's body are unaffected.
            # (Cost tuples handled their own explain above.)
            if isinstance(q, CostQuery):
                continue
            if q.explain and responses[i] is not None \
                    and responses[i].status == 200:
                responses[i] = self._attach_provenance(
                    responses[i], q.mode, q.schema_bounds, q.columns
                )
        return responses

    def _batch_compute(self, claimed: List[tuple], responses: list) -> None:
        """Execute all claimed tuples jointly and publish each call.

        Every claimed call is finished no matter what — on failure with
        the error (followers re-raise it), so nobody blocks forever.
        """
        try:
            with self.lock:
                if self.shared_spill:
                    self.stats.spill_reloads += bool(
                        self.catalog.maybe_load_cache()
                    )
                jobs: List[SuperpackJob] = []
                job_index: Dict[tuple, int] = {}
                slots: List[int] = []
                for _, q, _, _ in claimed:
                    jkey = (
                        q.mode,
                        tuple(sorted(q.schema_bounds.items()))
                        if q.schema_bounds else None,
                    )
                    idx = job_index.get(jkey)
                    if idx is None:
                        idx = job_index[jkey] = len(jobs)
                        jobs.append(SuperpackJob(
                            self.catalog, q.mode, q.schema_bounds
                        ))
                    slots.append(idx)
                with span(
                    "service.superpack",
                    tuples=len(claimed), groups=len(jobs), service=self.name,
                ) as sp:
                    result = superpack_estimate(jobs, engine=self.engine)
                    sp.set_attribute("engine_calls", result.engine_calls)
                self.stats.engine_runs += result.engine_calls
                if result.engine_calls and self.save_cache_on_commit:
                    self.catalog.save_cache()
                gen = self.ingestor.generation
                bodies = []
                for (i, q, key, call), idx in zip(claimed, slots):
                    est_map = result.estimates[idx]
                    names = q.columns if q.columns is not None else est_map
                    bounds_key = (
                        tuple(sorted(q.schema_bounds.items()))
                        if q.schema_bounds else ()
                    )
                    # Recomputed inside the lock: the body must describe
                    # the state its ETag names, even across a mid-flight
                    # refresh commit (same rule as `_cached_endpoint`).
                    body = {
                        "etag": self._etag(
                            "estimate", q.mode, bounds_key, q.columns
                        ),
                        "generation": gen,
                        "mode": q.mode,
                        "schema_bounds": q.schema_bounds,
                        "estimates": {
                            name: estimate_to_json(est_map[name])
                            for name in names
                        },
                    }
                    if q.columns is not None:
                        body["columns"] = list(q.columns)
                    bodies.append(body)
        except BaseException as e:
            for i, q, key, call in claimed:
                self._flight.finish(key, call, error=e)
                responses[i] = Response(
                    500, {"error": f"{type(e).__name__}: {e}"}, None
                )
            if not isinstance(e, Exception):
                raise  # KeyboardInterrupt and friends: release, then bubble
            return
        for (i, q, key, call), body in zip(claimed, bodies):
            self._flight.finish(key, call, result=body)
            self.stats.single_flight_leaders += 1
            self.stats.responses_200 += 1
            responses[i] = Response(200, body, body["etag"])

    # -- provenance + audit --------------------------------------------------

    _EXPLAIN_PAYLOADS_MAX = 32

    def explain_payload_peek(self, key: tuple) -> Optional[bytes]:
        """Memoized serialized explained payload, or None.

        Keys carry (etag, wire-format flag, audit_version): the ETag pins
        the estimate state and request identity, the audit version the
        q-error sidecar — nothing else can change an explained payload's
        bytes. Filled by the HTTP handler (`_Handler._encode_payload`).
        """
        with self.lock:
            payload = self._explain_payloads.get(key)
            if payload is not None:
                self._explain_payloads.move_to_end(key)
            return payload

    def explain_payload_store(self, key: tuple, payload: bytes) -> None:
        with self.lock:
            self._explain_payloads[key] = payload
            self._explain_payloads.move_to_end(key)
            while len(self._explain_payloads) > self._EXPLAIN_PAYLOADS_MAX:
                self._explain_payloads.popitem(last=False)

    def _attach_provenance(
        self,
        resp: Response,
        mode: str,
        schema_bounds: Optional[Dict[str, float]],
        columns: Optional[Tuple[str, ...]] = None,
    ) -> Response:
        """Explained twin of a 200 response: same ETag, body copy + provenance.

        Usually a provenance-cache hit (filled alongside every engine run);
        a spill-warmed estimate recomputes once through the catalog. Audit
        samples ride along per column when the auditor has visited it.
        """
        if resp.status != 200 or resp.body is None:
            return resp
        with self.lock:
            provs = self.catalog.provenance(
                mode=mode, schema_bounds=schema_bounds, engine=self.engine
            )
            audits = dict(self._audit_results)
        names = (
            columns if columns is not None
            else list(resp.body.get("estimates", {}))
        )
        prov_json: Dict[str, dict] = {}
        for name in names:
            p = provs.get(name)
            if p is None:
                continue
            d = provenance_to_json(p)
            a = audits.get(name)
            if a is not None:
                d["audit"] = {
                    "qerror": a.qerror,
                    "reference_ndv": a.reference,
                    "estimate_ndv": a.estimate,
                    "generation": a.generation,
                    "row_group": a.row_group,
                }
            prov_json[name] = d
        body = dict(resp.body)
        body["provenance"] = prov_json
        return Response(resp.status, body, resp.etag)

    def debug_explain(self) -> Response:
        """The catalog's provenance cache + audit samples, JSON-shaped.

        Never cached (no ETag): it describes the server's *cache contents*,
        not a deterministic function of dataset state.
        """
        with self.lock:
            entries = self.catalog.provenance_entries()
            audits = dict(self._audit_results)
            gen = self.ingestor.generation
        return Response(200, {
            "service": self.name,
            "generation": gen,
            "entries": [
                {
                    "mode": key[1],
                    "schema_bounds": (
                        {n: v for n, v in key[2]} if key[2] else None
                    ),
                    "files": len(key[0]),
                    "columns": {
                        name: provenance_to_json(p)
                        for name, p in sorted(provs.items())
                    },
                }
                for key, provs in entries
            ],
            "audits": {
                name: a._asdict() for name, a in sorted(audits.items())
            },
        }, None)

    def _audit_loop(self) -> None:
        while True:
            self._audit_wake.wait()
            self._audit_wake.clear()
            if self.closed:
                return
            try:
                self.run_audit()
            except Exception:
                # The auditor is a diagnostic sidecar: it must never take
                # the serving loop down. Failures show as missing samples.
                pass

    def run_audit(self) -> List[AuditResult]:
        """One audit pass: sample K columns, sketch a reference, record q-error.

        Public and synchronous so tests and smoke flows can drive it
        deterministically; the background thread calls exactly this.
        """
        with self.lock:
            if not self.catalog.scanned:
                return []
            gen = self.ingestor.generation
            names = sorted(self.catalog.column_names)
            files = list(self.catalog.files)
            ests = self.catalog.estimate(mode="paper")
            provs = self.catalog.provenance(mode="paper")
        if not names or not files:
            return []
        k = min(self.audit_columns, len(names))
        start = (gen * k) % len(names)
        sample = [names[(start + i) % len(names)] for i in range(k)]
        hist = registry().histogram(
            "ndv_audit_qerror",
            "Audit q-error max(est/ref, ref/est): metadata estimate vs a "
            "one-row-group-per-file HLL reference, by chosen route",
            QERROR_BUCKETS,
        )
        results: List[AuditResult] = []
        for col in sample:
            if col not in ests or col not in provs:
                continue
            ref = self._audit_reference(col, files, gen)
            if ref is None or ref <= 0.0:
                continue
            est = float(ests[col].ndv)
            q = max(est / ref, ref / est) if est > 0 else float("inf")
            route = provs[col].route
            hist.observe(q, route=route)
            results.append(AuditResult(
                column=col, route=route, estimate=est, reference=ref,
                qerror=q, generation=gen, row_group=gen,
            ))
        with self.lock:
            if results:
                for r in results:
                    self._audit_results[r.column] = r
                # New q-error sidecar: orphan memoized explained payloads
                # (they embed the audit results current at build time).
                self.audit_version += 1
                self._explain_payloads.clear()
        return results

    def _audit_reference(
        self, col: str, files: List[str], gen: int
    ) -> Optional[float]:
        """HLL reference NDV for one column: one row group per file.

        Registers merge by element-max across files, so the count covers
        the union of the sampled row groups. Values hash through their
        string form — distinctness, not representation, is what the sketch
        needs. Unreadable files (metadata-only sources) yield None.
        """
        import zlib

        import numpy as np

        from repro.columnar.reader import DataReader
        from repro.kernels import ops as kernel_ops

        regs = None
        for fid in files:
            try:
                reader = DataReader(fid)
                if col not in reader.npz.files:
                    continue
                n_rg = reader.footer.num_row_groups
                if not n_rg:
                    continue
                idx = gen % n_rg  # rotate the sampled row group per pass
                lo = sum(
                    rg.num_rows for rg in reader.footer.row_groups[:idx]
                )
                hi = lo + reader.footer.row_groups[idx].num_rows
                vals = reader.npz[col][lo:hi]
                mask = reader.null_mask(col)
                valid = (
                    ~mask[lo:hi] if mask is not None
                    else np.ones(len(vals), bool)
                )
            except Exception:
                continue
            if not len(vals):
                continue
            keys = np.fromiter(
                (zlib.crc32(str(v).encode()) for v in vals),
                np.uint32, len(vals),
            )
            bank = np.asarray(kernel_ops.hll_fold(
                keys[None, :], valid[None, :].astype(np.float32)
            ))
            regs = bank if regs is None else np.maximum(regs, bank)
        if regs is None:
            return None
        return float(np.asarray(kernel_ops.hll_count(regs))[0])

    def _cached_endpoint(
        self,
        kind: str,
        mode: str,
        schema_bounds: Optional[Dict[str, float]],
        if_none_match: Optional[str],
        build: Callable[[str, int], dict],
    ) -> Response:
        bounds_key = (
            tuple(sorted(schema_bounds.items())) if schema_bounds else ()
        )
        return self._cached_response(
            kind, mode, bounds_key, if_none_match, build
        )

    def _cached_response(
        self,
        kind: str,
        mode: str,
        ident_key: tuple,
        if_none_match: Optional[str],
        build: Callable[[str, int], dict],
        columns: Optional[Tuple[str, ...]] = None,
    ) -> Response:
        """The shared cacheable-endpoint skeleton (ETag precheck,
        single-flight, lock discipline). `ident_key` is whatever request
        identity the endpoint hashes besides kind/mode — schema bounds for
        the estimate family, (graph identity, max_plans) for `/cost`.

        Spans: `service.request` is the whole call; its self time is the
        state digest, the 304 check and the bookkeeping. Its children are
        `service.lock_wait` (queued behind another request or a refresh
        commit), `service.compute` (the body build under the lock) and,
        for a coalesced request, `service.flight_wait`. The request and
        the flight wait are `timed_span`s: they stay out of the trace
        ring, so a 304's trace is dropped as a childless root."""
        with timed_span("service.request"):
            self.stats.requests += 1
            if mode not in MODES:
                return Response(
                    400, {"error": f"mode {mode!r} not in {list(MODES)}"},
                    None,
                )
            self._ensure_ready()
            etag = self._etag(kind, mode, ident_key, columns)
            if if_none_match is not None and etag_matches(
                if_none_match, etag
            ):
                # The entire hit path: one lock-free digest. No pack, no
                # engine.
                self.stats.responses_304 += 1
                return Response(304, None, etag)

            def compute() -> dict:
                timed_acquire(self.lock, "service.lock_wait")
                try:
                    # Recompute the tag inside the lock: a refresh may have
                    # committed since the cheap pre-check, and the body must
                    # describe the state its ETag names.
                    etag_now = self._etag(kind, mode, ident_key, columns)
                    if self.shared_spill:
                        # A sibling replica may have computed (and
                        # spilled) this entry already: one stat when
                        # nothing changed, and a cache line instead of an
                        # engine run when it did.
                        self.stats.spill_reloads += bool(
                            self.catalog.maybe_load_cache()
                        )
                    misses = self.catalog.stats.estimate_cache_misses
                    with span(
                        "service.compute",
                        kind=kind, mode=mode, service=self.name,
                    ):
                        body = build(etag_now, self.ingestor.generation)
                    new_runs = (
                        self.catalog.stats.estimate_cache_misses - misses
                    )
                    self.stats.engine_runs += new_runs
                    if new_runs and self.save_cache_on_commit:
                        # the spill must include what was just computed,
                        # or a restart between now and the next commit
                        # starts cold
                        self.catalog.save_cache()
                    return body
                finally:
                    self.lock.release()

            body, leader = self._flight.do((kind, etag), compute)
            if leader:
                self.stats.single_flight_leaders += 1
            else:
                self.stats.coalesced_waits += 1
            self.stats.responses_200 += 1
            return Response(200, body, body["etag"])
