"""repro.obs — unified telemetry: metrics registry + cross-tier tracing.

Stdlib-only (no jax, no other repro imports), so every tier can depend
on it without layering cycles. Two halves behind one kill-switch:

    client ──POST /cost───────────────▶ StatsRouter   (root router.cost)
                                          │  traceparent: header + wire
                                          │                 frame section
                  ┌───────────────────────┴──────────────┐
                  ▼                                      ▼
            replica.call  (one per table)          replica B (HTTP,
                  │                                 its own root)
            service.request ─┬─ service.lock_wait
                             ├─ service.flight_wait   (a follower)
                             └─ service.compute
                                  ├─ engine.pack ── catalog.pack
                                  ├─ engine.h2d
                                  ├─ engine.dispatch     (the enqueue)
                                  ├─ engine.device_wait  (the device)
                                  └─ engine.d2h
            service.superpack  (POST /batch: the engine.* spans above)
            planner.compute_cost ─┬─ planner.enumerate
                                  ├─ planner.score ── planner.fold
                                  └─ planner.pick
            ingest.refresh ─┬─ ingest.lock_wait       (no root needed)
                            └─ catalog.merge

          spans of a trace close bottom-up → each lands in the bounded
          finished-span ring → grouped per trace at GET /debug/traces?limit=N
          (JSON trees); service.request and service.flight_wait are
          `timed_span`s, kept out of the ring (their children hang off
          the root), so a warm 304's trace stays childless and is
          dropped. Every span but an HTTP root, in a trace or not, feeds
          ndv_span_seconds{span=} and ndv_span_self_seconds_total{span=}
          (its time minus its children's); while a jax.profiler session
          collects, every span, roots included, is a host TraceMe of the
          same name (the bridge `repro.engine` installs; this package
          imports no jax).

    Counters / gauges / histograms land in the process-global
    `MetricsRegistry`; pre-existing stats objects (`ServiceStats`,
    `IngestStats`, `CatalogStats`, `PoolStats`) are registered as
    weakref VIEWS read at scrape time — single source of truth, no
    double counting → GET /metrics (Prometheus text exposition).
    The router re-emits each remote replica's scrape under a
    `replica="<name>"` label next to its own series.

Telemetry is NEUTRAL by contract: nothing here enters `cache_key`,
`cache_token`, or ETag derivation — estimate bytes and ETags are
byte-identical with telemetry on or off (`set_enabled(False)` turns
every increment and span into a no-op). On a TPU v5e host a span costs
about 3.0 us in a trace and 2.4 us outside one in a tight loop, and
about 3.5 times that inside a served request. `benchmarks/obs_overhead.py`
(warm /estimate, telemetry on against off; asserts < 5% in full mode)
read 4.55% and 5.2% there, and the revalidation-bound static TPC-H cell
gives up about 4.4% of its probes per second to the spans (PERF.md).

Estimation-quality observability rides the same registry. Every batch
the estimator runs also emits per-lane PROVENANCE (core/ndv: route
chosen + margin, detector margin, Newton iteration counts/residual,
clamps hit) — extra output lanes of the one shared program, so fused
and unfused twins produce identical diagnostics and nothing enters
cache identity:

    estimate_batch ──▶ BatchEstimates(+route, margins, iters, clamps)
         │ provenance_from_batch (estimator.py)
         ▼
    catalog.provenance_cache_store   ← the ONE funnel that records
         │                             ndv_route_total{route=},
         │                             ndv_newton_iters{solver=},
         │                             ndv_detector_margin
         ├─▶ ?explain=1 on /estimate and per-tuple in /batch
         │     (same ETag — explain never enters identity; wire frames
         │      carry it in a tagged section old peers skip)
         ├─▶ GET /debug/explain      (per-dataset cache dump; the
         │                            router aggregates per replica)
         └─▶ audit loop (service.py, opt-in): samples K columns per
               refresh generation, reference NDV from an HLL sketch
               over one row group (kernels/hll.py), q-error lands in
               ndv_audit_qerror{route=} and rides explain payloads

Metric naming conventions: every series is `ndv_<subsystem>_<noun>`
with unit suffixes per Prometheus style (`_total` counters, `_seconds`/
`_bytes` in the name, `_bucket`/`_sum`/`_count` for histograms). Labels
are low-cardinality enums only (route, solver, tier, status — never
column or dataset names on estimator series; the router adds
`replica="<name>"` when re-emitting remote scrapes).
"""
from repro.obs import _state
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS_S,
    MetricsRegistry,
    WIDTH_BUCKETS,
    registry,
)
from repro.obs.trace import (
    SPAN_NAMES,
    Span,
    TRACEPARENT_HEADER,
    TraceCollector,
    collector,
    current_span,
    current_traceparent,
    format_traceparent,
    parse_traceparent,
    root_span,
    set_profiler_bridge,
    span,
    timed_acquire,
    timed_span,
    trace_tree,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS_S",
    "MetricsRegistry",
    "SPAN_NAMES",
    "Span",
    "TRACEPARENT_HEADER",
    "TraceCollector",
    "WIDTH_BUCKETS",
    "collector",
    "current_span",
    "current_traceparent",
    "enabled",
    "format_traceparent",
    "parse_traceparent",
    "registry",
    "root_span",
    "set_enabled",
    "set_profiler_bridge",
    "span",
    "timed_acquire",
    "timed_span",
    "trace_tree",
]


def set_enabled(value: bool) -> None:
    """Flip the process-global telemetry switch (metrics AND spans)."""
    _state.enabled = bool(value)


def enabled() -> bool:
    return _state.enabled
