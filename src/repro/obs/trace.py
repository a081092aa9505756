"""Request tracing: spans, traceparent propagation, bounded trace ring.

A `Span` is (trace_id, span_id, parent_id, name, monotonic start/stop,
attributes). Root spans are opened only at the HTTP layer (`root_span`);
library code opens children with `span(name)`. Outside a served request
(no current root) `span(name)` still times its work but joins no trace:
it has no ids and never enters the ring. `timed_span(name)` is timed the
same way inside a trace too: it never enters the ring and never makes a
trace worth retaining, and spans opened inside it join the enclosing
trace as if it were absent. It wraps work that runs on every request,
such as the 304 path, whose traces retention drops.

Every span but an HTTP root, traced or not, feeds two registry series on
exit (a root's wall time is `ndv_http_request_seconds` already):
`ndv_span_seconds{span=<name>}` (a histogram of durations; its count is
the work done) and `ndv_span_self_seconds_total{span=<name>}` (duration
minus the durations of its direct children — the time spent in the
span's own code). Names come from the fixed set `SPAN_NAMES`, so the
label stays low-cardinality.

Profiler bridge: `set_profiler_bridge(jax.profiler.TraceAnnotation)`
(installed by `repro.engine`, which imports jax; this module stays
stdlib-only) makes every span also open a host TraceMe of the same name
while a profiler session is collecting, which puts the program's spans
on the profiler's clock beside the device's ops. With no session the
cost is one `is_enabled()` check per span. Spans never open inside a
jitted function body, so the bridge never touches a program's HLO.

Propagation follows the W3C traceparent shape
(`00-<32hex trace_id>-<16hex span_id>-01`): carried as an HTTP header on
JSON requests and as an optional tagged section in the wire frame
(`wire.codec._SECTION_TRACE`; unknown-section skip keeps old peers
compatible). The current span rides a `contextvars.ContextVar`, which is
per-thread under `ThreadingHTTPServer` — exactly the granularity we need.

The collector is deliberately flat: finishing a span appends it to one
bounded ring of finished spans and nothing else — no per-trace
registration on the hot path. Grouping spans into traces happens lazily
at `/debug/traces` scrape time, where a full scan of a few thousand
entries is irrelevant. Because parents exit after their children (spans
are context managers), a trace whose root span is in the ring is
complete; a scrape racing an in-flight request may see a rootless
partial trace, which `trace_tree` renders under a synthetic root.

Retention is interest-based: a childless local root (the warm cache-hit
request, which dominates traffic) is NOT retained — its only facts,
latency and status, are already in the request histograms — unless it
errored or was marked with `keep_trace()`. Spans with children, spans
whose parent lives in another process (joined traces), and child spans
always land in the ring.
"""
from __future__ import annotations

import contextvars
import os
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from repro.obs import _state
from repro.obs.metrics import registry

TRACEPARENT_HEADER = "Traceparent"

# Every library span the program opens, parent in the comment; the only
# values of the series' `span` label. HTTP roots, `<tier>.<route>`, are
# bridged to the profiler but feed no span series.
SPAN_NAMES = (
    "replica.call",          # router root or fleet.refresh_broadcast:
                             # one routed attempt
    "fleet.refresh_broadcast",  # router root: one refresh, every replica
                                # in turn
    "replica.sub_batch",     # router root: one /batch dispatch
    "service.request",       # a cacheable endpoint, whole (digest, 304);
                             # a timed_span: its children join the root
    "service.lock_wait",     # service.request: the lock held elsewhere
    "service.compute",       # service.request: body build under the lock
    "service.flight_wait",   # a follower waiting for its leader (timed_span)
    "service.superpack",     # /batch: one joint engine call
    "ingest.refresh",        # one scatter-gather refresh
    "ingest.lock_wait",      # ingest.refresh: the lock held elsewhere
    "catalog.merge",         # ingest.refresh: copy-on-write re-merge
    "engine.pack",           # catalog: build the pack of a state
    "catalog.pack",          # engine.pack: the packer's numpy work
    "engine.h2d",            # catalog: device_put of a pack
    "engine.dispatch",       # enqueue of the estimation program
    "engine.device_wait",    # host blocked on the estimation program
    "engine.d2h",            # results to host, caches filled
    "planner.compute_cost",  # one cold /cost, whole
    "planner.enumerate",     # planner.compute_cost: candidate orders
    "planner.score",         # planner.compute_cost: multiplier packing
    "planner.fold",          # planner.score: device fold and its reads
    "planner.pick",          # planner.compute_cost: best-plan pick
)

# Span durations run from microseconds (a 304's digest) to seconds (a
# cold sampled plan space).
SPAN_BUCKETS_S = (
    1e-5, 5e-5, 1e-4, 5e-4, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)
_SPAN_SECONDS = registry().histogram(
    "ndv_span_seconds",
    "Wall time of each program span (count = work done)",
    buckets=SPAN_BUCKETS_S,
)
_SPAN_SELF_SECONDS = registry().counter(
    "ndv_span_self_seconds_total",
    "Span wall time minus its direct children's",
)
# name -> both series' `record`, bound to {span=name}; names outside
# SPAN_NAMES (tests) bind on first use.
_BOUND: Dict[str, Callable[[float, float], None]] = {}


def _bind(name: str) -> Callable[[float, float], None]:
    record = _BOUND[name] = _SPAN_SECONDS.timer(
        _SPAN_SELF_SECONDS, span=name
    ).record
    return record


for _name in SPAN_NAMES:
    _bind(_name)

# A TraceMe-like class (`is_enabled()`, context manager), or None.
_bridge = None


def set_profiler_bridge(annotation) -> None:
    """Mirror every span as `annotation(name)` while it `is_enabled()`;
    None removes the bridge."""
    global _bridge
    _bridge = annotation


# Ring capacity in SPANS (not traces): warm singleton traces are one span
# each, deep /batch traces a few dozen — ample history either way, with
# one fixed memory bound. Kept modest on purpose: every retained span is
# an object the cyclic GC keeps re-scanning.
_MAX_SPANS = 1024
# Trim in chunks so the hot path never pays the O(ring) compaction.
_TRIM_SLACK = 256

# Span/trace ids need uniqueness, not unpredictability: a private PRNG
# seeded from os.urandom once avoids a syscall per id (two per span, on
# every served request).
_id_rng = random.Random(int.from_bytes(os.urandom(16), "big"))
_id_bits = _id_rng.getrandbits  # C-implemented, atomic under the GIL
_monotonic = time.monotonic


def _hex_id(nbytes: int) -> str:
    return f"{_id_bits(nbytes * 8):0{nbytes * 2}x}"


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(value: Optional[str]) -> Optional[Tuple[str, str]]:
    """`00-<32hex>-<16hex>-<2hex>` -> (trace_id, parent_span_id) or None."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, _flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
        return None
    return trace_id, span_id


class Span:
    """One timed unit of work, inside a trace or (ids None) outside one.

    Also its own context manager (enter publishes it as the current span
    and opens the profiler bridge; exit stamps the end time, restores the
    previous current span, records its series and notifies the
    collector) — one object per span on the request hot path, no
    separate guard wrapper.
    """

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name",
        "start_s", "end_s", "attributes", "_token", "_has_child", "_keep",
        "_up", "_child_s", "_tm", "_link", "_series",
    )

    def __init__(self, trace_id: Optional[str], span_id: Optional[str],
                 parent_id: Optional[str], name: str,
                 attributes: Optional[Dict[str, object]] = None,
                 up: Optional["Span"] = None,
                 link: Optional["Span"] = None,
                 series: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_s = _monotonic()
        self.end_s: Optional[float] = None
        self.attributes: Dict[str, object] = (
            attributes if attributes is not None else {}
        )
        self._has_child = False
        self._keep = False
        self._up = up  # the enclosing span in this process, for self time
        self._child_s = 0.0
        self._tm = None
        # Outside the ring (ids None): the traced span that spans opened
        # inside this one join, or None.
        self._link = link
        self._series = series  # False for HTTP roots

    def keep_trace(self) -> None:
        """Force this span into the ring even if it stays childless
        (callers mark error responses and other must-keep requests)."""
        self._keep = True

    def set_attribute(self, key: str, value) -> None:
        self.attributes[key] = value

    @property
    def duration_s(self) -> float:
        end = self.end_s if self.end_s is not None else _monotonic()
        return end - self.start_s

    @property
    def traceparent(self) -> Optional[str]:
        if self.trace_id is None:
            return None
        return format_traceparent(self.trace_id, self.span_id)

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_s": self.start_s,
            "duration_ms": round(self.duration_s * 1000.0, 3),
            "attributes": dict(self.attributes),
        }

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        bridge = _bridge
        if bridge is not None and bridge.is_enabled():
            self._tm = bridge(self.name).__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.attributes["error"] = repr(exc)
            self._keep = True
        self.end_s = end = _monotonic()
        if self._tm is not None:
            self._tm.__exit__(None, None, None)
        _current.reset(self._token)
        self._token = None  # a span kept in the ring keeps no Token
        duration = end - self.start_s
        up = self._up
        if up is not None:
            up._child_s += duration
            self._up = None  # a span kept in the ring keeps no parent
        if self._series:
            record = _BOUND.get(self.name) or _bind(self.name)
            record(duration, duration - self._child_s)
        if self.trace_id is None:
            return False  # timed only: outside a trace, never in the ring
        # Childless LOCAL roots are dropped: a warm cache-hit trace is a
        # single span whose only facts (latency, status) the histograms
        # already carry, and such requests dominate traffic — retaining
        # them would just churn the ring. Anything connected (a child, a
        # parent here or in another process) or marked must-keep lands in
        # the ring. Inlined _COLLECTOR.span_ended: this runs once per
        # served request, where an extra call frame is measurable.
        if self._has_child or self.parent_id is not None or self._keep:
            done = _COLLECTOR._done
            done.append(self)
            if len(done) > _COLLECTOR._cap:
                _COLLECTOR._trim()
        return False


class _NullSpan:
    """Absorbs the Span API when telemetry is off."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None
    traceparent = None

    def set_attribute(self, key: str, value) -> None:
        pass

    def keep_trace(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()

_current: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "repro_obs_current_span", default=None
)


class TraceCollector:
    """Bounded ring of finished spans, grouped into traces at read time.

    `span_ended` is the only hot-path entry point: one lock, one deque
    append. Everything trace-shaped (grouping, ordering, limits) runs at
    `/debug/traces` scrape time over a snapshot.
    """

    def __init__(self, max_spans: int = _MAX_SPANS):
        self._mu = threading.Lock()  # guards trims, not appends
        self._max = max_spans
        self._cap = max_spans + _TRIM_SLACK
        self._done: List[Span] = []

    def span_ended(self, span: Span) -> None:
        # list.append is a single C call — atomic under the GIL, so the
        # per-span hot path takes no lock. Only the (rare, chunked) trim
        # serializes; appends racing a trim land after the slice and
        # survive it. (`Span.__exit__` inlines this body.)
        done = self._done
        done.append(span)
        if len(done) > self._cap:
            self._trim()

    def _trim(self) -> None:
        with self._mu:
            excess = len(self._done) - self._max
            if excess > 0:
                del self._done[:excess]

    def _snapshot(self) -> List[Span]:
        return list(self._done)[-self._max:]

    def traces(self, limit: int = 20) -> List[List[Span]]:
        """Most-recently-finished-first traces (spans in end order).

        A trace's recency is its LAST finished span, so the trace still
        being appended to ranks first. Spans evicted by the ring bound
        simply drop out of their trace (oldest requests first).
        """
        snap = self._snapshot()
        order: List[str] = []
        wanted = set()
        for s in reversed(snap):
            if s.trace_id not in wanted:
                wanted.add(s.trace_id)
                order.append(s.trace_id)
                if len(order) == limit:
                    break
        groups: Dict[str, List[Span]] = {tid: [] for tid in order}
        for s in snap:
            if s.trace_id in wanted:
                groups[s.trace_id].append(s)
        return [groups[tid] for tid in order]

    def find(self, trace_id: str) -> Optional[List[Span]]:
        spans = [s for s in self._snapshot() if s.trace_id == trace_id]
        return spans or None

    def clear(self) -> None:
        with self._mu:
            self._done.clear()


_COLLECTOR = TraceCollector()


def collector() -> TraceCollector:
    return _COLLECTOR


def _traced(span: Optional[Span]) -> Optional[Span]:
    """`span` if it is in a trace, else the traced span it links to."""
    if span is None or span.trace_id is not None:
        return span
    return span._link


def current_span() -> Optional[Span]:
    """The innermost open span of the current trace, or None."""
    return _traced(_current.get())


def current_traceparent() -> Optional[str]:
    span = _traced(_current.get())
    return span.traceparent if span is not None else None


def root_span(name: str, traceparent: Optional[str] = None, **attributes):
    """Open a trace root (HTTP layer only).

    With a valid incoming `traceparent` the new span joins that trace as
    a child of the remote span; otherwise a fresh trace id is minted.
    A root feeds no span series: the HTTP tier times each request in
    `ndv_http_request_seconds`, and a warm request's budget is small.
    """
    if not _state.enabled:
        return _NULL
    up = _current.get()
    parsed = parse_traceparent(traceparent)
    if parsed is not None:
        return Span(parsed[0], _hex_id(8), parsed[1], name, attributes, up,
                    series=False)
    # fresh trace: mint trace id + span id with one RNG draw / one format
    ids = f"{_id_bits(192):048x}"
    return Span(ids[:32], ids[32:], None, name, attributes, up, series=False)


def timed_acquire(lock, name: str) -> None:
    """Acquire `lock`; a wait for it, and only a wait, is span `name`."""
    if not lock.acquire(blocking=False):
        with span(name):
            lock.acquire()


def span(name: str, **attributes):
    """Open a child of the current span; without a current trace the span
    is timed (series, profiler bridge) but joins no trace."""
    if not _state.enabled:
        return _NULL
    up = _current.get()
    parent = up  # `_traced(up)`, inlined: this runs for every span
    if up is not None and up.trace_id is None:
        parent = up._link
    if parent is None:
        return Span(None, None, None, name, attributes, up)
    parent._has_child = True  # the parent's trace is now worth retaining
    # `_hex_id(8)`, inlined
    return Span(parent.trace_id, f"{_id_bits(64):016x}", parent.span_id,
                name, attributes, up)


def timed_span(name: str, **attributes):
    """Open a span that is timed (series, profiler bridge) but stays out
    of the trace ring and leaves its trace's retention as it was; spans
    opened inside it join the current trace."""
    if not _state.enabled:
        return _NULL
    up = _current.get()
    return Span(None, None, None, name, attributes, up, _traced(up))


def trace_tree(spans: List[Span]) -> dict:
    """Span list -> nested JSON tree (children sorted by start time).

    Spans whose parent is not in the list (e.g. the parent lives in the
    client process) become roots. A single synthetic root wraps multiple
    roots so the result is always one tree.
    """
    by_id = {s.span_id: s.to_dict() for s in spans}
    for node in by_id.values():
        node["children"] = []
    roots = []
    for s in spans:
        node = by_id[s.span_id]
        parent = by_id.get(s.parent_id) if s.parent_id else None
        if parent is not None:
            parent["children"].append(node)
        else:
            roots.append(node)
    for node in by_id.values():
        node["children"].sort(key=lambda c: c["start_s"])
    roots.sort(key=lambda c: c["start_s"])
    if len(roots) == 1:
        return roots[0]
    return {
        "trace_id": spans[0].trace_id if spans else None,
        "name": "(multiple roots)",
        "children": roots,
    }
