"""Unified telemetry tier: metrics registry, tracing, scrape endpoints.

Covers the observability acceptance criteria:
  * concurrent counter/histogram writes are EXACT (striped locks, and the
    hot-path label memo aliases every kwarg ordering to one cell)
  * Prometheus text exposition survives hostile label values and obeys
    the v0.0.4 line grammar (cumulative buckets, +Inf terminal, escaping)
  * weakref stats views read live objects and vanish when collected
  * traceparent propagation: header grammar round-trip, wire-frame trace
    section, contextvar parenting, interest-based ring retention
  * HTTP e2e: one trace id across router -> replica -> service -> engine
    for a fleet `/batch`; a killed replica's failover shows up as a
    re-parented sibling attempt, never an orphan
  * `/metrics` + `/debug/traces` on both tiers; pool counters in router
    `/health`; `slow_request_ms` structured logging
"""
import gc
import json
import logging
import os
import re
import threading
import time
import urllib.request
from dataclasses import dataclass

import numpy as np
import pytest

from repro import obs
from repro.columnar.writer import WriterOptions, write_file
from repro.fleet import DatasetRegistry, Fleet, StatsRequest, StatsRouter
from repro.obs.metrics import (
    MetricsRegistry,
    add_label_to_exposition,
    escape_label_value,
)
from repro.service import StatsServer, StatsService, fetch_json
from repro.wire import decode_frame, decode_traceparent, encode_frame, fetch


def _write(root, name, seed, vocab=64):
    rng = np.random.default_rng(seed)
    return write_file(
        os.path.join(root, name),
        {
            "tok": rng.integers(0, vocab, 512).astype(np.int64),
            "val": np.round(rng.uniform(0, 100, 512), 1),
        },
        options=WriterOptions(row_group_size=128),
    )


@pytest.fixture(autouse=True)
def _telemetry_on():
    obs.set_enabled(True)
    obs.collector().clear()
    yield
    obs.set_enabled(True)


@pytest.fixture()
def dataset(tmp_path):
    root = str(tmp_path / "ds")
    for i in range(3):
        _write(root, f"shard_{i:03d}", seed=i)
    return root


@pytest.fixture()
def fleet_registry(tmp_path):
    reg = DatasetRegistry()
    for name, seed in (("alpha", 10), ("beta", 20)):
        root = str(tmp_path / name)
        for i in range(2):
            _write(root, f"shard_{i:03d}", seed=seed + i, vocab=32)
        reg.add("wh", name, root)
    return reg


# -- metrics registry --------------------------------------------------------


def test_concurrent_increments_exact_across_label_orderings():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "t")
    h = reg.histogram("h", "h", buckets=(1.0, 10.0))
    n_threads, n_iter = 8, 500

    def worker(tid):
        for i in range(n_iter):
            # alternate kwarg order and value type: every variant must
            # alias the same canonical cell
            if i % 2:
                c.inc(a="1", b="2")
                h.observe(0.5, k="x")
            else:
                c.inc(b=2, a=1)
                h.observe(20.0, k="x")

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(a="1", b="2") == n_threads * n_iter
    # one series in the exposition, not one per kwarg ordering
    text = reg.exposition()
    assert text.count("t_total{") == 1
    assert f't_total{{a="1",b="2"}} {n_threads * n_iter}' in text
    # histogram: exact count/cumulative buckets; half the samples > 10
    assert f'h_count{{k="x"}} {n_threads * n_iter}' in text
    assert f'h_bucket{{k="x",le="1"}} {n_threads * n_iter // 2}' in text
    assert f'h_bucket{{k="x",le="+Inf"}} {n_threads * n_iter}' in text


def test_bound_handles_write_same_cells():
    reg = MetricsRegistry()
    c = reg.counter("b_total")
    h = reg.histogram("bh", buckets=(1.0,))
    c.labels(route="x").inc()
    c.inc(route="x")
    h.labels(route="x").observe(0.5)
    h.observe(2.0, route="x")
    assert c.value(route="x") == 2
    text = reg.exposition()
    assert 'bh_count{route="x"} 2' in text
    assert 'bh_bucket{route="x",le="1"} 1' in text


def test_bound_timer_writes_both_cells():
    reg = MetricsRegistry()
    c = reg.counter("t_total")
    h = reg.histogram("th", buckets=(1.0,))
    h.timer(c, route="x").record(0.5, 0.25)
    h.labels(route="x").observe(2.0)
    c.labels(route="x").inc(1)
    assert c.value(route="x") == 1.25
    text = reg.exposition()
    assert 'th_count{route="x"} 2' in text and 'th_sum{route="x"} 2.5' in text
    assert 'th_bucket{route="x",le="1"} 1' in text
    with pytest.raises(ValueError):
        h.timer(MetricsRegistry().counter("t_total"), route="x")


def test_exposition_escapes_hostile_labels_and_obeys_grammar():
    reg = MetricsRegistry()
    hostile = 'quo"te\\slash\nnewline'
    reg.counter("evil_total", 'help with \\ and\nnewline').inc(ds=hostile)
    reg.gauge("g").set(-1.5, k="v")
    reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
    text = reg.exposition()
    assert escape_label_value(hostile) == 'quo\\"te\\\\slash\\nnewline'
    assert f'evil_total{{ds="{escape_label_value(hostile)}"}} 1\n' in text
    # v0.0.4 line grammar: every sample line is name[{labels}] value,
    # with no raw newline/quote inside a label value
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
        r' (NaN|[+-]?Inf|-?[0-9.e+-]+)$'
    )
    comment = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*$")
    for line in text.splitlines():
        pat = comment if line.startswith("#") else sample
        assert pat.match(line), f"bad exposition line: {line!r}"
    # histogram buckets are cumulative and terminate at +Inf == count
    assert text.index('lat_bucket{le="0.1"} 1') < text.index(
        'lat_bucket{le="+Inf"} 1'
    )
    assert "lat_count 1" in text


def test_stats_view_reads_live_object_and_dies_with_it():
    @dataclass
    class MyStats:
        hits: int = 0
        ratio: float = 0.0
        _private: int = 7

    reg = MetricsRegistry()
    s = MyStats()
    reg.register_stats_view("my", {"who": "a"}, s)
    s.hits = 3
    s.ratio = 0.5
    text = reg.exposition()
    assert 'my_hits{who="a"} 3' in text
    assert 'my_ratio{who="a"} 0.5' in text
    assert "_private" not in text
    del s
    gc.collect()
    assert "my_hits" not in reg.exposition()


def test_add_label_to_exposition_injects_everywhere():
    blob = (
        "# TYPE x_total counter\n"
        "x_total 3\n"
        'y_bucket{le="+Inf"} 2\n'
    )
    out = add_label_to_exposition(blob, {"replica": "r0"})
    assert out == (
        'x_total{replica="r0"} 3\n'
        'y_bucket{le="+Inf",replica="r0"} 2\n'
    )


def test_disabled_telemetry_is_a_noop():
    reg = MetricsRegistry()
    c = reg.counter("off_total")
    bound = c.labels(k="v")
    obs.set_enabled(False)
    c.inc(k="v")
    bound.inc()
    reg.histogram("offh").observe(1.0)
    with obs.root_span("nope") as sp:
        assert sp.trace_id is None
        assert obs.span("child").trace_id is None
    obs.set_enabled(True)
    assert c.value(k="v") == 0
    assert obs.collector().traces() == []


# -- tracing primitives ------------------------------------------------------


def test_traceparent_grammar_roundtrip():
    tp = obs.format_traceparent("ab" * 16, "cd" * 8)
    assert obs.parse_traceparent(tp) == ("ab" * 16, "cd" * 8)
    for bad in (
        None, "", "junk", "00-short-cd-01",
        "00-" + "g" * 32 + "-" + "cd" * 8 + "-01",   # non-hex
        "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",   # all-zero trace id
        "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero span id
        "00-" + "ab" * 16 + "-" + "cd" * 8,          # missing flags
    ):
        assert obs.parse_traceparent(bad) is None, bad


def test_wire_frame_carries_traceparent_section():
    payload = {"tuples": [{"mode": "paper"}]}
    tp = obs.format_traceparent("12" * 16, "34" * 8)
    raw = encode_frame(payload, traceparent=tp)
    assert decode_traceparent(raw) == tp
    assert decode_frame(raw) == payload  # section is out-of-band
    assert decode_traceparent(encode_frame(payload)) is None
    assert decode_traceparent(b"not a frame") is None


def test_span_nesting_and_ids():
    with obs.root_span("root", method="GET") as root:
        assert re.fullmatch(r"[0-9a-f]{32}", root.trace_id)
        assert re.fullmatch(r"[0-9a-f]{16}", root.span_id)
        assert root.parent_id is None
        assert obs.current_span() is root
        assert obs.current_traceparent() == root.traceparent
        with obs.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            assert obs.current_span() is child
        assert obs.current_span() is root
    assert obs.current_span() is None
    # joined trace: the remote parent's ids are adopted
    with obs.root_span("joined", traceparent=root.traceparent) as j:
        assert j.trace_id == root.trace_id
        assert j.parent_id == root.span_id
    # no active trace -> child spans are free no-ops
    assert obs.span("orphan").trace_id is None


def test_ring_retention_is_interest_based():
    col = obs.collector()
    with obs.root_span("boring"):
        pass  # childless local root: latency is in the histograms already
    assert col.traces() == []
    with obs.root_span("kept") as sp:
        sp.keep_trace()
    with obs.root_span("parent"):
        with obs.span("child"):
            pass
    with obs.root_span("joined", traceparent=sp.traceparent):
        pass  # remote parent -> always retained
    with pytest.raises(RuntimeError):
        with obs.root_span("failed"):
            raise RuntimeError("boom")
    spans = [s for t in col.traces() for s in t]
    names = {s.name for s in spans}
    assert names == {"kept", "parent", "child", "joined", "failed"}
    assert "boring" not in names
    failed = next(s for s in spans if s.name == "failed")
    assert "RuntimeError" in failed.attributes["error"]
    # "joined" adopted the remote parent's trace id, so it groups with it
    joined = next(s for s in spans if s.name == "joined")
    assert joined.trace_id == sp.trace_id


def test_collector_bound_and_recency():
    from repro.obs.trace import Span, TraceCollector, _TRIM_SLACK

    col = TraceCollector(max_spans=16)
    for i in range(200):
        col.span_ended(Span(f"{i:032x}", f"{i:016x}", None, f"s{i}"))
    assert len(col._done) <= 16 + _TRIM_SLACK
    got = col.traces(limit=4)
    assert [t[0].name for t in got] == ["s199", "s198", "s197", "s196"]
    assert col.find(f"{199:032x}")[0].name == "s199"
    col.clear()
    assert col.traces() == []


def test_trace_tree_shapes():
    from repro.obs.trace import Span, trace_tree

    root = Span("t" * 32, "a" * 16, None, "root")
    kid = Span("t" * 32, "b" * 16, "a" * 16, "kid")
    orphan = Span("t" * 32, "c" * 16, "ffff" * 4, "orphan")
    tree = trace_tree([kid, root])
    assert tree["name"] == "root"
    assert [c["name"] for c in tree["children"]] == ["kid"]
    multi = trace_tree([root, orphan])
    assert multi["name"] == "(multiple roots)"
    assert {c["name"] for c in multi["children"]} == {"root", "orphan"}


# -- HTTP e2e ----------------------------------------------------------------


def _poll_trace(traces_url, root_name, timeout=5.0):
    """Scrape /debug/traces until a trace rooted at `root_name` appears.

    Spans land in the collector on the server thread AFTER the response
    body is flushed (the span wraps the send), so a client that scrapes
    immediately can see a trace whose root hasn't ended yet. Children
    always end before their root, so once the root is visible the whole
    tree is.
    """
    deadline = time.monotonic() + timeout
    while True:
        status, _, traces = fetch_json(traces_url)
        assert status == 200
        match = [t for t in traces["traces"] if t["name"] == root_name]
        if match or time.monotonic() >= deadline:
            assert match, [t["name"] for t in traces["traces"]]
            return match[0]
        time.sleep(0.01)


def test_service_trace_spans_engine_and_scrape_endpoints(dataset):
    with StatsServer(StatsService(dataset)) as srv:
        obs.collector().clear()
        status, _, _ = fetch_json(srv.url + "/estimate?mode=improved")
        assert status == 200

        tree = _poll_trace(
            srv.url + "/debug/traces?limit=5", "service.estimate"
        )
        assert tree["attributes"]["status"] == 200

        def names(node):
            yield node["name"]
            for c in node["children"]:
                yield from names(c)

        seen = set(names(tree))
        assert "service.compute" in seen
        assert "engine.pack" in seen  # cold request reached the engine
        ids = set()

        def tids(node):
            ids.add(node["trace_id"])
            for c in node["children"]:
                tids(c)

        tids(tree)
        assert len(ids) == 1  # one trace id across HTTP -> engine

        status, _, body = fetch_json(srv.url + "/debug/traces?limit=junk")
        assert status == 400

        import urllib.request

        with urllib.request.urlopen(srv.url + "/metrics") as r:
            assert r.status == 200
            assert "version=0.0.4" in r.headers["Content-Type"]
            text = r.read().decode()
        assert re.search(
            r'ndv_http_requests_total\{[^}]*route="estimate"[^}]*\} \d+', text
        )
        assert re.search(
            r'ndv_http_request_seconds_bucket\{[^}]*tier="service"', text
        )
        assert re.search(r"ndv_service_requests\b", text)  # stats view


def test_slow_request_logging(dataset, caplog):
    with StatsServer(
        StatsService(dataset), slow_request_ms=0.0
    ) as srv:
        with caplog.at_level(logging.WARNING, logger="repro.obs.slow"):
            fetch_json(srv.url + "/estimate?mode=paper")
            # the line is emitted on the server thread after the response
            # is written — give it a moment to land
            deadline = time.monotonic() + 5.0
            lines = []
            while not lines and time.monotonic() < deadline:
                lines = [r.getMessage() for r in caplog.records
                         if "slow_request" in r.getMessage()]
                time.sleep(0.01)
        assert lines, "expected a structured slow-request line"
        assert "tier=service" in lines[0]
        assert "endpoint=/estimate" in lines[0]
        assert "trace_id=" in lines[0]
    # default is OFF: no records without the threshold
    caplog.clear()
    with StatsServer(StatsService(dataset)) as srv:
        with caplog.at_level(logging.WARNING, logger="repro.obs.slow"):
            fetch_json(srv.url + "/estimate?mode=paper")
            time.sleep(0.05)
        assert not [r for r in caplog.records
                    if "slow_request" in r.getMessage()]


def test_fleet_batch_single_trace_and_router_scrapes(fleet_registry):
    router = StatsRouter(Fleet(fleet_registry, replicas_per_dataset=2)).start()
    try:
        obs.collector().clear()
        tuples = [
            {"namespace": "wh", "dataset": "alpha", "mode": "improved"},
            {"namespace": "wh", "dataset": "beta", "mode": "paper"},
        ]
        from repro.wire import ConnectionPool

        pool = ConnectionPool(name="obs_test")
        status, _, env = fetch(router.url + "/batch", pool=pool,
                               method="POST", payload={"tuples": tuples})
        assert status == 200
        assert [r["status"] for r in env["responses"]] == [200, 200]

        batch = _poll_trace(
            router.url + "/debug/traces?limit=10", "router.batch"
        )

        def walk(node):
            yield node
            for c in node["children"]:
                yield from walk(c)

        nodes = list(walk(batch))
        names = {n["name"] for n in nodes}
        # router -> per-replica sub-batches -> service superpack -> engine,
        # all under ONE trace id
        assert "replica.sub_batch" in names
        assert "service.superpack" in names
        assert len({n["trace_id"] for n in nodes}) == 1
        subs = [n for n in nodes if n["name"] == "replica.sub_batch"]
        assert all(n["parent_id"] == batch["span_id"] for n in subs)

        # router /metrics aggregates its own registry (local replicas
        # write the same process registry, so no replica label here)
        status, _, _ = fetch_json(router.url + "/datasets")
        import urllib.request

        with urllib.request.urlopen(router.url + "/metrics") as r:
            text = r.read().decode()
        assert re.search(
            r'ndv_http_requests_total\{[^}]*tier="router"', text
        )
        assert "ndv_fleet_batches" in text

        # pool counters ride the router health payload (remote hops only
        # carry pools; local replicas legitimately have none)
        status, _, health = fetch_json(router.url + "/health")
        assert status == 200 and "wh/alpha" in health["datasets"]
        pool.close()
    finally:
        router.stop()


def test_fleet_failover_reparents_attempt_spans(fleet_registry):
    router = StatsRouter(Fleet(fleet_registry, replicas_per_dataset=2)).start()
    try:
        url = router.url_for("wh", "alpha", "estimate") + "?mode=improved"
        status, _, _ = fetch_json(url)
        assert status == 200
        rset = router.fleet.sets["wh/alpha"]
        victim = rset.rank(StatsRequest("estimate", "improved").identity)[0]
        victim.kill()
        obs.collector().clear()
        status, _, _ = fetch_json(url)
        assert status == 200  # failover answered
        tree = _poll_trace(
            router.url + "/debug/traces?limit=5", "router.estimate"
        )
        calls = [c for c in tree["children"] if c["name"] == "replica.call"]
        assert len(calls) == 2, "failed attempt + retry, both re-parented"
        assert [c["attributes"]["attempt"] for c in calls] == [1, 2]
        assert "error" in calls[0]["attributes"]
        assert calls[0]["attributes"]["replica"] == victim.name
        assert "error" not in calls[1]["attributes"]
        # both attempts are SIBLINGS under the router span (re-parented,
        # not orphaned under the dead attempt)
        assert all(c["parent_id"] == tree["span_id"] for c in calls)
    finally:
        router.stop()


def test_remote_replica_scrape_rides_router_metrics(dataset):
    from repro.fleet import RemoteReplica

    with StatsServer(StatsService(dataset)) as upstream:
        remote = RemoteReplica("up", upstream.url)
        try:
            fetch_json(upstream.url + "/estimate?mode=paper")
            text = remote.scrape_metrics()
            assert text and "ndv_http_requests_total" in text
            labeled = add_label_to_exposition(text, {"replica": remote.name})
            assert re.search(
                r'ndv_http_requests_total\{[^}]*replica="up"', labeled
            )
        finally:
            remote.stop()


def test_etag_neutral_to_telemetry_state(dataset):
    with StatsServer(StatsService(dataset)) as srv:
        _, etag_on, body_on = fetch_json(srv.url + "/estimate?mode=improved")
    obs.set_enabled(False)
    try:
        with StatsServer(StatsService(dataset)) as srv:
            _, etag_off, body_off = fetch_json(
                srv.url + "/estimate?mode=improved"
            )
    finally:
        obs.set_enabled(True)
    assert etag_off == etag_on
    assert json.dumps(body_off, sort_keys=True) == json.dumps(
        body_on, sort_keys=True
    )


# -- span series and the profiler bridge ---------------------------------------


def _span_stats(name):
    """(count, seconds, self seconds) recorded so far for span `name`."""
    key = (("span", name),)
    reg = obs.registry()
    hist = dict(reg.histogram("ndv_span_seconds").snapshot()).get(key)
    own = dict(reg.counter("ndv_span_self_seconds_total").snapshot()).get(key)
    return (
        hist.count if hist is not None else 0,
        hist.sum if hist is not None else 0.0,
        own.value if own is not None else 0.0,
    )


def test_span_self_time_excludes_children():
    before_p, before_c = _span_stats("t.parent"), _span_stats("t.child")
    with obs.root_span("t.root"):
        with obs.span("t.parent"):
            time.sleep(0.02)
            with obs.span("t.child"):
                time.sleep(0.05)
    p, c = _span_stats("t.parent"), _span_stats("t.child")
    p_n, p_s, p_own = (a - b for a, b in zip(p, before_p))
    c_n, c_s, c_own = (a - b for a, b in zip(c, before_c))
    assert (p_n, c_n) == (1, 1)  # the histogram counts both
    assert c_s >= 0.05 and c_own == pytest.approx(c_s)  # a leaf owns it all
    assert p_s >= 0.07
    assert p_own == pytest.approx(p_s - c_s, abs=1e-9)
    assert 0.02 <= p_own < 0.05  # the child's sleep is not the parent's


def test_rootless_span_is_timed_but_not_traced():
    col = obs.collector()
    before = _span_stats("t.rootless")
    with obs.span("t.rootless") as sp:
        assert sp.trace_id is None and sp.traceparent is None
        assert obs.current_span() is None  # no trace to propagate
        assert obs.current_traceparent() is None
        with obs.span("t.rootless_kid") as kid:
            assert kid.trace_id is None
    n, secs, own = (a - b for a, b in zip(_span_stats("t.rootless"), before))
    assert n == 1 and secs > 0 and 0 < own <= secs
    assert col.traces() == []  # the ring only holds spans of a trace


class _SpyBridge:
    """Profiler bridge stand-in: counts enters, never collects."""

    entered = 0
    on = True

    @classmethod
    def is_enabled(cls):
        return cls.on

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def spy_bridge():
    from repro.obs import trace

    saved = trace._bridge
    _SpyBridge.entered, _SpyBridge.on = 0, True
    trace.set_profiler_bridge(_SpyBridge)
    yield _SpyBridge
    trace.set_profiler_bridge(saved)


def test_disabled_spans_record_nothing_and_skip_the_bridge(spy_bridge):
    before = _span_stats("t.off")
    obs.set_enabled(False)
    with obs.root_span("t.off"):
        with obs.span("t.off"):
            pass
    obs.set_enabled(True)
    assert _span_stats("t.off") == before
    assert spy_bridge.entered == 0
    with obs.span("t.off"):
        pass
    assert spy_bridge.entered == 1
    spy_bridge.on = False  # no profiler session: the bridge stays shut
    with obs.span("t.off"):
        pass
    assert spy_bridge.entered == 1


def test_profiler_bridge_puts_spans_on_the_host_plane(tmp_path):
    import glob

    import jax

    import repro.engine  # noqa: F401  (installs the bridge)
    from repro.obs import trace

    assert trace._bridge is jax.profiler.TraceAnnotation

    class Counting(jax.profiler.TraceAnnotation):
        made = 0

        def __init__(self, name, **kw):
            type(self).made += 1
            super().__init__(name, **kw)

    trace.set_profiler_bridge(Counting)
    try:
        with obs.span("t.unprofiled"):
            pass
        assert Counting.made == 0  # no session: is_enabled() is False
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("t.bridged"):
                time.sleep(0.001)
        assert Counting.made == 1
    finally:
        trace.set_profiler_bridge(jax.profiler.TraceAnnotation)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {
        e.name
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    }
    assert "t.bridged" in host and "t.unprofiled" not in host


def test_bridge_leaves_lowered_programs_unchanged(spy_bridge):
    import jax.numpy as jnp

    from repro.catalog import StatsCatalog
    from repro.catalog.packer import BatchPacker
    from repro.catalog.source import InMemoryMetadataSource
    from repro.columnar.lakehouse import LakehouseShape, synthesize_lakehouse
    from repro.core.ndv.estimator import estimate_batch
    from repro.obs import trace
    from repro.planner.cost import _scan_fold

    footers, _, _ = synthesize_lakehouse(0, LakehouseShape(
        tables=1, columns_per_table=4, files=2, row_groups_per_file=4,
    ))
    cat = StatsCatalog(InMemoryMetadataSource(footers))
    batch = BatchPacker().pack(list(cat.merged_metadata().values()))
    ones = jnp.ones((8, 3), jnp.float32)

    def lowered():
        with obs.span("engine.dispatch"), obs.span("planner.fold"):
            return (
                estimate_batch.lower(batch, None, mode="paper").as_text(),
                _scan_fold(3, 8).lower(ones, ones).as_text(),
            )

    spy_bridge.entered = 0
    with_bridge = lowered()
    assert spy_bridge.entered == 2
    trace.set_profiler_bridge(None)
    assert lowered() == with_bridge


def test_engine_device_wait_once_per_dispatch():
    from repro.catalog import StatsCatalog
    from repro.catalog.source import InMemoryMetadataSource
    from repro.columnar.lakehouse import LakehouseShape, synthesize_lakehouse

    def dispatches():
        return sum(
            cell.value for _, cell in
            obs.registry().counter("ndv_engine_dispatches_total").snapshot()
        )

    footers, _, _ = synthesize_lakehouse(1, LakehouseShape(
        tables=1, columns_per_table=4, files=2, row_groups_per_file=4,
    ))
    cat = StatsCatalog(InMemoryMetadataSource(footers))
    waits, calls = _span_stats("engine.device_wait")[0], dispatches()
    cat.estimate(mode="paper")
    cat.estimate(mode="improved")
    cat.estimate(mode="paper")  # cached: no dispatch, no wait
    assert dispatches() - calls == 2
    assert _span_stats("engine.device_wait")[0] - waits == 2


def test_span_series_exported_and_tablestats_cost_neutral(dataset):
    graph = {
        "tables": [{"name": "a"}, {"name": "b"}],
        "edges": [{"left": "a", "left_column": "tok",
                   "right": "b", "right_column": "tok"}],
    }

    def serve():
        with StatsServer(StatsService(dataset)) as srv:
            got = [
                fetch_json(srv.url + "/tablestats?mode=paper"),
                fetch(srv.url + "/cost", payload={"graph": graph},
                      binary=False),
            ]
            with urllib.request.urlopen(srv.url + "/metrics") as r:
                text = r.read().decode()
        return [(etag, json.dumps(body, sort_keys=True))
                for _, etag, body in got], text

    on, text = serve()
    for name in obs.SPAN_NAMES:  # every span's series, run or not
        assert f'ndv_span_self_seconds_total{{span="{name}"}}' in text
    for name in ("service.request", "service.compute", "planner.pick",
                 "planner.compute_cost", "catalog.pack",
                 "engine.device_wait"):
        assert re.search(
            rf'ndv_span_seconds_count\{{span="{re.escape(name)}"\}} [1-9]',
            text,
        ), name
    assert re.search(r'ndv_pack_cells_total\{cell="padded"\} [1-9]', text)
    obs.set_enabled(False)
    try:
        off, _ = serve()
    finally:
        obs.set_enabled(True)
    assert off == on


def test_span_names_are_the_documented_fixed_set():
    import pathlib

    from repro.obs.trace import SPAN_NAMES

    src = pathlib.Path(obs.__file__).resolve().parent.parent
    opened = set()
    for path in src.rglob("*.py"):
        if path.parent.name != "obs":
            opened.update(re.findall(
                r'(?:(?<!root_)span\(|timed_acquire\([^,]+,)\s*"([a-z0-9_.]+)"',
                path.read_text()))
    assert opened == set(SPAN_NAMES)
    doc = (src.parent.parent / "docs" / "METRICS.md").read_text()
    assert [n for n in SPAN_NAMES if f"`{n}`" not in doc] == []


def test_span_series_exact_under_thread_stress():
    import sys

    before = _span_stats("t.stress")
    n_threads, per_thread = 16, 1500
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with obs.span("t.stress"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    n, secs, own = (a - b for a, b in zip(_span_stats("t.stress"), before))
    assert n == n_threads * per_thread  # no queued span lost in a fold
    assert own == pytest.approx(secs)


def test_timed_span_keeps_ring_retention_unchanged():
    from repro.service.service import SingleFlight

    col = obs.collector()
    before = _span_stats("t.timed")
    with obs.root_span("t.warm"):
        with obs.timed_span("t.timed") as sp:
            assert sp.trace_id is None
    assert _span_stats("t.timed")[0] - before[0] == 1  # timed all the same
    assert _span_stats("t.warm") == (0, 0.0, 0.0)  # roots feed no series
    flight = SingleFlight()
    call, leader = flight.claim(("k",))
    flight.finish(("k",), call, result=7)
    with obs.root_span("t.follower"):
        assert SingleFlight.wait(call) == 7
    assert col.traces() == []  # both roots stayed childless
    with obs.root_span("t.cold") as root:
        with obs.timed_span("t.timed"):
            assert obs.current_span() is root
            assert obs.current_traceparent() == root.traceparent
            with obs.span("t.inner") as inner:
                assert inner.parent_id == root.span_id
    (trace,) = col.traces()
    assert [s.name for s in trace] == ["t.inner", "t.cold"]


def test_warm_revalidation_trace_not_retained(dataset):
    def revalidated():
        return obs.registry().counter("ndv_http_requests_total").value(
            tier="service", route="estimate", status="304")

    with StatsServer(StatsService(dataset)) as srv:
        url = srv.url + "/estimate?mode=improved"
        status, etag, _ = fetch_json(url)
        assert status == 200
        tree = _poll_trace(srv.url + "/debug/traces?limit=5",
                           "service.estimate")
        # the body build hangs off the HTTP root; service.request is absent
        assert [c["name"] for c in tree["children"]] == ["service.compute"]
        obs.collector().clear()
        before, requests = revalidated(), _span_stats("service.request")[0]
        assert fetch_json(url, etag=etag)[0] == 304
        deadline = time.monotonic() + 5
        while revalidated() == before and time.monotonic() < deadline:
            time.sleep(0.01)  # counted once the root span has exited
        assert revalidated() == before + 1
        assert _span_stats("service.request")[0] == requests + 1
        assert obs.collector().traces() == []
