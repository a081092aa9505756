"""Stats service: async ingestion, ETag coherence, single-flight, HTTP e2e.

Covers the serving-correctness acceptance criteria:
  * /estimate responses are bit-identical to `StatsCatalog.estimate()` for
    the same engine config (reconstructed through `estimate_from_json`)
  * If-None-Match hits are answered with 304 and perform zero packs and
    zero engine executions (asserted by counters)
  * rewriting one file rotates the ETag; the old tag stops validating
  * N concurrent identical cold requests coalesce onto one engine pack
  * `AsyncIngestor` overlaps footer reads and keeps the last-good merged
    state serving while a refresh is blocked mid-flight
"""
import os
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.catalog import StatsCatalog, estimate_from_json
from repro.catalog.source import InMemoryMetadataSource
from repro.columnar.writer import WriterOptions, write_file
from repro.service import (
    AsyncIngestor,
    SingleFlight,
    StatsServer,
    StatsService,
    etag_matches,
    fetch_json,
    parse_bounds,
)


def _shard(seed, rows=256, vocab=64):
    rng = np.random.default_rng(seed)
    return {
        "tok": rng.integers(0, vocab, rows).astype(np.int64),
        "val": np.round(rng.uniform(0, 100, rows), 1),
    }


def _write(root, name, seed):
    return write_file(
        os.path.join(root, name), _shard(seed),
        options=WriterOptions(row_group_size=128),
    )


def _footer(seed, cols=None):
    return write_file(
        tempfile.mkdtemp(), cols if cols is not None else _shard(seed),
        options=WriterOptions(row_group_size=128),
    )


@pytest.fixture()
def dataset(tmp_path):
    root = str(tmp_path / "ds")
    for i in range(3):
        _write(root, f"shard_{i:03d}", seed=i)
    return root


@pytest.fixture()
def served(dataset):
    server = StatsServer(StatsService(dataset)).start()
    yield server
    server.stop()


# -- HTTP end-to-end ---------------------------------------------------------


def test_estimate_bit_identical_to_catalog(served, dataset):
    for mode in ("paper", "improved"):
        status, etag, body = fetch_json(served.url + f"/estimate?mode={mode}")
        assert status == 200 and etag and body["etag"] == etag
        got = {n: estimate_from_json(d) for n, d in body["estimates"].items()}
        ref = StatsCatalog(dataset).estimate(mode=mode)
        assert got == ref  # dataclass equality: every field, bit-exact


def test_tablestats_lock_wait_is_recorded(served):
    from repro.obs import registry

    def lock_wait_s():
        return registry().counter("ndv_span_self_seconds_total").value(
            span="service.lock_wait")

    url = served.url + "/tablestats?mode=paper"
    assert fetch_json(url)[0] == 200  # warm: the next call only waits
    held = threading.Event()

    def hold():
        # Long enough that the request below, sent once the lock is held,
        # still waits at least 50 ms for it.
        with served.service.lock:
            held.set()
            time.sleep(0.25)

    before = lock_wait_s()
    holder = threading.Thread(target=hold)
    holder.start()
    held.wait(5)
    assert fetch_json(url)[0] == 200
    holder.join()
    assert lock_wait_s() - before >= 0.05


def test_revalidation_304_zero_packs_zero_engine_runs(served):
    url = served.url + "/estimate"
    svc = served.service
    status, etag, _ = fetch_json(url)
    assert status == 200
    packs = svc.catalog.stats.packs
    runs = svc.stats.engine_runs
    misses = svc.catalog.stats.estimate_cache_misses
    for _ in range(3):
        status2, etag2, body = fetch_json(url, etag=etag)
        assert status2 == 304 and etag2 == etag and body is None
    assert svc.catalog.stats.packs == packs
    assert svc.stats.engine_runs == runs
    assert svc.catalog.stats.estimate_cache_misses == misses
    assert svc.stats.responses_304 == 3


def test_etag_rotates_on_rewrite_and_old_tag_stops_validating(served, dataset):
    url = served.url + "/estimate?mode=improved"
    _, etag1, body1 = fetch_json(url)
    assert fetch_json(url, etag=etag1)[0] == 304

    _write(dataset, "shard_001", seed=77)  # rewrite one existing file
    status, refreshed = fetch_json(served.url + "/refresh", method="POST")[0:3:2]
    assert status == 200
    assert refreshed["updated"] == 1 and refreshed["changed"]

    status, etag2, body2 = fetch_json(url, etag=etag1)  # old tag must NOT validate
    assert status == 200 and etag2 != etag1
    assert body2["estimates"] != body1["estimates"]
    assert body2["generation"] > body1["generation"]
    assert fetch_json(url, etag=etag2)[0] == 304
    # the commit compacted entries of the dead fingerprint set
    assert len(served.service.catalog._estimate_cache) <= 1


def test_etag_covers_mode_and_bounds_and_endpoint(served):
    tags = {
        fetch_json(served.url + path)[1]
        for path in (
            "/estimate?mode=paper",
            "/estimate?mode=improved",
            "/estimate?mode=paper&bounds=tok:10",
            "/plan?mode=paper",
            "/columns",
        )
    }
    assert len(tags) == 5  # every request identity gets its own tag


def test_schema_bounds_and_plan_match_library(served, dataset):
    _, _, body = fetch_json(served.url + "/estimate?bounds=tok:10")
    ref = StatsCatalog(dataset).estimate(schema_bounds={"tok": 10.0})
    got = {n: estimate_from_json(d) for n, d in body["estimates"].items()}
    assert got == ref and got["tok"].ndv <= 10.0

    _, _, plans = fetch_json(served.url + "/plan?mode=improved")
    import dataclasses

    ref_plans = StatsCatalog(dataset).plan(mode="improved")
    assert plans["plans"] == {
        n: dataclasses.asdict(p) for n, p in ref_plans.items()
    }


def test_columns_health_and_errors(served):
    status, etag, body = fetch_json(served.url + "/columns")
    assert status == 200 and set(body["columns"]) == {"tok", "val"}
    assert body["files"] == 3
    assert fetch_json(served.url + "/columns", etag=etag)[0] == 304

    status, _, health = fetch_json(served.url + "/health")
    assert status == 200 and health["status"] == "serving"
    assert health["files"] == 3 and health["generation"] == 1

    assert fetch_json(served.url + "/estimate?mode=bogus")[0] == 400
    assert fetch_json(served.url + "/nope")[0] == 404
    assert fetch_json(served.url + "/estimate?bounds=junk")[0] == 400


def test_concurrent_identical_cold_requests_one_engine_pack(served, dataset):
    svc = served.service
    url = served.url + "/estimate"
    fetch_json(url)  # settle jit/tracing so the patched sleep dominates

    _write(dataset, "shard_new", seed=50)  # rotate state -> next req is cold
    svc.refresh()
    orig = svc.catalog.estimate

    def slow_estimate(**kw):
        time.sleep(0.5)
        return orig(**kw)

    svc.catalog.estimate = slow_estimate
    try:
        packs = svc.catalog.stats.packs
        runs = svc.stats.engine_runs
        n = 8
        barrier = threading.Barrier(n)
        results = []

        def client():
            barrier.wait()
            results.append(fetch_json(url)[0])

        threads = [threading.Thread(target=client) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        svc.catalog.estimate = orig
    assert results == [200] * n
    assert svc.catalog.stats.packs - packs == 1       # ONE pack
    assert svc.stats.engine_runs - runs == 1          # ONE engine execution
    assert svc.stats.coalesced_waits >= 1             # real coalescing seen
    assert svc.stats.single_flight_leaders >= 1


# -- single-flight unit ------------------------------------------------------


def test_single_flight_coalesces_and_propagates_errors():
    flight = SingleFlight()
    entered = threading.Event()
    release = threading.Event()
    calls = []

    def fn():
        calls.append(1)
        entered.set()
        release.wait(5)
        return "value"

    out = []
    threads = [
        threading.Thread(target=lambda: out.append(flight.do(("k",), fn)))
        for _ in range(5)
    ]
    threads[0].start()
    assert entered.wait(5)
    for t in threads[1:]:
        t.start()
    time.sleep(0.05)  # let followers reach the wait
    release.set()
    for t in threads:
        t.join()
    assert len(calls) == 1
    assert [r for r, _ in out] == ["value"] * 5
    assert sorted(leader for _, leader in out) == [False] * 4 + [True]

    def boom():
        raise RuntimeError("nope")

    with pytest.raises(RuntimeError, match="nope"):
        flight.do(("k2",), boom)


def test_etag_matches_and_parse_bounds():
    assert etag_matches('"abc"', '"abc"')
    assert etag_matches('W/"abc"', '"abc"')
    assert etag_matches('"x", "abc"', '"abc"')
    assert etag_matches("*", '"anything"')
    assert not etag_matches('"x"', '"abc"')
    assert parse_bounds("tok:10,val:2.5") == {"tok": 10.0, "val": 2.5}
    with pytest.raises(ValueError):
        parse_bounds("junk")


# -- async ingestor ----------------------------------------------------------


class SlowSource(InMemoryMetadataSource):
    """InMemory source with configurable footer-read latency and a gate."""

    def __init__(self, footers, read_delay=0.0):
        super().__init__(footers)
        self.read_delay = read_delay
        self.gate = None  # when set, read_footer blocks until released

    def read_footer(self, file_id):
        if self.gate is not None:
            assert self.gate.wait(10)
        if self.read_delay:
            time.sleep(self.read_delay)
        return super().read_footer(file_id)


def test_ingestor_overlaps_footer_reads():
    n, delay = 6, 0.15
    src = SlowSource(
        {f"f{i}": _footer(seed=i) for i in range(n)}, read_delay=delay
    )
    ingestor = AsyncIngestor(StatsCatalog(src), max_workers=n)
    t0 = time.perf_counter()
    summary = ingestor.refresh()
    wall = time.perf_counter() - t0
    assert summary.added == n
    assert ingestor.stats.footers_read == n
    # serial would be >= n * delay; overlapped must beat half of that
    assert wall < 0.5 * n * delay, f"reads did not overlap: {wall:.2f}s"


def test_last_good_state_serves_during_inflight_refresh():
    src = SlowSource({"a": _footer(1), "b": _footer(2)})
    svc = StatsService(src)
    svc.start()
    r1 = svc.estimate(mode="paper")
    assert r1.status == 200 and svc.ingestor.generation == 1

    src.add("c", _footer(3))
    src.gate = threading.Event()  # block the refresh mid-footer-read
    t = threading.Thread(target=svc.refresh)
    t.start()
    time.sleep(0.1)  # refresh is now parked inside read_footer
    r2 = svc.estimate(mode="paper")  # must not block, must serve old state
    assert r2.status == 200 and r2.etag == r1.etag
    assert r2.body["estimates"] == r1.body["estimates"]
    assert svc.estimate(mode="paper", if_none_match=r1.etag).status == 304
    src.gate.set()
    t.join(10)
    assert svc.ingestor.generation == 2
    r3 = svc.estimate(mode="paper")
    assert r3.etag != r1.etag and r3.body["generation"] == 2


def test_refresh_error_keeps_last_good_and_records_it():
    src = SlowSource({"a": _footer(1), "b": _footer(2)})
    svc = StatsService(src)
    svc.start()
    before = svc.estimate(mode="paper")
    src.add("bad", _footer(9, cols={"other": np.arange(64)}))
    with pytest.raises(ValueError, match="schema"):
        svc.refresh()
    assert svc.ingestor.stats.errors == 1
    assert "schema" in svc.ingestor.stats.last_error
    assert svc.ingestor.generation == 1  # no commit
    after = svc.estimate(mode="paper", if_none_match=before.etag)
    assert after.status == 304  # last-good still validates


def test_ingestor_add_remove_rewrite_in_one_refresh():
    src = InMemoryMetadataSource(
        {"a": _footer(1), "b": _footer(2), "c": _footer(3)}
    )
    catalog = StatsCatalog(src)
    ingestor = AsyncIngestor(catalog)
    assert ingestor.refresh().added == 3
    src.add("d", _footer(4))       # add
    src.remove("b")                # remove
    src.add("c", _footer(33))      # rewrite
    summary = ingestor.refresh()
    assert summary == (1, 1, 1, 3)  # added, updated, removed, total
    assert set(catalog.files) == {"a", "c", "d"}
    # the committed view matches a cold catalog over the same source
    assert catalog.estimate() == StatsCatalog(src).estimate()


def test_server_stop_after_failed_start_does_not_hang(tmp_path):
    root = str(tmp_path / "bad")
    _write(root, "a", seed=1)
    write_file(  # schema-mismatched file: the initial refresh must raise
        os.path.join(root, "b"), {"other": np.arange(64)},
        options=WriterOptions(row_group_size=32),
    )
    server = StatsServer(StatsService(root))
    with pytest.raises(ValueError, match="schema"):
        server.start()
    server.stop()  # accept loop never ran; must return, not block


def test_save_cache_on_commit_keeps_spill_warm(dataset):
    svc = StatsService(dataset, save_cache_on_commit=True)
    with svc:
        r = svc.estimate(mode="improved")
        _write(dataset, "shard_new", seed=9)
        svc.refresh()   # commit rewrites the spill (compacted, now empty)
        r2 = svc.estimate(mode="improved")  # cold compute re-spills
        assert r2.etag != r.etag
    warm = StatsCatalog(dataset, auto_load_cache=True)
    got = warm.estimate(mode="improved")
    assert warm.stats.packs == 0            # restart serves the spill
    assert got == {
        n: estimate_from_json(d) for n, d in r2.body["estimates"].items()
    }


def test_polling_loop_picks_up_changes_and_stops():
    src = InMemoryMetadataSource({"a": _footer(1)})
    svc = StatsService(src, poll_interval=0.05)
    svc.start()
    try:
        assert svc.ingestor.running
        src.add("b", _footer(2))
        deadline = time.time() + 10
        while svc.ingestor.generation < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert svc.ingestor.generation == 2
    finally:
        svc.stop()
    assert not svc.ingestor.running


# -- estimation-quality observability: explain + audit (ISSUE 9) --------------


def test_explain_attaches_provenance_without_perturbing_identity(served):
    """?explain=1: same ETag, body copy + provenance — never a new identity."""
    status, etag, plain = fetch_json(served.url + "/estimate?mode=improved")
    assert status == 200
    status, etag_e, explained = fetch_json(
        served.url + "/estimate?mode=improved&explain=1"
    )
    assert status == 200
    assert etag_e == etag, "explain must not rotate the ETag"
    assert explained["provenance"].keys() == plain["estimates"].keys()
    stripped = {k: v for k, v in explained.items() if k != "provenance"}
    assert stripped == plain, "explained body minus provenance != plain body"
    for prov in explained["provenance"].values():
        assert prov["route"] in ("dict", "minmax")
        assert isinstance(prov["dict_iterations"], int)
        assert isinstance(prov["clamps"], list)
    # the old ETag still revalidates the explained URL (same identity)
    status, _, _ = fetch_json(
        served.url + "/estimate?mode=improved&explain=1", etag=etag
    )
    assert status == 304


def test_explain_does_not_mutate_cached_plain_body(served):
    status, _, _ = fetch_json(served.url + "/estimate?mode=paper&explain=1")
    assert status == 200
    status, _, plain = fetch_json(served.url + "/estimate?mode=paper")
    assert status == 200
    assert "provenance" not in plain, (
        "explain leaked into the cached response body"
    )


def test_explain_junk_value_is_400(served):
    status, _, body = fetch_json(served.url + "/estimate?explain=banana")
    assert status == 400 and "error" in body
    # explicit falsy forms are accepted and behave like absence
    for off in ("0", "false", "no", ""):
        status, _, body = fetch_json(served.url + f"/estimate?explain={off}")
        assert status == 200 and "provenance" not in body


def test_explain_wire_frame_value_section_is_explain_blind(served):
    """Provenance rides section 4; the value section stays byte-stable."""
    from repro.wire import ConnectionPool, decode_explain, decode_frame, fetch

    pool = ConnectionPool()
    try:
        url = served.url + "/estimate?mode=improved"
        wire_headers = {"Accept": "application/x-ndv-wire"}
        _, _, raw_plain = pool.request(url, headers=wire_headers)
        _, _, raw_expl = pool.request(url + "&explain=1", headers=wire_headers)
        assert decode_frame(raw_expl) == decode_frame(raw_plain)
        assert decode_explain(raw_plain) is None
        status, _, body_json = fetch_json(url + "&explain=1")
        assert decode_explain(raw_expl) == body_json["provenance"]
        # the wire client re-attaches: wire and JSON bodies identical
        status, _, body_wire = fetch(url + "&explain=1", pool=pool, binary=True)
        assert status == 200 and body_wire == body_json
    finally:
        pool.close()


def test_audit_loop_records_qerror_and_rides_explain(dataset):
    from repro.obs import registry

    svc = StatsService(dataset, audit=True, audit_columns=8)
    svc.refresh()
    results = svc.run_audit()
    assert results, "audit produced no samples on a readable dataset"
    audited = {r.column for r in results}
    assert audited == {"tok", "val"}
    for r in results:
        assert r.qerror >= 1.0
        assert r.reference > 0
        assert r.route in ("dict", "minmax")
    resp = svc.estimate(mode="paper", explain=True)
    provs = resp.body["provenance"]
    assert any("audit" in p for p in provs.values())
    for name, p in provs.items():
        if "audit" in p:
            assert p["audit"]["qerror"] >= 1.0
    text = registry().exposition()
    assert "ndv_audit_qerror" in text and 'route="' in text


def test_explained_payload_not_stale_after_audit(dataset):
    """The memoized explained payload must refresh when the audit does."""
    with StatsServer(StatsService(dataset, audit=True)) as server:
        url = server.url + "/estimate?mode=improved&explain=1"
        status, _, before = fetch_json(url)
        assert status == 200
        assert not any("audit" in p for p in before["provenance"].values())
        server.service.run_audit()
        status, _, after = fetch_json(url)
        assert status == 200
        assert any("audit" in p for p in after["provenance"].values()), (
            "explained payload served stale (pre-audit) bytes"
        )


def test_debug_explain_serves_provenance_cache(served):
    fetch_json(served.url + "/estimate?mode=paper")
    fetch_json(served.url + "/estimate?mode=improved&explain=1")
    status, etag, body = fetch_json(served.url + "/debug/explain")
    assert status == 200 and etag is None
    modes = {e["mode"] for e in body["entries"]}
    assert "improved" in modes
    for entry in body["entries"]:
        for name, prov in entry["columns"].items():
            assert prov["route"] in ("dict", "minmax")


def test_debug_query_params_hardened(served):
    """Malformed /debug/* query values answer 400, never an unhandled 500."""
    for q in ("limit=-1", "limit=abc", "limit=", "limit=1.5"):
        status, _, body = fetch_json(served.url + f"/debug/traces?{q}")
        assert status == 400 and "error" in body, q
    status, _, _ = fetch_json(served.url + "/debug/traces?limit=0")
    assert status == 200
