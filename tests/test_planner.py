"""Planner math: graph validation, enumeration, batched-vs-reference parity.

The load-bearing claims pinned here:
  * graph validation rejects exactly what must 400 at the HTTP layer —
    disconnected graphs, self-joins, unknown tables, junk fields — and
    `identity()` is insensitive to table/edge listing order
  * enumeration is deterministic: exhaustive (lexicographic) when the
    plan space fits `max_plans`, seed-pinned sampling with the identity
    permutation first when it does not
  * the batched JAX scorer matches the pure-Python float32 reference fold
    BIT-FOR-BIT over randomized connected graphs — the parity contract
    that makes `/cost` bodies byte-identical across replicas
  * the cost model degrades conservatively: NDV <= 0 clamps to 1 (edge
    becomes a pass-through), a join step with no connecting edge is a
    cross product, ties break on the lexicographically smallest plan
  * each plan space is built once and shared read-only, bit-identical to
    the loop it replaced, and the vectorized pick chooses exactly what
    the tuple-keyed `min` did, so cached bodies are byte-identical
"""
import math

import numpy as np
import pytest

from repro.planner import (
    ColumnStats,
    DEFAULT_MAX_PLANS,
    TableStats,
    compute_cost,
    enumerate_plans,
    make_graph,
    parse_join_graph,
    parse_max_plans,
    plan_space_size,
    reference_cost,
    score_plans,
)
from repro.planner.api import sequential_reference


def _graph(n_tables, edges, **table_kwargs):
    payload = {
        "tables": [{"name": f"t{i}", **table_kwargs} for i in range(n_tables)],
        "edges": [
            {"left": f"t{a}", "left_column": "k", "right": f"t{b}",
             "right_column": "k"}
            for a, b in edges
        ],
    }
    return parse_join_graph(payload)


def _stats(graph, rows_by_table, ndv_by_table):
    return {
        t.name: TableStats(
            rows=float(rows_by_table[t.name]),
            columns={
                col: ColumnStats(ndv=float(ndv_by_table[t.name]), non_null=1)
                for col in graph.columns_by_table()[t.name]
            } or {"k": ColumnStats(ndv=float(ndv_by_table[t.name]),
                                   non_null=1)},
        )
        for t in graph.tables
    }


# -- graph validation ---------------------------------------------------------


def test_single_table_graph_costs_zero():
    g = parse_join_graph({"tables": [{"name": "solo"}], "edges": []})
    body = compute_cost(
        g, {"solo": TableStats(rows=1000.0, columns={})},
        mode="paper", max_plans=DEFAULT_MAX_PLANS,
    )
    assert body["best_order"] == ["solo"]
    assert body["joins"] == []
    assert body["total_cost"] == 0.0
    assert body["plans_scored"] == 1 and body["plan_space"] == 1
    assert body["enumeration"] == "exhaustive"


def test_disconnected_graph_rejected():
    with pytest.raises(ValueError, match="disconnected"):
        _graph(3, [(0, 1)])  # t2 shares no edge with {t0, t1}
    with pytest.raises(ValueError, match="disconnected"):
        _graph(2, [])


def test_graph_junk_rejected():
    base = {"tables": [{"name": "a"}], "edges": []}
    with pytest.raises(ValueError, match="unknown"):
        parse_join_graph({**base, "surprise": 1})
    with pytest.raises(ValueError, match="unknown"):
        parse_join_graph(
            {"tables": [{"name": "a", "rows": 5}], "edges": []}
        )
    with pytest.raises(ValueError):
        parse_join_graph({"tables": [], "edges": []})
    with pytest.raises(ValueError):  # duplicate alias
        parse_join_graph(
            {"tables": [{"name": "a"}, {"name": "a"}], "edges": []}
        )
    with pytest.raises(ValueError):  # self-join
        parse_join_graph({
            "tables": [{"name": "a"}],
            "edges": [{"left": "a", "left_column": "x",
                       "right": "a", "right_column": "y"}],
        })
    with pytest.raises(ValueError):  # filter selectivity out of range
        parse_join_graph(
            {"tables": [{"name": "a", "filter_selectivity": 0.0}],
             "edges": []}
        )
    with pytest.raises(ValueError):  # namespace without dataset
        parse_join_graph(
            {"tables": [{"name": "a", "namespace": "wh"}], "edges": []}
        )


def test_identity_is_listing_order_insensitive():
    a = parse_join_graph({
        "tables": [{"name": "x"}, {"name": "y"}],
        "edges": [{"left": "x", "left_column": "k",
                   "right": "y", "right_column": "j"}],
    })
    b = parse_join_graph({
        "tables": [{"name": "y"}, {"name": "x"}],
        # the same edge, written from the other side
        "edges": [{"left": "y", "left_column": "j",
                   "right": "x", "right_column": "k"}],
    })
    assert a.identity() == b.identity()


def test_parse_max_plans():
    assert parse_max_plans(None) == DEFAULT_MAX_PLANS
    assert parse_max_plans(10) == 10
    assert parse_max_plans(10**9) == 65536  # ceiling
    for junk in (0, -1, 1.5, "many"):
        with pytest.raises(ValueError):
            parse_max_plans(junk)


# -- enumeration --------------------------------------------------------------


def test_enumeration_exhaustive_and_lexicographic():
    plans = enumerate_plans(4, DEFAULT_MAX_PLANS)
    assert plans.shape == (24, 4)
    assert [int(x) for x in plans[0]] == [0, 1, 2, 3]
    assert len({tuple(int(x) for x in p) for p in plans}) == 24
    # lexicographic order — itertools.permutations contract
    as_tuples = [tuple(int(x) for x in p) for p in plans]
    assert as_tuples == sorted(as_tuples)


def test_enumeration_sampled_deterministic():
    assert plan_space_size(7) == math.factorial(7) == 5040
    a = enumerate_plans(7, 1000)
    b = enumerate_plans(7, 1000)
    assert a.shape == (1000, 7)
    assert np.array_equal(a, b)  # seed-pinned
    assert [int(x) for x in a[0]] == list(range(7))  # identity first
    assert len({tuple(int(x) for x in p) for p in a}) == 1000  # deduped


# -- cost model edge cases ----------------------------------------------------


def test_zero_ndv_clamps_to_passthrough():
    g = _graph(2, [(0, 1)])
    stats = _stats(g, {"t0": 100, "t1": 200}, {"t0": 0.0, "t1": -3.0})
    body = compute_cost(g, stats, mode="paper", max_plans=16)
    join = body["joins"][0]
    edge = join["edges"][0]
    assert edge["ndv_left"] == 1.0 and edge["ndv_right"] == 1.0
    assert edge["selectivity"] == 1.0
    assert join["cardinality"] == 100.0 * 200.0  # |R||S| / max(1,1)


def test_cross_product_step_flagged_and_unfiltered():
    # Chain t0 - t1 - t2: the plan (t0, t2, t1) joins t2 with no edge to
    # the {t0} prefix — a cross product, multiplier exactly 1.
    g = _graph(3, [(0, 1), (1, 2)])
    rows = np.array([10.0, 20.0, 30.0], dtype=np.float32)
    factors = [(0, 1, 0.5), (1, 2, 0.25)]
    plan = [0, 2, 1]
    cost, cards = reference_cost(plan, rows, factors)
    assert cards[0] == np.float32(10.0 * 30.0)  # no selectivity applied
    # step 2 brings t1, connected to both t0 and t2: both edges fire
    assert cards[1] == np.float32(
        np.float32(np.float32(cards[0] * np.float32(20.0)) *
                   np.float32(np.float32(0.5) * np.float32(0.25)))
    )
    # the served body flags the cross-product step
    stats = _stats(g, {"t0": 10, "t1": 20, "t2": 30},
                   {"t0": 2, "t1": 2, "t2": 4})
    body = compute_cost(g, stats, mode="paper", max_plans=16)
    flagged = {j["table"]: j["cross_product"] for j in body["joins"]}
    assert flagged and not all(flagged.values())  # best order avoids them
    assert all(j["edges"] == [] for j in body["joins"]
               if j["cross_product"])


def test_tie_break_is_lexicographic_smallest_plan():
    # Perfectly symmetric 3-clique: every order costs the same, so the
    # winner must be the identity permutation — deterministically.
    g = _graph(3, [(0, 1), (0, 2), (1, 2)])
    stats = _stats(g, {t.name: 100 for t in g.tables},
                   {t.name: 10 for t in g.tables})
    for _ in range(3):
        body = compute_cost(g, stats, mode="paper", max_plans=16)
        assert body["best_order"] == ["t0", "t1", "t2"]


def test_best_order_prefers_selective_join_first():
    # t0 join t1 (on a, NDV 1000) is highly selective; t0 join t2 (on b,
    # NDV 2) barely filters. C_out must schedule the selective join first.
    g = parse_join_graph({
        "tables": [{"name": "t0"}, {"name": "t1"}, {"name": "t2"}],
        "edges": [
            {"left": "t0", "left_column": "a",
             "right": "t1", "right_column": "k"},
            {"left": "t0", "left_column": "b",
             "right": "t2", "right_column": "k"},
        ],
    })
    stats = {
        "t0": TableStats(rows=1000.0, columns={
            "a": ColumnStats(ndv=1000.0, non_null=1),
            "b": ColumnStats(ndv=2.0, non_null=1)}),
        "t1": TableStats(rows=1000.0, columns={
            "k": ColumnStats(ndv=1000.0, non_null=1)}),
        "t2": TableStats(rows=1000.0, columns={
            "k": ColumnStats(ndv=2.0, non_null=1)}),
    }
    body = compute_cost(g, stats, mode="paper", max_plans=16)
    assert body["best_order"].index("t1") < body["best_order"].index("t2")


# -- batched / reference parity (bit-for-bit) ---------------------------------


def _random_connected_graph(rng, n):
    edges = [(int(rng.integers(0, i)), i) for i in range(1, n)]  # spanning
    extra = rng.integers(0, n * (n - 1) // 2 - (n - 1) + 1) if n > 2 else 0
    seen = set(edges)
    for _ in range(int(extra)):
        a, b = sorted(rng.choice(n, size=2, replace=False).tolist())
        if (a, b) not in seen:
            seen.add((a, b))
            edges.append((a, b))
    return _graph(n, edges)


@pytest.mark.parametrize("seed", range(6))
def test_batched_scorer_matches_reference_bit_for_bit(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 8))
    g = _random_connected_graph(rng, n)
    rows = {t.name: float(rng.integers(10, 10**6)) for t in g.tables}
    ndv = {t.name: float(rng.integers(1, 10**4)) for t in g.tables}
    stats = _stats(g, rows, ndv)

    ref_costs, plans = sequential_reference(g, stats, max_plans=256)

    index = {name: i for i, name in enumerate(g.names)}
    base_rows = np.array(
        [np.float32(rows[name]) for name in g.names], dtype=np.float32
    )
    factors = []
    for e in g.edges:
        f = float(np.float32(1.0) / np.float32(
            max(max(1.0, ndv[e.left]), max(1.0, ndv[e.right]))
        ))
        factors.append((index[e.left], index[e.right], f))
    costs, cards = score_plans(plans, base_rows, factors)

    assert costs.dtype == np.float32
    assert costs.tobytes() == ref_costs.tobytes(), (
        f"seed={seed} n={n}: batched scorer diverged from reference"
    )
    # per-step cardinalities too, for every plan
    for p in range(plans.shape[0]):
        _, ref_cards = reference_cost(
            [int(x) for x in plans[p]], base_rows, factors
        )
        assert cards[p].tobytes() == np.asarray(
            ref_cards, dtype=np.float32
        ).tobytes()


def test_cold_cost_records_one_span_each_per_dispatch():
    from repro.obs import registry

    def counts():
        hist = dict(registry().histogram("ndv_span_seconds").snapshot())
        spans = {
            name: hist[(("span", name),)].count
            for name in ("planner.compute_cost", "planner.enumerate",
                         "planner.score", "planner.fold", "planner.pick")
        }
        return spans, registry().counter("planner_dispatches_total").value()

    g = _graph(5, [(0, 1), (1, 2), (2, 3), (3, 4)])
    stats = _stats(g, {t.name: 1000 for t in g.tables},
                   {t.name: 50 for t in g.tables})
    spans0, dispatches0 = counts()
    body = compute_cost(g, stats, mode="paper", max_plans=64)
    assert body["enumeration"] == "sampled"
    spans1, dispatches1 = counts()
    assert dispatches1 - dispatches0 == 1
    assert {k: spans1[k] - spans0[k] for k in spans1} == dict.fromkeys(
        spans1, 1)


# -- plan-space cache and vectorized pick -------------------------------------
#
# The oracles below are the planner's loops as they were before the plan
# space was cached and the pick vectorized: the cached arrays and the
# argmin must reproduce them exactly, or served bodies and ETags move.


def _oracle_enumerate_plans(n_tables, max_plans):
    import itertools

    total = math.factorial(n_tables)
    if total <= max_plans:
        plans = np.fromiter(
            itertools.chain.from_iterable(
                itertools.permutations(range(n_tables))
            ),
            dtype=np.int32,
            count=total * n_tables,
        )
        return plans.reshape(total, n_tables)

    rng = np.random.default_rng(0)
    seen = set()
    out = []
    identity = tuple(range(n_tables))
    seen.add(identity)
    out.append(identity)
    while len(out) < max_plans:
        perm = tuple(int(x) for x in rng.permutation(n_tables))
        if perm not in seen:
            seen.add(perm)
            out.append(perm)
    return np.array(out, dtype=np.int32)


def _oracle_best_plan_index(plans, costs):
    p = plans.shape[0]
    keys = [(float(costs[i]), tuple(int(x) for x in plans[i]))
            for i in range(p)]
    finite = [k for k in keys if k[0] == k[0]]
    target = min(finite) if finite else min(keys, key=lambda k: k[1])
    return keys.index(target)


def _space_cache_counts():
    from repro.obs import registry

    c = registry().counter("planner_space_cache_total")
    return c.value(result="hit"), c.value(result="miss")


@pytest.mark.parametrize("max_plans", [1, 24, 1000, 4096])
@pytest.mark.parametrize("n_tables", range(1, 10))
def test_cached_enumeration_matches_oracle(n_tables, max_plans):
    plans = enumerate_plans(n_tables, max_plans)
    want = _oracle_enumerate_plans(n_tables, max_plans)
    assert plans.dtype == want.dtype == np.int32
    assert np.array_equal(plans, want)


@pytest.mark.parametrize("n_tables,max_plans", [(4, 24), (7, 1000), (9, 4096)])
def test_plan_space_is_shared_read_only_and_ranked(n_tables, max_plans):
    from repro.planner.enumeration import plan_space

    space = plan_space(n_tables, max_plans)
    assert plan_space(n_tables, max_plans) is space
    assert enumerate_plans(n_tables, max_plans) is space.plans
    for arr in (space.plans, space.rank):
        with pytest.raises(ValueError):
            arr[0] = 0
    assert space.rank.dtype == np.int64
    ranked = [tuple(int(x) for x in p) for p in space.plans[
        np.argsort(space.rank)]]
    assert ranked == sorted(tuple(int(x) for x in p) for p in space.plans)


def _pick_case(case, rng, n):
    costs = rng.random(n).astype(np.float32) * 1e6
    if case == "ties":
        lanes = rng.choice(n, size=max(2, n // 4), replace=False)
        costs[lanes] = costs.min()
    elif case == "signed_zero":
        lanes = rng.choice(n, size=4, replace=False)
        costs[lanes[:2]] = np.float32(-0.0)
        costs[lanes[2:]] = np.float32(0.0)
    elif case == "nan":
        costs[rng.choice(n, size=n // 3, replace=False)] = np.nan
    elif case == "inf":
        costs[rng.choice(n, size=n // 5, replace=False)] = np.inf
        costs[rng.choice(n, size=3, replace=False)] = -np.inf
    elif case == "plus_inf_only":
        costs[:] = np.inf
        costs[rng.choice(n, size=n // 3, replace=False)] = np.nan
    elif case == "all_nan":
        costs[:] = np.nan
    return costs


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("case", ["random", "ties", "signed_zero", "nan",
                                  "inf", "plus_inf_only", "all_nan"])
@pytest.mark.parametrize("n_tables,max_plans", [(4, 24), (7, 1000)])
def test_vectorized_pick_matches_oracle(n_tables, max_plans, case, seed):
    from repro.planner.cost import best_plan_index
    from repro.planner.enumeration import plan_space

    space = plan_space(n_tables, max_plans)
    rng = np.random.default_rng(seed)
    costs = _pick_case(case, rng, space.plans.shape[0])
    want = _oracle_best_plan_index(space.plans, costs)
    assert best_plan_index(space.plans, costs, space.rank) == want
    assert best_plan_index(space.plans, costs) == want


@pytest.mark.parametrize("cost", [0.0, -0.0, np.inf, -np.inf, np.nan])
def test_vectorized_pick_single_plan(cost):
    from repro.planner.cost import best_plan_index

    plans = np.array([[2, 0, 1]], dtype=np.int32)
    costs = np.array([cost], dtype=np.float32)
    assert best_plan_index(plans, costs) == _oracle_best_plan_index(
        plans, costs) == 0


def test_vectorized_pick_repeated_order_takes_first():
    from repro.planner.cost import best_plan_index

    plans = np.array([[1, 0], [0, 1], [1, 0], [0, 1]], dtype=np.int32)
    for costs in ([5.0, 3.0, 3.0, 3.0], [np.nan] * 4, [2.0, 9.0, 2.0, 9.0]):
        costs = np.array(costs, dtype=np.float32)
        assert best_plan_index(plans, costs) == _oracle_best_plan_index(
            plans, costs)


def test_space_cache_counts_miss_then_hit_per_cold_cost():
    # A size no other test asks for, so the first /cost builds it.
    g = _graph(5, [(0, 1), (1, 2), (2, 3), (3, 4)])
    stats = _stats(g, {t.name: 1000 for t in g.tables},
                   {t.name: 50 for t in g.tables})
    hit0, miss0 = _space_cache_counts()
    first = compute_cost(g, stats, mode="paper", max_plans=97)
    hit1, miss1 = _space_cache_counts()
    second = compute_cost(g, stats, mode="paper", max_plans=97)
    hit2, miss2 = _space_cache_counts()
    assert (hit1 - hit0, miss1 - miss0) == (0, 1)
    assert (hit2 - hit1, miss2 - miss1) == (1, 0)
    assert first == second


def test_space_cache_over_budget_entry_is_a_miss_and_not_kept():
    from repro.planner.enumeration import PlanSpaceCache

    cache = PlanSpaceCache(max_bytes=1000)
    small = cache.get(3, 6)  # 6 x (3 x 4 + 8) = 120 bytes, kept
    hit0, miss0 = _space_cache_counts()
    a = cache.get(6, 720)  # 720 x (6 x 4 + 8) bytes, far over 1000
    b = cache.get(6, 720)
    hit1, miss1 = _space_cache_counts()
    assert (hit1 - hit0, miss1 - miss0) == (0, 2)
    # Not kept, and it pushed out nothing that fits.
    assert (6, 720) not in cache and len(cache) == 1
    assert cache.get(3, 6) is small and cache.nbytes == small.nbytes
    assert a is not b
    assert np.array_equal(a.plans, _oracle_enumerate_plans(6, 720))


def test_space_cache_evicts_least_recently_used_by_bytes():
    from repro.planner.enumeration import PlanSpaceCache

    one = PlanSpaceCache(max_bytes=1 << 20).get(4, 24).nbytes  # 24 x 24 B
    cache = PlanSpaceCache(max_bytes=2 * one)
    first = cache.get(4, 24)
    cache.get(4, 23)
    assert cache.get(4, 24) is first  # now the most recent
    cache.get(4, 22)  # (4, 23) is least recent: it goes
    assert (4, 24) in cache and (4, 22) in cache and (4, 23) not in cache
    assert cache.nbytes <= cache.max_bytes
    assert cache.nbytes == sum(
        cache.get(*k).nbytes for k in [(4, 24), (4, 22)])


def test_space_cache_threads_keep_byte_count_exact():
    import os
    import sys
    import threading

    from repro.planner.enumeration import PlanSpaceCache

    sizes = [(n, m) for n in (3, 4, 5) for m in (5, 6, 24, 120)]
    oracle = {k: _oracle_enumerate_plans(*k) for k in sizes}
    cache = PlanSpaceCache(max_bytes=6000)
    errors = []

    def work(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(150):
                k = sizes[int(rng.integers(len(sizes)))]
                if not np.array_equal(cache.get(*k).plans, oracle[k]):
                    errors.append(k)
        except Exception as exc:  # reported below, with the thread's seed
            errors.append((seed, exc))

    threads = [threading.Thread(target=work, args=(s,))
               for s in range((os.cpu_count() or 4) + 4)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    kept = [cache.get(*k).nbytes for k in sizes if k in cache]
    assert cache.nbytes == sum(kept) <= cache.max_bytes


@pytest.mark.parametrize("n_tables", [13, 5])
def test_cost_body_identical_to_oracle_path(monkeypatch, n_tables):
    import json
    import types

    from repro.planner import api

    rng = np.random.default_rng(n_tables)
    g = _random_connected_graph(rng, n_tables)
    rows = {t.name: float(rng.integers(10, 10**9)) for t in g.tables}
    ndv = {t.name: float(rng.integers(1, 10**6)) for t in g.tables}
    stats = _stats(g, rows, ndv)

    body = compute_cost(g, stats, mode="improved",
                        max_plans=DEFAULT_MAX_PLANS)
    with monkeypatch.context() as m:
        m.setattr(api, "plan_space", lambda n, mp: types.SimpleNamespace(
            plans=_oracle_enumerate_plans(n, mp), rank=None))
        m.setattr(api, "best_plan_index",
                  lambda plans, costs, rank: _oracle_best_plan_index(
                      plans, costs))
        want = compute_cost(g, stats, mode="improved",
                            max_plans=DEFAULT_MAX_PLANS)
    assert body["enumeration"] == ("sampled" if n_tables > 6
                                   else "exhaustive")
    assert json.dumps(body, sort_keys=True) == json.dumps(want,
                                                          sort_keys=True)
