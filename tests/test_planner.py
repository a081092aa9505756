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
