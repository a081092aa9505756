"""Planner entry point: join graph + per-table stats -> /cost body.

`compute_cost` is the one function both serving tiers call. The
single-dataset `StatsService` feeds it stats it reads from its own
catalog; the fleet `StatsRouter` feeds it stats fetched from each
dataset's replica set via `GET /tablestats`. Either way the body is a
pure function of (graph, stats, mode, max_plans) — replicas holding the
same dataset state produce byte-identical bodies, which is what lets
`/cost` ETags be state-derived and fleet-stable.

Stat resolution per edge endpoint: NDV comes from the named join
column's estimate, clamped to >= 1 (a zero/negative NDV would make the
selectivity 1/max(...) blow up; clamping to 1 degrades the edge to a
pass-through, the conservative choice). Unknown columns raise
`ValueError` -> HTTP 400.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import numpy as np

from repro.obs import span
from repro.planner.cost import (
    best_plan_index,
    reference_cost,
    score_plans,
)
from repro.planner.enumeration import (
    enumerate_plans,
    plan_space,
    plan_space_size,
)
from repro.planner.graph import JoinGraph

__all__ = ["ColumnStats", "TableStats", "compute_cost", "provenance_block"]


class ColumnStats(NamedTuple):
    """One join column's estimate as the planner consumes it."""

    ndv: float
    non_null: int
    confidence: Optional[float] = None
    route: Optional[str] = None


class TableStats(NamedTuple):
    """One table's planner inputs (rows + per-join-column stats)."""

    rows: float
    columns: Dict[str, ColumnStats]


def _clamped_ndv(stats: Dict[str, TableStats], table: str, column: str) -> float:
    ts = stats.get(table)
    if ts is None:
        raise ValueError(f"no stats for table {table!r}")
    cs = ts.columns.get(column)
    if cs is None:
        raise ValueError(f"table {table!r} has no stats for column {column!r}")
    return max(1.0, float(cs.ndv))


def compute_cost(
    graph: JoinGraph,
    stats: Dict[str, TableStats],
    *,
    mode: str,
    max_plans: int,
    explain: bool = False,
) -> dict:
    """Score the plan space and report the cheapest join order.

    `stats` maps each graph table NAME (the alias, not the dataset key)
    to its `TableStats`. Raises `ValueError` for resolvable-to-400
    problems (missing stats for a referenced table/column). The whole
    call is the `planner.compute_cost` span; its children are
    `planner.enumerate`, `planner.score` (with `planner.fold`) and
    `planner.pick`.
    """
    with span("planner.compute_cost", tables=len(graph.tables)):
        return _cost_body(graph, stats, mode, max_plans, explain)


def _cost_body(
    graph: JoinGraph,
    stats: Dict[str, TableStats],
    mode: str,
    max_plans: int,
    explain: bool,
) -> dict:
    names = graph.names
    n = len(names)
    index = {name: i for i, name in enumerate(names)}

    base_rows = np.empty(n, dtype=np.float32)
    for i, t in enumerate(graph.tables):
        ts = stats.get(t.name)
        if ts is None:
            raise ValueError(f"no stats for table {t.name!r}")
        base_rows[i] = np.float32(
            np.float32(ts.rows) * np.float32(t.filter_selectivity)
        )

    # Per-edge selectivity factor 1 / max(ndv_l, ndv_r), float32 like
    # everything downstream.
    factors = []
    edge_meta = []
    for e in graph.edges:
        ndv_l = _clamped_ndv(stats, e.left, e.left_column)
        ndv_r = _clamped_ndv(stats, e.right, e.right_column)
        factor = float(np.float32(1.0) / np.float32(max(ndv_l, ndv_r)))
        a, b = index[e.left], index[e.right]
        factors.append((a, b, factor))
        edge_meta.append({
            "left": e.left,
            "left_column": e.left_column,
            "right": e.right,
            "right_column": e.right_column,
            "ndv_left": ndv_l,
            "ndv_right": ndv_r,
            "selectivity": factor,
        })

    with span("planner.enumerate", tables=n, max_plans=max_plans):
        space = plan_space(n, max_plans)
    plans = space.plans
    with span("planner.score", plans=int(plans.shape[0]), tables=n):
        costs, step_cards = score_plans(plans, base_rows, factors)
    with span("planner.pick", plans=int(plans.shape[0])):
        best = best_plan_index(plans, costs, space.rank)
    best_plan = [int(x) for x in plans[best]]
    best_order = [names[i] for i in best_plan]

    # Per-join report for the winning order. The cardinalities come from
    # the batched fold's own output lanes (not recomputed), so the body
    # is exactly what was scored; reference_cost here would match
    # bit-for-bit (the tests pin that), we just avoid the second fold.
    pos = {t: k for k, t in enumerate(best_plan)}
    joins: List[dict] = []
    for k in range(1, n):
        step_edges = [
            edge_meta[j] for j, (a, b, _) in enumerate(factors)
            if max(pos[a], pos[b]) == k
        ]
        joins.append({
            "table": names[best_plan[k]],
            "cardinality": float(step_cards[best][k - 1]),
            "cross_product": not step_edges,
            "edges": step_edges,
        })
    total_cost = float(costs[best]) if n > 1 else 0.0

    body = {
        "mode": mode,
        "tables": [
            {
                "name": t.name,
                **({"namespace": t.namespace, "dataset": t.dataset}
                   if t.dataset_key else {}),
                "rows": float(stats[t.name].rows),
                "filter_selectivity": float(t.filter_selectivity),
                "effective_rows": float(base_rows[index[t.name]]),
            }
            for t in graph.tables
        ],
        "best_order": best_order,
        "joins": joins,
        "total_cost": total_cost,
        "plans_scored": int(plans.shape[0]),
        "plan_space": plan_space_size(n),
        "enumeration": (
            "exhaustive" if plan_space_size(n) <= max_plans else "sampled"
        ),
    }
    if explain:
        body["provenance"] = provenance_block(graph, stats)
    return body


def provenance_block(graph: JoinGraph, stats: Dict[str, TableStats]) -> dict:
    """Which NDV estimates fed each cardinality, with the quality signals.

    The `?explain=1` sidecar for `/cost`: per table, per join column, the
    NDV that entered the selectivity plus its route and confidence (the
    PR 9 signals). Identity-neutral — never hashed into the ETag, exactly
    like `?explain=1` on `/estimate`; both serving tiers attach it to a
    COPY of the cached body.
    """
    needed = graph.columns_by_table()
    return {
        name: {
            col: {
                "ndv": float(stats[name].columns[col].ndv),
                "non_null": int(stats[name].columns[col].non_null),
                "confidence": stats[name].columns[col].confidence,
                "route": stats[name].columns[col].route,
            }
            for col in cols if col in stats[name].columns
        }
        for name, cols in needed.items() if name in stats
    }


def sequential_reference(
    graph: JoinGraph,
    stats: Dict[str, TableStats],
    *,
    max_plans: int,
) -> tuple:
    """Score the same plan space one plan at a time in pure Python.

    The benchmark's sequential baseline and the tests' parity oracle:
    returns `(costs, plans)` where `costs[p]` is `reference_cost` of
    plan p over the identical enumeration.
    """
    names = graph.names
    index = {name: i for i, name in enumerate(names)}
    n = len(names)
    base_rows = np.empty(n, dtype=np.float32)
    for i, t in enumerate(graph.tables):
        base_rows[i] = np.float32(
            np.float32(stats[t.name].rows) * np.float32(t.filter_selectivity)
        )
    factors = []
    for e in graph.edges:
        ndv_l = _clamped_ndv(stats, e.left, e.left_column)
        ndv_r = _clamped_ndv(stats, e.right, e.right_column)
        factors.append((
            index[e.left], index[e.right],
            float(np.float32(1.0) / np.float32(max(ndv_l, ndv_r))),
        ))
    plans = enumerate_plans(n, max_plans)
    costs = np.array(
        [reference_cost([int(x) for x in p], base_rows, factors)[0]
         for p in plans],
        dtype=np.float32,
    )
    return costs, plans
