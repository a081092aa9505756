"""repro.planner — NDV-driven join ordering: the paper's application.

The paper's headline use of zero-cost NDV estimation is cost-based
query optimization. This package is that consumer: it turns the
catalog's NDV estimates into selectivity and join-cardinality
predictions, and picks the cheapest join order for a client-supplied
join graph — served fleet-wide as `POST /cost`.

    /cost request (JSON or wire frame)
         │ graph.parse_join_graph      — validation → 400s, canonical
         ▼                               identity() → ETag component
    JoinGraph (tables + equi-join edges)
         │ service: catalog rows + estimates     │ router: GET /tablestats
         ▼                                       ▼   per referenced dataset
    {name -> TableStats(rows, {col -> ColumnStats(ndv, conf, route)})}
         │ api.compute_cost
         ├─ enumeration.plan_space        all n! left-deep orders, or a
         │    (planner.enumerate span)    fixed-seed sample — ONE (P, N)
         │                                int32 array, deterministic, and
         │                                its lexicographic rank; built
         │                                once per (n, max_plans), then a
         │                                byte-bounded LRU cache lookup
         ├─ cost.score_plans              pack (rows, multipliers) lanes,
         │    (planner.score span)        pow2-pad P, fold C_out with one
         │                                jitted lax.scan — 1 dispatch
         │                                for thousands of plans
         ├─ cost.best_plan_index          argmin over costs, ties to the
         │    (planner.pick span)         lowest cached rank
         └─ best order + per-join cardinalities + total cost
              (?explain=1 adds per-column NDV/route/confidence provenance)

Cost model: C_out (sum of intermediate cardinalities) with the standard
NDV join estimate `|R ⋈ S| ~= |R|·|S| / max(ndv_R(k), ndv_S(k))`;
table pairs with no edge fall back to a cross product (selectivity 1);
NDVs clamp to >= 1. The batched scorer is bit-for-bit identical to the
pure-Python `cost.reference_cost` fold — same parity discipline as the
engine's fused/unfused twins — so serving topology never changes a plan.

Caching: a /cost body is a pure function of (graph identity, dataset
states, mode, max_plans). The service hashes its state token, the
router the per-dataset `/tablestats` ETags, so plans 304 exactly when
every input dataset's stats are unchanged — and ETags match across
replicas. See docs/ARCHITECTURE.md and docs/HTTP_API.md.
"""
from repro.planner.api import ColumnStats, TableStats, compute_cost
from repro.planner.cost import reference_cost, score_plans
from repro.planner.enumeration import enumerate_plans, plan_space_size
from repro.planner.graph import (
    DEFAULT_MAX_PLANS,
    JoinEdge,
    JoinGraph,
    TableRef,
    make_graph,
    parse_join_graph,
    parse_max_plans,
)

__all__ = [
    "ColumnStats",
    "DEFAULT_MAX_PLANS",
    "JoinEdge",
    "JoinGraph",
    "TableRef",
    "TableStats",
    "compute_cost",
    "enumerate_plans",
    "make_graph",
    "parse_join_graph",
    "parse_max_plans",
    "plan_space_size",
    "reference_cost",
    "score_plans",
]
