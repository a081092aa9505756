"""Batched plan scoring: thousands of join orders, one JAX dispatch.

The cost model is C_out — a plan's cost is the sum of its intermediate
join-result cardinalities — with the NDV-based equi-join estimate

    |R JOIN S on k|  ~=  |R| * |S| / max(ndv_R(k), ndv_S(k))

folded left-deep along each candidate order. Per-edge that is a
multiplicative selectivity `1 / max(ndv_l, ndv_r)` applied at the step
where the edge's later table enters the prefix; a table pair with no
edge contributes no multiplier (cross-product fallback, selectivity 1).

Scoring mirrors how `repro.engine` batches estimation: pack every
candidate plan as a lane of `(P, N)` float32 arrays — per-step row
counts and per-step accumulated edge multipliers — pad P to the next
power of two (bounding retraces, like `catalog.BatchPacker`), and fold
the cost recurrence with one jitted `lax.scan`:

    card_k  = card_{k-1} * rows_k * mult_k
    cost_k  = cost_{k-1} + card_k

Bit-for-bit parity with `reference_cost` (the pure-Python float32 fold
the tests pin) is a contract, same as the engine's fused/unfused twins.
Two things protect it: the edge-multiplier scatter runs HOST-side via
`np.multiply.at` (in-order per edge; XLA scatter order for duplicate
indices is unspecified), and `card_k` has two uses (carry and scan
output) so XLA cannot contract the multiply into an FMA with the cost
add.

Metrics (`repro.obs` registry): `planner_plans_scored_total`,
`planner_dispatches_total`; `score_plans` times its device fold and the
reads of its results in a `planner.fold` span.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import registry, span
from repro.planner.enumeration import plan_rank

__all__ = [
    "EdgeFactor",
    "best_plan_index",
    "reference_cost",
    "score_plans",
]

_PLANS_SCORED = registry().counter(
    "planner_plans_scored_total",
    "Candidate join orders scored by the batched planner",
)
_DISPATCHES = registry().counter(
    "planner_dispatches_total",
    "Batched plan-scoring dispatches (one per cold /cost computation)",
)

#: (left_table_index, right_table_index, float32 selectivity multiplier).
EdgeFactor = Tuple[int, int, float]


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=64)
def _scan_fold(n_tables: int, p_pad: int):
    """Jitted cost fold for one (plan length, padded lane count) shape."""

    def fold(rows: jnp.ndarray, mults: jnp.ndarray):
        # rows/mults: (p_pad, n_tables) float32, already gathered per plan.
        card0 = rows[:, 0]
        cost0 = jnp.zeros_like(card0)

        def step(carry, xs):
            card, cost = carry
            rows_k, mult_k = xs
            new_card = card * rows_k * mult_k
            # new_card is BOTH the carry and a scan output — the second
            # use keeps XLA from contracting the multiply chain into an
            # FMA with this add, which would break reference parity.
            new_cost = cost + new_card
            return (new_card, new_cost), new_card

        xs = (rows[:, 1:].T, mults[:, 1:].T)  # (n_tables-1, p_pad)
        (_, cost), cards = jax.lax.scan(step, (card0, cost0), xs)
        return cost, cards

    return jax.jit(fold)


def plan_positions(plans: np.ndarray) -> np.ndarray:
    """Invert plans: `pos[p, t]` = step at which plan p joins table t."""
    p, n = plans.shape
    pos = np.empty((p, n), dtype=np.int64)
    np.put_along_axis(
        pos, plans.astype(np.int64),
        np.broadcast_to(np.arange(n, dtype=np.int64), (p, n)).copy(), axis=1,
    )
    return pos


def pack_step_multipliers(
    plans: np.ndarray, n_tables: int, edges: Sequence[EdgeFactor]
) -> np.ndarray:
    """Per-plan per-step accumulated edge multipliers, host-side.

    Edge e applies at step `max(pos[left], pos[right])` — the moment its
    later table joins the prefix. Accumulation runs edge-by-edge in the
    graph's edge order with `np.multiply.at` (in-order, deterministic),
    which is exactly the order `reference_cost` multiplies in — scatter
    order is part of the bit-parity contract.
    """
    p = plans.shape[0]
    pos = plan_positions(plans)
    mults = np.ones((p, n_tables), dtype=np.float32)
    lanes = np.arange(p)
    for a, b, factor in edges:
        steps = np.maximum(pos[:, a], pos[:, b])
        np.multiply.at(mults, (lanes, steps), np.float32(factor))
    return mults


def score_plans(
    plans: np.ndarray,
    base_rows: np.ndarray,
    edges: Sequence[EdgeFactor],
) -> Tuple[np.ndarray, np.ndarray]:
    """Cost every candidate plan in ONE batched JAX dispatch.

    `plans` is `(P, N)` int32 permutations, `base_rows` the `(N,)`
    float32 filtered table cardinalities, `edges` the precomputed
    selectivity factors. Returns `(costs, step_cards)`:
    `costs[p]` = C_out of plan p (float32), `step_cards[p, k-1]` = the
    intermediate cardinality after step k of plan p (shape `(P, N-1)`).
    """
    p, n = plans.shape
    base_rows = np.asarray(base_rows, dtype=np.float32)
    rows = base_rows[plans]  # (P, N)
    mults = pack_step_multipliers(plans, n, edges)

    p_pad = _pow2_at_least(p)
    if p_pad != p:
        pad = ((0, p_pad - p), (0, 0))
        # Padding lanes fold all-ones — finite, discarded below.
        rows = np.pad(rows, pad, constant_values=1.0)
        mults = np.pad(mults, pad, constant_values=1.0)

    fold = _scan_fold(n, p_pad)
    with span("planner.fold", lanes=p_pad):
        cost, cards = fold(jnp.asarray(rows), jnp.asarray(mults))
        costs = np.asarray(cost)[:p]
        step_cards = np.asarray(cards).T[:p]  # (n-1, p_pad) -> (P, n-1)
    _DISPATCHES.inc()
    _PLANS_SCORED.inc(p)
    return costs, step_cards


def best_plan_index(
    plans: np.ndarray, costs: np.ndarray, rank: Optional[np.ndarray] = None
) -> int:
    """Cheapest plan; ties broken by lexicographically smallest order.

    NaN costs (a zero-row table joined under sampled overflow, say) lose
    to any other cost, infinities included; `-0.0` ties `0.0`. An all-NaN
    field degrades to the lexicographic minimum — still deterministic
    across replicas. `rank` is `plans`' `plan_rank` (the plan space
    caches it); without it the pick computes it.
    """
    if rank is None:
        rank = plan_rank(plans)
    costs = np.asarray(costs)
    ordered = ~np.isnan(costs)
    lanes = costs == costs[ordered].min() if ordered.any() else ~ordered
    idx = np.flatnonzero(lanes)
    return int(idx[np.argmin(rank[idx])])


def reference_cost(
    plan: Sequence[int],
    base_rows: np.ndarray,
    edges: Sequence[EdgeFactor],
) -> Tuple[float, List[float]]:
    """Pure-Python float32 cost fold — the parity reference for one plan.

    Every operation is an explicit `np.float32` scalar op in the same
    order as the batched fold: per-step multiplier accumulated over
    `edges` in sequence, then `(card * rows_k) * mult_k`, then
    `cost + card`. The batched scorer must match this bit-for-bit.
    """
    n = len(plan)
    pos = {int(t): i for i, t in enumerate(plan)}
    card = np.float32(base_rows[plan[0]])
    cost = np.float32(0.0)
    cards: List[float] = []
    for k in range(1, n):
        mult = np.float32(1.0)
        for a, b, factor in edges:
            if max(pos[a], pos[b]) == k:
                mult = np.float32(mult * np.float32(factor))
        rows_k = np.float32(base_rows[plan[k]])
        card = np.float32(np.float32(card * rows_k) * mult)
        cost = np.float32(cost + card)
        cards.append(float(card))
    return float(cost), cards
