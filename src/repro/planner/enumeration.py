"""Deterministic candidate-plan enumeration (left-deep join orders).

A candidate plan is a permutation of the graph's tables: join the first
two, then fold each subsequent table into the accumulated intermediate —
the classic System-R left-deep space. `enumerate_plans` returns the
candidate set as ONE `(P, N)` int32 array so the scorer can cost every
plan in a single batched dispatch (`repro.planner.cost`).

Determinism is load-bearing: the same graph must enumerate the same
plans in the same order on every replica, or `/cost` bodies (and their
ETags' usefulness) would differ across the fleet. Exhaustive
enumeration uses `itertools.permutations`' lexicographic order; the
sampled regime uses a fixed-seed generator.

The candidate set depends on `(n_tables, max_plans)` alone, so it is
built once per size: `plan_space` keeps each size's plans, with their
rank in sorted tuple order (the pick's tie-break), read-only in a
byte-bounded least-recently-used cache. Metric (`repro.obs` registry):
`planner_space_cache_total{result="hit"|"miss"}`, one per lookup.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import threading

import numpy as np

from repro.obs import registry

#: Fixed seed for the sampled regime — replicas must agree on the sample.
_SAMPLE_SEED = 0

#: Bytes of plans and ranks the plan-space cache keeps. The TPC-DS
#: templates at the default plan budget need under 6 MB; one entry can be
#: tens of MB (65,536 plans of a wide graph), and an entry larger than
#: the whole budget is built, used and not kept.
CACHE_BYTES = 64 << 20

_SPACE_LOOKUPS = registry().counter(
    "planner_space_cache_total",
    "Plan-space lookups, one per cold /cost: hit (reused) or miss (built)",
)
_HIT = _SPACE_LOOKUPS.labels(result="hit")
_MISS = _SPACE_LOOKUPS.labels(result="miss")


def plan_space_size(n_tables: int) -> int:
    """Size of the full left-deep space (n!)."""
    return math.factorial(n_tables)


def plan_rank(plans: np.ndarray) -> np.ndarray:
    """`rank[p]`: plan p's position in sorted tuple order, `(P,)` int64.

    One stable `np.lexsort`, first column the primary key, so a repeated
    order ranks lowest where it first occurs.
    """
    order = np.lexsort(plans.T[::-1])
    rank = np.empty(order.size, dtype=np.int64)
    rank[order] = np.arange(order.size, dtype=np.int64)
    return rank


@dataclasses.dataclass(frozen=True)
class PlanSpace:
    """The candidate orders of one `(n_tables, max_plans)`, read-only.

    `plans` is the `(P, N)` int32 array `enumerate_plans` documents;
    `rank` is its `plan_rank`.
    """

    plans: np.ndarray
    rank: np.ndarray

    @property
    def nbytes(self) -> int:
        return self.plans.nbytes + self.rank.nbytes


def _build(n_tables: int, max_plans: int) -> PlanSpace:
    plans = _enumerate(n_tables, max_plans)
    rank = plan_rank(plans)
    plans.flags.writeable = False
    rank.flags.writeable = False
    return PlanSpace(plans, rank)


def _enumerate(n_tables: int, max_plans: int) -> np.ndarray:
    if n_tables < 1:
        raise ValueError("need at least one table")
    if max_plans < 1:
        raise ValueError("max_plans must be >= 1")
    total = plan_space_size(n_tables)
    if total <= max_plans:
        plans = np.fromiter(
            itertools.chain.from_iterable(
                itertools.permutations(range(n_tables))
            ),
            dtype=np.int32,
            count=total * n_tables,
        )
        return plans.reshape(total, n_tables)

    rng = np.random.default_rng(_SAMPLE_SEED)
    seen = set()
    out = []
    # Identity first: the sample always contains at least one obvious
    # baseline order, whatever the draw.
    identity = tuple(range(n_tables))
    seen.add(identity)
    out.append(identity)
    while len(out) < max_plans:
        perm = tuple(int(x) for x in rng.permutation(n_tables))
        if perm not in seen:
            seen.add(perm)
            out.append(perm)
    return np.array(out, dtype=np.int32)


class PlanSpaceCache:
    """Plan spaces by `(n_tables, max_plans)`, least recently used first
    out once their plans and ranks pass `max_bytes`.

    Two threads that miss the same size both build it (the result is the
    same); the first to finish is kept and returned to both.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._entries: "collections.OrderedDict[tuple, PlanSpace]" = (
            collections.OrderedDict())
        self._bytes = 0
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    @property
    def nbytes(self) -> int:
        return self._bytes

    def get(self, n_tables: int, max_plans: int) -> PlanSpace:
        key = (int(n_tables), int(max_plans))
        with self._lock:
            space = self._entries.get(key)
            if space is not None:
                self._entries.move_to_end(key)
        if space is not None:
            _HIT.inc()
            return space
        space = _build(*key)
        _MISS.inc()
        if space.nbytes > self.max_bytes:
            return space
        with self._lock:
            kept = self._entries.setdefault(key, space)
            self._entries.move_to_end(key)
            if kept is space:
                self._bytes += space.nbytes
                while self._bytes > self.max_bytes:
                    _, old = self._entries.popitem(last=False)
                    self._bytes -= old.nbytes
        return kept


_SPACES = PlanSpaceCache(CACHE_BYTES)


def plan_space(n_tables: int, max_plans: int) -> PlanSpace:
    """The process's cached `PlanSpace` of this size, built on first use."""
    return _SPACES.get(n_tables, max_plans)


def enumerate_plans(n_tables: int, max_plans: int) -> np.ndarray:
    """All (or a deterministic sample of) table-order permutations.

    Returns a `(P, n_tables)` int32 array, `1 <= P <= max_plans`. When
    `n_tables! <= max_plans` the space is enumerated exhaustively in
    lexicographic order; otherwise `max_plans` permutations are drawn
    from a fixed-seed generator and deduplicated (first occurrence wins,
    so the order — and therefore any cost tie-break — is still
    deterministic). The array is read-only and shared by every caller of
    the same size (`plan_space`).
    """
    return plan_space(n_tables, max_plans).plans
