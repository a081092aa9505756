"""Plain reference: paper-mode NDV estimates and C_out join ordering, in numpy.

Imports nothing of the program. It reads the benchmark's own synthesized
chunks (`lake.Chunks`) and implements the paper's equations as written:

  section 4  invert S = ndv*len + nn*ceil(log2 ndv)/8 per chunk by Newton's
             method with the smooth derivative len + nn/(8 ndv ln 2) (Eq 1-3),
             32 steps from S/len stopping at |f| <= 1e-6 S, then the exact
             root of the linear piece when it lies on the same bit width;
             Eq 5's plain-encoding test; the column takes the largest
             dictionary-encoded, non-fallback chunk (or the largest chunk
             when there is none);
  section 5  invert m = ndv (1 - exp(-n/ndv)) for the distinct minima and
             maxima of n row groups by Newton's method in log space (40
             steps, |g| <= 1e-6 m), with m >= n - 1/2 saturated to m;
  section 7  ndv = min(max(dict, minmax), non-null rows), then the integer
             range bound (Eq 14) and the single-byte string bound (Eq 15).

Every step runs in the dtype given: float64 for the reference, bfloat16 for
the control that stands for a lower-precision program.

The planner reference enumerates every left-deep order in lexicographic
order and folds C_out = sum of intermediate cardinalities with the NDV
equi-join estimate |R||S|/max(ndv_R, ndv_S), in the dtype given (float32 is
the precision the configuration states).
"""
from __future__ import annotations

import itertools
import math
from typing import List, Sequence, Tuple

import numpy as np

DICT_ITERS, DICT_TOL = 32, 1e-6
COUPON_ITERS, COUPON_TOL = 40, 1e-6
SINGLE_BYTE_BOUND = 128.0
FALLBACK_NDV_RATIO, FALLBACK_SIZE_LO, FALLBACK_SIZE_HI = 0.9, 0.8, 1.2


def _ceil_log2(x, dt):
    m, e = np.frexp(np.maximum(x, dt(1)))
    bits = np.where(m == 0.5, e - 1, e)
    return np.maximum(bits, 1).astype(dt)


def dict_inversion(size, non_null, mean_len, dt) -> np.ndarray:
    """Section 4's per-chunk root of Eq 1, vectorized over chunks."""
    S = size.astype(dt)
    nn = non_null.astype(dt)
    ln = np.asarray(mean_len, dt)
    one, eight = dt(1), dt(8)
    ndv = np.maximum(S / ln, one)
    scale = np.maximum(S, one)
    hi = np.maximum(nn, one)
    done = np.zeros(S.shape, bool)
    for _ in range(DICT_ITERS):
        f = ndv * ln + nn * _ceil_log2(ndv, dt) / eight - S
        done |= np.abs(f) <= dt(DICT_TOL) * scale
        fp = ln + nn / (eight * np.maximum(ndv, one) * dt(math.log(2)))
        step = np.clip(ndv - f / fp, one, hi)
        ndv = np.where(done, ndv, step).astype(dt)
    bits = _ceil_log2(ndv, dt)
    linear = (S - nn * bits / eight) / ln
    snap = (_ceil_log2(np.maximum(linear, one), dt) == bits) & (linear >= one)
    return np.clip(np.where(snap, linear, ndv), one, hi).astype(dt)


def _expm1(x, dt):
    # numpy has expm1 for float64; bfloat16 computes through exp like a
    # lower-precision program would.
    return np.expm1(x) if dt is np.float64 else np.exp(x) - dt(1)


def coupon_inversion(m: float, n: float, dt) -> Tuple[float, bool]:
    """Section 5: NDV from m distinct extrema over n row groups."""
    m, n = dt(m), dt(n)
    one, half = dt(1), dt(0.5)
    saturated = bool(m >= n - half)
    m_eff = max(n - half, half) if saturated else m
    m_eff = dt(min(max(m_eff, half), max(n - dt(1e-3), half)))
    ndv0 = min(max(n * n / (dt(2) * max(n - m_eff, dt(1e-3))), one), dt(1e12))
    t = dt(np.log(ndv0))
    for _ in range(COUPON_ITERS):
        ndv = dt(np.exp(t))
        ndv_s = max(ndv, dt(1e-9))
        r = n / ndv_s
        g = ndv_s * -_expm1(-r, dt) - m_eff
        if abs(g) <= dt(COUPON_TOL) * max(m_eff, one):
            break
        gp = -_expm1(-r, dt) - np.exp(-r) * r
        t = dt(min(max(t - g / max(gp * ndv, dt(1e-12)), dt(0)), dt(28)))
    ndv = dt(np.exp(t))
    if saturated:
        ndv = max(m, one)
    if n <= 0:
        ndv = one
    if m_eff <= dt(0.5001):
        ndv = max(m, one)
    return float(max(ndv, max(m, one))), saturated


def paper_ndv(kind: str, width: int, chunks: Sequence, dt=np.float64) -> float:
    """Paper-mode NDV of one column over the chunks of every live file."""
    size = np.concatenate([c.size for c in chunks])
    rows = np.concatenate([c.rows for c in chunks])
    nulls = np.concatenate([c.nulls for c in chunks])
    dict_enc = np.concatenate([c.dict_encoded for c in chunks])
    lo = np.concatenate([c.lo for c in chunks])
    hi = np.concatenate([c.hi for c in chunks])
    one = dt(1)
    ln = dt(width)
    nn = np.maximum(rows - nulls, 0)
    roots = dict_inversion(size, nn, ln, dt)
    # Eq 5: a chunk whose size is about rows * len is written plain.
    S = size.astype(dt)
    nnd = nn.astype(dt)
    ndv_ratio = (S / ln) / np.maximum(nnd, one)
    size_ratio = S / np.maximum(nnd * ln, dt(1e-6))
    fallback = ((ndv_ratio >= dt(FALLBACK_NDV_RATIO))
                & (size_ratio >= dt(FALLBACK_SIZE_LO))
                & (size_ratio <= dt(FALLBACK_SIZE_HI)))
    usable = dict_enc & ~fallback
    pick = roots[usable] if usable.any() else roots
    ndv_dict = max(dt(pick.max()), one)
    n = len(size)
    lo_side, _ = coupon_inversion(len(np.unique(lo)), n, dt)
    hi_side, _ = coupon_inversion(len(np.unique(hi)), n, dt)
    ndv_minmax = dt(max(hi_side, lo_side))
    non_null = dt(nn.sum())
    ndv = min(max(ndv_dict, ndv_minmax), max(non_null, one))
    if kind in ("int", "date"):
        # Keys are domain index + a constant, so max - min + 1 is exact.
        ndv = min(ndv, max(dt(int(hi.max()) - int(lo.min()) + 1), one))
    if kind == "str" and width <= 1:
        ndv = min(ndv, min(dt(SINGLE_BYTE_BOUND), max(non_null, one)))
    return float(max(ndv, one))


# -- planner ------------------------------------------------------------------

def plan_costs(base_rows: Sequence[float], edges: Sequence[Tuple[int, int, float]],
               dt=np.float32) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(plans, costs, step cardinalities) over every left-deep order.

    ``edges`` carry (left index, right index, ndv): the selectivity is
    1 / max(ndv, 1) in ``dt``, applied at the step where the later of the
    two tables joins; a step with no edge is a cross product.
    """
    n = len(base_rows)
    plans = np.array(list(itertools.permutations(range(n))), np.int64)
    p = len(plans)
    pos = np.empty_like(plans)
    np.put_along_axis(pos, plans, np.arange(n)[None, :].repeat(p, 0), axis=1)
    rows = np.asarray(base_rows, dt)
    mult = np.ones((p, n), dt)
    lanes = np.arange(p)
    with np.errstate(over="ignore", invalid="ignore"):
        for a, b, ndv in edges:
            sel = dt(1) / dt(max(float(ndv), 1.0))
            step = np.maximum(pos[:, a], pos[:, b])
            mult[lanes, step] = (mult[lanes, step] * sel).astype(dt)
        card = rows[plans[:, 0]]
        cost = np.zeros(p, dt)
        cards = np.zeros((p, max(n - 1, 0)), dt)
        for k in range(1, n):
            card = ((card * rows[plans[:, k]]).astype(dt) * mult[:, k]).astype(dt)
            cost = (cost + card).astype(dt)
            cards[:, k - 1] = card
    return plans, cost.astype(np.float64), cards.astype(np.float64)


def best_plan(plans: np.ndarray, costs: np.ndarray) -> int:
    """Cheapest finite cost; ties to the lexicographically first order
    (``plans`` is in lexicographic order)."""
    finite = np.isfinite(costs)
    if not finite.any():
        return 0
    c = np.where(finite, costs, np.inf)
    return int(np.flatnonzero(c == c.min())[0])


def rel_gap(got: float, want: float) -> float:
    """|got - want| / |want|; 0 where both are the same infinity."""
    if got == want:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


def fold_order(order: Sequence[int], base_rows: Sequence[float],
               edges: Sequence[Tuple[int, int, float]], dt=np.float32
               ) -> Tuple[float, List[float]]:
    """C_out and step cardinalities of one order, as `plan_costs` folds."""
    n = len(order)
    pos = {t: i for i, t in enumerate(order)}
    rows = np.asarray(base_rows, dt)
    with np.errstate(over="ignore", invalid="ignore"):
        card, cost, cards = rows[order[0]], dt(0), []
        for k in range(1, n):
            mult = dt(1)
            for a, b, ndv in edges:
                if max(pos[a], pos[b]) == k:
                    mult = dt(mult * (dt(1) / dt(max(float(ndv), 1.0))))
            card = dt(dt(card * rows[order[k]]) * mult)
            cost = dt(cost + card)
            cards.append(float(card))
    return float(cost), cards
