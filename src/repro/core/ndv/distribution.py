"""Distribution detection from row-group range patterns (paper §6).

Classifies each column's physical layout from the sequence of per-row-group
(min_i, max_i) ranges:

  overlap(r_i, r_{i+1}) = max(0, min(max_i, max_{i+1}) - max(min_i, min_{i+1}))
  overlap_ratio = sum_i overlap(r_i, r_{i+1}) / total_span          (Eq 10-11)
  monotonicity  = 1 - sign_changes(delta midpoints) / (n - 2)       (Eq 12)

Classes (§6.2):
  Sorted:        overlap_ratio < 0.1 and monotonicity > 0.9
  Pseudo-sorted: overlap_ratio < 0.3 and monotonicity > 0.7
  Well-spread:   overlap_ratio > 0.7
  Mixed:         otherwise

All metrics are masked for padded row groups and vectorized over columns so
the same code serves the scalar API, the batched estimator, and the oracle
for the `minmax_scan` Pallas kernel.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.ndv.types import Layout

SORTED_OVERLAP = 0.1
SORTED_MONO = 0.9
PSEUDO_OVERLAP = 0.3
PSEUDO_MONO = 0.7
WELL_SPREAD_OVERLAP = 0.7


class DistributionMetrics(NamedTuple):
    overlap_ratio: jnp.ndarray   # (B,)
    monotonicity: jnp.ndarray    # (B,)
    total_span: jnp.ndarray      # (B,) global max - global min
    layout: jnp.ndarray          # (B,) int32 Layout codes


def detect_distribution(
    mins: jnp.ndarray,
    maxs: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    backend: str = "auto",
    keepdims: bool = False,
) -> DistributionMetrics:
    """Compute Eq 10-12 metrics and classify (§6.2), batched.

    Args:
      mins / maxs: (B, R) per-row-group extrema (float key space).
      valid: (B, R) bool mask; row groups are packed to the left.
      backend: "auto"/"ref" compute the reductions here in jnp; "pallas"
        (or "auto" on TPU) takes them from the `minmax_scan` kernel. The
        ratio/classification tail is shared.
      keepdims: return (B, 1) columns instead of (B,) vectors — the form
        the estimation pipeline computes in (see `estimate_batch_core`).

    Returns:
      DistributionMetrics with int32 layout codes from `Layout`.
    """
    mins = jnp.asarray(mins, jnp.float32)
    maxs = jnp.asarray(maxs, jnp.float32)
    valid = jnp.asarray(valid, bool)

    from repro.kernels import ops  # local: kernels.ref imports this package

    if ops.use_pallas(backend):
        mm = ops.minmax_scan(mins, maxs, valid, backend="pallas")
        n = mm.n_valid[:, None]
        gmin, gmax = mm.gmin[:, None], mm.gmax[:, None]
        overlap_sum = mm.overlap_sum[:, None]
        sign_changes = mm.sign_changes[:, None]
        # Row groups are packed to the left, so "any valid consecutive
        # pair" is exactly n >= 2.
        any_pairs = n >= 2.0
    else:
        n = jnp.sum(valid, axis=-1, keepdims=True).astype(jnp.float32)

        big = jnp.float32(3.4e38)
        gmin = jnp.min(jnp.where(valid, mins, big), axis=-1, keepdims=True)
        gmax = jnp.max(jnp.where(valid, maxs, -big), axis=-1, keepdims=True)

        # Consecutive-pair overlap (Eq 10), masked to valid pairs.
        pair_valid = valid[:, :-1] & valid[:, 1:]
        lo = jnp.maximum(mins[:, :-1], mins[:, 1:])
        hi = jnp.minimum(maxs[:, :-1], maxs[:, 1:])
        overlap = jnp.where(pair_valid, jnp.maximum(hi - lo, 0.0), 0.0)
        overlap_sum = jnp.sum(overlap, axis=-1, keepdims=True)

        # Midpoint monotonicity (Eq 12).
        mid = (mins + maxs) * 0.5
        d = mid[:, 1:] - mid[:, :-1]                  # (B, R-1)
        d = jnp.where(pair_valid, d, 0.0)
        sgn = jnp.sign(d)
        # Sign changes between consecutive non-zero deltas, masked.
        step_valid = pair_valid[:, :-1] & pair_valid[:, 1:]
        changes = jnp.where(
            step_valid & (sgn[:, :-1] * sgn[:, 1:] < 0), 1.0, 0.0
        )
        sign_changes = jnp.sum(changes, axis=-1, keepdims=True)
        any_pairs = jnp.sum(pair_valid, axis=-1, keepdims=True) > 0

    total_span = jnp.maximum(gmax - gmin, 0.0)

    # Degenerate spans (constant column / single row group): define the
    # overlap ratio as 1 when consecutive ranges coincide (full overlap) —
    # a constant column IS maximally well-spread.
    span_safe = jnp.maximum(total_span, 1e-30)
    degenerate = (total_span <= 0.0) & any_pairs
    overlap_ratio = jnp.where(
        degenerate, 1.0, jnp.clip(overlap_sum / span_safe, 0.0, None)
    )
    # (ratio can legitimately exceed 1 for heavy overlap with many groups;
    #  classification only needs thresholds, keep the raw value.)

    denom = jnp.maximum(n - 2.0, 1.0)
    monotonicity = jnp.where(
        n >= 3.0, 1.0 - sign_changes / denom, 1.0
    )

    layout = classify(overlap_ratio, monotonicity, n)
    out = DistributionMetrics(
        overlap_ratio=overlap_ratio,
        monotonicity=monotonicity,
        total_span=total_span,
        layout=layout,
    )
    return out if keepdims else DistributionMetrics(*(x[:, 0] for x in out))


def classify(
    overlap_ratio: jnp.ndarray,
    monotonicity: jnp.ndarray,
    n_groups: jnp.ndarray,
) -> jnp.ndarray:
    """§6.2 decision rules -> int32 Layout codes."""
    sorted_ = (overlap_ratio < SORTED_OVERLAP) & (monotonicity > SORTED_MONO)
    pseudo = (overlap_ratio < PSEUDO_OVERLAP) & (monotonicity > PSEUDO_MONO)
    spread = overlap_ratio > WELL_SPREAD_OVERLAP
    out = jnp.full_like(overlap_ratio, float(Layout.MIXED))
    out = jnp.where(spread, float(Layout.WELL_SPREAD), out)
    out = jnp.where(pseudo & ~spread, float(Layout.PSEUDO_SORTED), out)
    out = jnp.where(sorted_, float(Layout.SORTED), out)
    # With a single row group there is no layout signal: treat as well-spread
    # (dictionary inversion is exact for one group).
    out = jnp.where(n_groups <= 1, float(Layout.WELL_SPREAD), out)
    return out.astype(jnp.int32)
