"""Pallas TPU kernel: distribution-detector metrics over row-group stats.

Computes, for a tile of columns at once, the paper's §6 metrics from the
(B, R) min/max statistic matrices:

  lane 0: overlap_sum   = sum_i max(0, min(max_i,max_{i+1}) - max(min_i,min_{i+1}))
  lane 1: gmin          = global min
  lane 2: gmax          = global max
  lane 3: sign_changes  = # midpoint-delta sign flips
  lane 4: n_valid       = row-group count
  lane 5: shared_bounds = # boundaries with max_i == min_{i+1}  (improved mode)

Tiling: one grid step owns a (BLOCK_B, R) block of mins/maxs/valid — the
row-group axis is kept whole per step (R <= 4096 keeps the working set
~3 * BLOCK_B * R * 4 B = 1.5 MiB at BLOCK_B=32, well inside VMEM) so all
consecutive-pair terms stay tile-local and no cross-step carry is needed.
Output is a (BLOCK_B, 128) tile with metrics packed in the first lanes
(lane-padded to the TPU vector width).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_B = 32
LANES = 128
BIG = 3.0e38


class MinMaxMetrics(NamedTuple):
    overlap_sum: jnp.ndarray
    gmin: jnp.ndarray
    gmax: jnp.ndarray
    sign_changes: jnp.ndarray
    n_valid: jnp.ndarray
    shared_bounds: jnp.ndarray


def lane_padded_groups(r: int) -> int:
    """The row-group axis padded to the vector lane width.

    This is the kernel's reduction extent — reduction extent is part of the
    numerics, so it is named here rather than inlined at the pad site.
    """
    return max((r + LANES - 1) // LANES * LANES, LANES)


def minmax_metrics_math(
    mins: jnp.ndarray, maxs: jnp.ndarray, valid: jnp.ndarray
) -> MinMaxMetrics:
    """The §6 metric reductions over a (b, r) tile (``valid`` is bool).

    Factored out of the pallas_call plumbing so the metric math is testable
    independent of tiling; the kernel body packs these reductions into the
    lane-aligned output tile.
    """
    n = jnp.sum(valid.astype(jnp.float32), axis=1)
    gmin = jnp.min(jnp.where(valid, mins, BIG), axis=1)
    gmax = jnp.max(jnp.where(valid, maxs, -BIG), axis=1)

    pv = valid[:, :-1] & valid[:, 1:]
    lo = jnp.maximum(mins[:, :-1], mins[:, 1:])
    hi = jnp.minimum(maxs[:, :-1], maxs[:, 1:])
    overlap = jnp.sum(jnp.where(pv, jnp.maximum(hi - lo, 0.0), 0.0), axis=1)

    mid = (mins + maxs) * 0.5
    d = jnp.where(pv, mid[:, 1:] - mid[:, :-1], 0.0)
    sgn = jnp.sign(d)
    sv = pv[:, :-1] & pv[:, 1:]
    changes = jnp.sum(
        jnp.where(sv & (sgn[:, :-1] * sgn[:, 1:] < 0), 1.0, 0.0), axis=1
    )

    shared = jnp.sum(
        jnp.where(pv & (maxs[:, :-1] == mins[:, 1:]), 1.0, 0.0), axis=1
    )
    return MinMaxMetrics(
        overlap_sum=overlap,
        gmin=gmin,
        gmax=gmax,
        sign_changes=changes,
        n_valid=n,
        shared_bounds=shared,
    )


def pack_lanes(cols) -> jnp.ndarray:
    """(b, 1) columns -> one (b, LANES) float32 tile, column k in lane k.

    A select against a lane iota: the TPU lowering of Pallas has no
    scatter, so ``.at[:, k].set`` cannot pack lanes inside a kernel body.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], LANES), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for k, c in enumerate(cols):
        out = jnp.where(lane == k, c.astype(jnp.float32), out)
    return out


def _minmax_body(mins_ref, maxs_ref, valid_ref, out_ref):
    m = minmax_metrics_math(mins_ref[...], maxs_ref[...], valid_ref[...] > 0.5)
    out_ref[...] = pack_lanes([x[:, None] for x in m])


@functools.partial(jax.jit, static_argnames=("interpret",))
def minmax_scan(
    mins: jnp.ndarray,
    maxs: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    interpret: bool = True,
) -> MinMaxMetrics:
    """Detector metrics for (B, R) row-group stats. Returns (B,) metrics."""
    b, r = mins.shape
    pb = (b + BLOCK_B - 1) // BLOCK_B * BLOCK_B
    # Pad R to the lane width so the tile is vector-aligned.
    pr = lane_padded_groups(r)
    pad = lambda x, fill: jnp.pad(  # noqa: E731
        x.astype(jnp.float32), ((0, pb - b), (0, pr - r)), constant_values=fill
    )
    mins2 = pad(mins, 0.0)
    maxs2 = pad(maxs, 0.0)
    valid2 = pad(valid.astype(jnp.float32), 0.0)

    in_spec = pl.BlockSpec((BLOCK_B, pr), lambda i: (i, 0))
    out_spec = pl.BlockSpec((BLOCK_B, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _minmax_body,
        out_shape=jax.ShapeDtypeStruct((pb, LANES), jnp.float32),
        grid=(pb // BLOCK_B,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=out_spec,
        interpret=interpret,
    )(mins2, maxs2, valid2)
    out = out[:b]
    return MinMaxMetrics(
        overlap_sum=out[:, 0],
        gmin=out[:, 1],
        gmax=out[:, 2],
        sign_changes=out[:, 3],
        n_valid=out[:, 4],
        shared_bounds=out[:, 5],
    )
