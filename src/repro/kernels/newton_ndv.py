"""Pallas TPU kernel: batched Newton-Raphson NDV solves.

Fleet-scale planning runs the paper's two inversions over MILLIONS of column
chunks in one pass (one lane per chunk). The solves are fixed-iteration and
branch-free, which maps perfectly onto the TPU VPU's (8, 128) vector tiles:

  * ``dict_newton``   — invert  S = ndv*len + rows*ceil(log2 ndv)/8   (Eq 2)
  * ``coupon_newton`` — invert  m = D*(1 - exp(-n/D))  in log-space   (Eq 8)

Tiling: inputs are flat (M,) float32 arrays padded to BLOCK_M*128; each grid
step processes a (BLOCK_M, 128) VMEM tile (4 input tiles + 1 output tile
= 5 * BLOCK_M * 512 bytes; BLOCK_M=64 -> 160 KiB working set, far below
VMEM). No MXU involvement — pure VPU transcendental/elementwise work, so the
roofline term that matters is HBM streaming: 16 B/lane in, 4 B/lane out at
~20 flops*iters/lane.

These kernels target TPU; in this container they are validated with
``interpret=True`` against ``repro.kernels.ref`` oracles (see tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ndv.dict_inversion import ceil_log2
from repro.core.ndv.minmax_diversity import expm1

DICT_ITERS = 16
COUPON_ITERS = 40  # matches repro.core.ndv.minmax_diversity.NEWTON_ITERS
LN2 = 0.6931471805599453

BLOCK_M = 64      # sublane-tile rows per grid step
LANES = 128       # TPU vector lane count


# ---------------------------------------------------------------------------
# Solve math (shared by the standalone kernel bodies and the fused megakernel)
# ---------------------------------------------------------------------------
#
# The `*_math` functions are the kernels' numerics, factored out of the
# pallas_call plumbing: pure elementwise array -> array, shape-polymorphic,
# so the fixed-iteration solves are testable (and reusable) independent of
# tiling and padding geometry.


def dict_newton_math(s, rows, nulls, mean_len):
    """Eq-2 fixed-iteration Newton inversion, elementwise over any shape."""
    non_null = jnp.maximum(rows - nulls, 0.0)
    mean_len = jnp.maximum(mean_len, 1e-6)
    cap = jnp.maximum(non_null, 1.0)

    ndv = jnp.clip(s / mean_len, 1.0, cap)
    for _ in range(DICT_ITERS):
        f = ndv * mean_len + non_null * ceil_log2(ndv) / 8.0 - s
        fp = mean_len + non_null / (8.0 * jnp.maximum(ndv, 1.0) * LN2)
        ndv = jnp.clip(ndv - f / fp, 1.0, cap)
    # Plateau snap: solve the linear piece at the converged bit width.
    bits = ceil_log2(ndv)
    lin = (s - non_null * bits / 8.0) / mean_len
    keep = (ceil_log2(jnp.maximum(lin, 1.0)) == bits) & (lin >= 1.0)
    return jnp.clip(jnp.where(keep, lin, ndv), 1.0, cap)


def coupon_newton_math(m, n):
    """Eq-8 fixed-iteration log-space Newton inversion, elementwise."""
    saturated = m >= n - 0.5
    m_eff = jnp.where(saturated, jnp.maximum(n - 0.5, 0.5), m)
    m_eff = jnp.clip(m_eff, 0.5, jnp.maximum(n - 1e-3, 0.5))

    t = jnp.log(jnp.clip(n * n / (2.0 * jnp.maximum(n - m_eff, 1e-3)), 1.0, 1e12))
    for _ in range(COUPON_ITERS):
        ndv = jnp.exp(t)
        r = n / jnp.maximum(ndv, 1e-9)
        em1 = -expm1(-r)               # 1 - e^{-r}
        g = ndv * em1 - m_eff
        gp = em1 - jnp.exp(-r) * r     # g'(D)
        t = jnp.clip(t - g / jnp.maximum(gp * ndv, 1e-12), 0.0, 28.0)
    ndv = jnp.exp(t)
    # saturated (m == n): the MLE diverges — report the observable m
    # (a hard lower bound), matching repro.core.ndv.minmax_diversity.
    ndv = jnp.where(saturated, jnp.maximum(m, 1.0), ndv)
    ndv = jnp.where(n <= 0, 1.0, ndv)
    ndv = jnp.where(m_eff <= 0.5001, jnp.maximum(m, 1.0), ndv)
    return jnp.maximum(ndv, jnp.maximum(m, 1.0))


# ---------------------------------------------------------------------------
# Kernel bodies (operate on (BLOCK_M, 128) tiles)
# ---------------------------------------------------------------------------


def _dict_newton_body(s_ref, rows_ref, nulls_ref, len_ref, out_ref):
    out_ref[...] = dict_newton_math(
        s_ref[...], rows_ref[...], nulls_ref[...], len_ref[...]
    )


def _coupon_newton_body(m_ref, n_ref, out_ref):
    out_ref[...] = coupon_newton_math(m_ref[...], n_ref[...])


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1024)
def _tile_geometry(m: int) -> tuple[int, int]:
    """(padded length, tile-row count) for a flat (m,) input.

    Pure shape math, memoized per length: `_pad_to_tiles` runs inside every
    traced call of the kernel wrappers, and the fleet path re-pads the same
    handful of bucketed shapes millions of times.
    """
    per = BLOCK_M * LANES
    padded = (m + per - 1) // per * per
    return padded, padded // LANES


def _pad_to_tiles(x: jnp.ndarray, fill: float) -> tuple[jnp.ndarray, int]:
    m = x.shape[0]
    padded, rows = _tile_geometry(m)
    x = jnp.pad(x, (0, padded - m), constant_values=fill)
    return x.reshape(rows, LANES), m


@functools.partial(jax.jit, static_argnames=("interpret",))
def dict_newton(
    size: jnp.ndarray,
    rows: jnp.ndarray,
    nulls: jnp.ndarray,
    mean_len: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Batched Eq-2 inversion. Flat (M,) float32 in, (M,) ndv out."""
    s2, m = _pad_to_tiles(size.astype(jnp.float32), 1.0)
    r2, _ = _pad_to_tiles(rows.astype(jnp.float32), 1.0)
    n2, _ = _pad_to_tiles(nulls.astype(jnp.float32), 0.0)
    l2, _ = _pad_to_tiles(mean_len.astype(jnp.float32), 1.0)
    rows_tiles = s2.shape[0] // BLOCK_M
    spec = pl.BlockSpec((BLOCK_M, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _dict_newton_body,
        out_shape=jax.ShapeDtypeStruct(s2.shape, jnp.float32),
        grid=(rows_tiles,),
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(s2, r2, n2, l2)
    return out.reshape(-1)[:m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def coupon_newton(
    m_obs: jnp.ndarray,
    n_draws: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Batched Eq-8 inversion. Flat (M,) float32 in, (M,) NDV out."""
    m2, m = _pad_to_tiles(m_obs.astype(jnp.float32), 1.0)
    n2, _ = _pad_to_tiles(n_draws.astype(jnp.float32), 2.0)
    rows_tiles = m2.shape[0] // BLOCK_M
    spec = pl.BlockSpec((BLOCK_M, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _coupon_newton_body,
        out_shape=jax.ShapeDtypeStruct(m2.shape, jnp.float32),
        grid=(rows_tiles,),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(m2, n2)
    return out.reshape(-1)[:m]
