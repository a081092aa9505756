"""Dictionary size inversion (paper §4).

Inverts the dictionary-encoded storage equation

    S = ndv * len + (N - nulls) * ceil(log2(ndv)) / 8          (Eq 1)

for ``ndv`` via Newton-Raphson, using the *exact* residual f but a smooth
approximation of the derivative (the ceiling has zero derivative a.e.):

    f'(ndv) ~= len + (N - nulls) / (8 * ndv * ln 2)            (Eq 3)

Everything is vectorized over a batch of columns and expressed with
fixed-iteration ``lax.fori_loop`` so it jits cleanly and maps 1:1 onto the
Pallas kernel (`repro.kernels.newton_ndv`).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

NEWTON_ITERS = 32          # paper reports 5-10 to 1e-6; 32 is belt-and-braces
NEWTON_TOL = 1e-6
LN2 = 0.6931471805599453

# Eq 5 thresholds for plain-encoding fallback detection.
FALLBACK_NDV_RATIO = 0.9
FALLBACK_SIZE_LO = 0.8
FALLBACK_SIZE_HI = 1.2


def ceil_log2(ndv: jnp.ndarray) -> jnp.ndarray:
    """ceil(log2(ndv)) with ceil_log2(1) == 1 (1 bit minimum index width).

    Parquet's RLE/bit-packed hybrid needs at least 1 bit per index even for a
    single-entry dictionary, so we clamp below at 1 bit. Read exactly off
    the float32 bits (exponent, plus one unless the mantissa is zero), not
    off a float log2: log2 rounds differently per backend (the TPU kernel's
    can land just above k at 2^k), and one bit of index width moves Eq 1's
    root by rows/8/len.
    """
    x = jnp.maximum(jnp.asarray(ndv, jnp.float32), 1.0)
    raw = jax.lax.bitcast_convert_type(x, jnp.int32)
    exponent = (raw >> 23) - 127  # x >= 1: sign bit clear
    inexact = ((raw & 0x7FFFFF) != 0).astype(jnp.int32)
    return jnp.maximum(exponent + inexact, 1).astype(jnp.float32)


def smooth_log2(ndv: jnp.ndarray) -> jnp.ndarray:
    """Continuous relaxation of ceil(log2(ndv)) used for derivative only."""
    return jnp.maximum(jnp.log2(jnp.maximum(ndv, 1.0)), 1.0)


def dict_size_model(
    ndv: jnp.ndarray, mean_len: jnp.ndarray, non_null: jnp.ndarray
) -> jnp.ndarray:
    """Forward model: Eq 1 (what the writer's uncompressed size should be)."""
    return ndv * mean_len + non_null * ceil_log2(ndv) / 8.0


def residual(
    ndv: jnp.ndarray,
    size: jnp.ndarray,
    mean_len: jnp.ndarray,
    non_null: jnp.ndarray,
) -> jnp.ndarray:
    """Exact residual f(ndv) (Eq 2)."""
    return dict_size_model(ndv, mean_len, non_null) - size


def residual_derivative(
    ndv: jnp.ndarray, mean_len: jnp.ndarray, non_null: jnp.ndarray
) -> jnp.ndarray:
    """Smooth derivative approximation (Eq 3)."""
    return mean_len + non_null / (8.0 * jnp.maximum(ndv, 1.0) * LN2)


class DictInversionResult(NamedTuple):
    ndv: jnp.ndarray            # (B,) point estimate (>= 1)
    iterations: jnp.ndarray     # (B,) iterations to convergence
    converged: jnp.ndarray      # (B,) bool — |f| <= tol * scale at exit
    likely_fallback: jnp.ndarray  # (B,) bool — Eq 5 fired; treat as lower bound


def fallback_flags(
    size: jnp.ndarray,
    num_values: jnp.ndarray,
    null_count: jnp.ndarray,
    mean_len: jnp.ndarray,
) -> jnp.ndarray:
    """Eq 5 plain-encoding fallback indicator (closed form, solver-free).

    The first indicator uses the solver's degenerate-high-NDV interpretation
    S/len (the converged root absorbs index overhead and sits at
    (1 - bits/(8 len)) * rows for plain-encoded chunks, which would miss the
    0.9 threshold for narrow fixed-width types).
    """
    size = jnp.asarray(size, jnp.float32)
    non_null = jnp.maximum(
        jnp.asarray(num_values, jnp.float32)
        - jnp.asarray(null_count, jnp.float32),
        0.0,
    )
    mean_len = jnp.maximum(jnp.asarray(mean_len, jnp.float32), 1e-6)
    ndv_ratio = (size / mean_len) / jnp.maximum(non_null, 1.0)
    size_ratio = size / jnp.maximum(non_null * mean_len, 1e-6)
    return (
        (ndv_ratio >= FALLBACK_NDV_RATIO)
        & (size_ratio >= FALLBACK_SIZE_LO)
        & (size_ratio <= FALLBACK_SIZE_HI)
    )


def invert_dict_size(
    size: jnp.ndarray,
    num_values: jnp.ndarray,
    null_count: jnp.ndarray,
    mean_len: jnp.ndarray,
    *,
    iters: int = NEWTON_ITERS,
    tol: float = NEWTON_TOL,
    backend: str = "auto",
) -> DictInversionResult:
    """Solve Eq 2 for ndv, batched over columns.

    Args:
      size: (B,) total_uncompressed_size S in bytes.
      num_values: (B,) row count N.
      null_count: (B,) null count.
      mean_len: (B,) mean value byte length (Eq 4 / schema width).
      backend: execution route. "auto"/"ref" solve here in jnp — the route
        the fused megakernel's body (`repro.kernels.fused_estimate`) also
        takes, since a nested `pallas_call` is not allowed; "pallas" (or
        "auto" on TPU) routes the Newton solve through the `repro.kernels`
        Pallas kernel, with the Eq 5 flags and fixed iteration count filled
        in from the closed forms.

    Returns:
      DictInversionResult with ndv clamped to [1, N - nulls].
    """
    from repro.kernels import ops  # local: kernels.ref imports this module

    if ops.use_pallas(backend):
        shape = jnp.shape(size)
        mean_b = jnp.broadcast_to(jnp.asarray(mean_len, jnp.float32), shape)
        flat = lambda x: jnp.asarray(x, jnp.float32).reshape(-1)  # noqa: E731
        ndv = ops.dict_newton(
            flat(size), flat(num_values), flat(null_count), flat(mean_b),
            backend="pallas",
        ).reshape(shape)
        # The kernel is fixed-iteration and branch-free: it always runs
        # DICT_ITERS steps and converges by construction on Eq 2's
        # monotone residual.
        from repro.kernels.newton_ndv import DICT_ITERS

        return DictInversionResult(
            ndv=ndv,
            iterations=jnp.full(shape, DICT_ITERS, jnp.int32),
            converged=jnp.ones(shape, bool),
            likely_fallback=fallback_flags(
                size, num_values, null_count, mean_len
            ),
        )

    size = jnp.asarray(size, jnp.float32)
    non_null = jnp.maximum(
        jnp.asarray(num_values, jnp.float32) - jnp.asarray(null_count, jnp.float32),
        0.0,
    )
    mean_len = jnp.maximum(jnp.asarray(mean_len, jnp.float32), 1e-6)

    # Initial guess: index overhead assumed small (paper §4.2).
    ndv0 = jnp.maximum(size / mean_len, 1.0)

    # Relative tolerance scale: sizes span bytes..TB, so scale by S.
    scale = jnp.maximum(size, 1.0)

    def body(i, carry):
        ndv, it = carry
        # A lane stops counting at convergence, so its count lags the step
        # index exactly when it is done (no bool loop carry: Mosaic cannot
        # carry one).
        done = it < i
        f = residual(ndv, size, mean_len, non_null)
        fp = residual_derivative(ndv, mean_len, non_null)
        step = f / fp
        new_ndv = jnp.clip(ndv - step, 1.0, jnp.maximum(non_null, 1.0))
        stop = done | (jnp.abs(f) <= tol * scale)
        ndv = jnp.where(stop, ndv, new_ndv)
        it = it + jnp.where(stop, 0, 1).astype(jnp.int32)
        return ndv, it

    ndv, iters_used = jax.lax.fori_loop(
        0, iters, body, (ndv0, jnp.zeros_like(size, jnp.int32))
    )
    converged = iters_used < iters
    # The ceiling makes f piecewise-linear in ndv with jumps at powers of 2;
    # after Newton converges on the smooth surrogate's root, snap within the
    # final bit-width plateau by re-solving the linear piece exactly:
    #   ndv = (S - non_null*bits/8) / len   with bits = ceil_log2(ndv*)
    bits = ceil_log2(ndv)
    linear_ndv = (size - non_null * bits / 8.0) / mean_len
    # Only accept the snap if it stays inside the same bit plateau.
    same_plateau = ceil_log2(jnp.maximum(linear_ndv, 1.0)) == bits
    ndv = jnp.where(
        same_plateau & (linear_ndv >= 1.0),
        linear_ndv,
        ndv,
    )
    ndv = jnp.clip(ndv, 1.0, jnp.maximum(non_null, 1.0))

    likely_fallback = fallback_flags(size, num_values, null_count, mean_len)
    return DictInversionResult(
        ndv=ndv,
        iterations=iters_used,
        converged=converged,
        likely_fallback=likely_fallback,
    )


def invert_dict_size_scalar(
    size: float, num_values: float, null_count: float, mean_len: float
) -> Tuple[float, bool]:
    """Convenience scalar wrapper. Returns (ndv, likely_fallback)."""
    res = invert_dict_size(
        jnp.asarray([size]),
        jnp.asarray([num_values]),
        jnp.asarray([null_count]),
        jnp.asarray([mean_len]),
    )
    return float(res.ndv[0]), bool(res.likely_fallback[0])
