"""Min/max diversity estimation via coupon-collector inversion (paper §5).

The n row-group minima are modeled as n uniform draws (with replacement)
from a population of NDV distinct values:

    E[m] = NDV * (1 - exp(-n / NDV))                            (Eq 7)

Given the observed distinct-extrema count m, invert

    g(NDV) = NDV * (1 - exp(-n/NDV)) - m = 0                    (Eq 8)

with Newton-Raphson and derivative

    g'(NDV) = 1 - exp(-n/NDV) * (1 + n/NDV)                     (Eq 9)

Separate estimates from m_min and m_max; keep the larger (paper §5.3).

Numerical notes:
  * g is monotonically increasing in NDV with g(NDV) -> n - m as NDV -> inf,
    so a root exists only when m < n. When m == n (every row group exposed a
    different extremum — the sorted case), the MLE diverges; we return the
    standard regularized estimate from the (m = n-1/2) continuity-corrected
    count, and flag saturation so the combiner can treat it as a lower bound.
  * We iterate in log-space (NDV = exp(t)) which keeps Newton stable for the
    huge dynamic range (NDV in [1, 1e12]).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEWTON_ITERS = 40
NEWTON_TOL = 1e-6

# Below this |x|, `expm1` sums its Taylor series instead of exp(x) - 1.
_EXPM1_SERIES_BOUND = 0.5
_EXPM1_TERMS = 10


def expm1(x: jnp.ndarray) -> jnp.ndarray:
    """exp(x) - 1 without cancellation near 0, from ops Pallas TPU lowers.

    The coupon-collector terms need 1 - e^{-r} at tiny r (NDV >> draws),
    where 1 - exp(-r) cancels to zero; `jnp.expm1` has no Pallas TPU
    lowering, and the fused kernel runs this math. For |x| < 0.5 the
    Taylor series in Horner form, x(1 + x/2(1 + x/3(...))), is accurate
    to a few ulp (truncation after 10 terms is below 1e-9 relative);
    elsewhere exp(x) - 1 loses at most a bit to cancellation.
    """
    x = jnp.asarray(x, jnp.float32)
    small = jnp.abs(x) < _EXPM1_SERIES_BOUND
    xs = jnp.where(small, x, 0.0)
    series = jnp.ones_like(xs)
    for k in range(_EXPM1_TERMS, 1, -1):
        series = 1.0 + xs / float(k) * series
    return jnp.where(small, xs * series, jnp.exp(x) - 1.0)


def coupon_expected(ndv: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """E[distinct] = NDV*(1-exp(-n/NDV)) (Eq 6), safe at ndv -> 0."""
    ndv = jnp.maximum(ndv, 1e-9)
    return ndv * -expm1(-n / ndv)


def coupon_derivative(ndv: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """g'(NDV) (Eq 9)."""
    ndv = jnp.maximum(ndv, 1e-9)
    r = n / ndv
    return -expm1(-r) - jnp.exp(-r) * r


class CouponInversionResult(NamedTuple):
    ndv: jnp.ndarray         # (B,) estimate
    saturated: jnp.ndarray   # (B,) bool — m ~= n, estimate is a lower bound
    iterations: jnp.ndarray  # (B,)


def invert_coupon(
    m: jnp.ndarray,
    n: jnp.ndarray,
    *,
    iters: int = NEWTON_ITERS,
    tol: float = NEWTON_TOL,
    backend: str = "auto",
) -> CouponInversionResult:
    """Solve Eq 8 for NDV given observed distinct count m out of n draws.

    Args:
      m: (B,) observed number of distinct extrema (1 <= m <= n).
      n: (B,) number of row groups (draws).
      backend: "auto"/"ref" solve here in jnp; "pallas" (or "auto" on TPU)
        routes the full inversion — including saturation handling — through
        the `repro.kernels` Pallas kernel.

    Returns:
      CouponInversionResult. For the saturated case (m == n) we return the
      inversion at m_eff = n - 0.5 (continuity correction) and set
      ``saturated`` so the caller treats it as a lower bound.
    """
    m = jnp.asarray(m, jnp.float32)
    n = jnp.asarray(n, jnp.float32)

    from repro.kernels import ops  # local: kernels.ref imports this module

    if ops.use_pallas(backend):
        from repro.kernels.newton_ndv import COUPON_ITERS

        ndv = ops.coupon_newton(
            m.reshape(-1), n.reshape(-1), backend="pallas"
        ).reshape(jnp.shape(m))
        return CouponInversionResult(
            ndv=ndv,
            saturated=m >= n - 0.5,
            iterations=jnp.full(jnp.shape(m), COUPON_ITERS, jnp.int32),
        )

    # Saturation band of half a coupon: observed counts are integral, and
    # the inversion is hopelessly ill-conditioned within < 0.5 of n anyway.
    saturated = m >= n - 0.5
    # Continuity-corrected observation for the saturated case.
    m_eff = jnp.where(saturated, jnp.maximum(n - 0.5, 0.5), m)
    m_eff = jnp.clip(m_eff, 0.5, jnp.maximum(n - 1e-3, 0.5))

    # Initial guess. Expanding Eq 7 to second order: m ~ n - n^2/(2 NDV)
    # => NDV0 ~ n^2 / (2 (n - m)). Good near saturation; clamp elsewhere.
    ndv0 = jnp.clip(n * n / (2.0 * jnp.maximum(n - m_eff, 1e-3)), 1.0, 1e12)
    t0 = jnp.log(ndv0)

    def body(i, carry):
        t, it = carry
        # A lane stops counting at convergence, so its count lags the step
        # index exactly when it is done (no bool loop carry: Mosaic cannot
        # carry one).
        done = it < i
        ndv = jnp.exp(t)
        g = coupon_expected(ndv, n) - m_eff
        gp = coupon_derivative(ndv, n)
        # d/dt g(exp(t)) = g'(ndv) * ndv
        step = g / jnp.maximum(gp * ndv, 1e-12)
        new_t = jnp.clip(t - step, 0.0, 28.0)  # NDV in [1, ~1.4e12]
        stop = done | (jnp.abs(g) <= tol * jnp.maximum(m_eff, 1.0))
        t = jnp.where(stop, t, new_t)
        it = it + jnp.where(stop, 0, 1).astype(jnp.int32)
        return t, it

    t, iters_used = jax.lax.fori_loop(
        0, iters, body, (t0, jnp.zeros_like(m, jnp.int32))
    )
    ndv = jnp.exp(t)
    # Saturated observations (m == n) carry no upper-bound information: the
    # MLE diverges, and the continuity-corrected root (~n^2/2) is far too
    # aggressive as a POINT estimate (it would dominate Eq 13's max). Report
    # the observable itself — m, a hard lower bound — and let the saturation
    # flag drive lower-bound semantics downstream.
    ndv = jnp.where(saturated, jnp.maximum(m, 1.0), ndv)
    # Degenerate inputs: n == 0 -> no information; m <= 1 -> at least 1 value.
    ndv = jnp.where(n <= 0, 1.0, ndv)
    ndv = jnp.where(m_eff <= 0.5001, jnp.maximum(m, 1.0), ndv)
    return CouponInversionResult(
        ndv=jnp.maximum(ndv, jnp.maximum(m, 1.0)),
        saturated=saturated,
        iterations=iters_used,
    )


class MinMaxDiversityResult(NamedTuple):
    ndv: jnp.ndarray          # (B,) max of min-side / max-side estimates
    ndv_from_min: jnp.ndarray
    ndv_from_max: jnp.ndarray
    saturated: jnp.ndarray    # (B,) bool — the winning side saturated
    iterations: jnp.ndarray   # (B,) int32 — Newton iterations, winning side


def estimate_minmax_diversity(
    m_min: jnp.ndarray,
    m_max: jnp.ndarray,
    n_groups: jnp.ndarray,
    *,
    backend: str = "auto",
) -> MinMaxDiversityResult:
    """Paper §5.3: invert both sides, retain the larger estimate."""
    lo = invert_coupon(m_min, n_groups, backend=backend)
    hi = invert_coupon(m_max, n_groups, backend=backend)
    take_hi = hi.ndv >= lo.ndv
    ndv = jnp.where(take_hi, hi.ndv, lo.ndv)
    # Bool select as logic: Mosaic cannot lower a select of bool vectors.
    saturated = (take_hi & hi.saturated) | (~take_hi & lo.saturated)
    return MinMaxDiversityResult(
        ndv=ndv,
        ndv_from_min=lo.ndv,
        ndv_from_max=hi.ndv,
        saturated=saturated,
        iterations=jnp.where(take_hi, hi.iterations, lo.iterations),
    )
