"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``ref_*`` mirrors its kernel's numerics exactly (same iteration counts,
same clamps) so tests can assert_allclose with tight tolerances across shape
and dtype sweeps.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.ndv import dict_inversion, minmax_diversity
from repro.kernels import hll as hll_kernel


def ref_dict_newton(
    size: jnp.ndarray,
    rows: jnp.ndarray,
    nulls: jnp.ndarray,
    mean_len: jnp.ndarray,
) -> jnp.ndarray:
    """Oracle for newton_ndv.dict_newton (flat arrays)."""
    # backend="ref" pins the pure-jnp solve: with the default "auto" the
    # inversion would route back through the Pallas kernel on TPU and the
    # oracle would compare the kernel against itself.
    return dict_inversion.invert_dict_size(
        size, rows, nulls, mean_len, backend="ref"
    ).ndv


def ref_coupon_newton(m_obs: jnp.ndarray, n_draws: jnp.ndarray) -> jnp.ndarray:
    """Oracle for newton_ndv.coupon_newton (flat arrays)."""
    return minmax_diversity.invert_coupon(m_obs, n_draws, backend="ref").ndv


class RefMinMaxMetrics(NamedTuple):
    overlap_sum: jnp.ndarray
    gmin: jnp.ndarray
    gmax: jnp.ndarray
    sign_changes: jnp.ndarray
    n_valid: jnp.ndarray
    shared_bounds: jnp.ndarray


def ref_minmax_scan(
    mins: jnp.ndarray, maxs: jnp.ndarray, valid: jnp.ndarray
) -> RefMinMaxMetrics:
    """Oracle for minmax_scan.minmax_scan."""
    mins = jnp.asarray(mins, jnp.float32)
    maxs = jnp.asarray(maxs, jnp.float32)
    valid = jnp.asarray(valid, bool)
    big = jnp.float32(3.0e38)
    n = jnp.sum(valid, axis=1).astype(jnp.float32)
    gmin = jnp.min(jnp.where(valid, mins, big), axis=1)
    gmax = jnp.max(jnp.where(valid, maxs, -big), axis=1)
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
    return RefMinMaxMetrics(overlap, gmin, gmax, changes, n, shared)


def ref_fused_estimate(batch, schema_bound=None, *, mode: str = "paper"):
    """Oracle for fused_estimate.fused_estimate — the same core, no kernel.

    The megakernel body runs the reference pipeline
    (``estimate_tile(..., backend="ref")``) on its tile refs; this twin
    runs it outside any kernel through `estimate_batch_core`, materializing the
    absent schema bound as +inf the same way the kernel wrapper does. It is
    also the off-TPU serving path for ``fuse="on"`` (see `ops.fused_estimate`),
    which is what makes the fuse knob bit-neutral there by construction.
    """
    # local: estimator imports repro.kernels.ops lazily; importing it at
    # module scope here would close the cycle ops -> ref -> estimator.
    from repro.core.ndv.estimator import estimate_batch_core

    if schema_bound is None:
        schema_bound = jnp.full((batch.batch,), jnp.inf, jnp.float32)
    return estimate_batch_core(
        batch, schema_bound, mode=mode, backend="ref"
    )


def ref_hll_fold(keys: jnp.ndarray, valid: jnp.ndarray, *, p: int = 8) -> jnp.ndarray:
    """Oracle for hll.hll_fold — scatter-max formulation."""
    b, _ = keys.shape
    m = 1 << p
    nbits = 32 - p
    h = hll_kernel._murmur32(keys.astype(jnp.uint32))
    idx = (h >> (32 - p)).astype(jnp.int32)
    rest = (h << p).astype(jnp.uint32)
    rho = jnp.minimum(hll_kernel._clz32(rest) + 1, nbits + 1)
    rho = jnp.where(jnp.asarray(valid, bool), rho, 0)

    def per_col(idx_r, rho_r):
        return jnp.zeros((m,), jnp.float32).at[idx_r].max(rho_r.astype(jnp.float32))

    return jax.vmap(per_col)(idx, rho)
