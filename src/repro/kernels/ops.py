"""Public jit'd entry points for the NDV kernels.

Dispatch policy: on TPU the Pallas kernels run compiled; elsewhere they run
in ``interpret=True`` mode (bit-faithful kernel-body execution on CPU). The
``backend`` argument forces either path or the pure-jnp reference
(``"ref"``) — benchmarks use that to measure kernel-vs-XLA deltas.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import fused_estimate as _fused
from repro.kernels import hll as _hll
from repro.kernels import minmax_scan as _mm
from repro.kernels import newton_ndv as _newton
from repro.kernels import ref as _ref

Backend = Literal["auto", "pallas", "ref"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def use_pallas(backend: Backend) -> bool:
    """Resolve an estimator-level backend request to a kernel-path decision.

    "pallas" always takes the kernels (interpreted off-TPU — bit-faithful
    but slow, a correctness knob). "ref" never does. "auto" takes them only
    where they are the fast path (compiled on TPU); elsewhere the jnp
    reference IS the production path, so "auto" resolves to it.
    """
    if backend == "pallas":
        return True
    if backend == "ref":
        return False
    return _on_tpu()


def use_fused(fuse: str) -> bool:
    """Resolve the `EngineConfig.fuse` knob to a fused-pipeline decision.

    "on" always takes the fused pipeline; "off" never does; "auto" takes it
    exactly where fusing buys anything — on TPU, where the separate path
    costs 3-4 kernel launches plus XLA glue per estimate. The fused pipeline
    computes the REFERENCE numerics (`fused_estimate`'s body runs
    `estimate_tile(..., backend="ref")`), and `fused_estimate` below
    only compiles the kernel where the kernel path is the production path —
    elsewhere the pure-XLA twin runs, which is the same program as the
    unfused reference path. That is why the knob is numerics-neutral and
    never enters `cache_key`/`cache_token`.
    """
    if fuse == "off":
        return False
    if fuse == "on":
        return True
    if fuse != "auto":
        raise ValueError(f'fuse must be "auto", "on", or "off", got {fuse!r}')
    return _on_tpu()


def fused_estimate(batch, schema_bound=None, *, mode: str = "paper",
                   backend: Backend = "auto"):
    """One-dispatch §4-§7 pipeline over a packed ColumnBatch (megakernel).

    Backend resolution mirrors `use_pallas`: the Pallas megakernel runs
    where the kernel path is production (compiled on TPU) or explicitly
    pinned (``backend="pallas"``, interpreted off-TPU — the validation
    configuration). Otherwise the pure-jnp twin (`ref.ref_fused_estimate`)
    serves — bit-identical to the unfused reference path by construction.
    """
    if use_pallas(backend):
        return _fused.fused_estimate(
            batch, schema_bound, mode=mode, interpret=_interpret()
        )
    return _ref.ref_fused_estimate(batch, schema_bound, mode=mode)


def dict_newton(size, rows, nulls, mean_len, *, backend: Backend = "auto"):
    """Batched Eq-2 dictionary-size inversion (flat float32 arrays)."""
    if backend == "ref":
        return _ref.ref_dict_newton(size, rows, nulls, mean_len)
    return _newton.dict_newton(
        size, rows, nulls, mean_len, interpret=_interpret()
    )


def coupon_newton(m_obs, n_draws, *, backend: Backend = "auto"):
    """Batched Eq-8 coupon-collector inversion (flat float32 arrays)."""
    if backend == "ref":
        return _ref.ref_coupon_newton(m_obs, n_draws)
    return _newton.coupon_newton(m_obs, n_draws, interpret=_interpret())


def minmax_scan(mins, maxs, valid, *, backend: Backend = "auto"):
    """Detector metric reductions over (B, R) row-group statistics."""
    if backend == "ref":
        return _ref.ref_minmax_scan(mins, maxs, valid)
    return _mm.minmax_scan(mins, maxs, valid, interpret=_interpret())


def hll_fold(keys, valid, *, p: int = 8, backend: Backend = "auto"):
    """HLL register fold over (B, R) uint32 keys -> (B, 2^p) registers."""
    if backend == "ref":
        return _ref.ref_hll_fold(keys, valid, p=p)
    return _hll.hll_fold(keys, valid, p=p, interpret=_interpret())


def hll_count(registers):
    """Register banks -> cardinality estimates."""
    return _hll.hll_count(registers)
