"""Cross-dataset super-pack execution: many estimate jobs, few engine calls.

A batched RPC (`POST /batch`) hands the serving tier T cold
(catalog, mode, bounds) tuples at once. Running them as T `estimate()`
calls costs T engine dispatches; this module concatenates the jobs'
already-packed (and device-resident) `ColumnBatch`es along the B axis —
`repro.catalog.packer.concat_batches` — and runs one composed-strategy
engine call per compatibility group, then materializes each job's
estimates from its own lane span (`estimates_from_batch(offset=...)`).

Jobs group by (engine, mode, R):

  * engine — jobs pinned to different engines cannot share a dispatch;
  * mode — a static jit argument of `estimate_batch`;
  * R (the packed row-group axis) — same-R batches concatenate with zero
    re-padding, which keeps every lane's result BIT-IDENTICAL to the
    job's standalone `estimate()`. That exactness is load-bearing: the
    stats tier's state-derived ETags promise one deterministic body per
    tag, so a super-packed replica and a sequential replica must emit
    the same bytes. (Ragged-R concat is masked-correct but lets masked
    R reductions re-associate, so it is deliberately not used here.)

Results are read through and written back to each catalog's estimate
cache (`estimate_cache_peek` / `estimate_cache_store`): a warm job costs
a dict hit, a cold job's result is spillable and LRU-managed exactly as
if `estimate()` had produced it.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.catalog.packer import concat_batches
from repro.core.ndv.estimator import estimates_from_batch, provenance_from_batch
from repro.core.ndv.types import NDVEstimate
from repro.obs import span as _obs_span

import numpy as np


class SuperpackJob(NamedTuple):
    """One estimate request against one catalog."""

    catalog: object  # StatsCatalog
    mode: str = "paper"
    schema_bounds: Optional[Dict[str, float]] = None


class SuperpackResult(NamedTuple):
    """Per-job estimate maps plus execution counters (test material)."""

    estimates: List[Dict[str, NDVEstimate]]
    engine_calls: int    # engine dispatches performed (0 if all warm)
    cold_jobs: int       # jobs that missed their catalog's cache


class _ColdJob(NamedTuple):
    index: int           # position in the caller's job list
    job: SuperpackJob
    key: tuple           # the catalog cache key to fill
    batch: object        # the catalog's packed ColumnBatch


def superpack_estimate(
    jobs: List[SuperpackJob], *, engine=None
) -> SuperpackResult:
    """Run many (catalog, mode, bounds) estimate jobs, batched.

    Returns one estimate map per job, in order, each `==` (bit-identical
    to) what `job.catalog.estimate(mode=..., schema_bounds=...)` returns.
    Warm jobs are served from their catalog's cache; all cold jobs of a
    compatibility group execute as ONE engine call over the concatenated
    batch. `engine` overrides every job's engine (the service tier pins
    its own); None uses each catalog's.
    """
    results: List[Optional[Dict[str, NDVEstimate]]] = [None] * len(jobs)
    groups: Dict[tuple, List[_ColdJob]] = {}
    engines: Dict[tuple, object] = {}
    cold = 0
    for i, job in enumerate(jobs):
        eng = engine or job.catalog.engine
        key = job.catalog.estimate_key(
            mode=job.mode, schema_bounds=job.schema_bounds, engine=eng
        )
        cached = job.catalog.estimate_cache_peek(key)
        if cached is not None:
            results[i] = cached
            continue
        if not job.catalog.column_names:
            results[i] = {}
            continue
        cold += 1
        batch = job.catalog.packed_batch()
        gkey = (id(eng), job.mode, batch.max_groups)
        engines[gkey] = eng
        groups.setdefault(gkey, []).append(_ColdJob(i, job, key, batch))

    engine_calls = 0
    for gkey, members in groups.items():
        eng = engines[gkey]
        _run_group(eng, members, results)
        engine_calls += 1
    return SuperpackResult(
        estimates=results, engine_calls=engine_calls, cold_jobs=cold
    )


def _run_group(eng, members: List[_ColdJob], results: list) -> None:
    """One engine call for one (engine, mode, R) group of cold jobs."""
    mode = members[0].job.mode
    batches = [m.batch for m in members]
    total = sum(b.batch for b in batches)
    R = batches[0].max_groups
    # Bound trace shapes the same way individual packs are bounded: round
    # the concatenated width up to the engine packer's bucket for it.
    target_b, _ = eng.make_packer().shape_for(total, R)
    batch = concat_batches(batches, pad_to=target_b)

    offsets = []
    lo = 0
    for b in batches:
        offsets.append(lo)
        lo += b.batch

    sb = None
    if any(m.job.schema_bounds for m in members):
        # Per-job bound lanes at each job's offset; +inf elsewhere is the
        # combine step's identity, same as the engine's own materialization.
        arr = np.full(batch.batch, np.inf, np.float32)
        for m, off in zip(members, offsets):
            if m.job.schema_bounds:
                part = m.job.catalog.bounds_array(
                    m.job.schema_bounds, m.batch.batch
                )
                arr[off:off + m.batch.batch] = part
        sb = jnp.asarray(arr)

    out = eng.estimate(batch, sb, mode=mode)
    with _obs_span("engine.device_wait"):
        jax.block_until_ready(out)
    with _obs_span("engine.d2h", jobs=len(members), batch=int(batch.batch)):
        for m, off in zip(members, offsets):
            names = m.job.catalog.column_names
            ests = estimates_from_batch(out, batch, names, offset=off)
            result = {e.column_name: e for e in ests}
            m.job.catalog.estimate_cache_store(m.key, result)
            # Same lane span, same output — the super-packed path fills the
            # provenance cache exactly as a standalone estimate() would.
            provs = provenance_from_batch(out, batch, names, offset=off)
            m.job.catalog.provenance_cache_store(
                m.key, {p.column_name: p for p in provs}
            )
            results[m.index] = dict(result)
