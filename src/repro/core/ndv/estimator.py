"""Top-level zero-cost NDV estimator (paper §3-§7 end to end).

`estimate_batch` is the pure jit-compiled per-shard kernel: metadata arrays
in, estimates out, no knowledge of devices or batch budgets. Execution —
local vs sharded vs chunked, and the kernel backend knob — is owned by
`repro.engine.EstimationEngine`, which every consumer (catalog, pipeline,
planner, benchmarks) routes through. `estimate_columns` is the convenience
object API over `ColumnMetadata`, delegating to the default engine.

Pipeline per column (all batched over B columns x R chunks):
  1. distribution detection from (min_i, max_i) patterns         (§6)
  2. PER-CHUNK dictionary size inversion w/ fallback detection,
     aggregated across chunks by masked max                      (§4)
  3. min/max diversity via coupon-collector inversion            (§5)
  4. hybrid combination + type/schema bounds                     (§7)

Why max-aggregation for §4: each chunk's dictionary holds the distinct
values OF THAT CHUNK, so a chunk inversion lower-bounds the global NDV. When
values are well-spread, every chunk sees nearly all distinct values and the
max is tight; when sorted, each chunk sees ~NDV/n values and the max
underestimates — exactly the complementarity of paper Table 1.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ndv import combine as combine_mod
from repro.core.ndv import dict_inversion, distribution, improved, minmax_diversity
from repro.core.ndv.types import ColumnBatch, ColumnMetadata, Layout, NDVEstimate


class BatchEstimates(NamedTuple):
    """Struct-of-arrays estimation output for B columns.

    The trailing provenance fields (route onward) are per-lane diagnostics
    of HOW each estimate was produced. They are emitted by the same
    single-definition pipeline body as the estimates themselves — fused and
    unfused paths, every engine strategy — so they obey the identical
    bit-parity contract, and they never enter cache keys or ETags.
    """

    ndv: jnp.ndarray
    ndv_dict: jnp.ndarray
    ndv_minmax: jnp.ndarray
    layout: jnp.ndarray
    is_lower_bound: jnp.ndarray
    confidence: jnp.ndarray
    overlap_ratio: jnp.ndarray
    monotonicity: jnp.ndarray
    mean_len: jnp.ndarray
    dict_iterations: jnp.ndarray
    route: jnp.ndarray             # int32 — combine.ROUTE_DICT / ROUTE_MINMAX
    route_margin: jnp.ndarray      # float32 in [0, 1) — Eq 13 decisiveness
    detector_margin: jnp.ndarray   # float32 — distance to nearest §6 threshold
    dict_residual: jnp.ndarray     # float32 — worst normalized Eq 2 residual
    coupon_iterations: jnp.ndarray  # int32 — §5 Newton iters, winning side
    clamp_flags: jnp.ndarray       # int32 — combine.CLAMP_* bounds that bit


def dict_estimate_column(
    batch: ColumnBatch,
    *,
    backend: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """§4 per-chunk inversion -> (ndv_dict, likely_fallback, iters, residual).

    Chunks whose writer-recorded encoding is plain are excluded from the max
    (their S does not obey Eq 1); if ALL chunks of a column are plain, the
    column-level fallback flag is raised and ndv_dict falls back to the
    plain-size implied bound S/len ~ rows (a lower-bound signal).

    ``residual`` is the worst |Eq 2 residual| / S across the column's valid
    chunks at the converged roots — the solver's own error signal, surfaced
    for provenance (a large value means Eq 1 never fit that chunk's size).

    ``batch`` is in column form and so are the results: (B, 1) per column.
    """
    inv = dict_inversion.invert_dict_size(
        batch.chunk_S,
        batch.chunk_rows,
        batch.chunk_nulls,
        batch.mean_len,
        backend=backend,
    )
    usable = batch.valid & batch.chunk_dict_encoded & ~inv.likely_fallback
    neg = jnp.float32(-1.0)
    ndv_usable = jnp.max(
        jnp.where(usable, inv.ndv, neg), axis=-1, keepdims=True
    )
    # Fallback path: no usable dictionary chunk -> max over ALL valid chunks
    # (plain chunks invert to ~rows; Eq 5 semantics: a lower bound).
    ndv_any = jnp.max(
        jnp.where(batch.valid, inv.ndv, neg), axis=-1, keepdims=True
    )
    no_usable = ndv_usable < 0.0
    ndv_col = jnp.where(no_usable, ndv_any, ndv_usable)
    ndv_col = jnp.maximum(ndv_col, 1.0)
    fallback_col = no_usable
    iters = jnp.max(
        jnp.where(batch.valid, inv.iterations, 0), axis=-1, keepdims=True
    )
    chunk_non_null = jnp.maximum(batch.chunk_rows - batch.chunk_nulls, 0.0)
    resid = jnp.abs(
        dict_inversion.residual(
            inv.ndv, batch.chunk_S, batch.mean_len, chunk_non_null
        )
    ) / jnp.maximum(batch.chunk_S, 1.0)
    resid = jnp.max(jnp.where(batch.valid, resid, 0.0), axis=-1, keepdims=True)
    return ndv_col, fallback_col, iters, resid.astype(jnp.float32)


# The ColumnBatch fields that hold one value per column.
_PER_COLUMN = (
    "N", "nulls", "n_groups", "m_min", "m_max", "mean_len", "len_sample",
    "fixed_width", "int_like", "single_byte",
)


def estimate_batch_core(
    batch: ColumnBatch,
    schema_bound: Optional[jnp.ndarray] = None,
    *,
    mode: str = "paper",
    backend: str = "auto",
) -> BatchEstimates:
    """The unjitted §4-§7 pipeline body: ColumnBatch in, (B,) estimates out.

    Shared verbatim by the unfused `estimate_batch` path and (with
    ``backend="ref"``) by the fused megakernel's oracle
    (`repro.kernels.ref.ref_fused_estimate`); the megakernel's body runs
    `estimate_tile`, the same pipeline in column form — one definition of
    the numerics is what makes the fuse knob provably numerics-neutral.
    """
    cols = dataclasses.replace(
        batch, **{f: getattr(batch, f)[:, None] for f in _PER_COLUMN}
    )
    sb = None if schema_bound is None else schema_bound[:, None]
    est = estimate_tile(cols, sb, mode=mode, backend=backend)
    return BatchEstimates(*(x[:, 0] for x in est))


def estimate_tile(
    batch: ColumnBatch,
    schema_bound: Optional[jnp.ndarray] = None,
    *,
    mode: str = "paper",
    backend: str = "auto",
) -> BatchEstimates:
    """The §4-§7 pipeline in column form.

    Every per-column value, in ``batch``, ``schema_bound`` and the result,
    is a (B, 1) column rather than a (B,) vector, and every reduction over
    the row-group axis keeps its dims. That keeps the whole pipeline 2-D,
    the form a Pallas TPU kernel body can lower (Mosaic has no 1-D bool
    vectors), so the megakernel runs this function on its tiles as is.
    """
    # --- §6: distribution detection --------------------------------------
    metrics = distribution.detect_distribution(
        batch.mins, batch.maxs, batch.valid, backend=backend, keepdims=True
    )

    # --- §4: dictionary size inversion (per chunk -> column aggregate) ----
    if mode == "improved":
        imp = improved.improved_dict_estimate(
            batch, metrics.overlap_ratio, backend=backend
        )
        ndv_dict, likely_fallback = imp.ndv, imp.likely_fallback
        _, _, dict_iters, dict_resid = dict_estimate_column(
            batch, backend=backend
        )
    else:
        ndv_dict, likely_fallback, dict_iters, dict_resid = (
            dict_estimate_column(batch, backend=backend)
        )

    # --- §5: min/max diversity --------------------------------------------
    mm = minmax_diversity.estimate_minmax_diversity(
        batch.m_min,
        batch.m_max,
        batch.n_groups.astype(jnp.float32),
        backend=backend,
    )

    # --- §7: combine -------------------------------------------------------
    big = jnp.float32(3.4e38)
    gmin = jnp.min(
        jnp.where(batch.valid, batch.mins, big), axis=-1, keepdims=True
    )
    gmax = jnp.max(
        jnp.where(batch.valid, batch.maxs, -big), axis=-1, keepdims=True
    )
    non_null = batch.N - batch.nulls
    # Clustered signature: range overlap says "well-spread" while the
    # extrema diversity saturates — runs are hiding the domain tail.
    n_f = batch.n_groups.astype(jnp.float32)
    suspect_clustered = (
        (metrics.layout == int(Layout.WELL_SPREAD))
        & mm.saturated
        & (n_f >= 8.0)
    ) if mode == "improved" else None
    comb = combine_mod.combine_estimates(
        ndv_dict,
        mm.ndv,
        non_null=non_null,
        layout=metrics.layout,
        likely_fallback=likely_fallback,
        minmax_saturated=mm.saturated,
        int_like=batch.int_like,
        gmin=gmin,
        gmax=gmax,
        single_byte=batch.single_byte,
        len_sample=batch.len_sample,
        schema_bound=schema_bound,
        suspect_clustered=suspect_clustered,
    )
    # Detector margin: distance of the (overlap, monotonicity) metrics to
    # the NEAREST §6 classification threshold. A small margin means the
    # layout class — and with it the aggregation route — was a near-tie.
    ov, mono = metrics.overlap_ratio, metrics.monotonicity
    detector_margin = jnp.minimum(
        jnp.minimum(
            jnp.minimum(
                jnp.abs(ov - distribution.SORTED_OVERLAP),
                jnp.abs(mono - distribution.SORTED_MONO),
            ),
            jnp.minimum(
                jnp.abs(ov - distribution.PSEUDO_OVERLAP),
                jnp.abs(mono - distribution.PSEUDO_MONO),
            ),
        ),
        jnp.abs(ov - distribution.WELL_SPREAD_OVERLAP),
    ).astype(jnp.float32)
    return BatchEstimates(
        ndv=comb.ndv,
        ndv_dict=ndv_dict,
        ndv_minmax=mm.ndv,
        layout=metrics.layout,
        is_lower_bound=comb.is_lower_bound,
        confidence=comb.confidence,
        overlap_ratio=metrics.overlap_ratio,
        monotonicity=metrics.monotonicity,
        mean_len=batch.mean_len,
        dict_iterations=dict_iters,
        route=comb.route,
        route_margin=comb.route_margin,
        detector_margin=detector_margin,
        dict_residual=dict_resid,
        coupon_iterations=mm.iterations,
        clamp_flags=comb.clamp_flags,
    )


@functools.partial(jax.jit, static_argnames=("mode", "backend", "fuse"))
def estimate_batch(
    batch: ColumnBatch,
    schema_bound: Optional[jnp.ndarray] = None,
    *,
    mode: str = "paper",
    backend: str = "auto",
    fuse: str = "auto",
) -> BatchEstimates:
    """Vectorized zero-cost NDV estimation over a ColumnBatch.

    This is the pure per-shard kernel: the `repro.engine` package is the
    public path onto it and owns sharding/chunking of the B axis.

    Args:
      mode: "paper" — faithful reproduction (per-chunk max + Eq 13 hybrid);
            "improved" — beyond-paper layout-aware aggregation
            (coverage-corrected mean / disjoint-sum routing, see improved.py).
      backend: `repro.kernels.ops` execution knob, threaded through the
        engine config. "auto" = fastest correct path per platform (Pallas
        kernels on TPU, jnp reference elsewhere); "pallas"/"ref" force one.
      fuse: megakernel routing knob ("auto"/"on"/"off", threaded from
        `EngineConfig.fuse`). "on" (and "auto" on TPU) runs the whole §4-§7
        pipeline as one fused computation of the REFERENCE numerics: a
        single `pallas_call` (`repro.kernels.fused_estimate`) where the
        kernel path is production, the pure-XLA twin elsewhere — instead of
        3-4 kernel dispatches plus XLA glue. Numerics-neutral by the engine
        parity contract (the fused body runs `estimate_tile`, the body of
        `estimate_batch_core`, with the reference backend), so the knob never enters
        `cache_key`/`cache_token`. "off" pins the unfused per-stage path.
    """
    from repro.kernels import ops  # local: kernels.ref imports this module

    if ops.use_fused(fuse):
        return ops.fused_estimate(batch, schema_bound, mode=mode, backend=backend)
    return estimate_batch_core(batch, schema_bound, mode=mode, backend=backend)


def estimates_from_batch(
    out: BatchEstimates, batch: ColumnBatch, names: Sequence[str],
    *, offset: int = 0
) -> List[NDVEstimate]:
    """Materialize per-column NDVEstimate objects from batched output.

    `names` may be shorter than the batch axis: the packer pads B up to a
    shape bucket, and the padding lanes carry no column. `offset` selects
    where on the B axis the named lanes start — a super-packed batch
    (`repro.catalog.superpack`) concatenates several column sets along B
    and materializes each set from its own lane span.

    Each field is pulled to the host once (one device-to-host copy per
    field, not one per column) and indexed as numpy from there — per-column
    indexing of device arrays would dispatch a device gather per scalar,
    shipping every Python index host-to-device, which both scales badly on
    wide catalogs and breaks the catalog's zero-H2D warm-path contract.
    """
    host = {f: np.asarray(getattr(out, f)) for f in out._fields}
    len_sample = np.asarray(batch.len_sample)
    res: List[NDVEstimate] = []
    for j, name in enumerate(names):
        i = offset + j
        res.append(
            NDVEstimate(
                ndv=float(host["ndv"][i]),
                ndv_dict=float(host["ndv_dict"][i]),
                ndv_minmax=float(host["ndv_minmax"][i]),
                layout=Layout(int(host["layout"][i])),
                is_lower_bound=bool(host["is_lower_bound"][i]),
                mean_len=float(host["mean_len"][i]),
                len_sample_size=int(len_sample[i]),
                overlap_ratio=float(host["overlap_ratio"][i]),
                monotonicity=float(host["monotonicity"][i]),
                confidence=float(host["confidence"][i]),
                column_name=name,
            )
        )
    return res


ROUTE_NAMES = {
    int(combine_mod.ROUTE_MINMAX): "minmax",
    int(combine_mod.ROUTE_DICT): "dict",
}

_CLAMP_NAMES = (
    (combine_mod.CLAMP_NON_NULL, "non_null"),
    (combine_mod.CLAMP_INT_RANGE, "int_range"),
    (combine_mod.CLAMP_SINGLE_BYTE, "single_byte"),
    (combine_mod.CLAMP_SCHEMA, "schema_bound"),
)


def clamp_names(flags: int) -> List[str]:
    """Human-readable names of the CLAMP_* bits set in ``flags``."""
    return [name for bit, name in _CLAMP_NAMES if flags & bit]


@dataclasses.dataclass(frozen=True)
class Provenance:
    """How one column's estimate was produced (per-lane diagnostics).

    Deliberately a SEPARATE record from `NDVEstimate`: estimate identity
    (bodies, ETags, caches, spills) is derived by iterating NDVEstimate's
    fields, so diagnostics must live outside it to stay bit-neutral.
    Attached to responses only on explicit `?explain=1` request.
    """

    column_name: str
    route: str              # "dict" (§4 won Eq 13's max) or "minmax" (§5)
    route_margin: float     # [0, 1): 0 = the two signals tied
    detector_margin: float  # distance to the nearest §6 threshold
    overlap_ratio: float
    monotonicity: float
    layout: str
    dict_iterations: int    # §4 Newton iterations (max over chunks)
    dict_residual: float    # worst |Eq 2 residual| / S at the roots
    coupon_iterations: int  # §5 Newton iterations, winning side
    clamp_flags: int        # raw combine.CLAMP_* bitmask
    clamps: tuple           # decoded clamp names, e.g. ("schema_bound",)
    schema_bound_hit: bool
    is_lower_bound: bool
    confidence: float


def provenance_from_batch(
    out: BatchEstimates, batch: ColumnBatch, names: Sequence[str],
    *, offset: int = 0
) -> List[Provenance]:
    """Materialize per-column Provenance from batched output.

    Mirrors `estimates_from_batch` (one device-to-host copy per field,
    `offset` selects the lane span of a super-packed batch). Reads ONLY
    `out` — callers that cached the BatchEstimates can materialize
    provenance later without re-running the engine.
    """
    host = {
        f: np.asarray(getattr(out, f))
        for f in (
            "route", "route_margin", "detector_margin", "dict_iterations",
            "dict_residual", "coupon_iterations", "clamp_flags", "layout",
            "overlap_ratio", "monotonicity", "is_lower_bound", "confidence",
        )
    }
    res: List[Provenance] = []
    for j, name in enumerate(names):
        i = offset + j
        flags = int(host["clamp_flags"][i])
        res.append(
            Provenance(
                column_name=name,
                route=ROUTE_NAMES[int(host["route"][i])],
                route_margin=float(host["route_margin"][i]),
                detector_margin=float(host["detector_margin"][i]),
                overlap_ratio=float(host["overlap_ratio"][i]),
                monotonicity=float(host["monotonicity"][i]),
                layout=Layout(int(host["layout"][i])).name,
                dict_iterations=int(host["dict_iterations"][i]),
                dict_residual=float(host["dict_residual"][i]),
                coupon_iterations=int(host["coupon_iterations"][i]),
                clamp_flags=flags,
                clamps=tuple(clamp_names(flags)),
                schema_bound_hit=bool(flags & combine_mod.CLAMP_SCHEMA),
                is_lower_bound=bool(host["is_lower_bound"][i]),
                confidence=float(host["confidence"][i]),
            )
        )
    return res


_PROVENANCE_FIELDS = tuple(f.name for f in dataclasses.fields(Provenance))


def provenance_to_json(p: Provenance) -> dict:
    """JSON-representable dict form (lists instead of tuples).

    Built by direct attribute access, not `dataclasses.asdict` — asdict
    runs the recursive deep-copy machinery, which dominated the warm
    explain path (every `?explain=1` response serializes every column).
    """
    d = {name: getattr(p, name) for name in _PROVENANCE_FIELDS}
    d["clamps"] = list(p.clamps)
    return d


def record_provenance_metrics(provs: Sequence[Provenance]) -> None:
    """Observe freshly-computed provenance into the metrics registry.

    Called once per engine run at materialization time (never on cache
    hits), so the `ndv_route_total` / `ndv_newton_iters` /
    `ndv_detector_margin` series count estimator work, not request traffic.
    """
    from repro.obs import metrics as obs_metrics

    reg = obs_metrics.registry()
    route_total = reg.counter(
        "ndv_route_total", "estimates produced per winning estimator route"
    )
    newton = reg.histogram(
        "ndv_newton_iters",
        "Newton iterations per estimate, by solver",
        buckets=obs_metrics.ITER_BUCKETS,
    )
    margin = reg.histogram(
        "ndv_detector_margin",
        "distance of detector metrics to the nearest layout threshold",
        buckets=obs_metrics.MARGIN_BUCKETS,
    )
    for p in provs:
        route_total.inc(route=p.route)
        newton.observe(p.dict_iterations, solver="dict")
        newton.observe(p.coupon_iterations, solver="coupon")
        margin.observe(p.detector_margin)


def estimate_columns(
    cols: Sequence[ColumnMetadata],
    schema_bounds: Optional[Sequence[float]] = None,
    *,
    mode: str = "paper",
    engine=None,
) -> List[NDVEstimate]:
    """Object API: list of ColumnMetadata -> list of NDVEstimate.

    Delegates to the process-wide default `EstimationEngine` (or the one
    passed in), which packs through ONE shared bucketing `BatchPacker` —
    ad-hoc calls get the same bucketing (and trace reuse) as the catalog
    path, with O(log B · log R) jit traces of `estimate_batch` across all
    callers instead of one per distinct shape.
    """
    from repro import engine as engine_mod  # local: avoid import cycle

    if not cols:
        return []
    engine = engine or engine_mod.default_engine()
    return engine.estimate_columns(cols, schema_bounds, mode=mode)


def estimate_file(
    file_meta, schema_bounds=None, *, mode: str = "paper", engine=None
) -> List[NDVEstimate]:
    """Estimate every column of a PQLite file from its footer only."""
    from repro.columnar.reader import column_metadata_from_footer

    cols = [
        column_metadata_from_footer(file_meta, name)
        for name in file_meta.column_names
    ]
    return estimate_columns(cols, schema_bounds, mode=mode, engine=engine)
