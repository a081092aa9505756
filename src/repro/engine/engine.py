"""`EstimationEngine`: strategy-routed execution of `estimate_batch`.

See the package docstring for the seam design. The engine is stateless
apart from its config — all caching lives in `StatsCatalog`, keyed by
`engine.cache_key` so differently-configured engines never share entries.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.catalog.packer import BatchPacker
from repro.obs import registry, span as _obs_span
from repro.core.ndv.estimator import (
    BatchEstimates,
    Provenance,
    estimate_batch,
    estimates_from_batch,
    provenance_from_batch,
)
from repro.core.ndv.types import ColumnBatch, ColumnMetadata, NDVEstimate
from repro.engine.config import DEFAULT_MAX_BATCH, EngineConfig

# max_batch="auto" sizing. A packed lane (one column) costs ~22 bytes per
# (lane, row-group) cell across the seven (B, R) planes plus ~50 bytes of
# per-lane scalars; at the bucketed R ceilings real warehouses hit (<=256)
# that is ~6 KB, and the estimators' masked intermediates (several
# temporaries per plane across the Newton iterations) multiply it by a
# small constant. 64 KB/lane is that footprint with ~10x headroom — the
# budget only needs the right order of magnitude, since chunk width is
# numerics-neutral and merely bounds peak memory.
AUTO_MEM_FRACTION = 0.25
NOMINAL_LANE_BYTES = 1 << 16
AUTO_MIN_BATCH = 1024
AUTO_MAX_BATCH = 1 << 20

logger = logging.getLogger(__name__)

_DISPATCHES = registry().counter(
    "ndv_engine_dispatches_total",
    "Engine estimate() dispatches, by resolved strategy and mode",
)


def detect_device_memory() -> Optional[int]:
    """Bytes of memory on the first visible device; None on the host CPU.

    Accelerators (TPU/GPU) report it through the allocator's
    `memory_stats()`. The host CPU reports nothing, and the auto budget
    then falls back to `DEFAULT_MAX_BATCH`. On an accelerator a failed or
    empty report raises: shrinking the budget to the CPU default would
    hide a broken device behind a slower, still-working engine.
    """
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return None
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no memory "
            f"limit (memory_stats() = {stats!r})"
        )
    return int(limit)


def auto_chunk_budget(mem_bytes: Optional[int], shards: int = 1) -> int:
    """Device memory -> chunk budget: the largest power of two of nominal
    lanes fitting in `AUTO_MEM_FRACTION` of memory, clamped to
    [AUTO_MIN_BATCH, AUTO_MAX_BATCH]. None -> `DEFAULT_MAX_BATCH`.

    `shards > 1` (the composed strategy) divides the memory report first:
    `memory_stats()` on a forced-host-platform mesh reports the one shared
    physical pool from every simulated device, so the per-shard budget must
    shrink as the mesh grows. On real accelerators with dedicated HBM the
    division is merely conservative — chunk width is numerics-neutral, so
    a smaller budget bounds the working set tighter at no accuracy cost.
    """
    if not mem_bytes:
        return DEFAULT_MAX_BATCH
    lanes = int(mem_bytes * AUTO_MEM_FRACTION / NOMINAL_LANE_BYTES / max(shards, 1))
    lanes = max(AUTO_MIN_BATCH, min(lanes, AUTO_MAX_BATCH))
    return 1 << (lanes.bit_length() - 1)  # previous power of two


def composed_plan(
    width: int, shards: int, chunk: int
) -> Tuple[int, List[Tuple[int, int]]]:
    """(padded B, super-chunk spans) for the composed strategy.

    A super-chunk is one `shard_map` dispatch: `shards * chunk` lanes, of
    which each shard sees exactly `chunk`. A batch wider than one
    super-chunk pads up to a whole number of them — every span has the same
    width (one jit trace shape) and every shard's slice of every span is a
    full `chunk` (no ragged tail). A batch that already fits one dispatch
    pads only to a multiple of the shard count and runs as plain sharding,
    so narrow catalogs never blow up to `shards * chunk` lanes of padding.

    Pure shape math (no device access) — the hypothesis coverage property
    in tests runs directly against this function.
    """
    if width < 1 or shards < 1 or chunk < 1:
        raise ValueError(f"need positive width/shards/chunk, got "
                         f"({width}, {shards}, {chunk})")
    stride = shards * chunk
    if width <= stride:
        padded = -(-width // shards) * shards
        return padded, [(0, padded)]
    padded = -(-width // stride) * stride
    return padded, [(lo, lo + stride) for lo in range(0, padded, stride)]


@functools.lru_cache(maxsize=None)
def _sharded_fn(devices: tuple, mode: str, backend: str, fuse: str = "auto"):
    """Jitted shard_map of `estimate_batch` over a 1-D column mesh.

    Cached per (device tuple, mode, backend, fuse): shard_map construction
    and tracing are not free, and warm engine calls must stay dispatch-only
    (the jit cache then keys on batch shape as usual). `fuse` is in the
    MEMO key because it changes the traced computation (megakernel vs
    separate launches) — never in the engine's cache identity, because it
    does not change the results.
    """
    mesh = Mesh(np.asarray(devices), ("cols",))
    return jax.jit(
        jax.shard_map(
            functools.partial(
                estimate_batch, mode=mode, backend=backend, fuse=fuse
            ),
            mesh=mesh,
            in_specs=(P("cols"), P("cols")),
            out_specs=P("cols"),
            check_vma=False,
        )
    )


def _pad_axis0(x: jnp.ndarray, target: int) -> jnp.ndarray:
    """Zero-pad the leading (B) axis up to `target` lanes.

    Zero is the packer's own padding value for every field — it yields
    `valid=False` / `n_groups=0` lanes that the estimator fully masks.
    """
    if x.shape[0] == target:
        return x
    pad = [(0, target - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


class EstimationEngine:
    """Routes a packed `ColumnBatch` to one of three execution strategies."""

    def __init__(self, config: Optional[EngineConfig] = None):
        self.config = config or EngineConfig()
        self._packer: Optional[BatchPacker] = None
        self._mem_checked = False
        self._mem_bytes: Optional[int] = None
        self._auto_budgets: Dict[int, int] = {}
        self._clamp_logged = False

    # -- identity ------------------------------------------------------------

    @property
    def shard_count(self) -> int:
        """Resolved shard count: config, clamped to visible devices.

        The clamp is surfaced (one log line per engine, not per call): a
        `num_shards` larger than the mesh silently becoming "all devices"
        used to be invisible, and under the composed strategy a wrong shard
        count also silently changes the per-shard chunk budget.
        """
        n_dev = jax.device_count()
        want = self.config.num_shards or n_dev
        if want > n_dev and not self._clamp_logged:
            self._clamp_logged = True
            logger.warning(
                "EngineConfig(num_shards=%d) exceeds the %d visible "
                "device(s); clamping to %d (this also sets the composed "
                "per-shard chunk budget)",
                want, n_dev, n_dev,
            )
        return max(1, min(want, n_dev))

    @property
    def cache_key(self) -> tuple:
        """Hashable config identity (catalog cache key component).

        Deliberately only the fields that can change numerics — which, by
        the engine parity contract, is `backend` alone. Strategy, shard
        count, and chunk budget are execution-shape knobs with bit-identical
        outputs, so engines that differ only in those SHARE cache lines: a
        persisted cache written under "local" on one topology stays warm
        under "composed" on another (the whole point of `save_cache()`).
        The backend stays unresolved ("auto" as configured) so spills stay
        portable across hosts of one platform class.
        """
        return (self.config.backend,)

    @property
    def cache_token(self) -> str:
        """Engine identity as a compact stable string — wire/ETag material.

        The stats service folds this into every response's ETag so that two
        servers fronting the same dataset through engines that could answer
        differently can never validate each other's cached responses.
        Unlike `cache_key`, the backend appears RESOLVED ("auto" becomes
        the kernel path it picks on this platform): a TPU replica and a CPU
        replica both configured "auto" execute different numerics, so their
        tags must differ even though their configs match. Nothing else
        enters the token — strategy, shard count, and chunk budget are
        numerics-neutral by the parity contract, so a composed replica and
        a local replica of one dataset emit byte-identical ETags and a
        strategy change invalidates no client cache.
        """
        from repro.kernels import ops

        backend = "pallas" if ops.use_pallas(self.config.backend) else "ref"
        return f"k.{backend}"

    def make_packer(self) -> BatchPacker:
        """Shard- and chunk-aware packer, coordinated with this engine.

        B rounds up to a multiple of the shard count so the sharded split
        is even; under the composed strategy (and "auto", which may resolve
        to it on a mesh) the packer additionally carries the per-shard
        chunk budget (`col_chunk`), so batches wider than one super-chunk
        round up to `num_shards * chunk` — every shard's slice then splits
        into equal full chunks with no engine-side re-padding copy.

        One instance per engine (packers are stateless frozen dataclasses;
        sharing keeps every caller on the same bucketing policy object).
        """
        if self._packer is None:
            strategy = self.config.strategy
            mult = (
                self.shard_count
                if strategy in ("auto", "sharded", "composed")
                else 1
            )
            chunk = 0
            if mult > 1 and strategy in ("auto", "composed"):
                chunk = self.resolve_max_batch(shards=mult)
            self._packer = BatchPacker(col_multiple=mult, col_chunk=chunk)
        return self._packer

    # -- strategy resolution --------------------------------------------------

    def resolve_max_batch(self, *, shards: int = 1) -> int:
        """The chunk budget this engine executes with.

        A fixed config value passes through; "auto" is derived per engine
        from the first device's reported memory, detected once (fallback:
        `DEFAULT_MAX_BATCH` where the backend reports none, e.g. host CPU).
        `shards > 1` is the composed strategy's PER-SHARD budget: the memory
        report is divided across the mesh before sizing (see
        `auto_chunk_budget`), so the budget shrinks as the mesh grows.
        Resolution never enters `cache_key`/`cache_token` — chunk width is
        numerics-neutral by the parity contract, so caches and ETags stay
        portable across differently-sized hosts.
        """
        mb = self.config.max_batch
        if mb != "auto":
            return mb
        if not self._mem_checked:
            self._mem_bytes = detect_device_memory()
            self._mem_checked = True
        budget = self._auto_budgets.get(shards)
        if budget is None:
            budget = self._auto_budgets[shards] = auto_chunk_budget(
                self._mem_bytes, shards
            )
        return budget

    def per_shard_budget(self) -> int:
        """The composed strategy's per-shard chunk budget on this engine."""
        return self.resolve_max_batch(shards=self.shard_count)

    def resolve_strategy(self, batch_width: int) -> str:
        s = self.config.strategy
        if s != "auto":
            return s
        n = self.shard_count
        if n > 1:
            # Over the mesh-wide budget: plain sharding would hand some
            # device a slice wider than its chunk budget — stream instead.
            if batch_width > n * self.per_shard_budget():
                return "composed"
            return "sharded"
        if batch_width > self.resolve_max_batch():
            return "chunked"
        return "local"

    # -- execution -----------------------------------------------------------

    def estimate(
        self,
        batch: ColumnBatch,
        schema_bound: Optional[jnp.ndarray] = None,
        *,
        mode: str = "paper",
    ) -> BatchEstimates:
        """ColumnBatch -> BatchEstimates under the configured strategy.

        For real (non-padding) lanes the output is bit-identical across
        strategies: padding lanes are fully masked and no estimator op
        mixes information across the B axis, so re-tiling B is exact.
        """
        strategy = self.resolve_strategy(batch.batch)
        _DISPATCHES.inc(strategy=strategy, mode=mode)
        with _obs_span(
            "engine.dispatch",
            strategy=strategy, mode=mode, batch=int(batch.batch),
        ):
            if strategy == "sharded":
                return self._estimate_sharded(batch, schema_bound, mode)
            if strategy == "chunked":
                return self._estimate_chunked(batch, schema_bound, mode)
            if strategy == "composed":
                return self._estimate_composed(batch, schema_bound, mode)
            return estimate_batch(
                batch, schema_bound, mode=mode,
                backend=self.config.backend, fuse=self.config.fuse,
            )

    def _padded_to_multiple(self, batch, schema_bound, multiple):
        """(batch, schema_bound, original B) with B padded to `multiple`."""
        b = batch.batch
        target = -(-b // multiple) * multiple
        if target == b:
            return batch, schema_bound, b
        batch = jax.tree.map(lambda x: _pad_axis0(x, target), batch)
        if schema_bound is not None:
            # +inf = "no bound": combine() keeps the estimate unchanged.
            schema_bound = jnp.pad(
                schema_bound, (0, target - b), constant_values=np.inf
            )
        return batch, schema_bound, b

    def _estimate_sharded(self, batch, schema_bound, mode) -> BatchEstimates:
        n = self.shard_count
        batch, schema_bound, b = self._padded_to_multiple(batch, schema_bound, n)
        if schema_bound is None:
            # Materialize "no bound" so one shard_map signature serves both;
            # min(ndv, +inf) is the identity, bit-for-bit.
            schema_bound = jnp.full(batch.batch, np.inf, jnp.float32)
        fn = _sharded_fn(
            tuple(jax.devices()[:n]), mode, self.config.backend,
            self.config.fuse,
        )
        out = fn(batch, schema_bound)
        return self._trim(out, b)

    def _estimate_chunked(self, batch, schema_bound, mode) -> BatchEstimates:
        c = self.resolve_max_batch()
        if batch.batch <= c:
            return estimate_batch(
                batch, schema_bound, mode=mode,
                backend=self.config.backend, fuse=self.config.fuse,
            )
        batch, schema_bound, b = self._padded_to_multiple(batch, schema_bound, c)
        spans = [(lo, lo + c) for lo in range(0, batch.batch, c)]
        return self._stream_spans(
            batch, schema_bound, b, spans,
            lambda sub, sb: estimate_batch(
                sub, sb, mode=mode,
                backend=self.config.backend, fuse=self.config.fuse,
            ),
        )

    def _estimate_composed(self, batch, schema_bound, mode) -> BatchEstimates:
        """Sharded AND chunked: stream super-chunks through the mesh.

        Each super-chunk is one `shard_map` dispatch of `shards * chunk`
        lanes — every device sees exactly `chunk` lanes per dispatch, so
        the per-device working set stays bounded by the per-shard budget
        no matter how wide the catalog grows, while all `shards` devices
        advance in lockstep through the stream. `composed_plan` guarantees
        equal spans (one jit trace shape) and no ragged tail; concatenating
        span outputs in order preserves lane order because `shard_map`'s
        `P("cols")` out-spec already concatenates device outputs in order.
        Bit-identical to local for real lanes: this path only re-tiles the
        B axis twice (chunk-of-sharded), and both tilings are proven
        numerics-neutral by the parity contract.
        """
        n = self.shard_count
        chunk = self.per_shard_budget()
        target, spans = composed_plan(batch.batch, n, chunk)
        batch, schema_bound, b = self._padded_to_multiple(
            batch, schema_bound, target
        )
        if schema_bound is None:
            schema_bound = jnp.full(batch.batch, np.inf, jnp.float32)
        fn = _sharded_fn(
            tuple(jax.devices()[:n]), mode, self.config.backend,
            self.config.fuse,
        )
        return self._stream_spans(batch, schema_bound, b, spans, fn)

    def _stream_spans(
        self, batch, schema_bound, b, spans, fn
    ) -> BatchEstimates:
        """Run `fn` over each B-axis span, concatenate in order, trim to `b`.

        The one streaming loop shared by the chunked (fn = estimate_batch)
        and composed (fn = the sharded dispatch) strategies — span order is
        lane order, so concatenation reassembles the unstreamed result.
        """
        parts: List[BatchEstimates] = []
        for lo, hi in spans:
            sub = jax.tree.map(lambda x: x[lo:hi], batch)
            sb = None if schema_bound is None else schema_bound[lo:hi]
            parts.append(fn(sub, sb))
        if len(parts) == 1:
            return self._trim(parts[0], b)
        out = BatchEstimates(
            *[jnp.concatenate(field) for field in zip(*parts)]
        )
        return self._trim(out, b)

    @staticmethod
    def _trim(out: BatchEstimates, b: int) -> BatchEstimates:
        """Drop engine-added padding lanes (keep packer padding intact)."""
        if out.ndv.shape[0] == b:
            return out
        return BatchEstimates(*[field[:b] for field in out])

    # -- object API ----------------------------------------------------------

    def estimate_columns(
        self,
        cols: Sequence[ColumnMetadata],
        schema_bounds: Optional[Sequence[float]] = None,
        *,
        mode: str = "paper",
        packer: Optional[BatchPacker] = None,
    ) -> List[NDVEstimate]:
        """List of ColumnMetadata -> list of NDVEstimate via this engine."""
        if not cols:
            return []
        batch = (packer or self.make_packer()).pack(cols)
        sb = None
        if schema_bounds is not None:
            arr = np.full(batch.batch, np.inf, np.float32)
            arr[: len(cols)] = np.asarray(schema_bounds, np.float32)
            sb = jnp.asarray(arr)
        out = self.estimate(batch, sb, mode=mode)
        return estimates_from_batch(out, batch, [c.column_name for c in cols])

    def estimate_columns_explained(
        self,
        cols: Sequence[ColumnMetadata],
        schema_bounds: Optional[Sequence[float]] = None,
        *,
        mode: str = "paper",
        packer: Optional[BatchPacker] = None,
    ) -> Tuple[List[NDVEstimate], List[Provenance]]:
        """`estimate_columns` plus per-column `Provenance`, one engine run.

        Both views are materialized from the same `BatchEstimates`, so the
        estimates are bit-identical to the unexplained call and the
        provenance describes exactly the numbers returned beside it.
        """
        if not cols:
            return [], []
        batch = (packer or self.make_packer()).pack(cols)
        sb = None
        if schema_bounds is not None:
            arr = np.full(batch.batch, np.inf, np.float32)
            arr[: len(cols)] = np.asarray(schema_bounds, np.float32)
            sb = jnp.asarray(arr)
        out = self.estimate(batch, sb, mode=mode)
        names = [c.column_name for c in cols]
        return (
            estimates_from_batch(out, batch, names),
            provenance_from_batch(out, batch, names),
        )


@dataclasses.dataclass
class _Defaults:
    engine: Optional[EstimationEngine] = None


_DEFAULTS = _Defaults()


def default_engine() -> EstimationEngine:
    """Process-wide default engine (strategy "auto", backend "auto").

    Shared by `estimate_columns`, `estimate_file`, and every `StatsCatalog`
    constructed without an explicit engine, so ad-hoc calls and catalog
    calls agree on bucketing and execution.
    """
    if _DEFAULTS.engine is None:
        _DEFAULTS.engine = EstimationEngine(EngineConfig())
    return _DEFAULTS.engine


def default_packer() -> BatchPacker:
    """The default engine's shared packer (one bucketing policy per process)."""
    return default_engine().make_packer()
