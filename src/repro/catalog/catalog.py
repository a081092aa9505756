"""`StatsCatalog`: cached, incremental dataset-level NDV estimation.

The catalog's contract (see the package docstring for the design):

  * `update()` scans the source, re-reading only footers whose fingerprint
    changed, and maintains one merged `ColumnMetadata` per column. Each
    live file's per-column views are kept, keyed by file id and
    fingerprint, so only new or rewritten footers are ever parsed. Pure
    additions merge into the existing view (O(new files)); a rewrite or
    removal re-merges every live file's kept views (no footer re-parsed).
    Metric (`repro.obs` registry): `ndv_merge_views_total{result=
    "reused"|"built"}`, one per (file, column) view a merge reads. The
    footer I/O and the commit are split: `apply_footers()` is the atomic
    merge-and-swap seam, so the async ingestor (`repro.service`) can
    scatter-gather footers on a thread pool and commit through the same
    code path.
  * `estimate()` packs the merged view through the bucketing `BatchPacker`
    and executes through an injected `EstimationEngine` (local / sharded /
    chunked / composed — see `repro.engine`). Packed batches are cached
    per (fingerprint set, packer) and promoted once per fingerprint
    generation into a device-resident tier (`jax.device_put`, blocked until
    materialized), so every estimate call against an unchanged dataset —
    across modes, schema bounds, and engines — reuses the same on-device
    arrays with zero host-to-device traffic. Estimates are cached per
    (fingerprint set, mode, schema bounds, engine identity) — a warm call
    performs zero packing and zero tracing, just a dict hit. Engine
    identity is `cache_key`:
    only the numerics-bearing backend, so engines differing merely in
    execution shape (strategy, shards, chunk budget — all bit-identical
    by the parity contract) share entries, and a strategy change never
    cools the cache; engines that could answer differently never share.
  * `save_cache()` / `load_cache()` spill the estimate cache to a JSON file
    next to the dataset so restarts serve warm.
  * `plan()` turns estimates into `NDVPlanner` memory plans.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import threading
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

try:
    import fcntl
except ImportError:  # non-POSIX: fall back to atomic-replace-only safety
    fcntl = None

import jax
import jax.numpy as jnp
import numpy as np

from repro.catalog.merge import merge_column_metadata
from repro.catalog.packer import BatchPacker
from repro.obs import registry
from repro.obs import span as _obs_span
from repro.catalog.source import MetadataSource, PQLiteMetadataSource
from repro.core.ndv.estimator import (
    Provenance,
    estimates_from_batch,
    provenance_from_batch,
    record_provenance_metrics,
)
from repro.core.ndv.types import ColumnBatch, ColumnMetadata, Layout, NDVEstimate

CACHE_FILE_NAME = ".ndv_estimate_cache.json"
# v2: engine identity in entry keys went from the 4-field config tuple to
# the backend-only `cache_key` (strategy/shards/budget are numerics-neutral).
# v1 files load as clean cold starts instead of as permanently-unreachable
# entries that the merge-not-clobber save path would re-persist forever.
_CACHE_VERSION = 2

# One lock per spill path: replicas of the same dataset inside one process
# (the fleet tier runs several `StatsService`s over one root) serialize
# their read-merge-write cycles here. Cross-PROCESS writers are covered by
# the atomic tempfile + `os.replace` protocol plus the mtime/fingerprint
# guard in `save_cache()` — a reader never observes a torn file, and a
# concurrent writer's entries are merged rather than clobbered whenever the
# mtime reveals them.
_SPILL_LOCKS: Dict[str, threading.Lock] = {}
_SPILL_LOCKS_MU = threading.Lock()

_MERGE_VIEWS = registry().counter(
    "ndv_merge_views_total",
    "Per-file column views merged: reused (kept) or built (footer parsed)",
)
_REUSED_VIEWS = _MERGE_VIEWS.labels(result="reused")
_BUILT_VIEWS = _MERGE_VIEWS.labels(result="built")

# A live file's views: the fingerprint they were built at, and one
# `ColumnMetadata` per column name.
_FileViews = Tuple[str, Dict[str, ColumnMetadata]]


def _spill_lock(path: str) -> threading.Lock:
    key = os.path.abspath(path)
    with _SPILL_LOCKS_MU:
        lock = _SPILL_LOCKS.get(key)
        if lock is None:
            lock = _SPILL_LOCKS[key] = threading.Lock()
        return lock


@contextlib.contextmanager
def _cross_process_spill_lock(path: str):
    """Advisory flock on a sidecar `<path>.lock` spanning one writer's
    read-merge-write cycle, so two PROCESSES cannot interleave between the
    merge read and the `os.replace` and drop each other's entries. No-op
    where `fcntl` is unavailable — atomic replace still guarantees
    readers a consistent file there, only cross-process merge completeness
    degrades to best-effort."""
    if fcntl is None:
        yield
        return
    fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)  # closing drops the flock


def estimate_to_json(est: NDVEstimate) -> dict:
    """`NDVEstimate` -> plain-JSON dict (enums as ints, floats untouched)."""
    d = {
        f.name: getattr(est, f.name)
        for f in dataclasses.fields(NDVEstimate)
        if f.name != "layout"
    }
    d["layout"] = int(est.layout)
    return d


def estimate_from_json(d: dict) -> NDVEstimate:
    """Inverse of `estimate_to_json`.

    Bit-exact: Python's json emits shortest-round-trip float reprs, so a
    serialized estimate reconstructs `==` to the original — the cache spill
    and the stats-service wire format both rely on this.
    """
    return NDVEstimate(**{**d, "layout": Layout(d["layout"])})


@dataclasses.dataclass(frozen=True)
class FileEntry:
    """One ingested file: identity, change token, parsed footer."""

    file_id: str
    fingerprint: str
    footer: object  # FileFooter-shaped


class UpdateSummary(NamedTuple):
    added: int
    updated: int
    removed: int
    total: int

    @property
    def changed(self) -> bool:
        return bool(self.added or self.updated or self.removed)


@dataclasses.dataclass
class CatalogStats:
    """Observability counters (asserted by tests and benchmarks)."""

    footers_read: int = 0
    merges: int = 0
    packs: int = 0
    estimate_cache_hits: int = 0
    estimate_cache_misses: int = 0
    device_puts: int = 0      # batches promoted to the device-resident tier
    resident_hits: int = 0    # estimate calls served from resident arrays


class StatsCatalog:
    """Dataset-level statistics catalog over a `MetadataSource`."""

    def __init__(
        self,
        source: Union[MetadataSource, str],
        *,
        packer: Optional[BatchPacker] = None,
        engine=None,
        max_cache_entries: int = 64,
        auto_load_cache: bool = False,
    ):
        from repro import engine as engine_mod  # local: avoid import cycle

        if isinstance(source, str):
            source = PQLiteMetadataSource(source)
        self.source = source
        self.engine = engine or engine_mod.default_engine()
        self.packer = packer or self.engine.make_packer()
        self.stats = CatalogStats()
        self._entries: "OrderedDict[str, FileEntry]" = OrderedDict()
        self._merged: Optional[Dict[str, ColumnMetadata]] = None
        # file id -> its views; exactly the files of `_entries`.
        self._views: Dict[str, _FileViews] = {}
        self._column_names: List[str] = []
        self._batch_cache: "OrderedDict[frozenset, ColumnBatch]" = OrderedDict()
        self._resident_cache: "OrderedDict[frozenset, ColumnBatch]" = (
            OrderedDict()
        )
        self._estimate_cache: "OrderedDict[tuple, Dict[str, NDVEstimate]]" = (
            OrderedDict()
        )
        # Per-estimate provenance, keyed like `_estimate_cache`. NEVER
        # spilled: the on-disk format (and with it every body/ETag the
        # service derives) stays byte-identical to the pre-provenance
        # layout; a spill-warmed entry recomputes provenance on demand.
        self._provenance_cache: "OrderedDict[tuple, Dict[str, Provenance]]" = (
            OrderedDict()
        )
        self._max_cache_entries = max_cache_entries
        self._scanned = False
        self._fp_key: Optional[frozenset] = None
        self._cache_file_mtime_ns: Optional[int] = None
        if auto_load_cache:
            self.maybe_load_cache()

    # -- ingestion -----------------------------------------------------------

    def update(self) -> UpdateSummary:
        """Re-scan the source; ingest new/changed footers, drop removed ones.

        A file that vanishes between listing and reading (its fingerprint or
        footer raises FileNotFoundError) is treated exactly like a file the
        listing never returned: it is reported as removed if it was
        previously ingested, never as added — the same semantics the async
        ingestion path (`repro.service.AsyncIngestor`) applies.

        All catalog state (entries, views, merged view, fingerprint key) is
        committed only after merging succeeds, so a failed update — e.g. a
        schema-mismatched file — leaves the previous consistent view intact.
        """
        fresh: List[FileEntry] = []
        live_ids: List[str] = []
        for fid in self.source.list_files():
            try:
                fp = self.source.fingerprint(fid)
                prev = self._entries.get(fid)
                if prev is not None and prev.fingerprint == fp:
                    live_ids.append(fid)
                    continue
                footer = self.source.read_footer(fid)
            except FileNotFoundError:
                continue  # vanished mid-scan: counted as removed, not added
            self.stats.footers_read += 1
            fresh.append(FileEntry(fid, fp, footer))
            live_ids.append(fid)
        return self.apply_footers(fresh, live_ids=live_ids)

    def apply_footers(
        self, fresh: Sequence[FileEntry], *, live_ids: Sequence[str]
    ) -> UpdateSummary:
        """Commit prefetched footers — the ingestion seam below `update()`.

        `live_ids` is the authoritative set of files that currently exist
        (its order becomes the entry iteration order); `fresh` carries a
        parsed `FileEntry` for every live id that is new or changed. Ids in
        `live_ids` with no fresh entry must already be ingested (their
        previous entry is reused); previously-ingested ids absent from
        `live_ids` are dropped and reported as removed. A fresh entry whose
        fingerprint matches the existing one (an ingestion race re-read an
        unchanged footer) is a no-op, not an update.

        Views are built only for the fresh entries applied; every other
        live file's kept views are reused, and a rewrite or removal merges
        all of them in `live_ids` order — the same view a merge of every
        footer from scratch gives.

        This is the single commit point for both the synchronous `update()`
        loop and the scatter-gathered async path: footer I/O can happen
        anywhere, concurrently, while the merge + state swap stays atomic —
        on any failure (e.g. schema mismatch) the previous consistent view
        keeps serving.
        """
        by_id = {e.file_id: e for e in fresh}
        added = updated = 0
        new_entries: "OrderedDict[str, FileEntry]" = OrderedDict()
        applied: List[FileEntry] = []
        for fid in live_ids:
            entry = by_id.get(fid)
            prev = self._entries.get(fid)
            if entry is None:
                if prev is None:
                    raise ValueError(
                        f"live file {fid!r} has neither a previous catalog "
                        f"entry nor a prefetched footer"
                    )
                new_entries[fid] = prev
                continue
            if prev is not None and prev.fingerprint == entry.fingerprint:
                new_entries[fid] = prev
                continue
            new_entries[fid] = entry
            applied.append(entry)
            if prev is None:
                added += 1
            else:
                updated += 1
        removed = len(set(self._entries) - set(new_entries))
        pure_addition = updated == 0 and removed == 0
        if not new_entries:
            merged, names, views = {}, [], {}
        elif self._merged is not None and pure_addition and not applied:
            merged, names = self._merged, self._column_names
            views = self._views
        elif self._merged and pure_addition:
            merged, names, views = self._merge_into(applied)
        else:
            merged, names, views = self._merge_all(list(new_entries.values()))
        # commit point: merge succeeded, swap the whole view atomically
        self._scanned = True
        self._entries = new_entries
        self._merged, self._column_names = merged, names
        self._views = views
        self._fp_key = None
        summary = UpdateSummary(added, updated, removed, len(new_entries))
        if summary.changed:
            # The resident tier holds device memory for exactly one reason:
            # serving the live fingerprint generation without re-transfer.
            # A changed commit makes every resident batch stale, so release
            # the device arrays here rather than waiting for LRU pressure.
            self._resident_cache.clear()
        return summary

    def _per_file(
        self, entry: FileEntry, names: Sequence[str]
    ) -> Dict[str, ColumnMetadata]:
        try:
            return {n: self.source.column_metadata(entry.footer, n) for n in names}
        except KeyError as e:
            raise ValueError(
                f"file {entry.file_id!r} is missing column {e.args[0]!r} "
                f"expected by the dataset schema {list(names)}"
            ) from e

    @staticmethod
    def _check_schema(names: Sequence[str], entry: FileEntry) -> None:
        got = set(entry.footer.column_names)
        if got != set(names):
            missing = sorted(set(names) - got)
            extra = sorted(got - set(names))
            raise ValueError(
                f"file {entry.file_id!r} does not match the dataset schema: "
                f"missing columns {missing}, unexpected columns {extra}"
            )

    def _merge_all(self, entries: List[FileEntry]) -> tuple:
        names = list(entries[0].footer.column_names)
        for e in entries[1:]:
            self._check_schema(names, e)
        views: Dict[str, _FileViews] = {}
        built = 0
        for e in entries:
            held = self._views.get(e.file_id)
            if held is None or held[0] != e.fingerprint:
                held = (e.fingerprint, self._per_file(e, names))
                built += 1
            views[e.file_id] = held
        per_file = [views[e.file_id][1] for e in entries]
        merged = {
            name: merge_column_metadata([pf[name] for pf in per_file])
            for name in names
        }
        self.stats.merges += 1
        _REUSED_VIEWS.inc((len(entries) - built) * len(names))
        _BUILT_VIEWS.inc(built * len(names))
        return merged, names, views

    def _merge_into(self, fresh: List[FileEntry]) -> tuple:
        names = self._column_names
        for e in fresh:
            self._check_schema(names, e)
        per_file = [self._per_file(e, names) for e in fresh]
        merged = dict(self._merged)
        for name in names:
            merged[name] = merge_column_metadata(
                [merged[name]] + [pf[name] for pf in per_file]
            )
        views = dict(self._views)
        for e, pf in zip(fresh, per_file):
            views[e.file_id] = (e.fingerprint, pf)
        self.stats.merges += 1
        _BUILT_VIEWS.inc(len(fresh) * len(names))
        return merged, names, views

    def _ensure_scanned(self) -> None:
        if not self._scanned:
            self.update()

    # -- views ---------------------------------------------------------------

    @property
    def scanned(self) -> bool:
        """Whether any scan has committed (False = no view to serve yet)."""
        return self._scanned

    @property
    def num_files(self) -> int:
        self._ensure_scanned()
        return len(self._entries)

    @property
    def column_names(self) -> List[str]:
        self._ensure_scanned()
        return list(self._column_names)

    @property
    def files(self) -> List[str]:
        self._ensure_scanned()
        return list(self._entries)

    def fingerprint_key(self) -> frozenset:
        """Identity of the current dataset state (the cache key).

        Computed once per `update()` — warm `estimate()` calls stay O(1)
        in file count (update() is the only mutation point).
        """
        self._ensure_scanned()
        if self._fp_key is None:
            self._fp_key = frozenset(
                f"{e.file_id}@{e.fingerprint}" for e in self._entries.values()
            )
        return self._fp_key

    def entry_fingerprints(self) -> Dict[str, str]:
        """Snapshot of ingested file id -> fingerprint.

        Unlike `files`, this never triggers a scan: the async ingestor uses
        it to diff a fresh fingerprint sweep against the committed state
        without forcing the synchronous `update()` path.
        """
        return {fid: e.fingerprint for fid, e in self._entries.items()}

    def merged_metadata(self) -> Dict[str, ColumnMetadata]:
        """One logical ColumnMetadata per column, across all files."""
        self._ensure_scanned()
        return dict(self._merged or {})

    def non_nulls(self) -> Dict[str, float]:
        return {n: m.non_null for n, m in self.merged_metadata().items()}

    def total_rows(self) -> int:
        """Total row count across every ingested file (footer sums only).

        The planner's base-cardinality input (`|R|` in the join-size
        formula) — like everything else here it comes from metadata the
        footers already carry, never from scanning data.
        """
        self._ensure_scanned()
        return sum(e.footer.num_rows for e in self._entries.values())

    # -- estimation ----------------------------------------------------------

    def _packed(self, key: frozenset) -> ColumnBatch:
        """Packed batch for a fingerprint generation, device-resident.

        Two tiers: `_batch_cache` holds the packer's output (one pack per
        fingerprint set), `_resident_cache` holds that batch explicitly
        `jax.device_put` and blocked until materialized — transferred ONCE
        per fingerprint generation, then reused by every estimate call
        (across modes, bounds, and engines) with zero host-to-device
        traffic on the warm path. Both tiers share the same LRU bound;
        resident entries are additionally dropped eagerly whenever an
        `apply_footers` commit changes the dataset.
        """
        resident = self._resident_cache.get(key)
        if resident is not None:
            self.stats.resident_hits += 1
            self._resident_cache.move_to_end(key)
            return resident
        batch = self._batch_cache.get(key)
        if batch is None:
            cols = [self._merged[n] for n in self._column_names]
            with _obs_span("engine.pack", columns=len(cols)):
                batch = self.packer.pack(cols)
            self.stats.packs += 1
            self._cache_put(self._batch_cache, key, batch)
        else:
            self._batch_cache.move_to_end(key)
        # On the engine's own device when it has one (a replica placed on
        # a chip keeps its packs there); otherwise uncommitted on the
        # default device, so the sharded/composed strategies remain free to
        # lay the batch out across their mesh.
        with _obs_span("engine.h2d", batch=int(batch.batch)):
            resident = jax.device_put(batch, self.engine.committed_device)
            jax.block_until_ready(resident)
        self.stats.device_puts += 1
        self._cache_put(self._resident_cache, key, resident)
        return resident

    def warm(self, *, mode: str = "paper") -> None:
        """Warm the engine's program for the current state's (B, R) bucket
        on the engine's devices (`EstimationEngine.warm`): no pack is
        built and no cache line filled."""
        self._ensure_scanned()
        merged = self._merged or {}
        if not merged:
            return
        self.engine.warm(
            len(merged), max(m.num_row_groups for m in merged.values()),
            mode=mode,
        )

    @property
    def num_resident_batches(self) -> int:
        """Batches currently held in the device-resident tier.

        Observability for the residency lifecycle: rises to 1 after the
        first estimate of a fingerprint generation, drops to 0 when an
        `apply_footers` commit changes the dataset (tests and the fleet
        tier's memory accounting read this).
        """
        return len(self._resident_cache)

    def _cache_put(self, cache: OrderedDict, key, value) -> None:
        cache[key] = value
        cache.move_to_end(key)
        while len(cache) > self._max_cache_entries:
            cache.popitem(last=False)

    def estimate_key(
        self,
        *,
        mode: str = "paper",
        schema_bounds: Optional[Dict[str, float]] = None,
        engine=None,
    ) -> tuple:
        """The estimate-cache key one `estimate()` call would use.

        Shared with `repro.catalog.superpack`, which probes and fills the
        same cache so super-packed and individual estimates are one cache
        population (and one spill file).
        """
        self._ensure_scanned()
        engine = engine or self.engine
        sb_key = (
            tuple(sorted(schema_bounds.items())) if schema_bounds else None
        )
        return (self.fingerprint_key(), mode, sb_key, engine.cache_key)

    def bounds_array(
        self, schema_bounds: Optional[Dict[str, float]], width: int
    ) -> Optional[np.ndarray]:
        """Per-lane schema-bound array for a `width`-lane packed batch.

        Unnamed and padding lanes get +inf ("no bound" — the combine step's
        identity); None when no bounds were given (the engine materializes
        the same +inf lanes itself, bit-identically).
        """
        if not schema_bounds:
            return None
        arr = np.full(width, np.inf, np.float32)
        for i, name in enumerate(self._column_names):
            if name in schema_bounds:
                arr[i] = float(schema_bounds[name])
        return arr

    def packed_batch(self) -> ColumnBatch:
        """The current fingerprint generation's packed batch (cached,
        device-resident — see `_packed`)."""
        self._ensure_scanned()
        return self._packed(self.fingerprint_key())

    def estimate_cache_peek(self, key: tuple) -> Optional[Dict[str, NDVEstimate]]:
        """Cache probe by `estimate_key()`, counting hit/miss like
        `estimate()` does. Returns a copy, or None on miss."""
        cached = self._estimate_cache.get(key)
        if cached is not None:
            self.stats.estimate_cache_hits += 1
            self._estimate_cache.move_to_end(key)
            return dict(cached)
        self.stats.estimate_cache_misses += 1
        return None

    def estimate_cache_store(
        self, key: tuple, result: Dict[str, NDVEstimate]
    ) -> None:
        """Insert an externally-computed estimate map under `estimate_key()`.

        The superpack write-back seam: results land in the same LRU the
        spill serializes, so batched cold estimates warm-start restarts
        exactly like individually-computed ones.
        """
        self._cache_put(self._estimate_cache, key, dict(result))

    def estimate(
        self,
        *,
        mode: str = "paper",
        schema_bounds: Optional[Dict[str, float]] = None,
        engine=None,
    ) -> Dict[str, NDVEstimate]:
        """Dataset-level NDV estimates for every column (cached).

        Args:
          mode: "paper" or "improved" — threaded to `estimate_batch`.
          schema_bounds: optional column -> upper-bound NDV (Eq 14-15 family
            of schema knowledge, e.g. an enum's domain size).
          engine: optional `EstimationEngine` override for this call. The
            cache key includes the engine's numeric identity
            (`engine.cache_key` — the backend), so engines that could
            answer differently are cached independently while execution
            shapes that are bit-identical by the parity contract share.
        """
        self._ensure_scanned()
        engine = engine or self.engine
        key = self.estimate_key(
            mode=mode, schema_bounds=schema_bounds, engine=engine
        )
        cached = self.estimate_cache_peek(key)
        if cached is not None:
            return cached
        if not self._column_names:
            return {}
        batch = self._packed(self.fingerprint_key())
        arr = self.bounds_array(schema_bounds, batch.batch)
        sb = None if arr is None else jnp.asarray(arr)
        out = engine.estimate(batch, sb, mode=mode)
        with _obs_span("engine.device_wait"):
            jax.block_until_ready(out)
        with _obs_span("engine.d2h", columns=len(self._column_names)):
            ests = estimates_from_batch(out, batch, self._column_names)
            provs = provenance_from_batch(out, batch, self._column_names)
        result = {e.column_name: e for e in ests}
        self._cache_put(self._estimate_cache, key, result)
        self.provenance_cache_store(key, {p.column_name: p for p in provs})
        return dict(result)

    def estimate_column(self, name: str, *, mode: str = "paper") -> NDVEstimate:
        return self.estimate(mode=mode)[name]

    # -- provenance ----------------------------------------------------------

    def provenance_cache_peek(
        self, key: tuple
    ) -> Optional[Dict[str, Provenance]]:
        """Provenance probe by `estimate_key()`; copy on hit, None on miss.

        Unlike `estimate_cache_peek` this counts nothing — provenance is a
        diagnostic sidecar, and its hit rate must not perturb the estimate
        counters tests and dashboards assert on.
        """
        cached = self._provenance_cache.get(key)
        if cached is None:
            return None
        self._provenance_cache.move_to_end(key)
        return dict(cached)

    def provenance_cache_store(
        self, key: tuple, provs: Dict[str, Provenance]
    ) -> None:
        """Insert freshly-materialized provenance and observe its metrics.

        The single funnel for both the direct `estimate()` path and the
        superpack write-back: `ndv_route_total`/`ndv_newton_iters`/
        `ndv_detector_margin` are recorded exactly once per engine run here,
        never on cache hits.
        """
        record_provenance_metrics(list(provs.values()))
        self._cache_put(self._provenance_cache, key, dict(provs))

    def provenance(
        self,
        *,
        mode: str = "paper",
        schema_bounds: Optional[Dict[str, float]] = None,
        engine=None,
    ) -> Dict[str, Provenance]:
        """Per-column provenance for the same state `estimate()` serves.

        Usually a cache hit (filled alongside every engine run). A miss —
        the estimate was warmed from the on-disk spill, which deliberately
        carries no diagnostics — recomputes through the engine; the
        estimates produced on the way are bit-identical by contract and
        refresh the estimate cache too.
        """
        self._ensure_scanned()
        engine = engine or self.engine
        key = self.estimate_key(
            mode=mode, schema_bounds=schema_bounds, engine=engine
        )
        cached = self.provenance_cache_peek(key)
        if cached is not None:
            return cached
        if not self._column_names:
            return {}
        batch = self._packed(self.fingerprint_key())
        arr = self.bounds_array(schema_bounds, batch.batch)
        sb = None if arr is None else jnp.asarray(arr)
        out = engine.estimate(batch, sb, mode=mode)
        with _obs_span("engine.device_wait"):
            jax.block_until_ready(out)
        with _obs_span("engine.d2h", columns=len(self._column_names)):
            ests = estimates_from_batch(out, batch, self._column_names)
            provs = provenance_from_batch(out, batch, self._column_names)
        self._cache_put(
            self._estimate_cache, key, {e.column_name: e for e in ests}
        )
        result = {p.column_name: p for p in provs}
        self.provenance_cache_store(key, result)
        return dict(result)

    def provenance_entries(self) -> List[Tuple[tuple, Dict[str, Provenance]]]:
        """Snapshot of the provenance cache (the `/debug/explain` source)."""
        return [(k, dict(v)) for k, v in self._provenance_cache.items()]

    # -- estimate-cache persistence ------------------------------------------

    def _default_cache_path(self) -> str:
        root = getattr(self.source, "root", None)
        if root is None:
            raise ValueError(
                "this catalog's source has no filesystem root; pass an "
                "explicit path to save_cache()/load_cache()"
            )
        return os.path.join(root, CACHE_FILE_NAME)

    @staticmethod
    def _key_to_json(key: tuple) -> dict:
        fp_key, mode, sb_key, engine_key = key
        return {
            "files": sorted(fp_key),
            "mode": mode,
            "schema_bounds": (
                [[n, v] for n, v in sb_key] if sb_key is not None else None
            ),
            "engine": list(engine_key),
        }

    @staticmethod
    def _key_from_json(d: dict) -> tuple:
        sb = d["schema_bounds"]
        return (
            frozenset(d["files"]),
            d["mode"],
            tuple((n, v) for n, v in sb) if sb is not None else None,
            tuple(d["engine"]),
        )

    def _read_spill(
        self, path: str
    ) -> Tuple[Optional[List[tuple]], Optional[int]]:
        """Parse an existing spill file -> ([(key, estimates)], mtime_ns).

        ``(None, None)`` when the file is missing, version-mismatched, or
        unparseable (a foreign writer is mid-protocol — treat as absent
        rather than fail the save).
        """
        try:
            mtime_ns = os.stat(path).st_mtime_ns
            with open(path) as f:
                payload = json.load(f)
            if payload.get("version") != _CACHE_VERSION:
                return None, None
            items = [
                (
                    self._key_from_json(entry["key"]),
                    {
                        name: estimate_from_json(d)
                        for name, d in entry["estimates"].items()
                    },
                )
                for entry in payload["entries"]
            ]
        except (FileNotFoundError, json.JSONDecodeError,
                KeyError, TypeError, ValueError, AttributeError):
            # valid-JSON-wrong-shape is as foreign as non-JSON
            return None, None
        return items, mtime_ns

    def save_cache(self, path: Optional[str] = None, *, compact: bool = True) -> str:
        """Spill the estimate cache to a JSON file next to the dataset.

        Values survive a round trip exactly: floats serialize at full
        double precision, so a warm restart serves bit-identical
        `NDVEstimate`s. Returns the path written.

        With ``compact=True`` (the default) the pass drops entries whose
        fingerprint set no longer matches the live dataset state before
        writing: stale keys are unreachable anyway (any rewrite changed the
        fingerprint set) and would otherwise accumulate in the file across
        every rewrite the LRU happened to retain. ``compact=False`` persists
        the LRU verbatim, useful when several dataset states legitimately
        coexist (e.g. snapshotting mid-migration).

        Safe under concurrent writers (replicas of one dataset spilling to
        the shared file):

          * the payload goes to a uniquely-named temp file in the target
            directory and lands via `os.replace`, so a racing reader or
            writer never observes a torn spill, no matter how many
            processes write;
          * on the compact path, live-fingerprint entries already on disk
            are merged into what we write (union; our values win — by the
            engine parity contract they are bit-identical anyway), so two
            replicas spilling different (mode, bounds, engine) entries
            enrich rather than clobber each other;
          * the write is skipped entirely when the on-disk spill is newer
            than the last state this catalog loaded or saved AND already
            fingerprint-compatible with everything we would write —
            another replica got there first with a superset.
        """
        path = path or self._default_cache_path()
        with _spill_lock(path), _cross_process_spill_lock(path):
            items = list(self._estimate_cache.items())
            if compact:
                live = self.fingerprint_key()
                items = [(k, v) for k, v in items if k[0] == live]
                disk_items, disk_mtime_ns = self._read_spill(path)
                if disk_items is not None:
                    disk_live = [(k, v) for k, v in disk_items if k[0] == live]
                    ours = {k for k, _ in items}
                    if (
                        disk_mtime_ns != self._cache_file_mtime_ns
                        and ours <= {k for k, _ in disk_live}
                    ):
                        return path
                    merged = OrderedDict(disk_live)
                    merged.update(items)
                    items = list(merged.items())
            entries = []
            for key, ests in items:
                entries.append({
                    "key": self._key_to_json(key),
                    "estimates": {
                        name: estimate_to_json(e) for name, e in ests.items()
                    },
                })
            payload = {"version": _CACHE_VERSION, "entries": entries}
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path) or ".",
                prefix=os.path.basename(path) + ".",
                suffix=".tmp",
            )
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f)
                # Record the temp file's mtime BEFORE the replace: it is
                # the mtime our bytes carry into `path` (os.replace keeps
                # the inode), whereas re-statting the shared path after the
                # replace could capture a sibling's even-newer write and
                # alias it as already-loaded forever.
                mtime_ns = os.stat(tmp).st_mtime_ns
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            self._cache_file_mtime_ns = mtime_ns
        return path

    def load_cache(self, path: Optional[str] = None) -> int:
        """Load spilled estimates; returns the number of entries restored.

        Missing file is not an error (cold start). Entries whose
        fingerprint set no longer matches the live dataset are still
        loaded — the fingerprint set in the key makes stale entries
        unreachable, and LRU eviction discards them.
        """
        path = path or self._default_cache_path()
        with _spill_lock(path):
            items, _ = self._read_spill(path)
        if items is None:
            return 0
        for key, ests in items:
            self._cache_put(self._estimate_cache, key, ests)
        return len(items)

    def maybe_load_cache(self, path: Optional[str] = None) -> int:
        """mtime-guarded `load_cache()`: load only when the file changed.

        Remembers the cache file's mtime at each load, so construction with
        ``auto_load_cache=True`` and periodic service-side refresh calls are
        free when nothing rewrote the file. Returns the number of entries
        restored (0 when the file is missing or unchanged).
        """
        path = path or self._default_cache_path()
        try:
            mtime_ns = os.stat(path).st_mtime_ns
        except FileNotFoundError:
            return 0
        if mtime_ns == self._cache_file_mtime_ns:
            return 0
        loaded = self.load_cache(path)
        self._cache_file_mtime_ns = mtime_ns
        return loaded

    def compact_caches(self) -> int:
        """Drop in-memory batch/estimate entries for stale fingerprint sets.

        The service layer calls this after each committed refresh that
        changed the dataset, so long-running servers do not pin packed
        batches and estimate maps for states that can never be requested
        again. Returns the number of entries dropped.
        """
        live = self.fingerprint_key()
        dropped = 0
        for key in [k for k in self._batch_cache if k != live]:
            del self._batch_cache[key]
            dropped += 1
        for key in [k for k in self._resident_cache if k != live]:
            del self._resident_cache[key]
            dropped += 1
        for key in [k for k in self._estimate_cache if k[0] != live]:
            del self._estimate_cache[key]
            dropped += 1
        for key in [k for k in self._provenance_cache if k[0] != live]:
            del self._provenance_cache[key]
            dropped += 1
        return dropped

    # -- planning ------------------------------------------------------------

    def plan(self, planner=None, *, mode: str = "paper", engine=None):
        """Memory plans for every column via `NDVPlanner.plan_catalog`."""
        from repro.core.planner import NDVPlanner

        return (planner or NDVPlanner()).plan_catalog(
            self, mode=mode, engine=engine
        )
