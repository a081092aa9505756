"""Async footer ingestion: scatter-gather over a `MetadataSource`.

Footer I/O is the one non-free step in zero-cost NDV estimation (the paper
reads *metadata*, but the metadata still lives at the end of remote files).
`AsyncIngestor` overlaps that I/O over a bounded thread pool and commits
results through `StatsCatalog.apply_footers()`:

  scatter   fingerprint every listed file concurrently (stat-cheap), diff
            against the catalog's committed fingerprints, then read only
            the new/changed footers — again concurrently.
  gather    hand the parsed `FileEntry`s plus the authoritative live-id
            list to `apply_footers()`, which merges and swaps atomically.

The commit (and only the commit) runs under the shared service lock, so
the *last-good merged state keeps serving* for the entire duration of the
slow half: a refresh against an object store with hundred-millisecond
footer reads never blocks an `estimate()` call.

A file that vanishes between listing and reading is treated as removed
(never added) — the same semantics `StatsCatalog.update()` applies — so a
compaction job racing the ingestor produces a consistent, monotonic view.

`generation` increments on every committed refresh that changed the
dataset; the serving layer folds it into responses so clients can observe
state progression without comparing fingerprint sets.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import threading
import time
from typing import Callable, List, Optional, Tuple

from repro.catalog import FileEntry, StatsCatalog, UpdateSummary
from repro.obs import span, timed_acquire


@dataclasses.dataclass
class IngestStats:
    """Observability counters for the ingestion half (see `/health`)."""

    refreshes: int = 0            # refresh() calls that ran to completion
    commits: int = 0              # refreshes that changed the dataset
    fingerprints: int = 0         # fingerprint() calls issued
    footers_read: int = 0         # read_footer() calls that succeeded
    vanished: int = 0             # files lost between listing and reading
    errors: int = 0               # refreshes that raised (state untouched)
    last_error: Optional[str] = None
    last_refresh_s: float = 0.0   # wall time of the most recent refresh


class AsyncIngestor:
    """Non-blocking ingestion loop feeding one `StatsCatalog`.

    Args:
      catalog: the catalog to feed. The ingestor assumes it is the only
        writer; route manual rescans through `refresh()`, not
        `catalog.update()`.
      max_workers: thread-pool width for the scatter phases.
      poll_interval: seconds between automatic refreshes once `start()` is
        called; None means manual `refresh()` only.
      lock: the lock guarding catalog state, shared with the serving layer
        (reads and the commit both take it; footer I/O never does).
      on_commit: callback invoked (under the lock) after each committed
        refresh that changed the dataset — the service hooks cache
        compaction and optional cache spilling here.
    """

    def __init__(
        self,
        catalog: StatsCatalog,
        *,
        max_workers: int = 8,
        poll_interval: Optional[float] = None,
        lock: Optional[threading.RLock] = None,
        on_commit: Optional[Callable[[UpdateSummary], None]] = None,
    ):
        if max_workers < 1:
            raise ValueError("max_workers must be >= 1")
        self.catalog = catalog
        self.max_workers = max_workers
        self.poll_interval = poll_interval
        self.lock = lock if lock is not None else threading.RLock()
        self.on_commit = on_commit
        self.stats = IngestStats()
        self.generation = 0
        self._refresh_mutex = threading.Lock()  # serialize refreshes
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None

    # -- one refresh ---------------------------------------------------------

    def refresh(self) -> UpdateSummary:
        """Scatter-gather one full re-scan and commit it.

        Thread-safe and serialized: concurrent callers queue up rather than
        racing the snapshot/commit pair. Raises whatever the merge raises
        (e.g. a schema-mismatched file) — the previous state keeps serving
        and the error is recorded in `stats.last_error`.
        """
        with self._refresh_mutex, span("ingest.refresh") as sp:
            t0 = time.perf_counter()
            try:
                fresh, live_ids = self._scatter_gather()
                sp.set_attribute("footers", len(fresh))
                # ONE critical section for commit + generation + on_commit:
                # a reader must never observe the new merged state paired
                # with a pre-commit generation/ETag (the serving layer
                # rotates its state token inside on_commit).
                timed_acquire(self.lock, "ingest.lock_wait")
                try:
                    with span("catalog.merge", footers=len(fresh)):
                        summary = self.catalog.apply_footers(
                            fresh, live_ids=live_ids
                        )
                    if summary.changed:
                        self.generation += 1
                        self.stats.commits += 1
                        if self.on_commit is not None:
                            self.on_commit(summary)
                finally:
                    self.lock.release()
            except Exception as e:
                self.stats.errors += 1
                self.stats.last_error = f"{type(e).__name__}: {e}"
                raise
            finally:
                self.stats.last_refresh_s = time.perf_counter() - t0
            self.stats.refreshes += 1
            return summary

    def _scatter_gather(self) -> Tuple[List[FileEntry], List[str]]:
        """The slow, lock-free half: fingerprint sweep + footer reads."""
        source = self.catalog.source
        ids = source.list_files()
        with self.lock:
            prev = self.catalog.entry_fingerprints()

        def fingerprint(fid: str) -> Tuple[str, Optional[str]]:
            try:
                return fid, source.fingerprint(fid)
            except FileNotFoundError:
                return fid, None

        def read(fid_fp: Tuple[str, str]) -> Optional[FileEntry]:
            fid, fp = fid_fp
            try:
                return FileEntry(fid, fp, source.read_footer(fid))
            except FileNotFoundError:
                return None

        pool = self._get_pool()
        fps = list(pool.map(fingerprint, ids))
        self.stats.fingerprints += len(fps)
        live = [(fid, fp) for fid, fp in fps if fp is not None]
        changed = [(fid, fp) for fid, fp in live if prev.get(fid) != fp]
        fresh: List[FileEntry] = [
            e for e in pool.map(read, changed) if e is not None
        ]
        self.stats.footers_read += len(fresh)
        # A file can vanish between fingerprint and footer read: drop it
        # from the live set too, or apply_footers would demand its footer.
        lost = {fid for fid, _ in changed} - {e.file_id for e in fresh}
        self.stats.vanished += (len(fps) - len(live)) + len(lost)
        live_ids = [fid for fid, _ in live if fid not in lost]
        return fresh, live_ids

    def _get_pool(self) -> concurrent.futures.ThreadPoolExecutor:
        # One executor for the ingestor's lifetime (recreated after stop()):
        # a short poll_interval must not churn max_workers OS threads per
        # sweep. Only refresh() uses it, and refreshes are serialized.
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.max_workers, thread_name_prefix="ndv-ingest"
            )
        return self._pool

    # -- polling loop --------------------------------------------------------

    def start(self) -> None:
        """Start the background polling loop (requires `poll_interval`)."""
        if self._thread is not None:
            return
        if not self.poll_interval:
            raise ValueError("start() requires a poll_interval")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._poll_loop, name="ndv-ingest-poll", daemon=True
        )
        self._thread.start()

    def _poll_loop(self) -> None:
        while not self._stop.wait(self.poll_interval):
            try:
                self.refresh()
            except Exception:
                # recorded in stats.last_error; last-good state keeps serving
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()
