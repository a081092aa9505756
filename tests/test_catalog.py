"""StatsCatalog subsystem: packing, caching, incremental ingestion, parity.

Covers the acceptance criteria of the catalog refactor:
  * catalog estimates == estimate_columns on the merged metadata (exact)
  * warm calls perform no re-packing and hit the estimate cache
  * update() ingests only new/changed footers and merges incrementally
  * shape bucketing keeps jit traces shared across nearby shapes
  * the vectorized packer reproduces the legacy per-column loop bit-exactly
  * estimate_file threads mode through to the estimator
"""
import dataclasses

import numpy as np
import pytest

from repro.catalog import (
    BatchPacker,
    InMemoryMetadataSource,
    StatsCatalog,
    bucket_size,
)
from repro.columnar import read_footer, write_file
from repro.columnar.writer import WriterOptions
from repro.core import estimate_columns, estimate_file
from repro.core.ndv.estimator import estimate_batch
from repro.core.ndv.types import ColumnBatch, ColumnMetadata, PhysicalType


def _shard(seed, rows=512, vocab=64):
    rng = np.random.default_rng(seed)
    return {
        "tok": rng.integers(0, vocab, rows).astype(np.int64),
        "val": np.round(rng.uniform(0, 100, rows), 1),
        "tag": rng.choice(np.array(["red", "green", "blue", "cyan"]), rows),
    }


@pytest.fixture()
def dataset(tmp_path):
    for i in range(3):
        write_file(
            str(tmp_path / f"shard_{i:03d}"), _shard(i),
            options=WriterOptions(row_group_size=128),
        )
    return str(tmp_path)


def test_estimate_matches_estimate_columns_exactly(dataset):
    catalog = StatsCatalog(dataset)
    merged = catalog.merged_metadata()
    cols = [merged[n] for n in catalog.column_names]
    for mode in ("paper", "improved"):
        got = catalog.estimate(mode=mode)
        ref = {e.column_name: e for e in estimate_columns(cols, mode=mode)}
        assert got.keys() == ref.keys()
        for name in got:
            assert got[name] == ref[name], name


def test_warm_cache_no_repack_no_rescan(dataset):
    catalog = StatsCatalog(dataset)
    first = catalog.estimate(mode="improved")
    assert catalog.stats.packs == 1
    assert catalog.stats.estimate_cache_misses == 1
    second = catalog.estimate(mode="improved")
    assert second == first
    assert catalog.stats.packs == 1               # no re-pack
    assert catalog.stats.estimate_cache_hits == 1
    # a different mode re-estimates but still reuses the packed batch
    catalog.estimate(mode="paper")
    assert catalog.stats.packs == 1
    assert catalog.stats.estimate_cache_misses == 2


def test_incremental_update_reads_only_new_footers(dataset, tmp_path):
    catalog = StatsCatalog(dataset)
    catalog.estimate()
    reads = catalog.stats.footers_read
    assert reads == 3
    key_before = catalog.fingerprint_key()

    write_file(
        str(tmp_path / "shard_099"), _shard(99),
        options=WriterOptions(row_group_size=128),
    )
    summary = catalog.update()
    assert summary.added == 1 and summary.updated == 0 and summary.removed == 0
    assert catalog.stats.footers_read == reads + 1   # only the new footer
    assert catalog.fingerprint_key() != key_before
    assert catalog.num_files == 4

    # merged view covers the new chunks; estimates recompute (cache miss)
    misses = catalog.stats.estimate_cache_misses
    ests = catalog.estimate()
    assert catalog.stats.estimate_cache_misses == misses + 1
    merged = catalog.merged_metadata()
    assert merged["tok"].num_row_groups == 16  # 4 files x 4 row groups
    cols = [merged[n] for n in catalog.column_names]
    ref = {e.column_name: e for e in estimate_columns(cols)}
    for name in ests:
        assert ests[name] == ref[name]


def test_update_detects_rewrites_via_fingerprint():
    f0 = write_file_footer(_shard(0))
    f1 = write_file_footer(_shard(1))
    src = InMemoryMetadataSource({"a": f0, "b": f1})
    catalog = StatsCatalog(src)
    before = catalog.estimate()
    src.add("a", write_file_footer(_shard(7)))  # rewrite file "a"
    summary = catalog.update()
    assert summary.updated == 1 and summary.added == 0
    after = catalog.estimate()
    assert catalog.stats.estimate_cache_misses == 2
    assert set(after) == set(before)


def test_failed_update_preserves_consistent_state(dataset, tmp_path):
    catalog = StatsCatalog(dataset)
    before = catalog.estimate()
    files_before = catalog.num_files
    # a schema-mismatched file arrives: update() must fail...
    write_file(
        str(tmp_path / "shard_bad"), {"other": np.arange(64)},
        options=WriterOptions(row_group_size=32),
    )
    with pytest.raises(ValueError, match="schema"):
        catalog.update()
    # ...and every subsequent retry must fail the same way (the bad file's
    # fingerprint must not be committed as 'seen'),
    with pytest.raises(ValueError, match="schema"):
        catalog.update()
    # ...while the previous consistent view keeps serving.
    assert catalog.num_files == files_before
    assert catalog.estimate() == before


def test_schema_mismatch_raises_regardless_of_order(tmp_path):
    write_file(str(tmp_path / "a"), {"x": np.arange(50), "y": np.arange(50)})
    write_file(str(tmp_path / "b"), {"x": np.arange(50)})
    with pytest.raises(ValueError, match="missing columns \\['y'\\]"):
        StatsCatalog(str(tmp_path)).estimate()
    # reversed listing order: the extra-column direction must also raise,
    # not silently drop column y from the dataset view
    f_a = read_footer(str(tmp_path / "a"))
    f_b = read_footer(str(tmp_path / "b"))
    with pytest.raises(ValueError, match="unexpected columns \\['y'\\]"):
        StatsCatalog(InMemoryMetadataSource({"1b": f_b, "2a": f_a})).estimate()


def test_update_add_remove_rewrite_in_one_refresh():
    """One refresh covering all three change kinds reports them all —
    and matches the async ingestion path's semantics (see test_service)."""
    src = InMemoryMetadataSource({
        "a": write_file_footer(_shard(1)),
        "b": write_file_footer(_shard(2)),
        "c": write_file_footer(_shard(3)),
    })
    catalog = StatsCatalog(src)
    assert catalog.update() == (3, 0, 0, 3)
    src.add("d", write_file_footer(_shard(4)))   # add
    src.remove("b")                              # remove
    src.add("c", write_file_footer(_shard(33)))  # rewrite
    summary = catalog.update()
    assert summary == (1, 1, 1, 3)
    assert summary.changed
    assert set(catalog.files) == {"a", "c", "d"}
    assert catalog.estimate() == StatsCatalog(src).estimate()
    # steady state afterwards: nothing to report
    assert catalog.update() == (0, 0, 0, 3)


class _VanishingSource(InMemoryMetadataSource):
    """Lists a file whose fingerprint/footer read then fails: the race of a
    deletion landing between the listing and the stat."""

    def __init__(self, footers, vanished=()):
        super().__init__(footers)
        self.vanished = set(vanished)

    def list_files(self):
        return sorted(set(super().list_files()) | self.vanished)

    def fingerprint(self, file_id):
        if file_id in self.vanished:
            raise FileNotFoundError(file_id)
        return super().fingerprint(file_id)


def test_update_reports_vanished_files_as_removed():
    src = _VanishingSource({
        "a": write_file_footer(_shard(1)),
        "b": write_file_footer(_shard(2)),
    })
    catalog = StatsCatalog(src)
    assert catalog.update() == (2, 0, 0, 2)
    # "b" is deleted but still shows up in the listing
    footer_b = src.read_footer("b")
    src.remove("b")
    src.vanished.add("b")
    summary = catalog.update()
    assert summary == (0, 0, 1, 1)
    assert catalog.files == ["a"]
    # a vanished file that was never ingested is not reported as anything
    src.vanished.add("ghost")
    assert catalog.update() == (0, 0, 0, 1)
    # and reappearing is an ordinary addition
    src.vanished.remove("b")
    src.add("b", footer_b)
    assert catalog.update() == (1, 0, 0, 2)


def test_apply_footers_rejects_unknown_live_id():
    src = InMemoryMetadataSource({"a": write_file_footer(_shard(1))})
    catalog = StatsCatalog(src)
    catalog.update()
    with pytest.raises(ValueError, match="neither a previous"):
        catalog.apply_footers([], live_ids=["a", "mystery"])


# -- per-file views kept across commits ---------------------------------------


def _view_shard(seed, rows=256):
    rng = np.random.default_rng(seed)
    words = np.array(["alpha", "beta", "gamma", "delta", "epsilon"])
    return {
        "tok": rng.integers(0, 32, rows).astype(np.int64),
        "val": np.round(rng.uniform(0, 10, rows), 1),
        # every value shares the 8-byte prefix "shared__": only the length
        # and the repr tell the row-group extrema apart
        "s": np.char.add("shared__", rng.choice(words, rows)),
    }


def _view_footer(seed):
    return write_file_footer(_view_shard(seed), rg=64)


def _merge_view_counts():
    from repro.obs import registry

    c = registry().counter("ndv_merge_views_total")
    return c.value(result="reused"), c.value(result="built")


def _assert_same_view(got, want):
    for f in dataclasses.fields(ColumnMetadata):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            assert isinstance(a, np.ndarray) and a.dtype == b.dtype, f.name
            assert np.array_equal(a, b), f.name
        else:
            assert type(a) is type(b) and a == b, f.name


def _assert_views_are_the_live_files(catalog):
    assert {fid: fp for fid, (fp, _) in catalog._views.items()} == (
        catalog.entry_fingerprints()
    )
    for fp, views in catalog._views.values():
        assert sorted(views) == sorted(catalog.column_names)


def _assert_merges_like_every_footer(catalog):
    """The merged view equals a merge of every live footer from scratch
    (a catalog holding no views), field by field and in chunk order."""
    live = list(catalog._entries.values())
    want, names, _ = StatsCatalog(catalog.source)._merge_all(live)
    got = catalog.merged_metadata()
    assert list(got) == list(want) and catalog.column_names == names
    for name in names:
        _assert_same_view(got[name], want[name])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kept_views_merge_like_every_footer_from_scratch(seed):
    from repro.catalog import FileEntry
    from repro.service import AsyncIngestor

    rng = np.random.default_rng(seed)
    contents = iter(range(100 * seed + 1000, 10**6))
    src = _VanishingSource(
        {f"f{i:02d}": _view_footer(next(contents)) for i in range(6)})
    catalog = StatsCatalog(src)
    ingestor = AsyncIngestor(catalog, max_workers=2)
    catalog.update()
    _assert_views_are_the_live_files(catalog)
    _assert_merges_like_every_footer(catalog)
    next_id = 6
    kinds = ["insert", "rewrite", "delete", "reread", "vanish", "mixed"]
    seen = set()
    try:
        for step, kind in enumerate(rng.permutation(kinds * 4)):
            live = [f for f in src.list_files() if f not in src.vanished]
            if kind in ("delete", "vanish") and len(live) <= 2:
                kind = "insert"
            pick = str(rng.choice(live))
            if kind in ("insert", "mixed"):
                src.add(f"f{next_id:02d}", _view_footer(next(contents)))
                next_id += 1
            if kind in ("rewrite", "mixed"):
                src.add(pick, _view_footer(next(contents)))
            if kind == "delete":
                src.remove(pick)
            if kind == "mixed":
                src.remove(str(rng.choice([f for f in live if f != pick])))
            if kind == "vanish":
                src.remove(pick)
                src.vanished.add(pick)
            if kind == "reread":
                # an ingestion race re-read an unchanged footer: no change
                fresh = [FileEntry(pick, src.fingerprint(pick),
                                   src.read_footer(pick))]
                summary = catalog.apply_footers(fresh, live_ids=live)
                assert not summary.changed
            elif step % 2:
                assert ingestor.refresh().changed
            else:
                assert catalog.update().changed
            seen.add(kind)
            _assert_views_are_the_live_files(catalog)
            _assert_merges_like_every_footer(catalog)
    finally:
        ingestor.stop()
    assert seen == set(kinds)


@pytest.mark.parametrize("how", ["rewrite", "insert"])
@pytest.mark.parametrize("bad", ["columns", "type"])
def test_schema_mismatch_keeps_entries_views_and_merged_view(how, bad):
    src = InMemoryMetadataSource(
        {f: _view_footer(i) for i, f in enumerate("abcd")})
    catalog = StatsCatalog(src)
    catalog.update()
    src.remove("a")
    catalog.update()  # a re-merge from kept views
    entries, views, merged = catalog._entries, catalog._views, catalog._merged
    held = {fid: (fp, dict(v)) for fid, (fp, v) in views.items()}
    before = catalog.estimate()
    if bad == "columns":
        footer = write_file_footer({"other": np.arange(64)})
    else:  # same names, but "tok" turns from INT64 to DOUBLE
        footer = write_file_footer(
            {**_view_shard(9), "tok": np.arange(256, dtype=np.float64)},
            rg=64)
    src.add("c" if how == "rewrite" else "e", footer)
    with pytest.raises(ValueError):
        catalog.update()
    assert catalog._entries is entries and catalog._merged is merged
    assert catalog._views is views
    assert {fid: (fp, dict(v)) for fid, (fp, v) in views.items()} == held
    assert all(v[n] is held[fid][1][n]
               for fid, (_, v) in views.items() for n in v)
    _assert_views_are_the_live_files(catalog)
    assert catalog.estimate() == before


def test_rewrite_builds_views_only_for_the_rewritten_file(monkeypatch):
    from repro.service import AsyncIngestor

    src = InMemoryMetadataSource(
        {f"f{i}": _view_footer(i) for i in range(5)})
    catalog = StatsCatalog(src)
    catalog.update()
    parsed = []
    per_file = catalog._per_file

    def counting(entry, names):
        parsed.append(entry.file_id)
        return per_file(entry, names)

    monkeypatch.setattr(catalog, "_per_file", counting)
    reused0, built0 = _merge_view_counts()
    src.add("f2", _view_footer(22))
    assert catalog.update().updated == 1
    assert parsed == ["f2"]
    # three columns: four files' views reused, one file's built
    assert _merge_view_counts() == (reused0 + 12, built0 + 3)
    src.remove("f0")  # a delete parses nothing
    assert catalog.update().removed == 1
    assert parsed == ["f2"]
    assert _merge_view_counts() == (reused0 + 24, built0 + 3)
    ingestor = AsyncIngestor(catalog, max_workers=2)
    try:
        src.add("f4", _view_footer(44))
        assert ingestor.refresh().updated == 1
    finally:
        ingestor.stop()
    assert parsed == ["f2", "f4"]
    assert _merge_view_counts() == (reused0 + 33, built0 + 6)
    _assert_merges_like_every_footer(catalog)


# -- persistent-cache hygiene ------------------------------------------------


def test_save_cache_compacts_stale_fingerprint_sets(dataset, tmp_path):
    import json

    catalog = StatsCatalog(dataset)
    catalog.estimate(mode="paper")
    write_file(
        str(tmp_path / "shard_000"), _shard(42),   # rewrite one file
        options=WriterOptions(row_group_size=128),
    )
    catalog.update()
    catalog.estimate(mode="paper")
    catalog.estimate(mode="improved")
    assert len(catalog._estimate_cache) == 3       # 1 stale + 2 live
    path = catalog.save_cache()
    with open(path) as f:
        entries = json.load(f)["entries"]
    live = sorted(catalog.fingerprint_key())
    assert len(entries) == 2                       # stale entry dropped
    assert all(e["key"]["files"] == live for e in entries)
    # opting out persists the LRU verbatim
    catalog.save_cache(compact=False)
    with open(path) as f:
        assert len(json.load(f)["entries"]) == 3

    # in-memory hook drops the same stale entries (plus the stale batch
    # and the stale provenance sidecar, which is keyed like the estimates)
    assert catalog.compact_caches() == 3     # 1 estimate + 1 batch + 1 prov
    assert len(catalog._estimate_cache) == 2
    assert len(catalog._provenance_cache) == 2


def test_auto_load_cache_serves_warm_and_is_mtime_guarded(dataset):
    import os

    first = StatsCatalog(dataset)
    expected = first.estimate(mode="improved")
    path = first.save_cache()

    warm = StatsCatalog(dataset, auto_load_cache=True)
    got = warm.estimate(mode="improved")
    assert got == expected
    assert warm.stats.packs == 0                   # served from the spill
    assert warm.stats.estimate_cache_hits == 1
    # unchanged file -> guarded no-op; touched file -> reloaded
    assert warm.maybe_load_cache() == 0
    os.utime(path, ns=(os.stat(path).st_atime_ns, os.stat(path).st_mtime_ns + 1))
    assert warm.maybe_load_cache() == 1
    # missing file is a quiet cold start
    os.remove(path)
    assert StatsCatalog(dataset, auto_load_cache=True).maybe_load_cache() == 0


def write_file_footer(cols, rg=128):
    import tempfile

    d = tempfile.mkdtemp()
    return write_file(d, cols, options=WriterOptions(row_group_size=rg))


# -- packer ------------------------------------------------------------------


def _legacy_pack(cols):
    """The historical per-column Python loop, kept as a reference oracle."""
    import jax.numpy as jnp

    b = len(cols)
    r = max(max((c.num_row_groups for c in cols), default=1), 1)
    f = lambda: np.zeros((b,), np.float32)  # noqa: E731
    g = lambda: np.zeros((b, r), np.float32)  # noqa: E731
    chunk_S, chunk_rows, chunk_nulls = g(), g(), g()
    chunk_dict = np.zeros((b, r), bool)
    N, nulls, m_min, m_max, mean_len = f(), f(), f(), f(), f()
    n_groups = np.zeros((b,), np.int32)
    len_sample = np.zeros((b,), np.int32)
    mins, maxs = g(), g()
    valid = np.zeros((b, r), bool)
    fixed_width = np.zeros((b,), bool)
    int_like = np.zeros((b,), bool)
    single_byte = np.zeros((b,), bool)
    for i, c in enumerate(cols):
        n = c.num_row_groups
        chunk_S[i, :n] = np.asarray(c.chunk_sizes, np.float32)
        chunk_rows[i, :n] = np.asarray(c.chunk_rows, np.float32)
        chunk_nulls[i, :n] = np.asarray(c.chunk_nulls, np.float32)
        chunk_dict[i, :n] = np.asarray(c.chunk_dict_encoded, bool)
        N[i] = c.num_values
        nulls[i] = c.null_count
        n_groups[i] = n
        mins[i, :n] = np.asarray(c.mins, np.float32)[:n]
        maxs[i, :n] = np.asarray(c.maxs, np.float32)[:n]
        valid[i, :n] = True
        m_min[i] = c.distinct_min_count
        m_max[i] = c.distinct_max_count
        w = c.physical_type.fixed_width
        if w is not None:
            mean_len[i] = float(w)
            len_sample[i] = n * 2
            fixed_width[i] = True
        elif n == 1:
            mean_len[i] = float(
                (float(c.min_lengths[0]) + float(c.max_lengths[0])) / 2.0
            )
            len_sample[i] = 2
        else:
            lens = np.concatenate([
                np.asarray(c.min_lengths, np.float64)[:n],
                np.asarray(c.max_lengths, np.float64)[:n],
            ])
            mean_len[i] = float(lens.mean()) if lens.size else 1.0
            len_sample[i] = int(c.distinct_min_count + c.distinct_max_count)
        int_like[i] = c.physical_type.is_integer_like
        single_byte[i] = (
            c.physical_type == PhysicalType.BYTE_ARRAY
            and float(np.max(np.asarray(c.max_lengths)[:n], initial=0.0)) <= 1.0
        )
    J = jnp.asarray
    return ColumnBatch(
        chunk_S=J(chunk_S), chunk_rows=J(chunk_rows),
        chunk_nulls=J(chunk_nulls), chunk_dict_encoded=J(chunk_dict),
        N=J(N), nulls=J(nulls), n_groups=J(n_groups),
        mins=J(mins), maxs=J(maxs), valid=J(valid),
        m_min=J(m_min), m_max=J(m_max), mean_len=J(mean_len),
        len_sample=J(len_sample), fixed_width=J(fixed_width),
        int_like=J(int_like), single_byte=J(single_byte),
    )


def _mixed_columns(dataset):
    catalog = StatsCatalog(dataset)
    merged = catalog.merged_metadata()
    cols = [merged[n] for n in catalog.column_names]
    # add a ragged single-group column and an all-null-length corner
    rng = np.random.default_rng(3)
    cols.append(ColumnMetadata(
        chunk_sizes=np.array([512.0]),
        chunk_rows=np.array([100.0]),
        chunk_nulls=np.array([4.0]),
        chunk_dict_encoded=np.array([True]),
        mins=np.array([3.0]),
        maxs=np.array([9.0]),
        min_lengths=np.array([2.0]),
        max_lengths=np.array([6.0]),
        distinct_min_count=1.0,
        distinct_max_count=1.0,
        physical_type=PhysicalType.BYTE_ARRAY,
        column_name="ragged",
    ))
    return cols


def test_vectorized_packer_matches_legacy_loop(dataset):
    cols = _mixed_columns(dataset)
    got = BatchPacker(bucket_rows=False, bucket_cols=False).pack(cols)
    ref = _legacy_pack(cols)
    for field in dataclasses.fields(ColumnBatch):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field.name)),
            np.asarray(getattr(ref, field.name)),
            err_msg=field.name,
        )
    # from_columns is the same unbucketed path
    fc = ColumnBatch.from_columns(cols)
    np.testing.assert_array_equal(np.asarray(fc.chunk_S), np.asarray(ref.chunk_S))


def test_bucketed_pack_is_masked_superset(dataset):
    cols = _mixed_columns(dataset)
    plain = BatchPacker(bucket_rows=False, bucket_cols=False).pack(cols)
    bucketed = BatchPacker().pack(cols)
    b, r = plain.batch, plain.max_groups
    assert bucketed.batch == bucket_size(b)
    assert bucketed.max_groups == bucket_size(r, 8)
    for field in dataclasses.fields(ColumnBatch):
        got = np.asarray(getattr(bucketed, field.name))
        ref = np.asarray(getattr(plain, field.name))
        sliced = got[:b, :r] if got.ndim == 2 else got[:b]
        np.testing.assert_array_equal(sliced, ref, err_msg=field.name)
    # padding lanes are fully masked
    assert not np.asarray(bucketed.valid)[b:].any()
    assert not np.asarray(bucketed.valid)[:, r:].any()
    assert (np.asarray(bucketed.n_groups)[b:] == 0).all()


def test_bucketing_shares_jit_traces(dataset):
    cols = _mixed_columns(dataset)
    base = cols[0]
    packer = BatchPacker()
    shapes = set()
    before = estimate_batch._cache_size()
    for r in (9, 11, 13, 16):
        trimmed = dataclasses.replace(
            base,
            chunk_sizes=np.resize(np.asarray(base.chunk_sizes), r),
            chunk_rows=np.resize(np.asarray(base.chunk_rows), r),
            chunk_nulls=np.resize(np.asarray(base.chunk_nulls), r),
            chunk_dict_encoded=np.resize(np.asarray(base.chunk_dict_encoded), r),
            mins=np.resize(np.asarray(base.mins), r),
            maxs=np.resize(np.asarray(base.maxs), r),
            min_lengths=np.resize(np.asarray(base.min_lengths), r),
            max_lengths=np.resize(np.asarray(base.max_lengths), r),
            min_reprs=None,
            max_reprs=None,
        )
        batch = packer.pack([trimmed])
        shapes.add((batch.batch, batch.max_groups))
        estimate_batch(batch, mode="paper")
    assert shapes == {(1, 16)}  # 9..16 row groups share one bucketed shape
    assert estimate_batch._cache_size() - before <= 1


def test_estimate_file_threads_mode(dataset):
    from repro.columnar.reader import column_metadata_from_footer, list_files

    footer = read_footer(list_files(dataset)[0])
    cols = [
        column_metadata_from_footer(footer, n) for n in footer.column_names
    ]
    for mode in ("paper", "improved"):
        got = estimate_file(footer, mode=mode)
        ref = estimate_columns(cols, mode=mode)
        assert got == ref


def test_schema_bounds_via_catalog(dataset):
    catalog = StatsCatalog(dataset)
    unbounded = catalog.estimate()
    bounded = catalog.estimate(schema_bounds={"tok": 10.0})
    assert bounded["tok"].ndv <= 10.0 < unbounded["tok"].ndv
    # other columns unaffected by someone else's bound
    assert bounded["val"].ndv == unbounded["val"].ndv


def test_pipeline_plans_through_catalog(dataset):
    from repro.data.pipeline import DataConfig, TokenPipeline

    pipe = TokenPipeline(DataConfig(root=dataset, token_column="tok"))
    ests = pipe.catalog.estimate(mode=pipe.cfg.mode)
    assert pipe.plan.estimates == ests
    assert set(pipe.plan.memory) == set(ests)
    assert pipe.plan.total_staging_bytes > 0
    assert pipe.vocab_estimate() is ests["tok"] or (
        pipe.vocab_estimate() == ests["tok"]
    )


def test_concurrent_save_cache_merges_not_clobbers(dataset):
    # Two catalogs (standing in for two replica processes) spill different
    # entries to the shared file: the union must survive, whichever order
    # the writes land in.
    import json

    a = StatsCatalog(dataset)
    b = StatsCatalog(dataset)
    a.estimate(mode="paper")
    b.estimate(mode="improved")
    path = a.save_cache()
    assert b.save_cache() == path
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert sorted(e["key"]["mode"] for e in entries) == ["improved", "paper"]

    # a third cold catalog warms from the merged spill: both modes, no packs
    c = StatsCatalog(dataset, auto_load_cache=True)
    assert c.estimate(mode="paper") == a.estimate(mode="paper")
    assert c.estimate(mode="improved") == b.estimate(mode="improved")
    assert c.stats.packs == 0


def test_save_cache_skips_when_disk_is_newer_and_complete(dataset):
    import os

    a = StatsCatalog(dataset)
    b = StatsCatalog(dataset)
    a.estimate(mode="paper")
    b.estimate(mode="paper")
    b.estimate(mode="improved")
    path = b.save_cache()                  # b's spill is a superset of a's
    mtime = os.stat(path).st_mtime_ns
    a.save_cache()                         # nothing to add -> skipped
    assert os.stat(path).st_mtime_ns == mtime
    # with something new to contribute the write happens (and merges)
    a.estimate(mode="paper", schema_bounds={"tok": 8.0})
    a.save_cache()
    assert os.stat(path).st_mtime_ns != mtime
    fresh = StatsCatalog(dataset, auto_load_cache=True)
    assert fresh.estimate(mode="improved") == b.estimate(mode="improved")
    assert fresh.stats.packs == 0


def test_save_cache_survives_concurrent_thread_writers(dataset):
    # Hammer one spill path from many threads; every write must stay
    # atomic and the final file must contain every writer's entry.
    import json
    import os
    import threading

    catalogs = []
    bounds = [{"tok": float(2 ** i)} for i in range(6)]
    for sb in bounds:
        c = StatsCatalog(dataset)
        c.estimate(mode="paper", schema_bounds=sb)
        catalogs.append(c)
    threads = [
        threading.Thread(target=c.save_cache) for c in catalogs for _ in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    path = catalogs[0]._default_cache_path()
    with open(path) as f:
        entries = json.load(f)["entries"]   # parses: no torn writes
    got = {tuple(e["key"]["schema_bounds"][0]) for e in entries}
    assert got == {("tok", b["tok"]) for b in bounds}
    # no temp-file litter left next to the dataset
    litter = [f for f in os.listdir(dataset) if f.endswith(".tmp")]
    assert litter == []


def test_spill_with_foreign_shape_is_treated_as_absent(dataset):
    # Valid JSON, right version, wrong shape: loads as a cold start and
    # save_cache overwrites it rather than crashing replica boot.
    import json

    catalog = StatsCatalog(dataset)
    catalog.estimate(mode="paper")
    path = catalog._default_cache_path()
    for junk in ('{"version": 1}', '{"version": 1, "entries": [{}]}', "[1]"):
        with open(path, "w") as f:
            f.write(junk)
        assert StatsCatalog(dataset, auto_load_cache=True).load_cache() == 0
        assert catalog.save_cache() == path
        with open(path) as f:
            assert len(json.load(f)["entries"]) == 1
