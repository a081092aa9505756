"""The reader of the catalog's per-file view counter."""
import numpy as np

import run


def _read(series):
    return run.load_readers(["merge_view_reuse_share.maint"])[
        "merge_view_reuse_share.maint"]({"series": series})


def test_a_program_without_the_counter_reads_nothing():
    assert _read({"ndv_engine_dispatches_total": [({}, 3.0)]}) is None
    assert _read({}) is None


def test_a_window_that_merged_nothing_reads_nothing():
    assert _read({"ndv_merge_views_total": [
        ({"result": "reused"}, 0.0), ({"result": "built"}, 0.0)]}) is None


def test_reused_over_merged_in_the_window():
    from repro.catalog import InMemoryMetadataSource, StatsCatalog
    from repro.columnar.writer import WriterOptions, write_file

    def footer(seed, path):
        rng = np.random.default_rng(seed)
        return write_file(str(path), {
            "tok": rng.integers(0, 64, 256).astype(np.int64),
            "tag": rng.choice(np.array(["red", "blue"]), 256)},
            options=WriterOptions(row_group_size=64))

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        src = InMemoryMetadataSource(
            {f"f{i}": footer(i, f"{d}/f{i}") for i in range(4)})
        catalog = StatsCatalog(src)
        catalog.update()
        before = run.registry_values()
        src.add("f1", footer(11, f"{d}/f1b"))  # rewrite: 3 reused, 1 built
        catalog.update()
        src.add("f4", footer(4, f"{d}/f4"))    # insert: 1 built
        catalog.update()
        series = run.series_delta(before, run.registry_values())
    # two columns: 6 views reused, 2 + 2 built
    assert _read(series) == 60.0
