"""Synthesized lakehouse footers (`repro.columnar.lakehouse`)."""
import numpy as np
import pytest

from repro.catalog import StatsCatalog
from repro.catalog.source import InMemoryMetadataSource
from repro.columnar.lakehouse import (
    LAYOUTS, LakehouseShape, synthesize_lakehouse, table_columns,
)
from repro.columnar.writer import DEFAULT_DICT_PAGE_LIMIT
from repro.core.ndv.types import Layout
from repro.engine import EngineConfig, EstimationEngine

SHAPE = LakehouseShape(tables=8, columns_per_table=8, files=4,
                       row_groups_per_file=4, rows_per_group=1 << 14)


@pytest.fixture(scope="module")
def lake():
    return synthesize_lakehouse(3, SHAPE)


def test_shape_and_schema(lake):
    footers, truth, layouts = lake
    assert len(footers) == SHAPE.files
    names = [n for t in range(SHAPE.tables) for n in table_columns(SHAPE, t)]
    assert sorted(truth) == sorted(names) == sorted(layouts)
    for f in footers.values():
        assert f.column_names == names
        assert f.num_row_groups == SHAPE.row_groups_per_file
        assert f.num_rows == SHAPE.rows_per_group * SHAPE.row_groups_per_file
    assert all(1 <= v <= SHAPE.rows for v in truth.values())
    assert set(layouts.values()) == set(LAYOUTS)


def test_deterministic_in_seed(lake):
    again, _, _ = synthesize_lakehouse(3, SHAPE)
    other, _, _ = synthesize_lakehouse(4, SHAPE)
    first = sorted(lake[0])[0]
    assert again[first].to_json() == lake[0][first].to_json()
    assert other[first].to_json() != lake[0][first].to_json()


def test_chunks_follow_the_writer_accounting(lake):
    footers, _, _ = lake
    for f in footers.values():
        for name in f.column_names[:16]:
            for c in f.chunks(name):
                assert c.min_key <= c.max_key
                assert c.total_uncompressed_size == (
                    c.dict_page_size + c.data_page_size
                )
                assert c.dictionary_encoded == (
                    0 < c.dict_page_size <= DEFAULT_DICT_PAGE_LIMIT
                )


def _ranges(footers, name):
    chunks = [c for fid in sorted(footers) for c in footers[fid].chunks(name)]
    return (np.array([c.min_key for c in chunks]),
            np.array([c.max_key for c in chunks]))


def test_sorted_columns_own_consecutive_ranges(lake):
    footers, _, layouts = lake
    for name, layout in layouts.items():
        if layout == "sorted":
            lo, hi = _ranges(footers, name)
            assert np.all(np.diff(lo) >= 0) and np.all(hi[:-1] <= lo[1:])


def test_catalog_packs_and_detects_layouts(lake):
    footers, _, layouts = lake
    catalog = StatsCatalog(
        InMemoryMetadataSource(footers),
        engine=EstimationEngine(EngineConfig(backend="ref")),
    )
    batch = catalog.packed_batch()
    assert (batch.batch, batch.max_groups) == (
        SHAPE.tables * SHAPE.columns_per_table, SHAPE.row_groups
    )
    est = catalog.estimate(mode="improved")
    spread = [n for n, l in layouts.items() if l == "well_spread"]
    assert all(est[n].layout == Layout.WELL_SPREAD for n in spread)
    ordered = [n for n, l in layouts.items() if l == "sorted"]
    assert sum(est[n].layout == Layout.SORTED for n in ordered) > len(ordered) // 2
