"""Footers of a lakehouse-scale catalog, synthesized without writing data.

The estimator reads footers only, so a catalog at the metadata scale of a
real lake can be served without a byte of data on disk: this module builds
the `FileFooter`s a Parquet writer would have emitted, from a seed, for
``tables x columns_per_table`` columns whose files all share one row-group
layout (by default 16 files x 16 row groups of 2^20 rows, parquet-mr's
128 MB block at ~4 bytes per value).

Each table gets one of the four layouts of `repro.columnar.generator`
(well-spread, sorted, partitioned, clustered), and every column a true NDV
drawn log-uniformly up to its row count. Per row group, the local distinct
count, the min/max statistics and the dictionary-encoded chunk size follow
from that layout the way they would for real data: uniform draws cover
D(1 - e^{-k/D}) of D values, sorted groups own consecutive slices of the
domain, partitions own one contiguous range per file, and clustered runs
draw only k/64 values. Sizes follow the writer's own accounting
(`repro.columnar.writer`): dictionary page + bit-packed indices, or plain
pages past the 1 MiB dictionary limit.

All tables share one schema here (a catalog spans the union of their
columns, named ``t{table}_c{column}``), which is what lets one dataset
hand the engine every column of the lake in one pack.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import numpy as np

from repro.columnar import format as fmt
from repro.columnar.writer import DEFAULT_DICT_PAGE_LIMIT
from repro.core.ndv.types import PhysicalType

LAYOUTS = ("well_spread", "sorted", "partitioned", "clustered")
# (physical type, value width in bytes); strings are 12-byte keys.
_TYPES = (
    (PhysicalType.INT64, 8),
    (PhysicalType.DATE32, 4),
    (PhysicalType.FLOAT64, 8),
    (PhysicalType.BYTE_ARRAY, 12),
)
_CLUSTER_RUN = 64   # mean rows per run of a clustered column


@dataclasses.dataclass(frozen=True)
class LakehouseShape:
    """Scale of the synthesized catalog (defaults: 4,096 columns x 256
    row groups of 2^20 rows each)."""

    tables: int = 128
    columns_per_table: int = 32
    files: int = 16
    row_groups_per_file: int = 16
    rows_per_group: int = 1 << 20

    @property
    def row_groups(self) -> int:
        return self.files * self.row_groups_per_file

    @property
    def rows(self) -> int:
        return self.row_groups * self.rows_per_group


def column_name(table: int, column: int) -> str:
    return f"t{table:03d}_c{column:02d}"


def table_columns(shape: LakehouseShape, table: int) -> list:
    return [column_name(table, c) for c in range(shape.columns_per_table)]


def _value_key(idx: np.ndarray, ptype: PhysicalType) -> Tuple[np.ndarray, list]:
    """Domain index -> (order-preserving stat keys, stat reprs)."""
    if ptype == PhysicalType.BYTE_ARRAY:
        reprs = [f"k{int(i):011d}" for i in idx]
        return np.array([fmt.stat_key(r, ptype) for r in reprs]), reprs
    if ptype == PhysicalType.DATE32:
        keys = 8000.0 + idx
    elif ptype == PhysicalType.FLOAT64:
        keys = idx * 0.37 - 1e6
    else:
        keys = idx * 10.0 + 7.0
    return keys.astype(np.float64), [repr(float(k)) for k in keys]


def _coverage(draws: np.ndarray, ndv: np.ndarray) -> np.ndarray:
    """Expected distinct values among `draws` uniform draws from `ndv`."""
    return ndv * -np.expm1(-draws / ndv)


def _column_groups(
    rng: np.random.Generator, layout: str, ndv: int, shape: LakehouseShape
):
    """Per row group (local ndv, min index, max index), each (row_groups,)."""
    n, k = shape.row_groups, shape.rows_per_group
    g = np.arange(n)
    if layout == "sorted":
        lo = np.floor(g * ndv / n)
        hi = np.maximum(np.floor((g + 1) * ndv / n) - 1, lo)
        return np.minimum(hi - lo + 1, k), lo, hi
    if layout == "partitioned":
        # One contiguous key range per file, files in shuffled order.
        part = rng.permutation(shape.files)[g // shape.row_groups_per_file]
        width = max(ndv / shape.files, 1.0)
        base, draws = np.floor(part * width), float(k)
    else:
        width, base = float(ndv), 0.0
        draws = float(k // _CLUSTER_RUN if layout == "clustered" else k)
    # The least (greatest) of `draws` uniform draws over `width` values:
    # P(min > x) = (1 - x)^draws, inverted at a uniform sample.
    lo_x = 1 - (1 - rng.random(n)) ** (1.0 / draws)
    hi_x = 1 - (1 - rng.random(n)) ** (1.0 / draws)
    lo = base + np.floor(width * lo_x)
    hi = base + np.minimum(np.floor(width * (1 - hi_x)), width - 1)
    local = _coverage(np.full(n, draws), np.full(n, width))
    return local, np.minimum(lo, hi), np.maximum(lo, hi)


def synthesize_lakehouse(
    seed: int, shape: LakehouseShape = LakehouseShape()
) -> Tuple[Dict[str, fmt.FileFooter], Dict[str, int], Dict[str, str]]:
    """(footers by file id, true NDV by column, layout by column).

    Deterministic in ``seed``. Table ``t`` takes layout ``LAYOUTS[t % 4]``;
    column ``c`` of a table takes type ``_TYPES[c % 4]``.
    """
    rng = np.random.default_rng(seed)
    n, k = shape.row_groups, shape.rows_per_group
    per_file = shape.row_groups_per_file
    chunks: Dict[str, list] = {}
    schema: Dict[str, int] = {}
    truth: Dict[str, int] = {}
    layouts: Dict[str, str] = {}
    for t in range(shape.tables):
        layout = LAYOUTS[t % len(LAYOUTS)]
        for c in range(shape.columns_per_table):
            name = column_name(t, c)
            ptype, width = _TYPES[c % len(_TYPES)]
            ndv = int(np.exp(rng.uniform(np.log(10.0), np.log(shape.rows))))
            null_frac = 0.0 if c % 3 else float(rng.uniform(0.0, 0.2))
            local, lo, hi = _column_groups(rng, layout, ndv, shape)
            local = np.maximum(np.round(local), 1.0)
            nulls = np.full(n, int(round(k * null_frac)))
            non_null = k - nulls
            dict_page = local * width
            plain = dict_page > DEFAULT_DICT_PAGE_LIMIT
            bits = np.maximum(np.ceil(np.log2(np.maximum(local, 1.0)) - 1e-9), 1)
            data_page = np.ceil(non_null * bits / 8.0)
            total = np.where(plain, non_null * width, dict_page + data_page)
            lo_keys, lo_reprs = _value_key(lo, ptype)
            hi_keys, hi_reprs = _value_key(hi, ptype)
            schema[name] = int(ptype)
            truth[name] = ndv
            layouts[name] = layout
            chunks[name] = [
                fmt.ColumnChunkMeta(
                    name=name,
                    physical_type=int(ptype),
                    num_values=k,
                    null_count=int(nulls[i]),
                    total_uncompressed_size=int(total[i]),
                    dict_page_size=0 if plain[i] else int(dict_page[i]),
                    data_page_size=int(total[i] - (0 if plain[i] else dict_page[i])),
                    encodings=["PLAIN"] if plain[i] else ["DICTIONARY"],
                    min_key=float(lo_keys[i]),
                    max_key=float(hi_keys[i]),
                    min_len=width,
                    max_len=width,
                    min_repr=lo_reprs[i],
                    max_repr=hi_reprs[i],
                )
                for i in range(n)
            ]
    footers = {}
    for f in range(shape.files):
        groups = [
            fmt.RowGroupMeta(
                num_rows=k,
                columns={name: cs[f * per_file + j] for name, cs in chunks.items()},
            )
            for j in range(per_file)
        ]
        footers[f"part-{f:05d}.parquet"] = fmt.FileFooter(
            num_rows=k * per_file, schema=dict(schema), row_groups=groups
        )
    return footers, truth, layouts
