"""A TPC catalog's footers, synthesized from a seed, and the source that serves them.

The estimator reads footers only, so a catalog at the metadata scale of TPC-H
or TPC-DS at SF 1000 needs no data: each table is a list of files, each file a
list of row groups, and each (row group, column) chunk carries what a Parquet
writer records. A configuration file (``configs/<name>.json``) states per table
its rows and per column its type, its number of distinct values (NDV) and its
layout in the order the generator writes:

  sorted   row group g owns a consecutive slice of the domain;
  corr     correlated with the write order: row group g draws from a window of
           ``CORR_WINDOW`` of the domain that slides with g;
  spread   every row group draws from the whole domain.

A row group's distinct count, min and max follow from its draws: k uniform
draws over W values cover W(1 - e^(-k/W)) of them, and the least and greatest
draw are order statistics sampled from the seed. Sizes follow the writer's
accounting (`repro.columnar.writer`): a dictionary page plus bit-packed
indices, or plain pages once the dictionary passes 1 MiB.

Two views of one synthesis are kept apart. `Chunks` are plain numpy arrays per
(file, column): what the plain reference reads. `footer()` builds from them the
program's `FileFooter`, which is what the system under test ingests.

`LakeSource` is the benchmark's `MetadataSource`: footers in memory, a
fingerprint per file made of its id and the commit that wrote it (an object
store's ETag costs nothing to read), and a `commit()` that adds and removes
files atomically, as a table format's snapshot does.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

DICT_PAGE_LIMIT = 1 << 20   # parquet-mr's dictionary page size limit
CORR_WINDOW = 0.02          # share of the domain a correlated row group spans
EPOCH_1992 = 8035           # days from 1970-01-01 to 1992-01-01 (DATE32)
_B36 = "0123456789abcdefghijklmnopqrstuvwxyz"


@dataclasses.dataclass(frozen=True)
class Column:
    name: str
    kind: str        # "int" | "date" | "dec" | "str"
    width: int       # bytes per value
    ndv: int
    layout: str      # "sorted" | "corr" | "spread"
    null_frac: float


@dataclasses.dataclass(frozen=True)
class Table:
    name: str
    rows: int
    columns: Tuple[Column, ...]


@dataclasses.dataclass
class Chunks:
    """One column of one file, per row group: the reference's input."""

    size: np.ndarray       # total_uncompressed_size, bytes
    dict_page: np.ndarray  # dictionary page bytes (0 where written plain)
    rows: np.ndarray       # values including nulls
    nulls: np.ndarray
    dict_encoded: np.ndarray
    lo: np.ndarray         # domain index of the row group's min
    hi: np.ndarray         # domain index of the row group's max


@dataclasses.dataclass
class FileData:
    table: str
    rows: int
    group_rows: np.ndarray
    columns: Dict[str, Chunks]
    x0: float            # the file's place in the table's write order
    x1: float


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _ndv(rule, rows: int, table_rows: Dict[str, int]) -> int:
    """A column's NDV from its rule: a number, "rows", "@table", "x*f"."""
    if isinstance(rule, (int, float)):
        return max(int(rule), 1)
    base, _, frac = rule.partition("*")
    n = rows if base == "rows" else table_rows[base.lstrip("@")]
    return max(int(n * float(frac or 1.0)), 1)


def _kind(spec: str) -> Tuple[str, int]:
    if spec.startswith("s"):
        return "str", int(spec[1:])
    return spec, {"int": 8, "date": 4, "dec": 8}[spec]


def tables_of(config: dict) -> List[Table]:
    """The configuration's tables, with every NDV rule resolved."""
    table_rows = {t: int(v["rows"]) for t, v in config["tables"].items()}
    out = []
    for name, spec in config["tables"].items():
        rows = table_rows[name]
        not_null = set(spec.get("not_null", ()))
        cols = []
        for cname, ctype, rule, layout in spec["columns"]:
            kind, width = _kind(ctype)
            null_frac = 0.0 if cname in not_null else float(spec.get("nulls", 0.0))
            cols.append(Column(cname, kind, width,
                               min(_ndv(rule, rows, table_rows), rows),
                               layout, null_frac))
        out.append(Table(name, rows, tuple(cols)))
    return out


def _index_bits(n: np.ndarray) -> np.ndarray:
    """Index bits for n dictionary entries: ceil(log2 n), at least 1."""
    n = np.maximum(n.astype(np.int64), 1)
    return np.maximum(np.frexp((n - 1).astype(np.float64))[1], 1).astype(np.int64)


def _column_groups(rng, col: Column, x0: np.ndarray, x1: np.ndarray,
                   non_null: np.ndarray, ndv: int):
    """Per row group (distinct count, min index, max index).

    ``x0``/``x1`` place each row group in the table's write order, as row
    offsets over the table's rows (inserted rows lie past 1).
    """
    n = len(x0)
    draws = np.maximum(non_null.astype(np.float64), 1.0)
    if col.layout == "sorted":
        lo = np.floor(x0 * ndv)
        hi = np.maximum(np.floor(x1 * ndv) - 1, lo)
        local = np.minimum(hi - lo + 1, draws)
        return local, lo, hi
    if col.layout == "corr":
        width = max(ndv * CORR_WINDOW, 1.0)
        mid = 0.5 * (x0 + x1) * ndv
        base = np.clip(np.floor(mid - width / 2), 0, max(ndv - width, 0))
    else:
        width, base = float(ndv), np.zeros(n)
    # The least (greatest) of `draws` uniform draws over `width` values.
    lo_x = 1 - (1 - rng.random(n)) ** (1.0 / draws)
    hi_x = 1 - (1 - rng.random(n)) ** (1.0 / draws)
    lo = base + np.floor(width * lo_x)
    hi = base + np.minimum(np.floor(width * (1 - hi_x)), width - 1)
    local = width * -np.expm1(-draws / width)
    return local, np.minimum(lo, hi), np.maximum(lo, hi)


def synthesize_file(rng, table: Table, group_rows: Sequence[int],
                    x0: float, x1: float) -> FileData:
    """One file of ``table`` whose rows span [x0, x1) of the write order."""
    group_rows = np.asarray(group_rows, np.int64)
    edges = x0 + (x1 - x0) * np.concatenate(
        [[0.0], np.cumsum(group_rows) / max(group_rows.sum(), 1)])
    gx0, gx1 = edges[:-1], edges[1:]
    columns = {}
    for col in table.columns:
        nulls = np.round(group_rows * col.null_frac).astype(np.int64)
        non_null = group_rows - nulls
        local, lo, hi = _column_groups(rng, col, gx0, gx1, non_null, col.ndv)
        local = np.maximum(np.minimum(np.round(local), non_null), 1)
        dict_page = local.astype(np.int64) * col.width
        plain = dict_page > DICT_PAGE_LIMIT
        data_page = -(-(non_null * _index_bits(local)) // 8)
        size = np.where(plain, non_null * col.width, dict_page + data_page)
        columns[col.name] = Chunks(size.astype(np.int64),
                                   np.where(plain, 0, dict_page),
                                   group_rows.copy(),
                                   nulls, ~plain, lo.astype(np.int64),
                                   hi.astype(np.int64))
    return FileData(table.name, int(group_rows.sum()), group_rows, columns,
                    x0, x1)


def split_rows(rows: int, per_group: int) -> List[int]:
    full, rest = divmod(int(rows), per_group)
    return [per_group] * full + ([rest] if rest else [])


def synthesize_table(seed: int, index: int, table: Table, per_group: int,
                     per_file: int) -> Dict[str, FileData]:
    """Every file of one table at its full row count, ids ``part-NNNNN``."""
    rng = np.random.default_rng([seed, index])
    groups = split_rows(table.rows, per_group)
    files, done = {}, 0
    for f in range(0, len(groups), per_file):
        rows = groups[f:f + per_file]
        x0 = done / table.rows
        done += sum(rows)
        files[f"part-{f // per_file:05d}"] = synthesize_file(
            rng, table, rows, x0, done / table.rows)
    return files


# -- the program's footer view ------------------------------------------------

def _b36(i: int, width: int) -> str:
    s = ""
    while True:
        i, r = divmod(i, 36)
        s = _B36[r] + s
        if not i:
            break
    return s.rjust(width, "0")[-width:] if width >= len(s) else s


def value_key(kind: str, idx):
    """The order key of domain value ``idx``: the number the footer stores."""
    if kind == "int":
        return np.asarray(idx, np.float64) + 1.0
    if kind == "date":
        return np.asarray(idx, np.float64) + EPOCH_1992
    return np.asarray(idx, np.float64) * 0.01


def _string_key(s: str) -> float:
    b = (s.encode()[:8] + b"\x00" * 8)[:8]
    return float(int.from_bytes(b, "big"))


def footer(table: Table, data: FileData):
    """The `FileFooter` a writer would have emitted for this file."""
    from repro.columnar import format as fmt
    from repro.core.ndv.types import PhysicalType

    ptypes = {"int": PhysicalType.INT64, "date": PhysicalType.DATE32,
              "dec": PhysicalType.FLOAT64, "str": PhysicalType.BYTE_ARRAY}
    per_column = {}
    for col in table.columns:
        ch = data.columns[col.name]
        ptype = int(ptypes[col.kind])
        if col.kind == "str":
            lo_r = [_b36(int(i), col.width) for i in ch.lo]
            hi_r = [_b36(int(i), col.width) for i in ch.hi]
            lo_k = [_string_key(s) for s in lo_r]
            hi_k = [_string_key(s) for s in hi_r]
        else:
            lo_k = value_key(col.kind, ch.lo).tolist()
            hi_k = value_key(col.kind, ch.hi).tolist()
            lo_r = hi_r = [""] * len(lo_k)
        per_column[col.name] = [
            fmt.ColumnChunkMeta(
                name=col.name,
                physical_type=ptype,
                num_values=int(ch.rows[g]),
                null_count=int(ch.nulls[g]),
                total_uncompressed_size=int(ch.size[g]),
                dict_page_size=int(ch.dict_page[g]),
                data_page_size=int(ch.size[g] - ch.dict_page[g]),
                encodings=["DICTIONARY"] if ch.dict_encoded[g] else ["PLAIN"],
                min_key=lo_k[g], max_key=hi_k[g],
                min_len=col.width, max_len=col.width,
                min_repr=lo_r[g], max_repr=hi_r[g],
            )
            for g in range(len(data.group_rows))
        ]
    schema = {c.name: int(ptypes[c.kind]) for c in table.columns}
    groups = [
        fmt.RowGroupMeta(num_rows=int(k), columns={
            name: chunks[g] for name, chunks in per_column.items()})
        for g, k in enumerate(data.group_rows)
    ]
    return fmt.FileFooter(num_rows=data.rows, schema=schema, row_groups=groups)


# -- the benchmark's MetadataSource -------------------------------------------

def make_source_class():
    """`LakeSource`, built on the program's `MetadataSource` interface."""
    from repro.catalog.source import MetadataSource

    class LakeSource(MetadataSource):
        """Footers in memory; fingerprint = (file id, commit that wrote it).

        ``root`` is a directory for the catalog's estimate-cache spill,
        which a fleet replica writes; footers never touch it.
        """

        def __init__(self, root: str):
            self.root = root
            self._lock = threading.Lock()
            self._footers: Dict[str, object] = {}
            self._written: Dict[str, int] = {}
            self.version = 0
            self.history: List[Tuple[str, ...]] = []

        def list_files(self) -> List[str]:
            with self._lock:
                return sorted(self._footers)

        def fingerprint(self, file_id: str) -> str:
            with self._lock:
                if file_id not in self._written:
                    raise FileNotFoundError(file_id)
                return f"{file_id}@{self._written[file_id]}"

        def read_footer(self, file_id: str):
            with self._lock:
                if file_id not in self._footers:
                    raise FileNotFoundError(file_id)
                return self._footers[file_id]

        def commit(self, add: Dict[str, object],
                   remove: Sequence[str] = ()) -> Tuple[str, ...]:
            """Apply one snapshot; returns the live file ids after it."""
            with self._lock:
                for fid in remove:
                    del self._footers[fid]
                    del self._written[fid]
                self.version += 1
                for fid, f in add.items():
                    self._footers[fid] = f
                    self._written[fid] = self.version
                live = tuple(sorted(self._footers))
                self.history.append(live)
                return live

    return LakeSource


@dataclasses.dataclass
class Commit:
    """One staged table snapshot of a maintenance run."""

    index: int
    function: str
    table: str
    kind: str                     # "insert" | "delete"
    add: Dict[str, FileData]
    remove: Tuple[str, ...]


class Lake:
    """A configuration's tables, files and staged commits, from one seed."""

    def __init__(self, config: dict, seed: int, *, scale: float = 1.0):
        self.config = config
        self.seed = int(seed) % (1 << 63)
        self.per_group = int(config["rows_per_group"])
        self.per_file = int(config["groups_per_file"])
        self.scale = scale
        tables = tables_of(config)
        if scale != 1.0:
            # Tests only: a smaller catalog of the same shape.
            tables = [dataclasses.replace(
                t, rows=max(int(t.rows * scale), 1),
                columns=tuple(dataclasses.replace(
                    c, ndv=max(min(int(c.ndv * scale), int(t.rows * scale)), 1))
                    for c in t.columns))
                for t in tables]
        self.tables = {t.name: t for t in tables}
        self.files: Dict[str, Dict[str, FileData]] = {
            t.name: synthesize_table(self.seed, i, t, self.per_group,
                                     self.per_file)
            for i, t in enumerate(tables)
        }
        self._appended = {t: 0 for t in self.tables}

    def stage_commits(self, count: int) -> List[Commit]:
        """``count`` commits cycling through the configuration's refresh run.

        An insert adds one file of the run's rows past the table's end. A
        delete rewrites one live file, drawn from the seed among those at
        least twice the run's rows, without that many rows.
        """
        run = self.config["maintenance"]["run"]
        rng = np.random.default_rng([self.seed, 1 << 20])
        live = {t: dict(fs) for t, fs in self.files.items()}
        out = []
        for k in range(count):
            op = run[k % len(run)]
            table = self.tables[op["table"]]
            rows = max(int(op["rows"] * self.scale), 1)
            trng = np.random.default_rng([self.seed, 1 << 21, k])
            if op["kind"] == "insert":
                x0 = 1.0 + self._appended[table.name] / table.rows
                self._appended[table.name] += rows
                data = synthesize_file(
                    trng, table, split_rows(rows, self.per_group), x0,
                    1.0 + self._appended[table.name] / table.rows)
                add, remove = {f"ins-{k:05d}": data}, ()
            else:
                big = sorted(f for f, d in live[table.name].items()
                             if d.rows >= 2 * rows)
                victim = big[int(rng.integers(len(big)))]
                old = live[table.name].pop(victim)
                data = synthesize_file(
                    trng, table, split_rows(old.rows - rows, self.per_group),
                    old.x0, old.x1)
                add = {f"{victim.split('.')[0]}.cow-{k:05d}": data}
                remove = (victim,)
            live[table.name].update(add)
            out.append(Commit(k, op["function"], table.name, op["kind"],
                              add, remove))
        return out
