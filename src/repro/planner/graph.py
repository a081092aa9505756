"""Join-graph model: tables, equi-join edges, validation, identity.

A `JoinGraph` is the `/cost` endpoint's unit of work: a set of named
tables (each optionally bound to a registered `namespace/dataset` and
carrying a filter selectivity) and a set of equi-join edges keyed by
column. Everything request-shaped is validated HERE, at construction /
parse time, with `ValueError` — the HTTP layer maps those to 400s, so a
malformed graph can never reach the scoring kernel.

`identity()` is the canonical, order-insensitive tuple the caching tier
hashes into `/cost` ETags: two requests naming the same tables and edges
in any order produce the same identity, so they validate and coalesce
against each other.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

#: Enumeration cap when the request does not set one. 7! = 5040 exceeds
#: it, so graphs of 7+ tables score a deterministic sample (`enumerate`).
DEFAULT_MAX_PLANS = 4096

#: Hard ceiling on the enumeration width a request may ask for — the
#: scored lanes are (P, N) device arrays; an unbounded client-supplied P
#: would be a memory-exhaustion vector on the serving tier.
MAX_PLANS_CEILING = 65536


@dataclasses.dataclass(frozen=True)
class TableRef:
    """One table of a join graph.

    `name` is the graph-local alias edges refer to. `namespace`/`dataset`
    bind the table to a registered dataset on the fleet tier; on the
    single-dataset server they may be omitted (every table reads the
    served dataset — self-join graphs). `filter_selectivity` scales the
    table's base cardinality before any join ((0, 1], default 1.0 — the
    standard independent-filter model).
    """

    name: str
    namespace: Optional[str] = None
    dataset: Optional[str] = None
    filter_selectivity: float = 1.0

    @property
    def dataset_key(self) -> Optional[str]:
        if self.namespace is None or self.dataset is None:
            return None
        return f"{self.namespace}/{self.dataset}"


@dataclasses.dataclass(frozen=True)
class JoinEdge:
    """One equi-join predicate `left.left_column = right.right_column`."""

    left: str
    left_column: str
    right: str
    right_column: str


@dataclasses.dataclass(frozen=True)
class JoinGraph:
    """Validated join graph (construct via `make_graph`/`parse_join_graph`)."""

    tables: Tuple[TableRef, ...]
    edges: Tuple[JoinEdge, ...]

    @property
    def names(self) -> List[str]:
        return [t.name for t in self.tables]

    def table(self, name: str) -> TableRef:
        for t in self.tables:
            if t.name == name:
                return t
        raise KeyError(name)

    def columns_by_table(self) -> Dict[str, List[str]]:
        """Join columns each table contributes (sorted, deduplicated)."""
        cols: Dict[str, set] = {t.name: set() for t in self.tables}
        for e in self.edges:
            cols[e.left].add(e.left_column)
            cols[e.right].add(e.right_column)
        return {name: sorted(c) for name, c in cols.items()}

    def identity(self) -> tuple:
        """Canonical order-insensitive identity (the ETag component)."""
        tables = tuple(sorted(
            (t.name, t.namespace or "", t.dataset or "",
             float(t.filter_selectivity))
            for t in self.tables
        ))
        edges = tuple(sorted(
            # An equi-join is symmetric: (l.a = r.b) == (r.b = l.a).
            tuple(sorted([
                (e.left, e.left_column), (e.right, e.right_column)
            ]))
            for e in self.edges
        ))
        return (tables, edges)


def make_graph(
    tables: List[TableRef], edges: List[JoinEdge]
) -> JoinGraph:
    """Validate and freeze a join graph (ValueError on any request error).

    Checks: at least one table, unique aliases, edges referencing known
    aliases, no self-edges, selectivities in (0, 1], and CONNECTIVITY —
    a disconnected multi-table graph is rejected outright (the caller
    forgot an edge; silently costing the implied cross product of the
    components would hide the mistake). A missing edge on a PAIR inside a
    connected graph is fine: enumeration handles it as a cross-product
    step (`repro.planner.cost`).
    """
    if not tables:
        raise ValueError("join graph needs at least one table")
    names = [t.name for t in tables]
    if len(set(names)) != len(names):
        dupes = sorted({n for n in names if names.count(n) > 1})
        raise ValueError(f"duplicate table names {dupes}")
    for t in tables:
        if not t.name:
            raise ValueError("table names must be non-empty strings")
        if not (0.0 < float(t.filter_selectivity) <= 1.0):
            raise ValueError(
                f"table {t.name!r}: filter_selectivity must be in (0, 1], "
                f"got {t.filter_selectivity}"
            )
        if (t.namespace is None) != (t.dataset is None):
            raise ValueError(
                f"table {t.name!r}: namespace and dataset must be given "
                "together"
            )
    known = set(names)
    for e in edges:
        for side, col in ((e.left, e.left_column), (e.right, e.right_column)):
            if side not in known:
                raise ValueError(f"edge references unknown table {side!r}")
            if not col:
                raise ValueError(
                    f"edge {e.left}~{e.right}: join columns must be "
                    "non-empty strings"
                )
        if e.left == e.right:
            raise ValueError(
                f"self-edge on table {e.left!r}: equi-join edges must "
                "connect two distinct tables"
            )
    _check_connected(names, edges)
    return JoinGraph(tuple(tables), tuple(edges))


def _check_connected(names: List[str], edges: List[JoinEdge]) -> None:
    """Union-find connectivity; ValueError naming the stranded component."""
    parent = {n: n for n in names}

    def find(x: str) -> str:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for e in edges:
        ra, rb = find(e.left), find(e.right)
        if ra != rb:
            parent[ra] = rb
    roots = {find(n) for n in names}
    if len(roots) > 1:
        components = sorted(
            sorted(n for n in names if find(n) == r) for r in roots
        )
        raise ValueError(
            f"disconnected join graph: components {components} share no "
            "edge (add a join edge, or cost the components separately)"
        )


def parse_join_graph(payload, *, require_datasets: bool = False) -> JoinGraph:
    """`/cost` request body -> validated `JoinGraph` (ValueError on junk).

    Shape::

        {"tables": [{"name": "l", "namespace": "wh", "dataset": "lineitem",
                     "filter_selectivity": 0.4}, ...],
         "edges":  [{"left": "l", "left_column": "l_orderkey",
                     "right": "o", "right_column": "o_orderkey"}, ...]}

    `require_datasets=True` (the fleet router) insists every table names a
    registered `namespace`/`dataset`; the single-dataset server accepts
    tables without them (they read the served dataset).
    """
    if not isinstance(payload, dict):
        raise ValueError(
            f"join graph must be an object, got {type(payload).__name__}"
        )
    unknown = set(payload) - {"tables", "edges"}
    if unknown:
        raise ValueError(f"unknown join-graph fields {sorted(unknown)}")
    raw_tables = payload.get("tables")
    if not isinstance(raw_tables, list) or not raw_tables:
        raise ValueError("'tables' must be a non-empty list")
    raw_edges = payload.get("edges", [])
    if not isinstance(raw_edges, list):
        raise ValueError("'edges' must be a list")

    tables: List[TableRef] = []
    for i, t in enumerate(raw_tables):
        if not isinstance(t, dict):
            raise ValueError(f"tables[{i}] must be an object")
        unknown = set(t) - {"name", "namespace", "dataset",
                            "filter_selectivity"}
        if unknown:
            raise ValueError(f"tables[{i}]: unknown fields {sorted(unknown)}")
        name = t.get("name")
        if not isinstance(name, str) or not name:
            raise ValueError(f"tables[{i}]: 'name' must be a non-empty string")
        ns, ds = t.get("namespace"), t.get("dataset")
        for label, v in (("namespace", ns), ("dataset", ds)):
            if v is not None and not isinstance(v, str):
                raise ValueError(f"tables[{i}]: '{label}' must be a string")
        if require_datasets and (ns is None or ds is None):
            raise ValueError(
                f"tables[{i}] ({name!r}): router cost tables need "
                "'namespace' and 'dataset'"
            )
        sel = t.get("filter_selectivity", 1.0)
        if not isinstance(sel, (int, float)) or isinstance(sel, bool):
            raise ValueError(
                f"tables[{i}]: 'filter_selectivity' must be a number"
            )
        tables.append(TableRef(name, ns, ds, float(sel)))

    edges: List[JoinEdge] = []
    for i, e in enumerate(raw_edges):
        if not isinstance(e, dict):
            raise ValueError(f"edges[{i}] must be an object")
        unknown = set(e) - {"left", "left_column", "right", "right_column"}
        if unknown:
            raise ValueError(f"edges[{i}]: unknown fields {sorted(unknown)}")
        parts = {}
        for field in ("left", "left_column", "right", "right_column"):
            v = e.get(field)
            if not isinstance(v, str) or not v:
                raise ValueError(
                    f"edges[{i}]: '{field}' must be a non-empty string"
                )
            parts[field] = v
        edges.append(JoinEdge(**parts))
    return make_graph(tables, edges)


def parse_max_plans(value) -> int:
    """`max_plans` request field -> bounded int (ValueError on junk)."""
    if value is None:
        return DEFAULT_MAX_PLANS
    if isinstance(value, bool) or not isinstance(value, int):
        raise ValueError(f"max_plans must be an integer, got {value!r}")
    if value < 1:
        raise ValueError(f"max_plans must be >= 1, got {value}")
    return min(value, MAX_PLANS_CEILING)
