"""The per-layer readers of the program's span series."""
import os

import numpy as np
import pytest

import run

SPAN_READERS = (
    "service_lock_wait_ms.static", "service_build_ms.static",
    "service_lock_wait_ms.maint", "planner_enumerate_ms.maint",
    "planner_pick_ms.maint", "catalog_merge_ms.maint",
    "ingest_refresh_ms.maint", "engine_device_wait_ms.maint",
    "packer_useful_share.maint",
)


@pytest.fixture(scope="module")
def readers():
    return run.load_readers(SPAN_READERS)


def test_a_program_without_the_series_reads_nothing(readers):
    # What a program older than its spans leaves: the other series only.
    series = {"planner_dispatches_total": [({}, 12.0)],
              "ndv_engine_dispatches_total": [({"mode": "paper"}, 3.0)]}
    for name, read in readers.items():
        assert read({"series": series}) is None, name
        assert read({"series": {}}) is None, name


def test_readers_read_what_the_program_recorded(readers, tmp_path):
    from repro.columnar.writer import WriterOptions, write_file
    from repro.service import StatsService

    def shard(name, seed):
        rng = np.random.default_rng(seed)
        write_file(os.path.join(tmp_path, name),
                   {"tok": rng.integers(0, 64, 256).astype(np.int64)},
                   options=WriterOptions(row_group_size=64))

    shard("a", 0)
    graph = {"tables": [{"name": "x"}, {"name": "y"}],
             "edges": [{"left": "x", "left_column": "tok",
                        "right": "y", "right_column": "tok"}]}
    from repro.planner import parse_join_graph

    svc = StatsService(str(tmp_path))
    before = run.registry_values()
    assert svc.table_stats().status == 200
    shard("b", 1)
    assert svc.refresh().body["changed"]
    assert svc.cost(graph=parse_join_graph(graph)).status == 200
    ctx = {"series": run.series_delta(before, run.registry_values())}
    values = {name: read(ctx) for name, read in readers.items()}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["service_build_ms.static"] > 0
    assert values["ingest_refresh_ms.maint"] > values["catalog_merge_ms.maint"]
    # one tok column: 4 then 8 row groups, in packs of R = 8
    assert values["packer_useful_share.maint"] == pytest.approx(
        100.0 * (4 + 8) / (8 + 8))
