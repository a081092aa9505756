"""The readers of the fleet's refresh broadcast span and the per-device
dispatch counter."""
import os

import numpy as np
import pytest

import run
import span_series

READERS = ("refresh_broadcast_ms.fleet", "busiest_chip_dispatch_share.fleet")


@pytest.fixture(scope="module")
def readers():
    return run.load_readers(READERS)


def test_a_program_without_the_series_reads_nothing(readers):
    # What a program older than the span and the label leaves.
    series = {"planner_dispatches_total": [({}, 12.0)],
              "ndv_engine_dispatches_total": [
                  ({"mode": "paper", "strategy": "sharded"}, 3.0)]}
    for name, read in readers.items():
        assert read({"series": series}) is None, name
        assert read({"series": {}}) is None, name


def test_busiest_chip_share_of_the_window(readers):
    read = readers["busiest_chip_dispatch_share.fleet"]
    even = [({"device": str(d), "mode": "paper", "strategy": "local"}, 5.0)
            for d in range(4)]
    assert read({"series": {"ndv_engine_dispatches_total": even}}) == 25.0
    # Two label sets on one device add up; a device idle in the window
    # (delta 0) counts for nothing.
    skewed = [({"device": "0", "mode": "paper"}, 4.0),
              ({"device": "0", "mode": "improved"}, 2.0),
              ({"device": "1", "mode": "paper"}, 2.0),
              ({"device": "2", "mode": "paper"}, 0.0)]
    assert read({"series": {"ndv_engine_dispatches_total": skewed}}) == 75.0
    idle = [({"device": "0", "mode": "paper"}, 0.0)]
    assert read({"series": {"ndv_engine_dispatches_total": idle}}) is None


def test_refresh_broadcast_reads_the_recorded_span(readers, tmp_path):
    from repro.catalog.source import InMemoryMetadataSource
    from repro.columnar.writer import WriterOptions, write_file
    from repro.fleet import DatasetRegistry, Fleet

    def footer(seed):
        rng = np.random.default_rng(seed)
        return write_file(os.path.join(tmp_path, f"f{seed}"),
                          {"tok": rng.integers(0, 64, 256).astype(np.int64)},
                          options=WriterOptions(row_group_size=64))

    source = InMemoryMetadataSource({"f0": footer(0)},
                                    root=str(tmp_path / "spill"))
    os.makedirs(source.root)
    registry = DatasetRegistry()
    registry.add("wh", "t", source)
    fleet = Fleet(registry, replicas_per_dataset=3).start()
    try:
        before = run.registry_values()
        source.add("f1", footer(1))
        fleet.refresh("wh", "t")
        fleet.refresh("wh", "t")  # nothing changed: a cheap broadcast
        series = run.series_delta(before, run.registry_values())
    finally:
        fleet.stop()
    got = readers["refresh_broadcast_ms.fleet"]({"series": series})
    assert span_series.count(series, "fleet.refresh_broadcast") == 2
    assert span_series.count(series, "ingest.refresh") == 6
    # Each broadcast holds its three replicas' refreshes, one after another.
    assert got >= span_series.seconds(series, "ingest.refresh") / 2 * 1e3
    assert got == pytest.approx(
        span_series.seconds(series, "fleet.refresh_broadcast") / 2 * 1e3)
