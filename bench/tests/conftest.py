"""Shared set-up for the benchmark's own tests (CPU, small catalogs).

Run from the repository root:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def small():
    """run_cell at a size a test holds: 1/1000 of the rows, 64k-row groups."""
    import run

    bench = run.load_benchmark()

    def go(cell_name, seconds=3.0, seed=20260917, **kw):
        cell = run.find_cell(bench, cell_name)
        config = run.lake.load_json("configs", cell["config"] + ".json")
        config["rows_per_group"] = 65536
        return run.run_cell(cell, bench, seed, seconds, scale=0.001,
                            config_override=config, **kw)

    return go
