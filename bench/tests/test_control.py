"""The control: the reference in bfloat16 in the program's place must come
out as not correct, while the program itself does (CPU, small catalog).

On the chip the same readings come from ``run.py --control 1`` at the
cell's own size; the limits in ``configs/*.json`` sit between the two.
"""
import pytest


@pytest.mark.parametrize("cell", ["tpcds_sf1000.maintenance",
                                  "tpch_sf1000.probe_static"])
def test_control_fails_where_the_program_passes(small, cell):
    out = small(cell, control=True)
    assert out["correct"], out["checks"]
    assert out["control"]["fails"], out["control"]
