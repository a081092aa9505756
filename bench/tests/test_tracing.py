"""The trace reduction on a small trace recorded on a TPU v5e.

``data/tiny.xplane.pb`` holds one ``estimate_batch`` of a 32-column,
128-row-group pack (the fused kernel pads B to 64) and one planner fold.
"""
import os

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_finds_the_kernel_and_the_planner_scan():
    r = tracing.reduce(DATA)
    k = r["kernels"]["fused_estimate"]
    assert k["count"] == 1
    # result f32[64,128] and 8 operands f32[64,128]: 9 x 64 x 128 x 4 bytes
    assert k["bytes"] == 9 * 64 * 128 * 4
    assert 0 < k["seconds"] < 1e-3
    assert r["modules"]["jit_fold"]["count"] == 1
    assert r["modules"]["jit_estimate_batch"]["count"] == 1
    assert 0 < r["busy_s"] < 1e-3
    assert r["busy_s"] >= k["seconds"]


def test_breakdown_keeps_the_ten_largest():
    b = tracing.breakdown(tracing.reduce(DATA))
    assert b["device_ops"][0][0].startswith("%fused_estimate")
    assert len(b["device_ops"]) == 10
    assert len(b["idle_gaps"]) <= 10
    assert all(g[1] > 0 for g in b["idle_gaps"])


def test_hlo_bytes_skips_layout_constraints():
    text = ("%k.1 = f32[8,128]{1,0:T(8,128)} custom-call(f32[8,256]{1,0:T(8,128)}"
            " %a, s32[8]{0} %b), custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={f32[8,256]{1,0}, s32[8]{0}}")
    assert tracing.hlo_bytes(text) == 8 * 128 * 4 + 8 * 256 * 4 + 8 * 4


def test_reduce_totals_every_host_span_by_name():
    spans = tracing.reduce(DATA)["host_spans"]
    assert spans
    assert all(v["count"] >= 1 and v["seconds"] >= 0 for v in spans.values())
