"""Per-file column views reused over views merged in the window, %.

The program's `ndv_merge_views_total{result="reused"|"built"}`, one per
(file, column) view a catalog merge reads: a reused view was kept from an
earlier commit, a built one was parsed from its footer. None when the
window merged nothing, or on a program without the counter.
"""


def read(ctx):
    counts = {labels.get("result"): d for labels, d in
              ctx["series"].get("ndv_merge_views_total", ())}
    reused = counts.get("reused", 0.0)
    merged = reused + counts.get("built", 0.0)
    return 100.0 * reused / merged if merged else None
