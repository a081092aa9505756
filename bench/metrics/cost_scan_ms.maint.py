"""Device time of the planner's scan program per /cost miss, in ms.

The seconds of the ``jit_fold`` program (`repro.planner.cost`'s batched
C_out fold) in the trace, over the planner dispatches the window counted
(`planner_dispatches_total`).
"""


def read(ctx):
    m = (ctx["trace"] or {}).get("modules", {}).get("jit_fold")
    calls = sum(d for _, d in ctx["series"].get("planner_dispatches_total", ()))
    if not m or not calls:
        return None
    return m["seconds"] / calls * 1e3
