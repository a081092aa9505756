"""Host best-plan pick per planner dispatch, ms.

Self time of the program's `planner.pick` span (`best_plan_index`) over the
planner's dispatches in the window (`planner_dispatches_total`).
"""
import span_series


def read(ctx):
    return span_series.self_ms(ctx["series"], "planner.pick",
                               per="planner_dispatches_total")
