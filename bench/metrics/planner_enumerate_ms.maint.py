"""Host enumeration of candidate join orders per planner dispatch, ms.

Self time of the program's `planner.enumerate` span over the planner's
dispatches in the window (`planner_dispatches_total`, one per cold /cost).
"""
import span_series


def read(ctx):
    return span_series.self_ms(ctx["series"], "planner.enumerate",
                               per="planner_dispatches_total")
