"""Mean body build under the service lock per cacheable request, ms.

Self time of the program's `service.compute` span over the times it ran: the
`/tablestats` or `/cost` body built from cached estimates, with the engine's
and the planner's own spans taken out.
"""
import span_series


def read(ctx):
    return span_series.self_ms(ctx["series"], "service.compute")
