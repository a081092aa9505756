"""Mean wait for the service lock per cacheable request that built a body, ms.

Self time of the program's `service.lock_wait` spans (opened only when
`StatsService.lock` is held elsewhere: by another request or a refresh
commit) over the `service.compute` spans that ran under the lock; an
uncontended acquisition counts as no wait.
"""
import span_series


def read(ctx):
    return span_series.lock_wait_ms(ctx["series"])
