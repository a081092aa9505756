"""Mean wall time of a refresh, ms, timed inside the program.

Sum over count of the program's `ingest.refresh` span: scatter-gather, lock
wait, re-merge and commit. The in-program twin of `refresh_ms.maint`, which
the harness times around the call and keeps only for refreshes that changed
the dataset; this one takes every refresh.
"""
import span_series


def read(ctx):
    return span_series.mean_ms(ctx["series"], "ingest.refresh")
