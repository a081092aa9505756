"""Mean copy-on-write re-merge per refresh commit, ms.

Self time of the program's `catalog.merge` span (`apply_footers` inside
`AsyncIngestor.refresh`, under the service lock) over the times it ran.
"""
import span_series


def read(ctx):
    return span_series.self_ms(ctx["series"], "catalog.merge")
