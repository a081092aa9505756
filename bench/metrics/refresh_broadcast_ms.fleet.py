"""Mean wall time of a refresh broadcast to every replica of a dataset, ms.

Sum over count of the program's `fleet.refresh_broadcast` span, which the
router opens around `ReplicaSet.refresh_all`: one `replica.call` per replica,
in turn, each an `ingest.refresh`. None on a program without the span.
"""
import span_series


def read(ctx):
    return span_series.mean_ms(ctx["series"], "fleet.refresh_broadcast")
