"""Mean host time of `AsyncIngestor.refresh` per commit in the window, ms.

Timed by the benchmark's wrapper around the call (the writer's
``POST /refresh`` runs exactly one per commit).
"""


def read(ctx):
    durations = [d for _, d, changed in ctx["refreshes"] if changed]
    if not durations:
        return None
    return sum(durations) / len(durations) * 1e3
