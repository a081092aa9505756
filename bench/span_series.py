"""The program's span series over a window, for the per-layer readers.

Every span of the program (`repro.obs.trace`) feeds two registry series on
exit: ``ndv_span_seconds{span=<name>}``, a histogram whose count is the work
done, and ``ndv_span_self_seconds_total{span=<name>}``, its time minus its
children's. A reader gets what the window added to each (``ctx["series"]``);
a program without these series reads as zero, and the readers then return
None.
"""


def _delta(series: dict, metric: str, name: str):
    for labels, d in series.get(metric, ()):
        if labels.get("span") == name:
            return d
    return None


def count(series: dict, name: str) -> int:
    d = _delta(series, "ndv_span_seconds", name)
    return d["count"] if d else 0


def seconds(series: dict, name: str) -> float:
    d = _delta(series, "ndv_span_seconds", name)
    return d["sum"] if d else 0.0


def self_seconds(series: dict, name: str) -> float:
    return _delta(series, "ndv_span_self_seconds_total", name) or 0.0


def total(series: dict, metric: str) -> float:
    return sum(d for _, d in series.get(metric, ()))


def self_ms(series: dict, name: str, per: str = None):
    """Self time of span ``name`` in ms, per time it ran or, with ``per``,
    per unit of that counter; None when the span never ran or ``per`` is 0.
    """
    n = count(series, name)
    if per is not None and n:
        n = total(series, per)
    return self_seconds(series, name) / n * 1e3 if n else None


def mean_ms(series: dict, name: str):
    """Mean wall time of span ``name`` in ms; None when it never ran."""
    n = count(series, name)
    return seconds(series, name) / n * 1e3 if n else None


def lock_wait_ms(series: dict):
    """The service's wait for its lock per request that took it, in ms:
    `service.lock_wait` (opened only when the lock is held elsewhere) over
    the `service.compute` spans that ran under the lock; None when none
    ran."""
    n = count(series, "service.compute")
    return self_seconds(series, "service.lock_wait") / n * 1e3 if n else None
