"""Share of the traced window in which no operation ran on the device, %.

1 - busy / window: busy is the union of the device's "XLA Ops" intervals
in the profiler trace, the window the host clock's go-to-close span.
"""


def read(ctx):
    window = ctx["window_s"]
    if not window or ctx["trace"] is None:
        return None
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / window)
