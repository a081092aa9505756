"""Engine dispatches (`ndv_engine_dispatches_total`) per commit."""


def read(ctx):
    if not ctx["commits"]:
        return None
    calls = sum(d for _, d in ctx["series"].get("ndv_engine_dispatches_total",
                                                 ()))
    return calls / ctx["commits"]
