"""The busiest chip's share of the window's engine dispatches, %.

`ndv_engine_dispatches_total` by its `device` label (the engine's first
device): 25 when four chips share the work evenly, 100 when it all lands on
one. None when the window dispatched nothing, or on a program whose counter
has no `device` label.
"""


def read(ctx):
    by_device = {}
    for labels, d in ctx["series"].get("ndv_engine_dispatches_total", ()):
        if "device" not in labels:
            return None
        by_device[labels["device"]] = by_device.get(labels["device"], 0.0) + d
    total = sum(by_device.values())
    return 100.0 * max(by_device.values()) / total if total else None
