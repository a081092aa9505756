"""The estimation megakernel's share of its HBM roofline, in %.

Least time = bytes / HBM bandwidth, where the bytes are those of the
kernel's result and operands, read from each traced custom call's HLO shapes
(`tracing.hlo_bytes`); the time is the kernel's device time in the trace.
The bytes alone bound this kernel, so the share cannot pass 100%. The peak
comes from ``peaks.json`` by device kind; an unknown kind is an error.
"""


def read(ctx):
    k = (ctx["trace"] or {}).get("kernels", {}).get("fused_estimate")
    if not k or not k["seconds"]:
        return None
    bandwidth = ctx["peaks"][ctx["device_kind"]]["hbm_bytes_per_s"]
    return 100.0 * (k["bytes"] / bandwidth) / k["seconds"]
