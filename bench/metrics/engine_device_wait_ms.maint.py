"""Host blocked on the estimation program per engine dispatch, ms.

Self time of the program's `engine.device_wait` span (a
`jax.block_until_ready` on the engine's output) over the engine's dispatches
in the window (`ndv_engine_dispatches_total`). The span ends when the thread
has the interpreter lock back, so under load it also holds the wait for that
lock: read it beside the device time of `fused_estimate` in the same trace.
"""
import span_series


def read(ctx):
    return span_series.self_ms(ctx["series"], "engine.device_wait",
                               per="ndv_engine_dispatches_total")
