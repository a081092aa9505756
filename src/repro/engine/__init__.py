"""Estimation engine: the single execution seam between packed batches and
estimates.

The paper's estimators are embarrassingly parallel over columns — every
reduction inside `estimate_batch` runs along the row-group axis (R) or is
per-lane, never across the column axis (B). That makes the B axis free to
split, which is exactly what fleet-scale serving needs: a warehouse with
100k+ merged columns should not run on one device or OOM because the packed
batch grew with dataset width.

`EstimationEngine` owns that split. Every consumer (`StatsCatalog`,
`estimate_columns`, `NDVPlanner.plan_catalog`, the data pipeline, the
benchmarks) goes through `engine.estimate(batch, ...)` instead of calling
the jit'd `estimate_batch` directly; `estimate_batch` itself remains the
pure per-shard kernel. Three execution strategies hide behind one config:

  local    today's single-device jit path. The default on one device.
  sharded  split the bucketed batch on the B axis across a 1-D
           `jax.sharding.Mesh` via `shard_map`, one `estimate_batch` body
           per device, per-shard `BatchEstimates` combined by the runtime.
           The engine's packer rounds B up to a multiple of the shard count
           so the split is even and the extra lanes are ordinary masked
           padding.
  chunked  stream batches wider than a budget (`max_batch`) through
           equal-size sub-batches, so B — and therefore device memory and
           trace shapes — stays bounded regardless of dataset width. The
           budget is either a fixed power of two or "auto", derived from
           the device's reported memory (`resolve_max_batch()`).
  composed sharded AND chunked: the batch streams through the mesh in
           super-chunks of `num_shards * max_batch` lanes, so each device
           sees at most its per-shard budget per dispatch. This is the
           strategy that lets a mesh of small devices serve a catalog
           wider than any single device's memory; "auto" picks it when
           both >1 device and over-the-mesh-budget hold. The shape math
           lives in `composed_plan()` (pure, property-tested).

The parity contract is strict: for real (non-padding) lanes, the sharded,
chunked, and composed paths produce bit-identical outputs to the local path
(asserted by tests/test_engine.py, run as a strategy×device CI matrix on
simulated multi-device CPU). That holds because padding lanes are fully
masked and no estimator op mixes information across B — the engine only
ever re-tiles the same per-lane program. The contract extends upward: since
strategies are numerics-neutral, they never enter `cache_key`/`cache_token`,
so estimate caches, on-disk spills, and client ETag caches all survive
strategy changes unchanged.

An engine runs on its own devices (`EstimationEngine(devices=...)`, every
local device by default). The fleet gives each replica a share of the
host's chips: one chip each when there are at least as many replicas as
chips, and a one-device engine resolves "auto" to local or chunked with
its inputs committed to that chip. The device set, like the strategy,
stays out of `cache_key`/`cache_token`.

The config also carries the `kernels/ops` backend knob ("auto" / "pallas" /
"ref"), which used to be unreachable from the public API: the engine threads
it into `estimate_batch`, which routes the Newton inversions and the
detector scan through the Pallas kernels or the jnp reference accordingly.

Importing this package installs the `repro.obs` profiler bridge: while a
`jax.profiler` session collects, every program span is also a host
TraceMe of the same name, on the clock of the device's ops.
"""
import jax

from repro.obs import trace as _trace
from repro.engine.config import DEFAULT_MAX_BATCH, EngineConfig  # noqa: F401
from repro.engine.engine import (  # noqa: F401
    EstimationEngine,
    auto_chunk_budget,
    composed_plan,
    default_engine,
    default_packer,
    detect_device_memory,
)

_trace.set_profiler_bridge(jax.profiler.TraceAnnotation)
