"""Engine configuration: one frozen record that names an execution plan.

`EngineConfig` is deliberately tiny and hashable — `StatsCatalog` keys its
estimate caches by it (via `EstimationEngine.cache_key`), so two engines
with the same config are interchangeable and two engines that would execute
differently never share a cache line.
"""
from __future__ import annotations

import dataclasses
from typing import Union

STRATEGIES = ("auto", "local", "sharded", "chunked", "composed")
BACKENDS = ("auto", "pallas", "ref")
FUSE_MODES = ("auto", "on", "off")

# The chunk budget used when max_batch="auto" finds no usable device memory
# report (host CPU backends return no `memory_stats()`), and the historical
# fixed default.
DEFAULT_MAX_BATCH = 4096


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Execution plan for `EstimationEngine`.

    Attributes:
      strategy: "local" (single-device jit), "sharded" (split B across a
        device mesh), "chunked" (bounded-B streaming), "composed" (split B
        across the mesh AND chunk-stream each shard's slice under a
        per-shard budget — the strategy for meshes of small devices serving
        catalogs wider than any one device's memory), or "auto" — composed
        when the engine has more than one device and the batch exceeds
        the mesh-wide budget (`num_shards * per-shard max_batch`), sharded
        when it has more than one device, otherwise chunked only when the
        batch exceeds `max_batch`, otherwise local.
      backend: the `repro.kernels.ops` knob, threaded into `estimate_batch`.
        "auto" picks the fastest correct path per platform (compiled Pallas
        kernels on TPU, the jnp reference elsewhere — interpret-mode Pallas
        is a correctness tool, not a serving path); "pallas" forces the
        kernels (interpreted off-TPU); "ref" forces the jnp reference.
      num_shards: device count for the sharded and composed strategies; 0
        means all the engine's devices. Clamped to that count at
        run time (the clamp is logged once per engine: under composed a
        silently wrong shard count would also silently change the
        per-shard chunk budget).
      max_batch: the chunk budget — the widest B a single `estimate_batch`
        call may see under the chunked strategy, and the widest slice a
        single SHARD may see under composed. Must be a power of two so
        power-of-two-bucketed batches always split into equal full chunks
        (one jit trace shape, no ragged tail). "auto" derives the budget
        from the accelerator's reported memory at first use
        (`EstimationEngine.resolve_max_batch()`), falling back to
        `DEFAULT_MAX_BATCH` where no report exists (host CPU); under
        composed the report is divided by the shard count first (simulated
        host meshes share one physical pool), so the per-shard budget
        shrinks as the mesh grows.

      fuse: the estimation-megakernel knob, threaded into `estimate_batch`
        and resolved by `repro.kernels.ops.use_fused`. "on" (and "auto" on
        TPU, where the separate path costs 3-4 kernel launches plus XLA
        glue per estimate) runs the whole §4-§7 pipeline as ONE fused
        computation of the reference numerics — a single `pallas_call`
        (`repro.kernels.fused_estimate`) where the kernel path is
        production, its pure-XLA twin elsewhere. "off" pins the unfused
        per-stage path. Off-TPU the twin is literally the same program as
        the unfused reference path, so the knob is bit-neutral by
        construction; pinning ``backend="pallas"`` off-TPU remains the
        interpret-mode validation configuration, not a serving path.

    Cache-key neutrality rules: by the engine parity contract every
    strategy produces bit-identical estimates for real lanes, so
    `strategy`, `num_shards`, and `max_batch` are execution-shape knobs
    that never enter `EstimationEngine.cache_key` or `cache_token`.
    Estimate caches, on-disk spills, and client ETag caches therefore stay
    valid across strategy changes — switching a dataset from local to
    composed invalidates nothing. `fuse` is the same kind of knob one level
    down — dispatch shape over the same reference numerics, bit-identical
    by the fused parity cells — so it too stays out of both identities and
    a fuse flip invalidates no cache line or client ETag. Only `backend`
    can change numerics, and only it is identity.
    """

    strategy: str = "auto"
    backend: str = "auto"
    num_shards: int = 0
    max_batch: Union[int, str] = DEFAULT_MAX_BATCH
    fuse: str = "auto"

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"strategy {self.strategy!r} not in {STRATEGIES}"
            )
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        if self.fuse not in FUSE_MODES:
            raise ValueError(f"fuse {self.fuse!r} not in {FUSE_MODES}")
        if self.num_shards < 0:
            raise ValueError("num_shards must be >= 0 (0 = all devices)")
        mb = self.max_batch
        if isinstance(mb, str):
            if mb != "auto":
                raise ValueError(
                    f'max_batch must be "auto" or a power of two, got {mb!r}'
                )
        elif mb < 1 or (mb & (mb - 1)) != 0:
            raise ValueError(f"max_batch must be a power of two, got {mb}")
