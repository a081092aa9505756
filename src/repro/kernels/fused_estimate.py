"""Pallas TPU megakernel: the whole §4-§7 estimation pipeline in one dispatch.

The separate kernel path costs 4 `pallas_call` launches per estimate — the
§6 detector scan, the §4 dict Newton, and two §5 coupon Newtons — plus the
XLA glue (masked aggregations, Eq 13-15 combine) between them, with every
intermediate bouncing through HBM. Serving-shaped workloads (one catalog
lookup per query-optimizer probe) are launch-bound, not FLOP-bound, so this
kernel runs the entire pipeline per (block_b, R) column tile in one launch:
detector metrics, both Newton inversions, and the branchless
`jnp.where`-select of Eq 13 on the detector verdict, all on VMEM-resident
tiles.

Numerics contract (what lets `EngineConfig.fuse` stay out of
`cache_key`/`cache_token`): the body does not reimplement anything — it
reconstructs a tile-shaped `ColumnBatch` from its refs and calls
`estimate_tile(..., backend="ref")`, i.e. the REFERENCE pipeline in the
column form that `estimate_batch_core` itself runs. The dispatch layer
(`repro.kernels.ops.fused_estimate`) compiles this kernel only where the
kernel path is the production path (TPU, or an explicit ``backend="pallas"``
pin); everywhere else it routes to the pure-XLA twin
(`repro.kernels.ref.ref_fused_estimate`) — which is *the same program* as
the unfused path, so fuse=on vs fuse=off is bit-identical by construction
there, not by hoping two compilations of the same ops agree. (They don't:
measured on CPU, wrapping identical math in an interpret-mode `pallas_call`
flips last-ulp bits in transcendental tails — codegen context changes
fusion/FMA decisions. That is the normal kernel-vs-oracle gap every kernel
in this repo carries, and the interpret path is validated against the twin
the same way: tight allclose plus exact discrete fields.)

I/O layout: seven (B, R) float32 planes (bools as 0/1, reconstructed with
`> 0.5`), per-column scalars packed into one (B, LANES) float32 array, and
one (B, LANES) float32 output with results in the leading lanes. Every
scalar is either an exact small int, a 0/1 flag, or already float32, so the
trip through lanes is exact. Inside the body each scalar is a (block_b, 1)
column, never a 1-D vector, and results are packed with a lane-iota select
(`minmax_scan.pack_lanes`).

Tiling: the grid walks B in blocks of `block_rows(R)` columns, with R kept
whole per step so every row-group reduction stays tile-local. The block
shrinks as R grows so that a step's planes and the pipeline's (block_b, R)
temporaries fit the TPU's default scoped VMEM up to R = 8192 at the
8-column floor; wider R raises the scoped limit in proportion, up to
`MAX_GROUPS` (see `vmem_limit`). B is zero-padded to a whole number of
blocks and R to at least `MIN_GROUPS`: zeros are the packer's own fully
masked padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.minmax_scan import LANES, pack_lanes

# Elements of one (block_b, R) plane per grid step, and the block bounds.
PLANE_ELEMS = 1 << 13
MIN_BLOCK_B = 8       # TPU sublane count
MAX_BLOCK_B = 256
# Row-group bounds: the pair terms need R >= 3 (the packer's floor is 8),
# and past MAX_GROUPS one 8-column step outgrows the VMEM of a v5e.
MIN_GROUPS = 8
MAX_GROUPS = 32768
# Widest R whose 8-column step fits the default scoped VMEM, and the
# scoped VMEM one such step needs per that many row groups (compiled for
# v5e: R = 16384 needs 32 MiB, R = 32768 64 MiB).
DEFAULT_VMEM_GROUPS = 8192
VMEM_PER_DEFAULT_GROUPS = 16 << 20

# BatchEstimates fields carried in output lanes, in lane order, with the
# dtype each is restored to (`mean_len` passes through from the input).
_OUT_LANES = (
    ("ndv", jnp.float32),
    ("ndv_dict", jnp.float32),
    ("ndv_minmax", jnp.float32),
    ("layout", jnp.int32),
    ("is_lower_bound", jnp.bool_),
    ("confidence", jnp.float32),
    ("overlap_ratio", jnp.float32),
    ("monotonicity", jnp.float32),
    ("dict_iterations", jnp.int32),
    ("route", jnp.int32),
    ("route_margin", jnp.float32),
    ("detector_margin", jnp.float32),
    ("dict_residual", jnp.float32),
    ("coupon_iterations", jnp.int32),
    ("clamp_flags", jnp.int32),
)


def block_rows(r: int) -> int:
    """Columns per grid step at row-group width ``r``.

    The largest power of two with ``block_b * r <= PLANE_ELEMS``, clamped
    to [MIN_BLOCK_B, MAX_BLOCK_B]: 32 columns at R = 256, the floor of 8
    from R = 1024 on.
    """
    b = max(PLANE_ELEMS // max(r, 1), 1)
    b = 1 << (b.bit_length() - 1)
    return max(MIN_BLOCK_B, min(b, MAX_BLOCK_B))


def vmem_limit(r: int):
    """Scoped VMEM for one grid step at width ``r`` (None: the default)."""
    if r <= DEFAULT_VMEM_GROUPS:
        return None
    return VMEM_PER_DEFAULT_GROUPS * -(-r // DEFAULT_VMEM_GROUPS)


def _from_lane(x, dtype):
    """A lane column back to its dtype (flags were packed as 0/1)."""
    return x > 0.5 if dtype == jnp.bool_ else x.astype(dtype)


def _fused_body(
    mode,
    in_dtypes,
    s_ref,
    rows_ref,
    nulls_ref,
    dict_ref,
    mins_ref,
    maxs_ref,
    valid_ref,
    scal_ref,
    out_ref,
):
    # Local imports: this module is imported by repro.kernels.ops, which the
    # estimator stack imports lazily — importing the stack at module scope
    # here would close the cycle.
    from repro.core.ndv.estimator import _PER_COLUMN, estimate_tile
    from repro.core.ndv.types import ColumnBatch

    scal = scal_ref[...]
    per_column = {
        f: _from_lane(scal[:, k:k + 1], dt)
        for k, (f, dt) in enumerate(zip(_PER_COLUMN, in_dtypes))
    }
    tile = ColumnBatch(
        chunk_S=s_ref[...],
        chunk_rows=rows_ref[...],
        chunk_nulls=nulls_ref[...],
        chunk_dict_encoded=dict_ref[...] > 0.5,
        mins=mins_ref[...],
        maxs=maxs_ref[...],
        valid=valid_ref[...] > 0.5,
        **per_column,
    )
    sb = scal[:, len(_PER_COLUMN):len(_PER_COLUMN) + 1]
    est = estimate_tile(tile, sb, mode=mode, backend="ref")
    out_ref[...] = pack_lanes([getattr(est, f) for f, _ in _OUT_LANES])


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def fused_estimate(batch, schema_bound=None, *, mode: str = "paper",
                   interpret: bool = True):
    """One-dispatch §4-§7 estimation over a packed `ColumnBatch`.

    Computes the reference pipeline
    (`estimate_batch_core(batch, schema_bound, mode=mode, backend="ref")`)
    inside one `pallas_call`; agreement with that oracle is exact on
    discrete fields and last-ulp-tight on floats (kernel-vs-oracle codegen
    gap, see module docstring). ``schema_bound=None`` materializes as +inf —
    `min(ndv, +inf)` is the identity bit-for-bit, the same trick the
    sharded engine path uses to keep one kernel signature.
    """
    from repro.core.ndv.estimator import _PER_COLUMN, BatchEstimates

    b, r0 = batch.chunk_S.shape
    if r0 > MAX_GROUPS:
        raise ValueError(
            f"{r0} row groups per column exceed the fused kernel's "
            f"MAX_GROUPS = {MAX_GROUPS}"
        )
    r = max(r0, MIN_GROUPS)
    block_b = block_rows(r)
    pb = -(-b // block_b) * block_b

    if schema_bound is None:
        sb = jnp.full((b,), jnp.inf, jnp.float32)
    else:
        sb = schema_bound.astype(jnp.float32)
    scalars = [getattr(batch, f) for f in _PER_COLUMN] + [sb]
    scal = jnp.stack([x.astype(jnp.float32) for x in scalars], axis=1)
    scal = jnp.pad(scal, ((0, pb - b), (0, LANES - len(scalars))))
    planes = [
        jnp.pad(x.astype(jnp.float32), ((0, pb - b), (0, r - r0)))
        for x in (
            batch.chunk_S, batch.chunk_rows, batch.chunk_nulls,
            batch.chunk_dict_encoded, batch.mins, batch.maxs, batch.valid,
        )
    ]

    plane_spec = pl.BlockSpec((block_b, r), lambda i: (i, 0))
    lane_spec = pl.BlockSpec((block_b, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(
            _fused_body, mode,
            tuple(getattr(batch, f).dtype for f in _PER_COLUMN),
        ),
        out_shape=jax.ShapeDtypeStruct((pb, LANES), jnp.float32),
        grid=(pb // block_b,),
        in_specs=[plane_spec] * 7 + [lane_spec],
        out_specs=lane_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem_limit(r)
        ),
        interpret=interpret,
    )(*planes, scal)[:b]

    return BatchEstimates(
        mean_len=batch.mean_len.astype(jnp.float32),
        **{
            f: _from_lane(out[:, k], dt)
            for k, (f, dt) in enumerate(_OUT_LANES)
        },
    )
