"""Pallas kernels for metadata-only NDV estimation.

Architecture sketch — how an estimate call reaches silicon::

    estimate_batch (core/ndv/estimator.py)
      |  ops.use_fused(fuse)?           fuse: "auto" | "on" | "off"
      |-- yes -> ops.fused_estimate ----+-- TPU / backend="pallas" pin:
      |                                 |     fused_estimate.py — ONE
      |                                 |     pallas_call running the whole
      |                                 |     detector + SS4 dict + SS5 coupon
      |                                 |     pipeline on packed (B, R) tiles
      |                                 +-- otherwise: ref.ref_fused_estimate,
      |                                       the pure-XLA twin — literally
      |                                       estimate_batch_core(backend="ref"),
      |                                       so fuse on/off is bit-identical
      |                                       by construction off-TPU
      +-- no  -> estimate_batch_core, which dispatches per stage through
            ops.dict_newton / ops.coupon_newton (newton_ndv.py),
            ops.minmax_scan (minmax_scan.py), ops.hll_fold (hll.py),
            each resolving pallas-vs-ref via ops.use_pallas(backend)

Each kernel module is layered the same way:

  * ``*_math`` functions — the numerics (fixed-iteration Newton solves,
    masked reductions) as plain jnp on unpadded values, shared verbatim
    by the kernel bodies and testable without tiling geometry;
  * kernel bodies — the ``*_math`` functions applied inside a
    ``pallas_call`` over lane-padded tiles (BLOCK_M x LANES);
  * wrappers — jit entry points owning pad/unpad and block specs;
  * ``ref.py`` — pure-XLA oracles every kernel is swept against.

Serving contract: off-TPU, ``backend="pallas"`` runs interpret-mode
Pallas — a correctness tool, never a serving path — so production
dispatch off-TPU is always the reference program, fused or not. The
``fuse`` knob therefore changes launch structure only, never numerics,
and stays out of engine cache identity (see engine/config.py).

Provenance lanes: both the megakernel and the unfused pipeline emit
per-column diagnostics as extra output lanes of the SAME program —
route chosen + decision margin, detector margin, Newton iteration
counts and final dict residual, clamp flags (see fused_estimate.py
``_OUT_LANES``). Because they are outputs of the
shared numerics rather than a side channel, fused and ref twins agree
on them bit-for-bit off-TPU, the strategy x device parity matrix pins
them across serving topologies, and they can never perturb estimates
or cache identity. The service tier surfaces them as `Provenance`
records (?explain=1, /debug/explain) and the sketch auditor scores
them against an hll.py reference — see repro.obs for the metrics side.
"""
from repro.kernels import ops  # noqa: F401
