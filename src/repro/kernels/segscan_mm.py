"""Segmented scan kernels: the carry resets at segment boundaries.

The paper scans one flat array; packed variable-length batches (MoE group
dispatch, continuous-batching decode, ragged data pipelines) need *segmented*
scans — prefix sums that restart at segment starts.

Representation: packed values ``(n,)`` plus int8 boundary flags ``(n,)`` where
``flags[i] = 1`` iff element ``i`` starts a new segment (derived from CSR-style
offsets by ``repro.core.segmented``).

A segmented sum is the linear recurrence ``y_i = (1 - flags_i) * y_{i-1} +
x_i``: the multiplier is 0 where a segment starts and 1 elsewhere.  So both
launch shapes are the linear-recurrence kernels of
:mod:`repro.kernels.linrec_mm` — the sequential-grid tile walk and the §4
blocked pipeline, whose affine phase-2 carry scan is exactly the segmented
carry scan — fed the int8 ``1 - flags`` as multipliers and run in the
accumulation dtype (``accum_dtype_for``: int8/bool values accumulate in
int32, bf16/f16 in fp32), so integer scans stay exact.  They do not use the
MXU, and ``precision=`` does not change them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import guards
from repro.core.scan import _operand_dtype, accum_dtype_for
from repro.kernels.linrec_mm import linrec_blocked_scan, linrec_scan_tiles

__all__ = ["seg_scan_tiles", "seg_blocked_scan"]


def _as_recurrence(x, flags, op):
    """``(multipliers, values, acc)`` of the segmented scan of ``x``."""
    guards.validate_broadcastable_to(jnp.shape(flags), x.shape, op=op)
    acc = accum_dtype_for(x.dtype)
    if x.dtype == jnp.bool_:
        x = x.astype(_operand_dtype(x.dtype))
    keep = (jnp.broadcast_to(flags, x.shape) == 0).astype(jnp.int8)
    return keep, x, acc


def seg_scan_tiles(x: jax.Array, flags: jax.Array, *, s: int = 128,
                   accum_dtype=None, interpret: bool | None = None,
                   precision: str = "highest") -> jax.Array:
    """Segmented scan of the last axis with one sequential-grid launch.

    ``x``: ``(..., n)`` packed values; ``flags``: broadcastable to it,
    nonzero where an element starts a new segment.
    """
    del precision
    keep, x, acc = _as_recurrence(x, flags, "seg_scan_tiles")
    return linrec_scan_tiles(keep, x, s=s, accum_dtype=accum_dtype or acc,
                             interpret=interpret, name="segscan_mm")


def seg_blocked_scan(x: jax.Array, flags: jax.Array, *, s: int = 128,
                     block_tiles: int = 8, accum_dtype=None,
                     interpret: bool | None = None,
                     precision: str = "highest") -> jax.Array:
    """Segmented scan of the last axis with the three-phase blocked pipeline.

    Phase 1 reduces each block to its affine summary (is there a boundary,
    trailing-segment sum), phase 2 scans the summaries (the segmented carry
    scan) and fused phases 1+3 produce the final segmented scan with each
    element read and written once.
    """
    del precision
    keep, x, acc = _as_recurrence(x, flags, "seg_blocked_scan")
    return linrec_blocked_scan(keep, x, s=s, block_tiles=block_tiles,
                               accum_dtype=accum_dtype or acc,
                               interpret=interpret, name="segscan_pipeline")
