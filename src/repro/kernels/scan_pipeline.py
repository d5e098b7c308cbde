"""Blocked multi-core scan pipeline (paper §4, Alg. 3) as Pallas grid kernels.

The paper's MCScan splits a length-``N`` input into ``B`` blocks and runs three
phases across Ascend AI cores:

  Phase 1  every block in parallel: the cube units compute a matmul *partial*
           scan of the block while the vector units independently *recompute*
           the block reduction ``r_i`` (so the reductions never wait on the
           scans).
  Phase 2  the ``B`` block sums are scanned (exclusive) to produce per-block
           carries.
  Phase 3  each block broadcast-adds its carry onto its partial scan.

TPU mapping (grid = blocks):

* ``block_partial_sums`` — the phase-1 *vector recompute*: a cheap reduction
  pass over the raw input (reads N elements, writes B scalars).  Keeping it a
  separate launch is what lets the main kernel below be single-pass.
* ``carry_scan`` — phase 2: an exclusive XLA cumsum of the ``(batch, B)``
  block sums (B is tiny compared to N).
* ``block_scan_carry`` — phases 1+3 *fused*: per-block matmul partial scans
  (the ScanU/ScanUL1 tile algebra of :func:`repro.kernels.tile.tile_scan`,
  walked over the block's ``m/s`` square tiles in order) plus the carry
  broadcast-add in the same launch.  Each element is read from HBM once and written once — on
  Ascend the carry add is a separate vector-core pass over GM (two extra trips);
  on TPU the MXU and VPU share VMEM so the add fuses behind the matmuls.

Traffic: ``N`` (sum pass) + ``N`` read + ``N`` write + ``O(B)``, vs. the
unfused 2 reads + 2 writes per element; the paper reports 74.9% of memcpy
bandwidth for the fused pipeline, which ``benchmarks/run.py --only
scan_pipeline`` tracks as ``memcpy_frac``.

Block algebra (paper Eq. 1 per ``s×s`` tile): ``scan(A) = A@U_s + row
carries``, the row carries propagated on the VPU for ``variant="scanu"``
(Alg. 1) or from ``L⁻_s @ A`` on the MXU for ``variant="scanul1"`` (Alg. 2);
the running carry crosses the block's tiles as a one-row value.

dtype rules follow ``accum_dtype_for``: int8/bool masks accumulate in int32
(the paper's mask-scan specialization), bf16/f16 in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scan import _operand_dtype, accum_dtype_for
from repro.kernels.tile import default_interpret, last_as_row, tile_scan

__all__ = ["blocked_scan", "block_partial_sums", "carry_scan", "block_scan_carry"]


def _row_spec(s: int):
    """``(b, nb, 1, s)`` per-block values, one value broadcast over lanes."""
    return pl.BlockSpec((1, 1, 1, s), lambda i, j: (i, j, 0, 0))


# ---------------------------------------------------------------------------
# Phase 1 (vector recompute): per-block reductions
# ---------------------------------------------------------------------------


def _block_sums_kernel(x_ref, o_ref, *, acc):
    total = jnp.sum(x_ref[0, 0].astype(acc), axis=(0, 1), keepdims=True)
    o_ref[0, 0] = jnp.broadcast_to(total, o_ref.shape[2:])


def block_partial_sums(blocks: jax.Array, *, accum_dtype=None,
                       interpret: bool | None = None) -> jax.Array:
    """Phase 1 reduction pass: block sums of ``(b, nb, m, s)`` -> ``(b, nb)``.

    This is the paper's vector-unit *recompute* of the block reductions: it
    reads the raw input once and has no data dependency on the partial scans,
    so the scheduler can overlap it with (or run it ahead of) the main scan
    launch.
    """
    interpret = default_interpret(interpret)
    b, nb, m, s = blocks.shape
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else accum_dtype_for(blocks.dtype)
    sums = pl.pallas_call(
        functools.partial(_block_sums_kernel, acc=acc),
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, 1, m, s), lambda i, j: (i, j, 0, 0))],
        out_specs=_row_spec(s),
        out_shape=jax.ShapeDtypeStruct((b, nb, 1, s), acc),
        interpret=interpret,
        name=f"scan_pipeline_block_sums_m{m}_s{s}",
    )(blocks)
    return sums[:, :, 0, 0]


# ---------------------------------------------------------------------------
# Phase 2: exclusive scan of the block sums (the carries)
# ---------------------------------------------------------------------------


def carry_scan(sums: jax.Array) -> jax.Array:
    """Phase 2: exclusive prefix of the ``(b, nb)`` block sums, per batch row.

    ``nb`` is small (N / block_len), so one XLA cumsum over the block sums
    suffices — the analogue of the paper's phase-2 scan of ``r`` in UB.
    """
    return jnp.cumsum(sums, axis=-1) - sums


# ---------------------------------------------------------------------------
# Phases 1+3 fused: per-block matmul partial scan + carry broadcast-add
# ---------------------------------------------------------------------------


def _block_scan_kernel(x_ref, c_ref, o_ref, carry_ref, *, variant, acc,
                       precision):
    s = x_ref.shape[-1]
    # the running carry goes through VMEM: kept as a value, Mosaic would fold
    # its lane broadcast into the tile add as a (1, 1) -> (s, s) broadcast,
    # which it cannot lower
    carry_ref[...] = c_ref[0, 0]
    for t in range(x_ref.shape[2] // s):                 # the block's tiles
        rows = slice(t * s, (t + 1) * s)
        out = tile_scan(x_ref[0, 0, rows, :], variant=variant, acc=acc,
                        precision=precision) + carry_ref[...]
        o_ref[0, 0, rows, :] = out
        carry_ref[...] = last_as_row(out)


def block_scan_carry(blocks: jax.Array, carries: jax.Array, *,
                     variant: str = "scanul1", accum_dtype=None,
                     interpret: bool | None = None,
                     precision: str = "highest") -> jax.Array:
    """Fused phases 1+3: matmul partial scan of each block + carry add.

    ``blocks``: ``(b, nb, m, s)`` row-major block views with ``m`` a multiple
    of ``s``; ``carries``: ``(b, nb)`` exclusive block prefixes from
    :func:`carry_scan`.  One grid step reads its block from HBM once, scans
    its ``m / s`` square tiles in order with the ScanU/ScanUL1 tile algebra
    in VMEM, starting from the block carry, and writes the final result once
    — the read/write-once property the paper obtains by overlapping cube and
    vector units.
    """
    if variant not in ("scanu", "scanul1"):
        raise ValueError(f"unknown scan variant {variant!r}")
    interpret = default_interpret(interpret)
    b, nb, m, s = blocks.shape
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else accum_dtype_for(blocks.dtype)
    block_spec = pl.BlockSpec((1, 1, m, s), lambda i, j: (i, j, 0, 0))
    rows = jnp.broadcast_to(carries.astype(acc)[:, :, None, None],
                            (b, nb, 1, s))
    # U_s / L⁻_s are built in-register inside the kernel from iota
    # comparisons, so the only operands streamed from HBM are the data blocks
    # and the nb carries.
    return pl.pallas_call(
        functools.partial(_block_scan_kernel, variant=variant, acc=acc,
                          precision=precision),
        grid=(b, nb),
        in_specs=[block_spec, _row_spec(s)],
        out_specs=block_spec,
        out_shape=jax.ShapeDtypeStruct((b, nb, m, s), acc),
        scratch_shapes=[pltpu.VMEM((1, s), acc)],
        interpret=interpret,
        name=f"scan_pipeline_{variant}_m{m}_s{s}",
    )(blocks, rows)


# ---------------------------------------------------------------------------
# Full pipeline
# ---------------------------------------------------------------------------


def blocked_scan(x: jax.Array, *, s: int = 128, block_tiles: int = 8,
                 variant: str = "scanul1", accum_dtype=None,
                 interpret: bool | None = None,
                 precision: str = "highest") -> jax.Array:
    """Scan the last axis of ``x`` with the three-phase blocked pipeline.

    ``x``: ``(..., n)`` for any ``n >= 1`` (ragged tails are zero-padded to a
    whole number of blocks and sliced off).  A block holds ``block_tiles``
    tiles of ``ell = s*s`` elements, viewed as an ``(block_tiles*s, s)``
    row-major matrix; ``block_tiles`` is clamped so a short input never pays
    for more than one partially-filled block.  Returns the inclusive scan in
    the accumulation dtype (``accum_dtype_for(x.dtype)`` unless overridden).
    """
    if variant not in ("scanu", "scanul1"):
        raise ValueError(f"unknown scan variant {variant!r}")
    interpret = default_interpret(interpret)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else accum_dtype_for(x.dtype)
    *lead, n = x.shape
    xb = x.reshape(-1, n) if lead else x[None]
    if xb.dtype == jnp.bool_:
        xb = xb.astype(_operand_dtype(xb.dtype))
    b = xb.shape[0]
    ell = s * s
    t = max(1, min(block_tiles, -(-n // ell)))   # tiles per block, clamped
    m = t * s                                    # rows per block
    block_len = m * s
    pad = (-n) % block_len
    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad)))
    nb = xb.shape[-1] // block_len
    blocks = xb.reshape(b, nb, m, s)
    if nb == 1:
        # Single block: the carry is provably zero — skip phases 1-2 entirely
        # (saves a full extra read of the input plus two launches).
        carries = jnp.zeros((b, 1), acc)
    else:
        sums = block_partial_sums(blocks, accum_dtype=acc, interpret=interpret)
        carries = carry_scan(sums)
    out = block_scan_carry(blocks, carries, variant=variant, accum_dtype=acc,
                           interpret=interpret, precision=precision)
    out = out.reshape(b, nb * block_len)[:, :n]
    return out.reshape(*lead, n) if lead else out[0]
