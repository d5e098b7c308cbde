"""Fused linear-recurrence (``y_t = a_t*y_{t-1} + b_t``) scan kernels.

Same two launch shapes as the prefix-scan kernels:

* :func:`linrec_scan_tiles` — the ``scan_mm`` analogue: one sequential-grid
  launch walks ``(s, s)`` tiles in order with the running state ``y`` in a
  one-row VMEM scratch.  On the sequential grid the general affine carry
  ``(Π a, sum)`` degenerates: each tile folds the incoming state immediately
  (``local + mult * y_in``), so only ``y`` needs carrying.
* the §4 blocked pipeline (:func:`linrec_blocked_scan`): phase 1 reduces each
  block to its affine summary ``(Π a, trailing affine sum)``, phase 2 scans
  the ``nb`` summaries under affine composition (one XLA associative scan,
  ``nb`` is tiny), and fused phases 1+3 rerun the block with the carry folded
  in, so every element is read from HBM once and written once.

Inside a tile the recurrence runs as two Kogge-Stone affine scans on the
vector unit (:func:`repro.kernels.tile.affine_scan`): along the lanes of each
row, then over the row summaries down the sublanes.  Mosaic lowers no
cumulative product, which the weighted-triangle (``W @ b``) contraction of
:mod:`repro.core.linrec` needs, so these kernels do not use the MXU and
``precision=`` does not change them: every product and sum is an fp32 (or
accumulation-dtype) VPU operation.  Integer-valued payloads stay bit-exact
while their partial products and sums fit the fp32 significand.  dtype rules
follow ``linrec_accum_dtype_for`` (floats widen per ``accum_dtype_for``;
integers accumulate in fp32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import guards
from repro.core.linrec import linrec_accum_dtype_for
from repro.kernels.tile import affine_scan, default_interpret, last_as_row

__all__ = ["linrec_scan_tiles", "linrec_blocked_scan", "linrec_block_summaries",
           "linrec_carry_scan", "linrec_block_scan_carry"]


def _pad_affine(ab, widths):
    """Pad an ``(a, b)`` pair with the identity affine element ``a=1, b=0``."""
    a, b = ab
    return jnp.pad(a, widths, constant_values=1), jnp.pad(b, widths)


def _to_rows(a, b, n):
    """Flatten leading dims to one batch axis of packed length-``n`` rows."""
    lead = a.shape[:-1]
    ab = a.reshape(-1, n) if lead else a[None]
    bb = b.reshape(-1, n) if lead else b[None]
    return ab, bb, lead


def _tile_recurrence(a, b, acc):
    """``(out, mult)`` of one ``(s, s)`` row-major tile from a zero state.

    ``out`` is the recurrence with no incoming state and ``mult[r, i]`` the
    product of ``a`` from the tile start through ``(r, i)`` — what an
    incoming state is multiplied by.
    """
    s = a.shape[-1]
    mult, y = affine_scan(a.astype(acc), b.astype(acc), axis=1)   # per row
    rows_p = jnp.broadcast_to(mult[:, s - 1:s], mult.shape)
    rows_y = jnp.broadcast_to(y[:, s - 1:s], y.shape)
    rows_p, rows_y = affine_scan(rows_p, rows_y, axis=0)   # through row r
    # shift down one row: the state entering row r, and the product before it
    first = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0) == 0
    y_in = jnp.where(first, jnp.zeros_like(y), pltpu.roll(rows_y, 1, 0))
    p_in = jnp.where(first, jnp.ones_like(y), pltpu.roll(rows_p, 1, 0))
    return y + mult * y_in, mult * p_in


def _walk_tiles(a_ref, b_ref, o_ref, state_ref, *, acc):
    """Run the tiles of one ``(m, s)`` block in order from ``state_ref``."""
    s = a_ref.shape[-1]
    for t in range(a_ref.shape[2] // s):
        rows = slice(t * s, (t + 1) * s)
        out, mult = _tile_recurrence(a_ref[0, 0, rows, :], b_ref[0, 0, rows, :],
                                     acc)
        out = out + mult * state_ref[...]
        o_ref[0, 0, rows, :] = out
        state_ref[...] = last_as_row(out)


# ---------------------------------------------------------------------------
# Sequential-grid fused kernel (the linrec analogue of scan_mm)
# ---------------------------------------------------------------------------


def _tile_kernel(a_ref, b_ref, o_ref, state_ref, *, acc):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros(state_ref.shape, acc)

    _walk_tiles(a_ref, b_ref, o_ref, state_ref, acc=acc)


def linrec_scan_tiles(a: jax.Array, b: jax.Array, *, s: int = 128,
                      accum_dtype=None, interpret: bool | None = None,
                      precision: str = "highest",
                      name: str = "linrec_mm") -> jax.Array:
    """Linear recurrence over the last axis in one sequential-grid launch.

    ``a``/``b``: ``(..., n)`` (already broadcast to a common shape by
    ``linear_scan``).  Tiles of ``ℓ = s²`` elements are walked in order; the
    VMEM scratch carries the running state across tiles.  ``precision`` is
    accepted for signature parity with the matmul paths and ignored (module
    docstring); ``name`` prefixes the kernel's name.
    """
    del precision
    guards.validate_same_shape(a.shape, b.shape, op="linrec_scan_tiles",
                               a_name="a", b_name="b")
    s = guards.validate_positive(s, name="s", op="linrec_scan_tiles")
    interpret = default_interpret(interpret)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else linrec_accum_dtype_for(jnp.result_type(a.dtype, b.dtype))
    n = a.shape[-1]
    ab, bb, lead = _to_rows(a, b, n)
    rows = ab.shape[0]
    ell = s * s
    pad = (-n) % ell
    if pad:
        ab, bb = _pad_affine((ab, bb), ((0, 0), (0, pad)))
    nt = ab.shape[-1] // ell
    spec = pl.BlockSpec((1, 1, s, s), lambda i, j: (i, j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_tile_kernel, acc=acc),
        grid=(rows, nt),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, nt, s, s), acc),
        scratch_shapes=[pltpu.VMEM((1, s), acc)],
        interpret=interpret,
        name=f"{name}_s{s}",
    )(ab.reshape(rows, nt, s, s), bb.reshape(rows, nt, s, s))
    out = out.reshape(rows, nt * ell)[:, :n]
    return out.reshape(*lead, n) if lead else out[0]


# ---------------------------------------------------------------------------
# Blocked pipeline (§4) with an affine phase-2 carry scan
# ---------------------------------------------------------------------------


def _row_spec(s: int):
    """``(rows, nb, 1, s)`` per-block values, one value broadcast over lanes."""
    return pl.BlockSpec((1, 1, 1, s), lambda i, j: (i, j, 0, 0))


def _summary_kernel(a_ref, b_ref, p_ref, l_ref, *, acc):
    s = a_ref.shape[-1]
    p_ref[0, 0] = jnp.ones((1, s), acc)
    l_ref[0, 0] = jnp.zeros((1, s), acc)
    for t in range(a_ref.shape[2] // s):
        rows = slice(t * s, (t + 1) * s)
        out, mult = _tile_recurrence(a_ref[0, 0, rows, :], b_ref[0, 0, rows, :],
                                     acc)
        # compose the block summary so far with this tile's affine map
        l_ref[0, 0] = last_as_row(out + mult * l_ref[0, 0])
        p_ref[0, 0] = last_as_row(mult * p_ref[0, 0])


def linrec_block_summaries(ablocks: jax.Array, bblocks: jax.Array, *,
                           accum_dtype=None, interpret: bool | None = None,
                           name: str = "linrec_pipeline"):
    """Phase 1 summary pass: the affine pair ``(Π a, trailing sum)`` per block.

    The prefix pipeline reduces each block to one sum; the linear recurrence
    reduces it to the affine map it applies to an incoming state —
    ``y_out = p * y_in + l``.  Returns two ``(rows, nb)`` arrays.
    """
    interpret = default_interpret(interpret)
    rows, nb, m, s = ablocks.shape
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else linrec_accum_dtype_for(jnp.result_type(ablocks.dtype, bblocks.dtype))
    spec = pl.BlockSpec((1, 1, m, s), lambda i, j: (i, j, 0, 0))
    prods, lasts = pl.pallas_call(
        functools.partial(_summary_kernel, acc=acc),
        grid=(rows, nb),
        in_specs=[spec, spec],
        out_specs=(_row_spec(s), _row_spec(s)),
        out_shape=(jax.ShapeDtypeStruct((rows, nb, 1, s), acc),
                   jax.ShapeDtypeStruct((rows, nb, 1, s), acc)),
        interpret=interpret,
        name=f"{name}_summaries_m{m}_s{s}",
    )(ablocks, bblocks)
    return prods[:, :, 0, 0], lasts[:, :, 0, 0]


def linrec_carry_scan(prods: jax.Array, lasts: jax.Array) -> jax.Array:
    """Phase 2: exclusive scan of the block summaries under affine composition.

    ``carry_in[c] = Σ_{q<c} l_q · Π_{r=q+1..c-1} p_r`` — the state entering
    block ``c`` — as one XLA associative scan per batch row (``nb`` is tiny
    compared to N, as in the prefix pipeline's phase 2).
    """
    def compose(first, second):
        return first[0] * second[0], second[0] * first[1] + second[1]

    _, inc = jax.lax.associative_scan(compose, (prods, lasts), axis=-1)
    return jnp.concatenate([jnp.zeros_like(inc[:, :1]), inc[:, :-1]], axis=-1)


def _block_carry_kernel(a_ref, b_ref, c_ref, o_ref, state_ref, *, acc):
    state_ref[...] = c_ref[0, 0]
    _walk_tiles(a_ref, b_ref, o_ref, state_ref, acc=acc)


def linrec_block_scan_carry(ablocks: jax.Array, bblocks: jax.Array,
                            carries: jax.Array, *, accum_dtype=None,
                            interpret: bool | None = None,
                            precision: str = "highest",
                            name: str = "linrec_pipeline") -> jax.Array:
    """Fused phases 1+3: block-local recurrence + carry fold, one read/write.

    Each grid step reads its block once, walks its tiles from the incoming
    state and writes the result once — the §4 read/write-once property
    carried over to linear recurrences.
    """
    del precision
    interpret = default_interpret(interpret)
    rows, nb, m, s = ablocks.shape
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else linrec_accum_dtype_for(jnp.result_type(ablocks.dtype, bblocks.dtype))
    spec = pl.BlockSpec((1, 1, m, s), lambda i, j: (i, j, 0, 0))
    crow = jnp.broadcast_to(carries.astype(acc)[:, :, None, None],
                            (rows, nb, 1, s))
    return pl.pallas_call(
        functools.partial(_block_carry_kernel, acc=acc),
        grid=(rows, nb),
        in_specs=[spec, spec, _row_spec(s)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, nb, m, s), acc),
        scratch_shapes=[pltpu.VMEM((1, s), acc)],
        interpret=interpret,
        name=f"{name}_m{m}_s{s}",
    )(ablocks, bblocks, crow)


def linrec_blocked_scan(a: jax.Array, b: jax.Array, *, s: int = 128,
                        block_tiles: int = 8, accum_dtype=None,
                        interpret: bool | None = None,
                        precision: str = "highest",
                        name: str = "linrec_pipeline") -> jax.Array:
    """Linear recurrence over the last axis with the three-phase blocked pipeline.

    Same decomposition as ``scan_pipeline.blocked_scan``: phase 1 computes the
    per-block affine summaries, phase 2 composes them into per-block incoming
    states, and fused phases 1+3 produce the final recurrence with each
    element read and written once.  Single-block inputs skip phases 1–2 (the
    incoming state is provably zero).
    """
    guards.validate_same_shape(a.shape, b.shape, op="linrec_blocked_scan",
                               a_name="a", b_name="b")
    s = guards.validate_positive(s, name="s", op="linrec_blocked_scan")
    block_tiles = guards.validate_positive(block_tiles, name="block_tiles",
                                           op="linrec_blocked_scan")
    interpret = default_interpret(interpret)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else linrec_accum_dtype_for(jnp.result_type(a.dtype, b.dtype))
    n = a.shape[-1]
    ab, bb, lead = _to_rows(a, b, n)
    rows = ab.shape[0]
    ell = s * s
    t = max(1, min(block_tiles, -(-n // ell)))
    m = t * s
    block_len = m * s
    pad = (-n) % block_len
    if pad:
        ab, bb = _pad_affine((ab, bb), ((0, 0), (0, pad)))
    nb = ab.shape[-1] // block_len
    ablocks = ab.reshape(rows, nb, m, s)
    bblocks = bb.reshape(rows, nb, m, s)
    if nb == 1:
        carries = jnp.zeros((rows, 1), acc)
    else:
        prods, lasts = linrec_block_summaries(ablocks, bblocks,
                                              accum_dtype=acc,
                                              interpret=interpret, name=name)
        carries = linrec_carry_scan(prods, lasts)
    out = linrec_block_scan_carry(ablocks, bblocks, carries, accum_dtype=acc,
                                  interpret=interpret, precision=precision,
                                  name=name)
    out = out.reshape(rows, nb * block_len)[:, :n]
    return out.reshape(*lead, n) if lead else out[0]
