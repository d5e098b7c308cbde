"""Fused Pallas TPU kernels for the paper's scan-based operators (§5).

The paper's SplitInd is the building block of compress / radix sort / top-k /
top-p: an int8 mask scan produces destination offsets, then values (and their
indices) are permuted.

* ``bucket_ranks`` — the mask-scan half of a stable ``R``-way split in one
  launch per batch row: for every bucket the one-hot digit tile is scanned on
  the MXU (``A @ U_s`` for the row-local counts, ``L⁻_s @ A`` for the rows
  above, both built in-register from iota comparisons, so no constant operand
  is streamed from HBM), and the running per-bucket counts cross tiles in
  VMEM.  It emits each element's rank inside its bucket and the bucket
  totals.  ``R = 2`` is the paper's binary SplitInd; ``R = 2^k`` retires
  ``k`` radix bits per pass (the matmul-scan trick Dakkak et al. use for TCU
  scans, applied per bucket).
* ``split_tiles`` / ``multi_split_tiles`` / ``radix_pass_multibit`` — the
  operators on top: destinations are ``bucket base + rank``, and the
  permutation of the payloads is one XLA scatter.  Ascend performs the
  permutation with vector-core scatter instructions; Mosaic lowers no dynamic
  scatter across a row, so on a TPU it stays outside the kernel.
* ``topp_mask_sample_tiles`` — the tail of nucleus sampling fused: prefix sum
  of the sorted probabilities, the ``cum - p > threshold`` cutoff, the masked
  CDF and the inverse-transform sample, emitting only one int32 per row.  Both
  prefix sums are matmul tile scans; they round differently from XLA's
  ``cumsum``, so the sample equals ``method="vector"``'s except where a
  probability sits within rounding of the cutoff or of the drawn threshold.

Destinations are exact integers, so every split, sort and top-k here is
bit-identical to ``method="vector"``.  dtype rule (paper's mask-scan
specialization): the one-hot masks feed the MXU as bf16 0/1 values with fp32
accumulation, exact for the counts of any row below 2^24 elements.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import guards
from repro.kernels.tile import (default_interpret, last_as_row, tile_scan,
                                triangle)

__all__ = ["bucket_ranks", "split_tiles", "multi_split_tiles",
           "radix_pass_multibit", "topp_mask_sample_tiles"]


# ---------------------------------------------------------------------------
# Shared in-kernel SplitInd body: per-bucket mask scans -> ranks and totals
# ---------------------------------------------------------------------------


def _ranks_kernel(d_ref, rank_ref, cnt_ref, seen_ref, *, radix: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        seen_ref[...] = jnp.zeros(seen_ref.shape, jnp.int32)

    d = d_ref[0]                                       # (s, s) digit tile
    s = d.shape[-1]
    upper = triangle(s, jnp.bfloat16, "upper")         # U_s
    lower = triangle(s, jnp.bfloat16, "strict_lower")  # L⁻_s

    def bucket(r, rank):
        hit = d == r
        oh = hit.astype(jnp.bfloat16)
        # inclusive count of bucket r up to each element: row-local counts
        # plus the lane sum of the counts in the rows above (exact in fp32)
        local = jnp.dot(oh, upper, preferred_element_type=jnp.float32)
        above = jnp.dot(lower, oh, preferred_element_type=jnp.float32)
        inc = (local + jnp.sum(above, axis=1, keepdims=True)).astype(jnp.int32)
        seen = seen_ref[pl.ds(r, 1), :]                # (1, s) earlier tiles
        seen_ref[pl.ds(r, 1), :] = seen + last_as_row(inc)
        return jnp.where(hit, inc - 1 + seen, rank)

    rank_ref[0] = jax.lax.fori_loop(0, radix, bucket,
                                    jnp.zeros(d.shape, jnp.int32))
    cnt_ref[0] = seen_ref[...]


def bucket_ranks(digits: jax.Array, *, radix: int, s: int = 128,
                 interpret: bool | None = None, name: str = "bucket_ranks"):
    """Rank of every element inside its bucket, and the bucket totals.

    ``digits``: ``(b, n)`` int32 bucket ids in ``[0, radix)``.  Returns
    ``(rank, counts)``: ``rank`` int32 ``(b, n)`` counts the earlier elements
    of the same row and bucket; ``counts`` int32 ``(b, radix)``.  The row is
    padded to whole ``s×s`` tiles with bucket ``radix - 1`` (removed from its
    count again), so padding ranks after every real element.
    """
    interpret = default_interpret(interpret)
    b, n = digits.shape
    ell = s * s
    pad = (-n) % ell
    d = digits.astype(jnp.int32)
    if pad:
        d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=radix - 1)
    nt = d.shape[-1] // ell
    rank, cnt = pl.pallas_call(
        functools.partial(_ranks_kernel, radix=radix),
        grid=(b, nt),
        in_specs=[pl.BlockSpec((1, s, s), lambda i, j: (i, j, 0))],
        out_specs=[pl.BlockSpec((1, s, s), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, radix, s), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, nt * s, s), jnp.int32),
                   jax.ShapeDtypeStruct((b, radix, s), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((radix, s), jnp.int32)],
        interpret=interpret,
        name=f"{name}_r{radix}_s{s}",
    )(d.reshape(b, nt * s, s))
    counts = cnt[:, :, 0]
    if pad:
        counts = counts.at[:, radix - 1].add(-pad)
    return rank.reshape(b, nt * ell)[:, :n], counts


def _group(payloads, digits, *, radix: int, s: int, interpret, with_ind: bool,
           name: str):
    """Stable ``radix``-way split of ``(b, n)`` rows: destinations from
    :func:`bucket_ranks`, then one XLA scatter of every payload."""
    from repro.core.primitives import _scatter_payloads, _take_along_last
    rank, counts = bucket_ranks(digits, radix=radix, s=s, interpret=interpret,
                                name=name)
    base = jnp.cumsum(counts, axis=-1) - counts        # exclusive bucket bases
    dest = rank + _take_along_last(base, digits)
    return _scatter_payloads(payloads, dest, with_indices=with_ind), counts


# ---------------------------------------------------------------------------
# split
# ---------------------------------------------------------------------------


def split_tiles(x: jax.Array, flags: jax.Array, *, s: int = 128,
                interpret: bool | None = None):
    """SplitInd over the last axis: ``(z, indices, n_true)``.

    ``x``: (..., n) payload; ``flags``: same shape, boolean/int.  Flagged
    elements go first (bucket 0), the rest after them (bucket 1), both in
    their original order.
    """
    guards.validate_same_shape(x.shape, jnp.shape(flags), op="split_tiles")
    s = guards.validate_positive(s, name="s", op="split_tiles")
    *lead, n = x.shape
    digits = 1 - (flags.reshape(-1, n) != 0).astype(jnp.int32)
    (z, ind), cnt = _group((x.reshape(-1, n),), digits, radix=2, s=s,
                           interpret=interpret, with_ind=True, name="split")
    n_true = cnt[:, 0].reshape(lead) if lead else cnt[0, 0]
    return z.reshape(*lead, n), ind.reshape(*lead, n), n_true


# ---------------------------------------------------------------------------
# multi-way split (radix-2^k SplitInd)
# ---------------------------------------------------------------------------


def multi_split_tiles(x: jax.Array, digits: jax.Array, *, num_buckets: int,
                      s: int = 128, interpret: bool | None = None):
    """Stable ``num_buckets``-way split: ``(z, indices, counts)``.

    ``x``: (..., n) payload; ``digits``: same shape, int bucket ids in
    ``[0, num_buckets)``.  ``counts`` has shape ``(..., num_buckets)``.
    """
    guards.validate_same_shape(x.shape, jnp.shape(digits),
                               op="multi_split_tiles", b_name="digits")
    num_buckets = guards.validate_positive(num_buckets, name="num_buckets",
                                           op="multi_split_tiles")
    s = guards.validate_positive(s, name="s", op="multi_split_tiles")
    *lead, n = x.shape
    (z, ind), cnt = _group((x.reshape(-1, n),), digits.reshape(-1, n),
                           radix=num_buckets, s=s, interpret=interpret,
                           with_ind=True, name="multi_split")
    return (z.reshape(*lead, n), ind.reshape(*lead, n),
            cnt.reshape(*lead, num_buckets))


# ---------------------------------------------------------------------------
# radix pass (radix-2^k; pass_bits=1 is the paper's binary formulation)
# ---------------------------------------------------------------------------


def radix_pass_multibit(work: jax.Array, perm: jax.Array, *, shift: int,
                        pass_bits: int, s: int = 128,
                        interpret: bool | None = None,
                        with_counts: bool = False):
    """One radix-2^k pass on ``(b, n)`` operands: ``(work, perm)`` grouped.

    ``work`` is an unsigned encoding; the ``pass_bits``-bit digit at
    ``shift`` picks the bucket, the fused :func:`bucket_ranks` launch gives
    the destinations and (keys, permutation) move in one scatter, so
    ``ceil(bits / k)`` passes sort the full key.  ``pass_bits=1`` is exactly
    the paper's binary LSB pass (zeros-first split on one bit).

    With ``with_counts`` the per-bucket totals of the pass — the per-shard
    digit histogram the distributed sort's bucket exchange is built from
    (``repro.core.dist_ops``) — are returned as a third ``(b, 2^pass_bits)``
    int32 output, straight from the same launch.
    """
    radix = 1 << pass_bits
    mask = jnp.asarray(radix - 1, work.dtype)
    digits = ((work >> shift) & mask).astype(jnp.int32)
    (wo, po), cnt = _group((work, perm), digits, radix=radix, s=s,
                           interpret=interpret, with_ind=False,
                           name=f"radix_pass_sh{shift}")
    return (wo, po, cnt) if with_counts else (wo, po)


# ---------------------------------------------------------------------------
# fused top-p tail (cumsum -> cutoff -> masked CDF -> inverse-transform sample)
# ---------------------------------------------------------------------------


def _topp_kernel(sp_ref, u_ref, j_ref, cdf_ref, carry_ref, *, p: float,
                 n_real: int):
    s = sp_ref.shape[-1]
    nt = sp_ref.shape[1] // s
    scan = functools.partial(tile_scan, variant="scanul1", acc=jnp.float32,
                             precision="highest")
    zero = jnp.zeros((1, s), jnp.float32)
    carry_ref[0:1, :] = zero                           # running cum
    carry_ref[1:2, :] = zero                           # running masked cdf
    for t in range(nt):
        rows = slice(t * s, (t + 1) * s)
        sp = sp_ref[0, rows, :]
        cum = scan(sp) + carry_ref[0:1, :]
        carry_ref[0:1, :] = last_as_row(cum)
        cut = (cum - sp) > p                           # llama3 sample_top_p formula
        cdf = scan(jnp.where(cut, jnp.zeros_like(sp), sp)) + carry_ref[1:2, :]
        carry_ref[1:2, :] = last_as_row(cdf)
        cdf_ref[rows, :] = cdf
    theta = u_ref[0] * carry_ref[1:2, :]               # (1, s): u * total
    j = jnp.zeros((1, 1), jnp.int32)
    for t in range(nt):
        below = cdf_ref[t * s:(t + 1) * s, :] < theta
        j = j + jnp.sum(below.astype(jnp.int32), axis=(0, 1), keepdims=True)
    j_ref[0] = jnp.broadcast_to(jnp.clip(j, 0, n_real - 1), (1, s))


def topp_mask_sample_tiles(sorted_p: jax.Array, u: jax.Array, *, p: float,
                           s: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """Fused nucleus-sampling tail.

    ``sorted_p``: (..., n) probabilities sorted descending; ``u``: (..., 1)
    uniform draws.  Returns the (...,) int32 index *into the sorted order* —
    two matmul prefix sums, the cutoff, the mask and the count in one launch
    per row, with only one int32 per row leaving VMEM.  Rows are padded with
    zero probabilities to whole ``s×s`` tiles.
    """
    guards.validate_probability(p, op="topp_mask_sample_tiles")
    interpret = default_interpret(interpret)
    *lead, n = sorted_p.shape
    sp = sorted_p.reshape(-1, n).astype(jnp.float32)
    b = sp.shape[0]
    pad = (-n) % (s * s)
    if pad:
        sp = jnp.pad(sp, ((0, 0), (0, pad)))
    rows = sp.shape[-1] // s
    ub = jnp.broadcast_to(u.reshape(-1, 1, 1).astype(jnp.float32), (b, 1, s))
    j = pl.pallas_call(
        functools.partial(_topp_kernel, p=p, n_real=n),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, rows, s), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, s), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, s), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, s), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.float32),
                        pltpu.VMEM((2, s), jnp.float32)],
        interpret=interpret,
        name="topp_mask_sample",
    )(sp.reshape(b, rows, s), ub)
    return j[:, 0, 0].reshape(lead) if lead else j[0, 0, 0]
