"""Scan-based operators (paper §5): split, sort, top-k/top-p over one dispatch table.

Every operator takes ``method=`` and routes through a single table:

* ``"matmul"`` — the paper's cube-unit scan (ScanU/ScanUL1) feeding unfused
  JAX gather/scatter (default).
* ``"vector"`` — the plain ``jnp.cumsum`` vector baseline, same surrounding ops.
* ``"kernel"`` — the fused Pallas kernels (``repro.kernels.split_mm``): the
  bucket mask scans and offsets in one launch per batch row, the permutation
  as one XLA scatter; the whole top-p tail in one launch.
* ``"blocked"`` — the unfused operators running their scans on the three-phase
  blocked pipeline of paper §4 (``repro.kernels.scan_pipeline``), for large-N
  inputs where read/write-once traffic dominates.

The ``"kernel"`` and ``"blocked"`` paths are bit-identical to ``"vector"`` for
split / multi_split / compress / radix_sort / sort / topk (mask-scan offsets
are exact integer counts).  ``top_p_sample`` sorts just as exactly, but the
fp32 prefix sums of its tail round differently per method, so a draw that
falls within rounding of the nucleus cutoff or of the sampled threshold may
pick a neighbouring token.  The sort-based operators take
``bits_per_pass`` (default 4): each radix pass is a stable ``2^k``-way
``multi_split`` retiring ``k`` bits, so fp32 keys sort in ``32 / k`` passes
instead of 32 — every (method, bits_per_pass) combination stays bit-identical
to ``method="vector"`` with ``bits_per_pass=1`` because bucket offsets remain
exact int8 -> int32 mask scans.

Every operator defaults to ``method="auto"``: the concrete method is resolved
per (op, length, dtype, backend) from the committed tuning table
(:mod:`repro.core.autotune`) before dispatch, in Python on static shapes — so
an ``"auto"`` call traces to a jaxpr identical to passing the resolved method
explicitly, and nested calls (e.g. the ``multi_split`` passes inside
``radix_sort``) always receive the one concrete method the entry point chose.

Shapes are static (JAX): operators that logically return a variable number of
elements (compress/split) return a full-size array plus a count, with the tail
filled.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import guards
from repro.core.autotune import maybe_resolve
from repro.core.scan import METHODS, scan

__all__ = [
    "split", "multi_split", "compress", "radix_sort", "sort", "topk",
    "top_p_sample", "weighted_sample", "float_to_sortable_int",
    "sortable_int_to_float", "dispatch", "METHODS",
]

# METHODS is re-exported from repro.core.scan — one source for the contract.

# Single dispatch table for the §5 operators: {op: {method: impl}}.  "matmul",
# "vector" and "blocked" share the unfused JAX implementations (the scan method
# differs underneath); "kernel" entries are the fused Pallas launches, imported
# lazily so importing repro.core never drags in pallas.
_DISPATCH: Dict[str, Dict[str, Callable]] = {}


def _register(op: str, *methods: str):
    """Register the decorated function as ``op``'s impl for ``methods``."""
    def deco(fn):
        """Add ``fn`` to the dispatch table and return it unchanged."""
        table = _DISPATCH.setdefault(op, {})
        for m in methods:
            table[m] = fn
        return fn
    return deco


def dispatch(op: str, method: str) -> Callable:
    """Look up the implementation of ``op`` for ``method``.

    Args:
        op: Operator name, e.g. ``"split"``, ``"radix_passes"``,
            ``"top_p_tail"``.
        method: One of ``METHODS``.

    Returns:
        The registered implementation callable.

    Raises:
        ValueError: If ``method`` is not in ``METHODS`` or ``op`` has no
            implementation for it.

    Example:
        >>> dispatch("split", "vector").__name__
        '_split_unfused'
    """
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
    try:
        return _DISPATCH[op][method]
    except KeyError:
        raise ValueError(f"operator {op!r} has no {method!r} implementation") from None


# ---------------------------------------------------------------------------
# shared unfused plumbing: dtype-stable gather + batched destination scatter
# ---------------------------------------------------------------------------


def _take_along_last(x, idx):
    """Gather ``x`` along the last axis with indices widened to int32.

    The single gather helper shared by the unfused operator paths (the sorted
    keys of a packed radix sort in :func:`_radix_passes_unfused`) and the fused
    wrappers (the ordering gathers in :func:`top_p_sample`): indices are cast
    to int32 in exactly one place, so permutation composition is dtype-stable
    regardless of how the caller produced its index array.

    Args:
        x: Source array ``(..., n)`` (any dtype).
        idx: Integer indices, broadcast-compatible with ``x`` along the last
            axis.

    Returns:
        ``jnp.take_along_axis(x, idx.astype(int32), axis=-1)``.

    Example:
        >>> import jax.numpy as jnp
        >>> _take_along_last(jnp.asarray([10, 20, 30]),
        ...                  jnp.asarray([2, 0, 1], jnp.int8)).tolist()
        [30, 10, 20]
    """
    return jnp.take_along_axis(x, idx.astype(jnp.int32), axis=-1)


def _scatter_payloads(payloads, dest, *, with_indices):
    """Scatter each ``(..., n)`` payload to per-row destinations ``dest``.

    The one scatter used by every unfused split-family operator.  ``dest``
    must be a permutation of ``0..n-1`` per row.  With ``with_indices`` an
    extra int32 array is appended holding the original position of every
    output element (the identity iota is materialised once, not per caller).

    Args:
        payloads: Tuple of arrays shaped like ``dest``.
        dest: int32 destination offsets ``(..., n)``.
        with_indices: Append the original-index permutation to the result.

    Returns:
        Tuple of scattered payloads (same order), plus the permutation last
        when ``with_indices``.
    """
    n = dest.shape[-1]
    iota = jnp.arange(n, dtype=jnp.int32)

    def scatter_1d(dest1, *rows):
        """Scatter one row of every payload (and optionally the iota)."""
        outs = tuple(jnp.zeros_like(r).at[dest1].set(r) for r in rows)
        if with_indices:
            outs += (jnp.zeros((n,), jnp.int32).at[dest1].set(iota),)
        return outs

    batch = dest.shape[:-1]
    if batch:
        flat = [p.reshape(-1, n) for p in payloads]
        outs = jax.vmap(scatter_1d)(dest.reshape(-1, n), *flat)
        return tuple(o.reshape(*batch, n) for o in outs)
    return scatter_1d(dest, *payloads)


# ---------------------------------------------------------------------------
# split / compress
# ---------------------------------------------------------------------------


@_register("split", "matmul", "vector", "blocked")
def _split_unfused(x, flags, *, method, tile_s, interpret):
    """SplitInd via ``scan`` + XLA scatter (the scanned mask lives in HBM)."""
    n = x.shape[-1]
    f8 = flags.astype(jnp.int8)
    ex = scan(f8, axis=-1, exclusive=True, method=method, tile_s=tile_s)
    fl = flags.astype(jnp.int32)
    n_true = ex[..., -1] + fl[..., -1]
    iota = jnp.arange(n, dtype=jnp.int32)
    pos_false = iota - ex                                        # falses before i
    dest = jnp.where(flags, ex, n_true[..., None] + pos_false)
    z, ind = _scatter_payloads((x,), dest, with_indices=True)
    return z, ind, n_true


@_register("split", "kernel")
def _split_fused(x, flags, *, method, tile_s, interpret):
    """SplitInd as one fused Pallas launch per batch row."""
    from repro.kernels import ops as _kops
    return _kops.split_kernel(x, flags, s=tile_s, interpret=interpret)


def split(x: jax.Array, flags: jax.Array, *, method: str = "auto",
          return_indices: bool = True, tile_s: int = 128,
          interpret: Optional[bool] = None):
    """Stable partition (the paper's SplitInd): flagged elements first, order kept.

    The destination offsets come from an exclusive scan of the int8 mask — the
    paper's int8 -> int32 cube-unit mask-scan specialization — so offsets are
    exact integers for every ``method``.

    Args:
        x: Payload array ``(..., n)``, any dtype.
        flags: Boolean array ``(..., n)``; true elements move to the front.
        method: One of ``METHODS`` (``"kernel"`` fuses scan + scatter into one
            launch; ``"blocked"`` runs the mask scan on the §4 pipeline).
        return_indices: If false, omit the permutation from the result.
        tile_s: Tile side ``s`` for the matmul scans.
        interpret: Force Pallas interpret mode (defaults to auto: interpret on
            CPU backends).

    Returns:
        ``(z, indices, n_true)`` — or ``(z, n_true)`` if ``return_indices`` is
        false.  ``z`` is the partitioned payload, ``indices[j]`` the original
        position of ``z[j]`` (int32), ``n_true`` the per-row count of flagged
        elements (int32).

    Example:
        >>> import jax.numpy as jnp
        >>> z, ind, k = split(jnp.asarray([10, 20, 30, 40]),
        ...                   jnp.asarray([False, True, False, True]))
        >>> z.tolist(), ind.tolist(), int(k)
        ([20, 40, 10, 30], [1, 3, 0, 2], 2)
    """
    guards.validate_same_shape(x.shape, jnp.shape(flags), op="split")
    method = maybe_resolve(method, "split", x.shape[-1], x.dtype)
    z, ind, n_true = dispatch("split", method)(
        x, flags, method=method, tile_s=tile_s, interpret=interpret)
    if return_indices:
        return z, ind, n_true
    return z, n_true


def compress(x: jax.Array, mask: jax.Array, *, method: str = "auto",
             fill_value=0, tile_s: int = 128,
             interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Masked select: gather elements where ``mask`` is true, packed left.

    Args:
        x: Payload array ``(..., n)``.
        mask: Boolean array ``(..., n)``.
        method: One of ``METHODS``; forwarded to :func:`split`.
        fill_value: Value for the ``values[count:]`` tail.
        tile_s: Tile side ``s`` for the matmul scans.
        interpret: Force Pallas interpret mode.

    Returns:
        ``(values, count)`` with ``values`` the same shape as ``x`` and
        ``values[..., count:]`` filled with ``fill_value``.

    Example:
        >>> import jax.numpy as jnp
        >>> v, k = compress(jnp.asarray([1, 2, 3, 4]),
        ...                 jnp.asarray([True, False, True, False]))
        >>> v.tolist(), int(k)
        ([1, 3, 0, 0], 2)
    """
    method = maybe_resolve(method, "compress", x.shape[-1], x.dtype)
    z, _, n_true = split(x, mask, method=method, tile_s=tile_s,
                         interpret=interpret)
    iota = jnp.arange(x.shape[-1], dtype=jnp.int32)
    keep = iota < n_true[..., None]
    z = jnp.where(keep, z, jnp.asarray(fill_value, z.dtype))
    return z, n_true


# ---------------------------------------------------------------------------
# multi_split (radix-2^k generalization of SplitInd)
# ---------------------------------------------------------------------------


def _multi_split_dest(digits, num_buckets, *, method, tile_s):
    """Destination offsets for a stable ``num_buckets``-way split.

    One *batched* exclusive :func:`~repro.core.scan.scan` call over the
    ``(..., R, n)`` int8 one-hot digit matrix yields all ``R`` per-bucket mask
    scans at once (the multi-way analogue of the paper's binary SplitInd mask
    scan); per-bucket bases are the tiny ``R``-wide exclusive prefix of the
    bucket counts.  Each element's offset is its bucket base plus its row of
    the mask scan, selected by the same one-hot matrix (no gather).

    Args:
        digits: Integer bucket ids ``(..., n)`` in ``[0, num_buckets)``.
        num_buckets: Number of buckets ``R``.
        method: Scan method for the mask scans, one of ``METHODS``.
        tile_s: Tile side ``s`` for the matmul scans.

    Returns:
        ``(dest, counts)`` — int32 destination offsets ``(..., n)`` and int32
        per-bucket counts ``(..., num_buckets)``.
    """
    d32 = digits.astype(jnp.int32)
    buckets = jnp.arange(num_buckets, dtype=jnp.int32)
    hit = d32[..., None, :] == buckets[:, None]                    # (..., R, n)
    oh = hit.astype(jnp.int8)
    ex = scan(oh, axis=-1, exclusive=True, method=method, tile_s=tile_s)
    counts = ex[..., -1] + oh[..., -1].astype(jnp.int32)           # (..., R)
    base = jnp.cumsum(counts, axis=-1) - counts                    # R-wide scan
    # read each element's own bucket row through the one-hot digits: an exact
    # R-way masked sum that fuses with the scan, where a gather along R and one
    # of ``base`` would each move every element
    dest = jnp.sum(jnp.where(hit, ex + base[..., None], 0), axis=-2)
    return dest, counts


@_register("multi_split", "matmul", "vector", "blocked")
def _multi_split_unfused(x, digits, num_buckets, *, method, tile_s, interpret):
    """Multi-way SplitInd via one batched ``scan`` + XLA scatter."""
    dest, counts = _multi_split_dest(digits, num_buckets, method=method,
                                     tile_s=tile_s)
    z, ind = _scatter_payloads((x,), dest, with_indices=True)
    return z, ind, counts


@_register("multi_split", "kernel")
def _multi_split_fused(x, digits, num_buckets, *, method, tile_s, interpret):
    """Multi-way SplitInd as one fused Pallas launch per batch row."""
    from repro.kernels import ops as _kops
    return _kops.multi_split_kernel(x, digits, num_buckets=num_buckets,
                                    s=tile_s, interpret=interpret)


def multi_split(x: jax.Array, digits: jax.Array, num_buckets: int, *,
                method: str = "auto", return_indices: bool = True,
                tile_s: int = 128, interpret: Optional[bool] = None):
    """Stable ``num_buckets``-way partition — radix-2^k SplitInd.

    Generalizes the paper's binary SplitInd: elements are grouped by their
    integer ``digits`` bucket (ascending, original order kept within each
    bucket), with all ``R`` bucket mask scans running as one batched int8 ->
    int32 matmul scan — the TCU-style multi-way split of Dakkak et al. that
    lets one radix pass retire ``log2(R)`` bits.  Offsets are exact integers
    for every ``method``, so all methods are bit-identical.

    Args:
        x: Payload array ``(..., n)``, any dtype.
        digits: Integer array ``(..., n)`` of bucket ids in
            ``[0, num_buckets)`` (values outside the range are undefined
            behaviour).
        num_buckets: Number of buckets ``R >= 1``.
        method: One of ``METHODS`` (``"kernel"`` fuses the one-hot build, the
            batched mask scan, offsets and the scatter into one launch).
        return_indices: If false, omit the permutation from the result.
        tile_s: Tile side ``s`` for the matmul scans.
        interpret: Force Pallas interpret mode (defaults to auto: interpret on
            CPU backends).

    Returns:
        ``(z, indices, counts)`` — or ``(z, counts)`` if ``return_indices``
        is false.  ``z`` is the bucket-grouped payload, ``indices[j]`` the
        original position of ``z[j]`` (int32), ``counts`` the per-bucket
        element counts, shape ``(..., num_buckets)`` (int32).

    Example:
        >>> import jax.numpy as jnp
        >>> z, ind, c = multi_split(jnp.asarray([50, 10, 70, 30]),
        ...                         jnp.asarray([2, 0, 2, 1]), 4)
        >>> z.tolist(), ind.tolist(), c.tolist()
        ([10, 30, 50, 70], [1, 3, 0, 2], [1, 1, 2, 0])
    """
    if num_buckets < 1:
        raise ValueError(f"num_buckets must be >= 1, got {num_buckets}")
    guards.validate_same_shape(x.shape, jnp.shape(digits), op="multi_split",
                               b_name="digits")
    method = maybe_resolve(method, "multi_split", x.shape[-1], x.dtype)
    z, ind, counts = dispatch("multi_split", method)(
        x, digits, num_buckets, method=method, tile_s=tile_s,
        interpret=interpret)
    if return_indices:
        return z, ind, counts
    return z, counts


# ---------------------------------------------------------------------------
# Radix sort (paper §5, LSB; fp16/fp32 via order-preserving bit encodings)
# ---------------------------------------------------------------------------


def float_to_sortable_int(x: jax.Array) -> jax.Array:
    """Order-preserving float -> unsigned encoding (paper's pre-processing phase).

    Positive floats: flip the MSB.  Negative floats: flip all bits.  The
    resulting unsigned integers compare in the same order as the floats.

    Args:
        x: Float array (fp16, bf16 or fp32).

    Returns:
        ``uint16`` (for 16-bit floats) or ``uint32`` (for fp32) keys.

    Raises:
        TypeError: For unsupported float dtypes.

    Example:
        >>> import jax.numpy as jnp
        >>> u = float_to_sortable_int(jnp.asarray([-1.0, 0.0, 1.0], jnp.float32))
        >>> bool(u[0] < u[1] < u[2])
        True
    """
    if x.dtype == jnp.float16:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16)
        sign = (u >> 15).astype(jnp.bool_)
        return jnp.where(sign, ~u, u | jnp.uint16(0x8000))
    if x.dtype == jnp.float32:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        sign = (u >> 31).astype(jnp.bool_)
        return jnp.where(sign, ~u, u | jnp.uint32(0x80000000))
    if x.dtype == jnp.bfloat16:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16)
        sign = (u >> 15).astype(jnp.bool_)
        return jnp.where(sign, ~u, u | jnp.uint16(0x8000))
    raise TypeError(f"unsupported float dtype {x.dtype}")


def sortable_int_to_float(u: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`float_to_sortable_int` (paper's post-processing phase).

    Args:
        u: Unsigned keys produced by :func:`float_to_sortable_int`.
        dtype: The original float dtype to decode back to.

    Returns:
        The decoded float array in ``dtype``.

    Raises:
        TypeError: For unsupported float dtypes.

    Example:
        >>> import jax.numpy as jnp
        >>> u = float_to_sortable_int(jnp.asarray([-1.0, 0.5], jnp.float32))
        >>> sortable_int_to_float(u, jnp.float32).tolist()
        [-1.0, 0.5]
    """
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.dtype(jnp.float16), jnp.dtype(jnp.bfloat16)):
        msb = jnp.uint16(0x8000)
        pos = (u & msb).astype(jnp.bool_)
        dec = jnp.where(pos, u & ~msb, ~u)
        return jax.lax.bitcast_convert_type(dec, dtype)
    if dtype == jnp.dtype(jnp.float32):
        msb = jnp.uint32(0x80000000)
        pos = (u & msb).astype(jnp.bool_)
        dec = jnp.where(pos, u & ~msb, ~u)
        return jax.lax.bitcast_convert_type(dec, dtype)
    raise TypeError(f"unsupported float dtype {dtype}")


def _encode_for_sort(x: jax.Array) -> Tuple[jax.Array, int, Callable]:
    """Map ``x`` to unsigned keys; returns ``(keys, n_bits, decode_fn)``."""
    dt = x.dtype
    if jnp.issubdtype(dt, jnp.floating):
        enc = float_to_sortable_int(x)
        bits = enc.dtype.itemsize * 8
        return enc, bits, lambda u: sortable_int_to_float(u, dt)
    if dt in (jnp.dtype(jnp.int16), jnp.dtype(jnp.int32)):
        udt = jnp.uint16 if dt == jnp.dtype(jnp.int16) else jnp.uint32
        bias = jnp.asarray(1 << (jnp.dtype(udt).itemsize * 8 - 1), udt)
        enc = jax.lax.bitcast_convert_type(x, udt) ^ bias
        bits = jnp.dtype(udt).itemsize * 8
        return enc, bits, lambda u: jax.lax.bitcast_convert_type(u ^ bias, dt)
    if dt in (jnp.dtype(jnp.uint16), jnp.dtype(jnp.uint32), jnp.dtype(jnp.uint8),
              jnp.dtype(jnp.int8)):
        if dt == jnp.dtype(jnp.int8):
            enc = (x.astype(jnp.int32) + 128).astype(jnp.uint8)
            return enc, 8, lambda u: (u.astype(jnp.int32) - 128).astype(jnp.int8)
        bits = dt.itemsize * 8
        return x, bits, lambda u: u
    raise TypeError(f"radix sort: unsupported dtype {dt}")


@_register("radix_passes", "matmul", "vector", "blocked")
def _radix_passes_unfused(enc, bits, *, method, tile_s, interpret,
                          bits_per_pass=1):
    """``ceil(bits / k)`` stable multi-way splits of the ``bits``-bit keys.

    On a TPU every data-dependent gather or scatter over a whole row costs
    milliseconds, however little it moves, so each pass moves its payload
    once.  Where ``bits - k + ceil(log2 n) <= 32`` (``k`` the first pass's
    width; 16-bit keys with ``k = 4`` up to ``n = 2^20``) the payload is one
    ``uint32`` per element: the key's bits above its first digit, shifted
    above the element's original index.  The first pass reads its digit from
    ``enc``, every later one from the payload, and each moves the payload
    once, by sorting each row on its destinations; after the last pass the
    low bits are the permutation, and the sorted keys are one gather of
    ``enc`` through it (dead code where the caller drops them, as
    ``top_p_sample`` does).
    Wider keys or longer rows (fp32/int32 keys, in general) move the keys and
    the permutation as two payloads through each pass.  Both forms give the
    same ``(keys, permutation)`` bit for bit.
    """
    n = enc.shape[-1]
    idx_bits = (n - 1).bit_length()
    k0 = min(bits_per_pass, bits)
    if bits - k0 + idx_bits > 32:
        perm = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), enc.shape)
        work = enc
        for shift in range(0, bits, bits_per_pass):
            k = min(bits_per_pass, bits - shift)
            mask = jnp.asarray((1 << k) - 1, work.dtype)
            digits = ((work >> shift) & mask).astype(jnp.int32)
            dest, _ = _multi_split_dest(digits, 1 << k, method=method,
                                        tile_s=tile_s)
            work, perm = _scatter_payloads((work, perm), dest,
                                           with_indices=False)
        return work, perm
    iota = jnp.arange(n, dtype=jnp.uint32)
    packed = ((enc >> k0).astype(jnp.uint32) << idx_bits) | iota
    for shift in range(0, bits, bits_per_pass):
        k = min(bits_per_pass, bits - shift)
        if shift == 0:
            digits = enc & jnp.asarray((1 << k) - 1, enc.dtype)
        else:
            digits = (packed >> (idx_bits + shift - k0)) & ((1 << k) - 1)
        dest, _ = _multi_split_dest(digits, 1 << k, method=method,
                                    tile_s=tile_s)
        # out[dest[i]] = packed[i] as a sort of each row keyed by ``dest``
        # (a permutation, so stability does not matter): on a TPU v5e it
        # moves 8 rows of 152064 in 1.2 ms, a unique-index scatter in 5.6 ms
        _, packed = jax.lax.sort((dest, packed), dimension=-1, num_keys=1,
                                 is_stable=False)
    perm = (packed & ((1 << idx_bits) - 1)).astype(jnp.int32)
    return _take_along_last(enc, perm), perm


@_register("radix_passes", "kernel")
def _radix_passes_fused(enc, bits, *, method, tile_s, interpret,
                        bits_per_pass=1):
    """All radix passes as fused Pallas launches, ``bits_per_pass`` bits each."""
    from repro.kernels import ops as _kops
    return _kops.radix_sort_enc_kernel(enc, bits=bits,
                                       bits_per_pass=bits_per_pass, s=tile_s,
                                       interpret=interpret)


def radix_sort(x: jax.Array, *, descending: bool = False, method: str = "auto",
               return_indices: bool = True, tile_s: int = 128,
               bits_per_pass: int = 4, interpret: Optional[bool] = None):
    """Stable LSB radix sort built on scan-based multi-way splits (paper §5).

    Each pass is a stable ``2^bits_per_pass``-way :func:`multi_split` on a
    ``bits_per_pass``-bit digit, so the key sorts in ``ceil(bits /
    bits_per_pass)`` passes — 8 for fp32 and 4 for bf16/fp16 at the default
    ``bits_per_pass=4``, vs. 32/16 binary splits in the paper's formulation —
    a ``bits_per_pass``-fold cut in HBM round-trips of the payload.  On a TPU
    each data-dependent movement of a row costs far more than the mask scans,
    so where the key bits left after the first pass and the index fit one
    ``uint32`` (``bits - bits_per_pass + ceil(log2 n) <= 32``: 16-bit keys at
    the default up to ``n = 2^20``) the unfused methods move that one word per
    element and pass; wider keys and longer rows move the keys and the
    permutation apart (see :func:`_radix_passes_unfused`).
    ``method="kernel"`` chains digit extraction, the batched matmul mask scans
    and the permutation inside one fused ``radix_pass_multibit`` launch per
    digit.  Every (method, bits_per_pass) combination is bit-identical: bucket
    offsets are exact int8 -> int32 mask scans.

    Args:
        x: Keys ``(..., n)``; floats (fp16/bf16/fp32) are sorted via the
            order-preserving bit encoding, ints via a sign-bias encoding.
        descending: Sort high-to-low (stability is preserved by complementing
            the encoded keys).
        method: One of ``METHODS``.
        return_indices: If false, return only the sorted values.
        tile_s: Tile side ``s`` for the mask scans.
        bits_per_pass: Bits retired per radix pass (``1..8``); ``1`` is the
            paper's binary SplitInd formulation, ``4`` the radix-16 default.
            A ragged final digit just uses the remaining bits.
        interpret: Force Pallas interpret mode.

    Returns:
        ``(values, permutation)`` — or just ``values`` if ``return_indices``
        is false.  ``permutation`` is int32 with ``values ==
        take_along_axis(x, permutation, -1)``.

    Raises:
        ValueError: If ``bits_per_pass`` is outside ``[1, 8]``.

    Example:
        >>> import jax.numpy as jnp
        >>> v, idx = radix_sort(jnp.asarray([3, -1, 2, -5], jnp.int8))
        >>> v.tolist(), idx.tolist()
        ([-5, -1, 2, 3], [3, 1, 2, 0])

        ``bits_per_pass`` trades passes for bucket width without changing the
        result — one radix-256 pass equals eight binary passes bit-for-bit:

        >>> x = jnp.asarray([7, 200, 7, 13], jnp.uint8)
        >>> v8, i8 = radix_sort(x, bits_per_pass=8)   # 1 pass of 256 buckets
        >>> v1, i1 = radix_sort(x, bits_per_pass=1)   # 8 binary passes
        >>> v8.tolist() == v1.tolist() == [7, 7, 13, 200]
        True
        >>> i8.tolist() == i1.tolist() == [0, 2, 3, 1]   # stable: first 7 first
        True
    """
    bits_per_pass = guards.validate_bits_per_pass(bits_per_pass,
                                                  op="radix_sort")
    method = maybe_resolve(method, "radix_sort", x.shape[-1], x.dtype)
    enc, bits, decode = _encode_for_sort(x)
    if descending:
        enc = ~enc  # complement keeps stability while reversing the order
    work, perm = dispatch("radix_passes", method)(
        enc, bits, method=method, tile_s=tile_s, interpret=interpret,
        bits_per_pass=min(bits_per_pass, bits))
    if descending:
        work = ~work
    values = decode(work)
    if return_indices:
        return values, perm
    return values


def sort(x: jax.Array, *, descending: bool = False, method: str = "auto",
         tile_s: int = 128, bits_per_pass: int = 4,
         interpret: Optional[bool] = None):
    """PyTorch-style ``sort`` returning ``(values, indices)``; radix under the hood.

    Args:
        x: Keys ``(..., n)`` (see :func:`radix_sort` for supported dtypes).
        descending: Sort high-to-low.
        method: One of ``METHODS``.
        tile_s: Tile side ``s`` for the mask scans.
        bits_per_pass: Bits retired per radix pass (see :func:`radix_sort`).
        interpret: Force Pallas interpret mode.

    Returns:
        ``(values, indices)`` as in :func:`radix_sort`.

    Example:
        >>> import jax.numpy as jnp
        >>> v, i = sort(jnp.asarray([2, 9, 4], jnp.int8), descending=True)
        >>> v.tolist(), i.tolist()
        ([9, 4, 2], [1, 2, 0])
    """
    return radix_sort(x, descending=descending, method=method,
                      return_indices=True, tile_s=tile_s,
                      bits_per_pass=bits_per_pass, interpret=interpret)


# ---------------------------------------------------------------------------
# top-k / top-p / weighted sampling
# ---------------------------------------------------------------------------


def topk(x: jax.Array, k: int, *, method: str = "auto", tile_s: int = 128,
         bits_per_pass: int = 4, interpret: Optional[bool] = None):
    """Top-k via descending radix sort (paper §5 implements it over SplitInd).

    Args:
        x: Keys ``(..., n)``.
        k: Number of leading elements to keep.
        method: One of ``METHODS``.
        tile_s: Tile side ``s`` for the mask scans.
        bits_per_pass: Bits retired per radix pass (see :func:`radix_sort`).
        interpret: Force Pallas interpret mode.

    Returns:
        ``(values, indices)`` of the ``k`` largest elements, sorted descending.

    Example:
        >>> import jax.numpy as jnp
        >>> v, i = topk(jnp.asarray([1, 9, 3, 7], jnp.int8), 2)
        >>> v.tolist(), i.tolist()
        ([9, 7], [1, 3])
    """
    values, idx = radix_sort(x, descending=True, method=method, tile_s=tile_s,
                             bits_per_pass=bits_per_pass, interpret=interpret)
    return values[..., :k], idx[..., :k]


def weighted_sample(w: jax.Array, key: jax.Array, *, method: str = "auto",
                    cdf: Optional[jax.Array] = None, tile_s: int = 128,
                    u: Optional[jax.Array] = None,
                    nonfinite: str = "propagate") -> jax.Array:
    """Inverse-transform sampling on the scanned CDF (paper §5).

    The paper invokes SplitInd with predicate ``scan(w) > θ·Σw`` and reads the
    last output index; counting ``scan(w) <= θ`` is the same index computed
    with the same scan, without the extra data movement.

    Args:
        w: Non-negative weights ``(..., n)`` (need not be normalized).
        key: JAX PRNG key (unused when ``u`` is given).
        method: Scan method for the CDF, one of ``METHODS``.
        cdf: Optional precomputed inclusive scan of ``w`` (skips the scan).
        tile_s: Tile side ``s`` for the matmul scans.
        u: Optional pre-drawn uniforms of shape ``w.shape[:-1] + (1,)``
            overriding the ``key`` draw — deterministic replay and the
            segmented sampler's per-segment parity tests use this.
        nonfinite: Non-finite weight policy (:mod:`repro.core.guards`,
            dispatch rule 10; context > ``REPRO_NONFINITE`` env > argument).
            ``"propagate"`` (default) keeps IEEE semantics; ``"raise"``
            rejects non-finite weights; ``"sanitize"`` zeroes non-finite
            weights and maps degenerate rows (total mass not finite and
            positive) to the deterministic greedy index (argmax of the
            sanitized weights, ties to the first).  Under ``REPRO_CHECKS=1``
            a checkified assertion additionally verifies the CDF is finite
            before the inverse-transform step.

    Returns:
        Sampled indices, shape ``w.shape[:-1]``, int32, in ``[0, n)``.

    Example:
        >>> import jax, jax.numpy as jnp
        >>> int(weighted_sample(jnp.asarray([0.0, 0.0, 1.0]), jax.random.PRNGKey(0)))
        2
        >>> int(weighted_sample(jnp.asarray([1.0, 1.0]), None,
        ...                     u=jnp.asarray([0.75])))
        1
        >>> int(weighted_sample(jnp.asarray([0.2, jnp.nan, 0.1]), None,
        ...                     u=jnp.asarray([0.99]), nonfinite="sanitize"))
        2
    """
    method = maybe_resolve(method, "weighted_sample", w.shape[-1], w.dtype)
    nonfinite = guards.resolve_nonfinite(nonfinite)
    w_eff = w
    if nonfinite == "raise":
        w_eff = guards.apply_nonfinite(w, nonfinite, op="weighted_sample")
    elif nonfinite == "sanitize":
        w_eff = guards.apply_nonfinite(w, nonfinite, op="weighted_sample")
        if w_eff is not w:
            cdf = None  # a caller-supplied CDF no longer matches
    if cdf is None:
        cdf = scan(w_eff, axis=-1, method=method, tile_s=tile_s)
    if jnp.issubdtype(jnp.result_type(cdf), jnp.floating):
        final_cdf = cdf
        guards.guard_check(lambda: jnp.all(jnp.isfinite(final_cdf)),
                           "weighted_sample: non-finite CDF before the "
                           "inverse-transform sample")
    total = cdf[..., -1:]
    if u is None:
        u = jax.random.uniform(key, w.shape[:-1] + (1,), dtype=cdf.dtype)
    theta = u.astype(cdf.dtype) * total
    idx = jnp.sum((cdf < theta).astype(jnp.int32), axis=-1)
    idx = jnp.clip(idx, 0, w.shape[-1] - 1)
    if nonfinite == "sanitize" and jnp.issubdtype(w_eff.dtype, jnp.floating):
        bad = ~(jnp.isfinite(total[..., 0]) & (total[..., 0] > 0))
        greedy = jnp.argmax(w_eff, axis=-1).astype(idx.dtype)
        idx = jnp.where(bad, greedy, idx)
    return idx


def _reject_poisoned_logits(logits: jax.Array) -> jax.Array:
    """``nonfinite="raise"`` for samplers: NaN/+inf and all-``-inf`` rows fail.

    ``-inf`` entries are legitimate vocabulary masks, so plain
    :func:`repro.core.guards.apply_nonfinite` is too strict here: a row is
    poisoned when it carries NaN or ``+inf``, or masks *every* token.
    Concrete logits raise :class:`repro.core.guards.NonFiniteError` eagerly;
    traced logits stage a checkified assertion (fires under
    ``guards.checked`` / ``REPRO_CHECKS=1`` harnesses).
    """
    msg = ("top_p_sample: poisoned logits under nonfinite='raise' (NaN/+inf "
           "entries or a fully masked row)")
    if guards.is_concrete(logits):
        import numpy as np
        arr = np.asarray(logits, dtype=np.float32)
        ok = (~np.isnan(arr).any() and not np.isposinf(arr).any()
              and bool(np.isfinite(arr).any(axis=-1).all()))
        if not ok:
            raise guards.NonFiniteError(msg)
    else:
        from jax.experimental import checkify
        checkify.debug_check(
            ~jnp.any(jnp.isnan(logits)) & ~jnp.any(jnp.isposinf(logits))
            & jnp.all(jnp.any(jnp.isfinite(logits), axis=-1)), msg)
    return logits


@_register("top_p_tail", "matmul", "vector", "blocked")
def _top_p_tail_unfused(sorted_p, key, *, p, method, tile_s, interpret, u=None):
    """Cumsum -> cutoff -> masked renormalised CDF -> inverse-transform sample."""
    cum = scan(sorted_p, axis=-1, method=method, tile_s=tile_s)
    cut = (cum - sorted_p) > p                    # llama3's sample_top_p formula
    masked = jnp.where(cut, 0.0, sorted_p)
    return weighted_sample(masked, key, method=method, tile_s=tile_s, u=u)


@_register("top_p_tail", "kernel")
def _top_p_tail_fused(sorted_p, key, *, p, method, tile_s, interpret, u=None):
    """The whole nucleus-sampling tail as one Pallas launch."""
    from repro.kernels import ops as _kops
    if u is None:
        u = jax.random.uniform(key, sorted_p.shape[:-1] + (1,),
                               dtype=jnp.float32)
    return _kops.topp_mask_sample_kernel(sorted_p, u.astype(jnp.float32), p=p,
                                         s=tile_s, interpret=interpret)


def top_p_sample(logits: jax.Array, key: jax.Array, p: float = 0.9,
                 temperature: float = 1.0, *, method: str = "auto",
                 sort_method: str = "radix", tile_s: int = 128,
                 bits_per_pass: int = 4, u: Optional[jax.Array] = None,
                 interpret: Optional[bool] = None,
                 nonfinite: str = "propagate") -> jax.Array:
    """Nucleus sampling exactly as in the paper's Llama3 case study (§5, §6.5).

    Sort (radix, scan-based) -> prefix-sum of sorted probabilities -> mask
    tokens whose *preceding* cumulative mass exceeds ``p`` -> renormalise ->
    weighted sample.  With fp16-style 16-bit keys this is the paper's "17 scans
    per batch row" operator; the default ``bits_per_pass=4`` sorts those keys
    in 4 radix-16 passes instead of 16 binary splits.  ``method="kernel"``
    runs the sort as fused radix passes and the whole sampling tail as one
    Pallas launch.

    Args:
        logits: Unnormalised scores ``(..., vocab)``; softmax is applied in
            fp32.
        key: JAX PRNG key.
        p: Nucleus mass threshold in ``(0, 1]``.
        temperature: Logit divisor applied before the softmax.
        method: One of ``METHODS`` for the sort and sampling scans.
        sort_method: ``"radix"`` (scan-based, on bf16-rounded keys = 16 sort
            bits as in the paper's fp16 evaluation) or ``"xla"`` (baseline
            ``argsort``).
        tile_s: Tile side ``s`` for the mask scans.
        bits_per_pass: Bits retired per radix pass (see :func:`radix_sort`);
            ignored for ``sort_method="xla"``.
        u: Optional pre-drawn uniforms of shape ``logits.shape[:-1] + (1,)``
            overriding the ``key`` draw in the sampling tail (deterministic
            replay; the segmented sampler's parity tests use this).
        interpret: Force Pallas interpret mode.
        nonfinite: Non-finite logit policy (:mod:`repro.core.guards`,
            dispatch rule 10; context > ``REPRO_NONFINITE`` env > argument).
            ``"propagate"`` (default) keeps IEEE semantics — an all-``-inf``
            or NaN-poisoned row yields an undefined sample, exactly as
            before; ``"raise"`` rejects non-finite *upward* logits (``-inf``
            mask entries are legitimate and always allowed); ``"sanitize"``
            maps rows whose softmax degenerates (all masked / all ``-inf`` /
            any NaN) to the deterministic greedy token — argmax over the
            logits with NaNs treated as ``-inf``, ties to the lowest id.

    Returns:
        Sampled token ids, shape ``logits.shape[:-1]``, int32.

    Raises:
        ValueError: If ``p`` (concrete) is outside ``[0, 1]`` or
            ``temperature`` (concrete) is negative or NaN.

    Note:
        ``temperature == 0`` is the documented greedy limit: the call returns
        the deterministic argmax (NaN logits treated as ``-inf``) for every
        ``method`` without tracing the sampling pipeline.

    Example:
        >>> import jax, jax.numpy as jnp
        >>> logits = jnp.asarray([[0.0, 20.0, 0.0, 0.0]])
        >>> int(top_p_sample(logits, jax.random.PRNGKey(1), p=0.9)[0])
        1
        >>> int(top_p_sample(logits, jax.random.PRNGKey(1), temperature=0.0)[0])
        1
    """
    guards.validate_probability(p, op="top_p_sample")
    guards.validate_temperature(temperature, op="top_p_sample")
    nonfinite = guards.resolve_nonfinite(nonfinite)
    if guards.is_concrete(temperature) and float(temperature) == 0.0:
        # the temperature -> 0 limit: all mass on the max logit
        greedy = jnp.where(jnp.isnan(logits), -jnp.inf, logits)
        return jnp.argmax(greedy, axis=-1).astype(jnp.int32)
    method = maybe_resolve(method, "top_p_sample", logits.shape[-1],
                           logits.dtype)
    if nonfinite == "raise":
        # -inf entries are legitimate vocabulary masks; reject NaN and +inf
        logits = _reject_poisoned_logits(logits)
    if temperature != 1.0:
        logits = logits / temperature
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if nonfinite == "sanitize":
        # degenerate rows (all masked / all--inf / NaN-poisoned) have a NaN
        # softmax; give them a one-hot at the deterministic greedy token so
        # the tail (and its staged finite-CDF check) sees a valid
        # distribution and samples the greedy fallback
        bad = ~jnp.all(jnp.isfinite(probs), axis=-1)
        greedy = jnp.argmax(jnp.where(jnp.isnan(logits), -jnp.inf, logits),
                            axis=-1)
        onehot = jax.nn.one_hot(greedy, probs.shape[-1], dtype=probs.dtype)
        probs = jnp.where(bad[..., None], onehot, probs)
    if sort_method == "radix":
        # Sort on bf16-rounded keys (16 bits, as in the paper's fp16
        # evaluation); ties/rounding only reorder within ~3-ulp probability bands.
        keys16 = probs.astype(jnp.bfloat16)
        _, order = radix_sort(keys16, descending=True, method=method,
                              tile_s=tile_s, bits_per_pass=bits_per_pass,
                              interpret=interpret)
    else:
        order = jnp.argsort(-probs, axis=-1)
    sorted_p = _take_along_last(probs, order)
    j = dispatch("top_p_tail", method)(
        sorted_p, key, p=p, method=method, tile_s=tile_s, interpret=interpret,
        u=u)
    tok = _take_along_last(order, j[..., None])[..., 0]
    if nonfinite == "sanitize":
        # belt-and-braces: the one-hot rewrite above makes the tail itself
        # deterministic for repaired rows, but pin the token regardless
        tok = jnp.where(bad, greedy.astype(tok.dtype), tok)
    return tok
