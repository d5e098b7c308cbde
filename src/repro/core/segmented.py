"""Segmented & ragged scan subsystem: packed-batch operators on the matmul scan.

Every operator in :mod:`repro.core.primitives` runs over one flat array; this
module lifts them to *packed variable-length batches* — the layout that MoE
group dispatch, continuous-batching decode and ragged data pipelines all
reduce to.  A packed batch is CSR-style: a ``values`` array of ``n`` elements
holding every segment back to back, plus int32 ``offsets`` of shape
``(num_segments + 1,)`` with ``offsets[0] == 0`` and ``offsets[-1] == n``
(empty segments are simply repeated offsets).  :class:`SegmentedBatch` bundles
the pair as a pytree.

The foundation is :func:`segment_scan` — a prefix sum whose carry resets at
segment boundaries — dispatched through the same ``method=`` table as
:func:`repro.core.scan.scan`:

* ``"matmul"`` / ``"vector"`` — the full unsegmented scan (matmul or cumsum)
  followed by subtracting the gathered scan value at each element's segment
  start.  Exact for the integer mask scans the operators are built from, and
  for integer-valued floats (the repo-wide float-parity contract).
* ``"kernel"`` — the fused sequential-grid segmented kernel
  (:mod:`repro.kernels.segscan_mm`): the segmented sum as the linear
  recurrence ``y = (1 - flag) * y_prev + x`` on the linrec tile kernel.
* ``"blocked"`` — the §4 three-phase pipeline with a *segmented* phase-2
  carry scan, so multi-block ragged inputs still read/write each element once.

On top of it ride the packed-batch operators: :func:`segment_cumsum`,
:func:`segment_sums`, :func:`segment_compress`, :func:`segment_sort`,
:func:`segment_topk`, :func:`segment_softmax` and
:func:`segment_top_p_sample`.  Parity contract (enforced by
``tests/test_segmented.py``): every segmented op is bit-identical to looping
the corresponding 1-D op over each segment slice, for every registered
method — offsets, permutations and counts are exact int8 -> int32 mask
scans, so the contract holds for any payload; float *sums* follow the same
exactly-representable rule as the unsegmented methods.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guards
from repro.core.autotune import maybe_resolve
from repro.core.linrec import linear_scan, linrec_accum_dtype_for
from repro.core.precision import resolve_precision
from repro.core.primitives import _encode_for_sort, _register, dispatch
from repro.core.scan import accum_dtype_for, scan

__all__ = [
    "SegmentedBatch", "boundary_flags", "segment_ids", "segment_scan",
    "segment_cumsum", "segment_sums", "segment_softmax", "segment_compress",
    "segment_sort", "segment_topk", "segment_top_p_sample",
    "segment_linear_scan",
]


# ---------------------------------------------------------------------------
# The packed container
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SegmentedBatch:
    """CSR-style packed batch: ``values`` back to back, ``offsets`` framing them.

    ``offsets`` is int32 of shape ``(num_segments + 1,)`` with
    ``offsets[0] == 0`` and ``offsets[-1] == values.shape[-1]``; segment ``i``
    is ``values[offsets[i]:offsets[i + 1]]``.  Empty segments are repeated
    offsets; the container is a registered pytree, so it passes through
    ``jax.jit`` / ``jax.vmap`` boundaries like any array.

    Example:
        >>> import jax.numpy as jnp
        >>> sb = SegmentedBatch.from_ragged([[1, 2, 3], [], [4, 5]])
        >>> sb.num_segments, sb.lengths.tolist()
        (3, [3, 0, 2])
        >>> [seg.tolist() for seg in sb.to_ragged()]
        [[1, 2, 3], [], [4, 5]]
    """

    values: jax.Array
    offsets: jax.Array

    def tree_flatten(self):
        """Flatten into ``(values, offsets)`` leaves (no static aux data)."""
        return (self.values, self.offsets), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from the ``(values, offsets)`` leaves."""
        return cls(*children)

    @property
    def num_segments(self) -> int:
        """Number of segments (static: ``offsets.shape[0] - 1``)."""
        return self.offsets.shape[0] - 1

    @property
    def lengths(self) -> jax.Array:
        """Per-segment lengths, int32 of shape ``(num_segments,)``."""
        return (self.offsets[1:] - self.offsets[:-1]).astype(jnp.int32)

    @classmethod
    def from_ragged(cls, segments: Sequence, dtype=None) -> "SegmentedBatch":
        """Pack a host-side list of per-segment arrays into one batch.

        Args:
            segments: Sequence of 1-D array-likes (may include empties).
            dtype: Optional dtype for the packed values.

        Returns:
            A :class:`SegmentedBatch` with ``offsets[0] == 0``.
        """
        arrs = [np.asarray(s).reshape(-1) for s in segments]
        ref = next((a for a in arrs if a.size), None)
        if ref is not None:  # keep empties from promoting the concat dtype
            arrs = [a.astype(ref.dtype) if a.size == 0 else a for a in arrs]
        lens = np.asarray([a.shape[0] for a in arrs], np.int32)
        offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        if ref is not None:
            values = np.concatenate(arrs)
        else:
            values = np.zeros((0,), np.int32)
        if dtype is not None:
            values = values.astype(dtype)
        return cls(jnp.asarray(values), jnp.asarray(offsets))

    def to_ragged(self) -> List[np.ndarray]:
        """Unpack to a host-side list of per-segment numpy arrays."""
        v = np.asarray(self.values)
        off = np.asarray(self.offsets)
        return [v[off[i]:off[i + 1]] for i in range(self.num_segments)]

    def to_dense(self, fill_value=0) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side conversion to a dense ``(num_segments, max_len)`` pair.

        Args:
            fill_value: Value for the ragged tails.

        Returns:
            ``(dense, mask)`` numpy arrays; ``mask`` is true on real elements.
        """
        segs = self.to_ragged()
        width = max((s.shape[0] for s in segs), default=0)
        dense = np.full((len(segs), width), fill_value,
                        dtype=np.asarray(self.values).dtype)
        mask = np.zeros((len(segs), width), bool)
        for i, s in enumerate(segs):
            dense[i, :s.shape[0]] = s
            mask[i, :s.shape[0]] = True
        return dense, mask


def _unwrap(values, offsets, *, op: str = "segmented"):
    """Accept either a :class:`SegmentedBatch` or a ``(values, offsets)`` pair.

    Offsets are validated on the way in (dispatch rule 10): static structure
    (rank, dtype) always, the full CSR contract eagerly when the offsets are
    concrete, and as a staged :func:`repro.core.guards.guard_check` assertion
    when they are traced — every packed-batch entry point shares this one
    choke point.
    """
    if isinstance(values, SegmentedBatch):
        values, offsets = values.values, values.offsets
    elif offsets is None:
        raise ValueError("offsets required when values is not a SegmentedBatch")
    else:
        offsets = jnp.asarray(offsets, jnp.int32)
    offsets = guards.validate_offsets(offsets, jnp.shape(values)[-1], op=op)
    return values, offsets


# ---------------------------------------------------------------------------
# Boundary structure (flags / ids / end gathers) — all scan-based
# ---------------------------------------------------------------------------


def boundary_flags(offsets: jax.Array, n: int) -> jax.Array:
    """Int8 flags marking segment starts: ``flags[i] = 1`` iff ``i`` starts one.

    Offsets equal to ``n`` (trailing empty segments) are dropped by the
    scatter; coinciding starts of empty segments collapse onto one flag.

    Args:
        offsets: ``(num_segments + 1,)`` int32 CSR offsets.
        n: Packed length ``offsets[-1]``.

    Returns:
        ``(n,)`` int8 array of {0, 1} boundary flags.

    Example:
        >>> import jax.numpy as jnp
        >>> boundary_flags(jnp.asarray([0, 2, 2, 5]), 5).tolist()
        [1, 0, 1, 0, 0]
    """
    return jnp.zeros((n,), jnp.int8).at[offsets[:-1]].set(1, mode="drop")


def segment_ids(offsets: jax.Array, n: int, *, method: str = "vector",
                tile_s: int = 128) -> jax.Array:
    """Segment id of every packed element, via a scan of the start counts.

    Scatter-adds one count per segment start (empty segments stack on the
    same index) and takes the inclusive prefix sum minus one — so even
    through empty segments each element maps to the segment that actually
    contains it.

    Args:
        offsets: ``(num_segments + 1,)`` int32 CSR offsets.
        n: Packed length ``offsets[-1]``.
        method: Scan method for the counting scan, one of ``METHODS``.
        tile_s: Tile side for the matmul scans.

    Returns:
        ``(n,)`` int32 ids in ``[0, num_segments)``.

    Example:
        >>> import jax.numpy as jnp
        >>> segment_ids(jnp.asarray([0, 2, 2, 5]), 5).tolist()
        [0, 0, 2, 2, 2]
    """
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    counts = jnp.zeros((n,), jnp.int32).at[offsets[:-1]].add(1, mode="drop")
    return scan(counts, method=method, tile_s=tile_s).astype(jnp.int32) - 1


def _segment_ends(per_element: jax.Array, offsets: jax.Array) -> jax.Array:
    """Gather a per-element array at each segment's last element (0 if empty).

    Used to read per-segment totals off an inclusive segmented scan.
    """
    n = per_element.shape[-1]
    num_segments = offsets.shape[0] - 1
    if n == 0:  # all segments empty: every total is zero
        return jnp.zeros(per_element.shape[:-1] + (num_segments,),
                         per_element.dtype)
    lens = offsets[1:] - offsets[:-1]
    ends = jnp.clip(offsets[1:] - 1, 0, n - 1)
    vals = jnp.take(per_element, ends, axis=-1)
    return jnp.where(lens > 0, vals, jnp.zeros((), per_element.dtype))


# ---------------------------------------------------------------------------
# segment_scan — the subsystem's foundation, method-dispatched
# ---------------------------------------------------------------------------


@_register("segment_scan", "matmul", "vector")
def _segment_scan_unfused(values, offsets, *, method, tile_s, block_tiles,
                          accum_dtype, precision="highest"):
    """Full unsegmented scan, then subtract the value at each segment start.

    ``seg[i] = scan(values)[i] - scan(values)[start(i) - 1]`` — the
    TCU-formulation correction step (Dakkak et al.); exact whenever the
    partial sums are exactly representable (all integer paths, integer-valued
    floats).
    """
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else accum_dtype_for(values.dtype)
    full = scan(values, axis=-1, method=method, tile_s=tile_s,
                block_tiles=block_tiles, accum_dtype=acc, precision=precision)
    n = values.shape[-1]
    ids = segment_ids(offsets, n)
    starts = jnp.take(offsets, ids)
    base = jnp.take(full, jnp.clip(starts - 1, 0, n - 1), axis=-1)
    return full - jnp.where(starts > 0, base, jnp.zeros((), acc))


@_register("segment_scan", "kernel")
def _segment_scan_fused(values, offsets, *, method, tile_s, block_tiles,
                        accum_dtype, precision="highest"):
    """Fused sequential-grid segmented kernel (one launch per batch row)."""
    from repro.kernels import ops as _kops
    flags = boundary_flags(offsets, values.shape[-1])
    return _kops.seg_scan_kernel(values, flags, s=tile_s,
                                 accum_dtype=accum_dtype, precision=precision)


@_register("segment_scan", "blocked")
def _segment_scan_blocked(values, offsets, *, method, tile_s, block_tiles,
                          accum_dtype, precision="highest"):
    """§4 blocked pipeline with the segmented phase-2 carry scan."""
    from repro.kernels import ops as _kops
    flags = boundary_flags(offsets, values.shape[-1])
    return _kops.seg_blocked_scan_kernel(values, flags, s=tile_s,
                                         block_tiles=block_tiles,
                                         accum_dtype=accum_dtype,
                                         precision=precision)


def segment_scan(values, offsets=None, *, exclusive: bool = False,
                 reverse: bool = False, method: str = "auto",
                 tile_s: int = 128, block_tiles: int = 8,
                 accum_dtype=None, precision: str = "highest",
                 nonfinite: str = "propagate") -> jax.Array:
    """Per-segment prefix sum of a packed batch — the carry resets at boundaries.

    The segmented analogue of :func:`repro.core.scan.scan`: same ``method=``
    dispatch, same accumulation-dtype rules (``int8 -> int32`` mask scans,
    ``bf16/f16 -> f32``), applied independently within every segment of the
    packed layout.  Leading batch dimensions share the same offsets (used by
    the one-hot mask scans of :func:`segment_sort` and MoE dispatch).

    Args:
        values: Packed array ``(..., n)`` — or a :class:`SegmentedBatch`
            (then ``offsets`` is taken from it).
        offsets: ``(num_segments + 1,)`` int32 CSR offsets framing the last
            axis; required unless ``values`` is a :class:`SegmentedBatch`.
        exclusive: Shift each segment's result right by one with a leading 0.
        reverse: Scan each segment from its end (per-segment suffix sums).
        method: One of ``METHODS`` (see module docstring for what runs).
        tile_s: Tile side ``s`` for the matmul scans.
        block_tiles: Tiles per block for ``method="blocked"``.
        accum_dtype: Accumulation dtype override.
        precision: Engine precision for the masked contractions —
            ``"highest"`` (default), ``"compensated"`` or ``"fast"``; see
            :mod:`repro.core.precision` (dispatch rule 9).  Integer mask
            scans stay exact under every precision.
        nonfinite: Non-finite input policy — ``"propagate"`` (default, IEEE
            semantics), ``"raise"`` or ``"sanitize"`` (non-finite elements
            become the additive identity 0); see
            :func:`repro.core.guards.resolve_nonfinite` (dispatch rule 10).

    Returns:
        The per-segment scanned array, same shape as ``values``, in the
        accumulation dtype.

    Raises:
        ValueError: If an explicit non-default ``precision`` is combined
            with an explicit ``method="vector"``, or the offsets break the
            CSR contract.
        repro.core.guards.NonFiniteError: Under ``nonfinite="raise"`` with
            a concrete non-finite payload.

    Example:
        >>> import jax.numpy as jnp
        >>> x = jnp.asarray([1, 1, 1, 1, 1], jnp.int32)
        >>> segment_scan(x, jnp.asarray([0, 2, 5])).tolist()
        [1, 2, 1, 2, 3]
        >>> segment_scan(x, jnp.asarray([0, 2, 5]), exclusive=True).tolist()
        [0, 1, 0, 1, 2]
    """
    values, offsets = _unwrap(values, offsets, op="segment_scan")
    values = guards.apply_nonfinite(
        values, guards.resolve_nonfinite(nonfinite), op="segment_scan")
    n = values.shape[-1]
    explicit_method = method != "auto"
    method = maybe_resolve(method, "segment_scan", n, values.dtype)
    precision = resolve_precision(precision, method=method,
                                  explicit_method=explicit_method)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else accum_dtype_for(values.dtype)
    if n == 0:
        return jnp.zeros(values.shape, acc)
    if reverse:
        rev_off = (n - offsets)[::-1]
        out = segment_scan(jnp.flip(values, axis=-1), rev_off,
                           exclusive=exclusive, method=method, tile_s=tile_s,
                           block_tiles=block_tiles, accum_dtype=accum_dtype,
                           precision=precision)
        return jnp.flip(out, axis=-1)
    out = dispatch("segment_scan", method)(
        values, offsets, method=method, tile_s=tile_s,
        block_tiles=block_tiles, accum_dtype=accum_dtype,
        precision=precision)
    if exclusive:
        pad = [(0, 0)] * (out.ndim - 1) + [(1, 0)]
        shifted = jnp.pad(out, pad)[..., :-1]
        out = jnp.where(boundary_flags(offsets, n) > 0,
                        jnp.zeros((), out.dtype), shifted)
    return out


def segment_cumsum(values, offsets=None, **kw) -> jax.Array:
    """Drop-in per-segment ``cumsum`` — alias of :func:`segment_scan`.

    Args:
        values: Packed array ``(..., n)`` or a :class:`SegmentedBatch`.
        offsets: CSR offsets (unless ``values`` is a batch).
        **kw: Forwarded to :func:`segment_scan` (``method=``, ``exclusive=``,
            …).

    Returns:
        Per-segment inclusive (or exclusive) prefix sums.

    Example:
        >>> import jax.numpy as jnp
        >>> segment_cumsum(jnp.asarray([3, 4, 5]), jnp.asarray([0, 1, 3])).tolist()
        [3, 4, 9]
    """
    return segment_scan(values, offsets, **kw)


def segment_linear_scan(a, b, offsets=None, *, exclusive: bool = False,
                        reverse: bool = False, method: str = "auto",
                        initial=0.0, tile_s: int = 128, block_tiles: int = 8,
                        accum_dtype=None, precision: str = "highest",
                        nonfinite: str = "propagate") -> jax.Array:
    """Per-segment linear recurrence ``y_t = a_t * y_{t-1} + b_t`` of a packed batch.

    The segmented analogue of :func:`repro.core.linrec.linear_scan`: at every
    segment boundary the carry resets to ``initial``.  The reset is the same
    masked-contraction trick as ``segscan_mm`` — zeroing ``a`` at flagged
    positions (and folding ``a_t * initial`` into ``b``) kills exactly the
    ``W[i, j]`` entries whose window straddles a boundary, so the packed batch
    runs as ONE unsegmented ``linear_scan`` under whichever ``method=`` is
    requested, with no extra kernel.  Exactness matches the unsegmented
    contract (true zeros of ``a`` are handled exactly by the weighted
    triangle).

    Args:
        a: Packed multipliers ``(..., n)`` — or a :class:`SegmentedBatch`
            (then ``offsets`` is taken from it); broadcast against ``b``.
        b: Packed additive inputs ``(..., n)``, broadcast against ``a``.
        offsets: ``(num_segments + 1,)`` int32 CSR offsets framing the last
            axis; required unless ``a`` is a :class:`SegmentedBatch`.
        exclusive: Return the state entering each step; segment starts get
            ``initial``.
        reverse: Scan each segment from its end.
        method: One of ``METHODS`` — forwarded to ``linear_scan``.
        initial: State the carry resets to at each segment start — a scalar,
            or an array broadcastable against the leading (batch) dims of
            ``a``/``b`` (it is aligned against the packed axis internally, so
            a ``(batch,)`` initial applies per row).
        tile_s: Tile side for the matmul scans.
        block_tiles: Tiles per block for ``method="blocked"``.
        accum_dtype: Accumulation dtype override.
        precision: Engine precision, forwarded to the underlying
            :func:`repro.core.linrec.linear_scan` (dispatch rule 9).
        nonfinite: Non-finite input policy (dispatch rule 10) —
            ``"sanitize"`` maps non-finite elements to the affine identity
            (``a -> 1``, ``b -> 0``).

    Returns:
        The per-segment recurrence, broadcast shape of ``a``/``b``, in the
        linrec accumulation dtype.

    Raises:
        ValueError: If an explicit non-default ``precision`` is combined
            with an explicit ``method="vector"``, or the offsets break the
            CSR contract.
        repro.core.guards.NonFiniteError: Under ``nonfinite="raise"`` with
            concrete non-finite coefficients.

    Example:
        >>> import jax.numpy as jnp
        >>> a = jnp.asarray([2.0, 2.0, 2.0, 2.0, 2.0])
        >>> b = jnp.ones(5)
        >>> segment_linear_scan(a, b, jnp.asarray([0, 2, 5])).tolist()
        [1.0, 3.0, 1.0, 3.0, 7.0]
        >>> segment_linear_scan(a, b, jnp.asarray([0, 2, 5]),
        ...                     initial=1.0).tolist()
        [3.0, 7.0, 3.0, 7.0, 15.0]
    """
    a, offsets = _unwrap(a, offsets, op="segment_linear_scan")
    nf = guards.resolve_nonfinite(nonfinite)
    a = guards.apply_nonfinite(a, nf, op="segment_linear_scan", identity=1.0)
    b = guards.apply_nonfinite(b, nf, op="segment_linear_scan", identity=0.0)
    shp = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shp)
    b = jnp.broadcast_to(b, shp)
    n = a.shape[-1]
    explicit_method = method != "auto"
    method = maybe_resolve(method, "segment_linear_scan", n,
                           jnp.result_type(a.dtype, b.dtype))
    precision = resolve_precision(precision, method=method,
                                  explicit_method=explicit_method)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else linrec_accum_dtype_for(jnp.result_type(a.dtype, b.dtype))
    if n == 0:
        return jnp.zeros(shp, acc)
    if reverse:
        rev_off = (n - offsets)[::-1]
        out = segment_linear_scan(
            jnp.flip(a, axis=-1), jnp.flip(b, axis=-1), rev_off,
            exclusive=exclusive, method=method, initial=initial,
            tile_s=tile_s, block_tiles=block_tiles, accum_dtype=accum_dtype,
            precision=precision)
        return jnp.flip(out, axis=-1)
    flags = boundary_flags(offsets, n) > 0
    init = jnp.asarray(initial, acc)
    # align an array initial with the *leading* dims: the packed axis is the
    # last one, so a per-batch-row initial needs a trailing length-1 axis.
    init_e = init[..., None] if init.ndim else init
    a_cut = jnp.where(flags, jnp.zeros((), acc), a.astype(acc))
    b_cut = jnp.where(flags, b.astype(acc) + a.astype(acc) * init_e,
                      b.astype(acc))
    out = linear_scan(a_cut, b_cut, method=method, tile_s=tile_s,
                      block_tiles=block_tiles, accum_dtype=acc,
                      precision=precision)
    if exclusive:
        pad = [(0, 0)] * (out.ndim - 1) + [(1, 0)]
        shifted = jnp.pad(out, pad)[..., :-1]
        out = jnp.where(flags, jnp.broadcast_to(init_e, out.shape), shifted)
    return out


def segment_sums(values, offsets=None, *, method: str = "auto",
                 tile_s: int = 128, block_tiles: int = 8,
                 accum_dtype=None, precision: str = "highest") -> jax.Array:
    """Per-segment totals, read off the inclusive segmented scan's last element.

    Args:
        values: Packed array ``(..., n)`` or a :class:`SegmentedBatch`.
        offsets: CSR offsets (unless ``values`` is a batch).
        method: Scan method, one of ``METHODS``.
        tile_s: Tile side for the matmul scans.
        block_tiles: Tiles per block for ``method="blocked"``.
        accum_dtype: Accumulation dtype override.
        precision: Engine precision, forwarded to :func:`segment_scan`.

    Returns:
        ``(..., num_segments)`` totals in the accumulation dtype (0 for empty
        segments).

    Example:
        >>> import jax.numpy as jnp
        >>> segment_sums(jnp.ones(5, jnp.int8), jnp.asarray([0, 2, 2, 5])).tolist()
        [2, 0, 3]
    """
    values, offsets = _unwrap(values, offsets)
    inc = segment_scan(values, offsets, method=method, tile_s=tile_s,
                       block_tiles=block_tiles, accum_dtype=accum_dtype,
                       precision=precision)
    return _segment_ends(inc, offsets)


# ---------------------------------------------------------------------------
# segment_compress — ragged tensor masking (per-segment SplitInd)
# ---------------------------------------------------------------------------


@_register("segment_compress", *("matmul", "vector", "kernel", "blocked"))
def _segment_compress_impl(values, mask, offsets, *, method, fill_value,
                           tile_s, block_tiles):
    """Per-segment masked select via one segmented int8 mask scan + scatter."""
    n = values.shape[-1]
    ids = segment_ids(offsets, n)
    seg_start = jnp.take(offsets, ids)
    ex = segment_scan(mask.astype(jnp.int8), offsets, exclusive=True,
                      method=method, tile_s=tile_s, block_tiles=block_tiles)
    inc = ex + mask.astype(jnp.int32)
    counts = _segment_ends(inc, offsets)
    pos_in_seg = jnp.arange(n, dtype=jnp.int32) - seg_start
    pos_false = pos_in_seg - ex
    dest = seg_start + jnp.where(mask, ex, jnp.take(counts, ids) + pos_false)
    z = jnp.zeros_like(values).at[dest].set(values)
    keep = pos_in_seg < jnp.take(counts, ids)
    z = jnp.where(keep, z, jnp.asarray(fill_value, z.dtype))
    return z, counts


def segment_compress(values, mask, offsets=None, *, method: str = "auto",
                     fill_value=0, tile_s: int = 128,
                     block_tiles: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Per-segment masked select: within each segment, kept elements pack left.

    The segmented analogue of :func:`repro.core.primitives.compress` — the
    destination offsets are an exclusive *segmented* int8 mask scan, so each
    segment behaves exactly like an independent 1-D ``compress`` while the
    whole packed batch runs in one pass.

    Args:
        values: Packed payload ``(n,)`` or a :class:`SegmentedBatch`.
        mask: Boolean ``(n,)``; true elements pack to their segment's front.
        offsets: CSR offsets (unless ``values`` is a batch).
        method: One of ``METHODS``.
        fill_value: Fill for every segment's dropped tail.
        tile_s: Tile side for the mask scans.
        block_tiles: Tiles per block for ``method="blocked"``.

    Returns:
        ``(packed, counts)`` — ``packed`` has the same shape as ``values``
        with each segment's kept elements first and its tail filled;
        ``counts`` is ``(num_segments,)`` int32 kept-counts.

    Example:
        >>> import jax.numpy as jnp
        >>> v = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
        >>> m = jnp.asarray([False, True, True, False, True])
        >>> z, c = segment_compress(v, m, jnp.asarray([0, 2, 5]))
        >>> z.tolist(), c.tolist()
        ([2, 0, 3, 5, 0], [1, 2])
    """
    values, offsets = _unwrap(values, offsets)
    method = maybe_resolve(method, "segment_compress", values.shape[-1],
                           values.dtype)
    return dispatch("segment_compress", method)(
        values, mask, offsets, method=method, fill_value=fill_value,
        tile_s=tile_s, block_tiles=block_tiles)


# ---------------------------------------------------------------------------
# segment_sort / segment_topk — per-segment radix passes, one packed launch set
# ---------------------------------------------------------------------------


def _segment_multi_split_dest(digits, num_buckets, offsets, ids, seg_start, *,
                              method, tile_s, block_tiles):
    """Destination offsets for a stable in-segment ``num_buckets``-way split.

    The segmented analogue of ``primitives._multi_split_dest``: all ``R``
    bucket mask scans run as one batched *segmented* int8 -> int32 scan
    (leading bucket dimension, shared offsets), per-(segment, bucket) bases
    come from a tiny ``R``-wide exclusive prefix of the per-segment bucket
    counts, and every destination stays inside its own segment.
    """
    d32 = digits.astype(jnp.int32)
    buckets = jnp.arange(num_buckets, dtype=jnp.int32)
    oh = (d32[None, :] == buckets[:, None]).astype(jnp.int8)      # (R, n)
    ex = segment_scan(oh, offsets, exclusive=True, method=method,
                      tile_s=tile_s, block_tiles=block_tiles)
    inc = ex + oh.astype(jnp.int32)
    counts = _segment_ends(inc, offsets)                          # (R, S)
    base = jnp.cumsum(counts, axis=0) - counts                    # R-wide scan
    ex_el = jnp.take_along_axis(ex, d32[None, :], axis=0)[0]
    dest = seg_start + base[d32, ids] + ex_el
    return dest, counts


def segment_sort(values, offsets=None, *, descending: bool = False,
                 method: str = "auto", bits_per_pass: int = 4,
                 return_indices: bool = True, tile_s: int = 128,
                 block_tiles: int = 8):
    """Stable per-segment radix sort of a packed batch — one pass set for all.

    Each radix pass is a stable in-segment ``2^bits_per_pass``-way split:
    elements never leave their segment, so after ``ceil(bits / k)`` passes
    every segment is independently sorted — bit-identical to running
    :func:`repro.core.primitives.radix_sort` on each segment slice, for every
    ``method`` (bucket offsets are exact segmented int8 -> int32 mask scans).

    Args:
        values: Packed keys ``(n,)`` or a :class:`SegmentedBatch` (dtypes as
            in :func:`repro.core.primitives.radix_sort`).
        offsets: CSR offsets (unless ``values`` is a batch).
        descending: Sort each segment high-to-low (stability preserved).
        method: One of ``METHODS``.
        bits_per_pass: Bits retired per radix pass (``1..8``).
        return_indices: If false, return only the sorted values.
        tile_s: Tile side for the mask scans.
        block_tiles: Tiles per block for ``method="blocked"``.

    Returns:
        ``(sorted_values, indices)`` — or just ``sorted_values`` — where
        ``indices`` are int32 positions into the *packed* array
        (``sorted_values == values[indices]``; subtract ``offsets[seg]`` for
        segment-local ranks).

    Raises:
        ValueError: If ``bits_per_pass`` is outside ``[1, 8]``.

    Example:
        >>> import jax.numpy as jnp
        >>> v, i = segment_sort(jnp.asarray([3, 1, 9, 2, 5], jnp.int32),
        ...                     jnp.asarray([0, 2, 5]))
        >>> v.tolist(), i.tolist()
        ([1, 3, 2, 5, 9], [1, 0, 3, 4, 2])
    """
    bits_per_pass = guards.validate_bits_per_pass(bits_per_pass,
                                                  op="segment_sort")
    values, offsets = _unwrap(values, offsets, op="segment_sort")
    if values.ndim != 1:
        raise ValueError("segment_sort expects 1-D packed values")
    n = values.shape[-1]
    method = maybe_resolve(method, "segment_sort", n, values.dtype)
    enc, bits, decode = _encode_for_sort(values)
    if descending:
        enc = ~enc
    ids = segment_ids(offsets, n)
    seg_start = jnp.take(offsets, ids)
    perm = jnp.arange(n, dtype=jnp.int32)
    for shift in range(0, bits, bits_per_pass):
        k = min(bits_per_pass, bits - shift)
        mask = jnp.asarray((1 << k) - 1, enc.dtype)
        digits = ((enc >> shift) & mask).astype(jnp.int32)
        dest, _ = _segment_multi_split_dest(
            digits, 1 << k, offsets, ids, seg_start, method=method,
            tile_s=tile_s, block_tiles=block_tiles)
        enc = jnp.zeros_like(enc).at[dest].set(enc)
        perm = jnp.zeros_like(perm).at[dest].set(perm)
    if descending:
        enc = ~enc
    sorted_values = decode(enc)
    if return_indices:
        return sorted_values, perm
    return sorted_values


def segment_topk(values, offsets=None, k: int = 1, *, method: str = "auto",
                 bits_per_pass: int = 4, fill_value=0, tile_s: int = 128,
                 block_tiles: int = 8):
    """Per-segment top-k of a packed batch via one descending segmented sort.

    Segments shorter than ``k`` return their full (sorted) contents; the
    output is dense ``(num_segments, k)`` with ragged tails filled, plus the
    per-segment valid counts — the static-shape convention of the 1-D
    operators.

    Args:
        values: Packed keys ``(n,)`` or a :class:`SegmentedBatch`.
        offsets: CSR offsets (unless ``values`` is a batch).
        k: Number of leading elements to keep per segment.
        method: One of ``METHODS``.
        bits_per_pass: Bits retired per radix pass.
        fill_value: Fill for rows of segments shorter than ``k``.
        tile_s: Tile side for the mask scans.
        block_tiles: Tiles per block for ``method="blocked"``.

    Returns:
        ``(topk_values, topk_indices, counts)`` — ``(S, k)`` values (filled
        past ``counts``), ``(S, k)`` int32 *segment-local* indices (-1 past
        ``counts``), and ``(S,)`` int32 ``counts = min(length, k)``.

    Example:
        >>> import jax.numpy as jnp
        >>> v, i, c = segment_topk(jnp.asarray([3, 1, 9, 2, 5], jnp.int32),
        ...                        jnp.asarray([0, 2, 5]), k=2)
        >>> v.tolist(), i.tolist(), c.tolist()
        ([[3, 1], [9, 5]], [[0, 1], [0, 2]], [2, 2])
    """
    values, offsets = _unwrap(values, offsets)
    n = values.shape[-1]
    num_segments = offsets.shape[0] - 1
    if n == 0:  # all segments empty: nothing to rank
        return (jnp.full((num_segments, k), fill_value, values.dtype),
                jnp.full((num_segments, k), -1, jnp.int32),
                jnp.zeros((num_segments,), jnp.int32))
    sv, sperm = segment_sort(values, offsets, descending=True, method=method,
                             bits_per_pass=bits_per_pass, tile_s=tile_s,
                             block_tiles=block_tiles)
    lens = offsets[1:] - offsets[:-1]
    counts = jnp.minimum(lens, k).astype(jnp.int32)
    col = jnp.arange(k, dtype=jnp.int32)[None, :]
    valid = col < counts[:, None]
    src = jnp.clip(offsets[:-1, None] + col, 0, max(n - 1, 0))
    vals = jnp.where(valid, jnp.take(sv, src), jnp.asarray(fill_value, sv.dtype))
    idx = jnp.where(valid, jnp.take(sperm, src) - offsets[:-1, None], -1)
    return vals, idx.astype(jnp.int32), counts


# ---------------------------------------------------------------------------
# segment_softmax / segment_top_p_sample — the ragged decode sampler
# ---------------------------------------------------------------------------


def segment_softmax(values, offsets=None, *, method: str = "auto",
                    tile_s: int = 128, block_tiles: int = 8) -> jax.Array:
    """Per-segment softmax of packed logits, in fp32.

    Max-subtraction uses an exact (order-independent) per-segment max; the
    normalizer is the per-segment total of the exponentials, read off the
    segmented scan.

    Args:
        values: Packed logits ``(n,)`` or a :class:`SegmentedBatch`.
        offsets: CSR offsets (unless ``values`` is a batch).
        method: Scan method for the normalizer, one of ``METHODS``.
        tile_s: Tile side for the matmul scans.
        block_tiles: Tiles per block for ``method="blocked"``.

    Returns:
        ``(n,)`` fp32 probabilities summing to 1 within each segment.

    Example:
        >>> import jax.numpy as jnp
        >>> p = segment_softmax(jnp.zeros(4), jnp.asarray([0, 1, 4]))
        >>> [round(float(v), 4) for v in p]
        [1.0, 0.3333, 0.3333, 0.3333]
    """
    values, offsets = _unwrap(values, offsets)
    n = values.shape[-1]
    num_segments = offsets.shape[0] - 1
    x = values.astype(jnp.float32)
    ids = segment_ids(offsets, n)
    m = jax.ops.segment_max(x, ids, num_segments=num_segments,
                            indices_are_sorted=True)
    e = jnp.exp(x - jnp.take(m, ids))
    denom = segment_sums(e, offsets, method=method, tile_s=tile_s,
                         block_tiles=block_tiles)
    return e / jnp.take(denom, ids)


def _segment_greedy(values, offsets, n: int, num_segments: int) -> jax.Array:
    """Per-segment argmax as a segment-local id — NaN as ``-inf``, ties low.

    The deterministic greedy fallback of dispatch rule 10: used for
    ``temperature == 0`` and for ``nonfinite="sanitize"`` on poisoned
    segments.  A segment whose entries are all ``-inf`` resolves to local
    id 0 (matching the batched sampler's convention).
    """
    x = jnp.asarray(values).astype(jnp.float32)
    x = jnp.where(jnp.isnan(x), -jnp.inf, x)
    ids = segment_ids(offsets, n)
    m = jax.ops.segment_max(x, ids, num_segments=num_segments,
                            indices_are_sorted=True)
    cand = jnp.where(x == jnp.take(m, ids), jnp.arange(n, dtype=jnp.int32),
                     jnp.asarray(n, jnp.int32))
    first = jax.ops.segment_min(cand, ids, num_segments=num_segments,
                                indices_are_sorted=True)
    return jnp.clip(first - offsets[:-1], 0, None).astype(jnp.int32)


def _reject_poisoned_packed_logits(values, offsets, n: int,
                                   num_segments: int) -> None:
    """The packed ``nonfinite="raise"`` gate for :func:`segment_top_p_sample`.

    ``-inf`` entries are legitimate vocab masks; what is rejected is NaN,
    ``+inf``, and any non-empty segment with no finite entry (no valid
    sample exists).  Concrete payloads raise
    :class:`repro.core.guards.NonFiniteError` eagerly; traced payloads stage
    a checkified assertion (fires through :func:`repro.core.guards.checked`).
    """
    if guards.is_concrete(values) and guards.is_concrete(offsets):
        v = np.asarray(values, dtype=np.float32)
        off = np.asarray(offsets)
        bad = bool(np.isnan(v).any() or np.isposinf(v).any())
        if not bad:
            finite = np.isfinite(v)
            for i in range(off.shape[0] - 1):
                seg = finite[off[i]:off[i + 1]]
                if seg.size and not seg.any():
                    bad = True
                    break
        if bad:
            raise guards.NonFiniteError(
                "segment_top_p_sample: poisoned logits under "
                "nonfinite='raise' — NaN, +inf, or a segment with no finite "
                "entry (-inf vocab masks are allowed)")
    else:
        from jax.experimental import checkify
        x = jnp.asarray(values).astype(jnp.float32)
        ids = segment_ids(offsets, n)
        has_finite = jax.ops.segment_max(
            jnp.isfinite(x).astype(jnp.int32), ids,
            num_segments=num_segments, indices_are_sorted=True)
        lens = offsets[1:] - offsets[:-1]
        ok = (~jnp.any(jnp.isnan(x)) & ~jnp.any(jnp.isposinf(x))
              & jnp.all((has_finite > 0) | (lens == 0)))
        checkify.debug_check(
            ok, "segment_top_p_sample: poisoned logits under "
                "nonfinite='raise'")


def segment_top_p_sample(values, offsets=None, key=None, p: float = 0.9,
                         temperature: float = 1.0, *, method: str = "auto",
                         bits_per_pass: int = 4, is_probs: bool = False,
                         u: Optional[jax.Array] = None, tile_s: int = 128,
                         block_tiles: int = 8,
                         nonfinite: str = "propagate") -> jax.Array:
    """Nucleus-sample every segment of a packed ragged batch in one launch.

    The packed analogue of :func:`repro.core.primitives.top_p_sample`:
    per-segment softmax, a descending segmented radix sort on bf16 keys, the
    segmented prefix sum of sorted probabilities, the nucleus cutoff, and a
    per-segment inverse-transform sample — every scan-shaped step running on
    the segmented matmul scan, so a ragged decode batch (active rows of
    different lengths) samples without padding to a rectangle.

    Args:
        values: Packed logits ``(n,)`` or a :class:`SegmentedBatch`.
        offsets: CSR offsets (unless ``values`` is a batch).
        key: JAX PRNG key; draws one uniform per segment (shape
            ``(num_segments, 1)``), so a rectangular batch consumes exactly
            the uniforms the batched sampler would.  Tokens then agree with
            the batched sampler except where fp32 rounding flips a
            threshold comparison (a flat packed scan accumulates
            differently from per-row scans — the module's float contract).
        p: Nucleus mass threshold in ``(0, 1]``.
        temperature: Logit divisor applied before the softmax;
            ``temperature == 0`` is the deterministic greedy limit
            (per-segment argmax, ties to the lowest id — no uniform is
            consumed).
        method: One of ``METHODS`` for every scan-shaped step.
        bits_per_pass: Bits retired per radix pass of the key sort.
        is_probs: If true, ``values`` are already per-segment probabilities
            (softmax and temperature are skipped).
        u: Optional ``(num_segments, 1)`` uniforms overriding the ``key``
            draw (deterministic replay / parity testing).
        tile_s: Tile side for the mask scans.
        block_tiles: Tiles per block for ``method="blocked"``.
        nonfinite: Non-finite logits policy (dispatch rule 10) —
            ``"raise"`` rejects NaN / ``+inf`` / fully-masked segments
            (``-inf`` vocab masks stay legal); ``"sanitize"`` maps poisoned
            segments to the deterministic per-segment greedy fallback.

    Returns:
        ``(num_segments,)`` int32 sampled *segment-local* token ids (0 for
        empty segments).

    Raises:
        ValueError: If ``p`` is outside ``[0, 1]`` or ``temperature`` is
            negative / non-finite, or the offsets break the CSR contract.
        repro.core.guards.NonFiniteError: Under ``nonfinite="raise"`` with
            concrete poisoned logits.

    Example:
        >>> import jax, jax.numpy as jnp
        >>> logits = jnp.asarray([0.0, 20.0, 0.0, 0.0, 20.0])
        >>> segment_top_p_sample(logits, jnp.asarray([0, 3, 5]),
        ...                      jax.random.PRNGKey(0), p=0.9).tolist()
        [1, 1]
    """
    values, offsets = _unwrap(values, offsets, op="segment_top_p_sample")
    guards.validate_probability(p, op="segment_top_p_sample")
    guards.validate_temperature(temperature, op="segment_top_p_sample")
    nonfinite = guards.resolve_nonfinite(nonfinite)
    n = values.shape[-1]
    num_segments = offsets.shape[0] - 1
    if n == 0:  # all segments empty: the documented 0-per-segment result
        return jnp.zeros((num_segments,), jnp.int32)
    if not is_probs and guards.is_concrete(temperature) \
            and float(temperature) == 0.0:
        seg_lens = offsets[1:] - offsets[:-1]
        greedy = _segment_greedy(values, offsets, n, num_segments)
        return jnp.where(seg_lens > 0, greedy, 0).astype(jnp.int32)
    method = maybe_resolve(method, "segment_top_p_sample", n, values.dtype)
    kw = dict(method=method, tile_s=tile_s, block_tiles=block_tiles)
    if nonfinite == "raise":
        _reject_poisoned_packed_logits(values, offsets, n, num_segments)
    if is_probs:
        probs = values.astype(jnp.float32)
    else:
        v = values if temperature == 1.0 else values / temperature
        probs = segment_softmax(v, offsets, **kw)
    keys16 = probs.astype(jnp.bfloat16)
    _, order = segment_sort(keys16, offsets, descending=True,
                            bits_per_pass=bits_per_pass, **kw)
    sorted_p = jnp.take(probs, order)
    cum = segment_scan(sorted_p, offsets, **kw)
    cut = (cum - sorted_p) > p                    # llama3's sample_top_p formula
    masked = jnp.where(cut, 0.0, sorted_p)
    cdf = segment_scan(masked, offsets, **kw)
    totals = _segment_ends(cdf, offsets)
    if u is None:
        u = jax.random.uniform(key, (num_segments, 1), dtype=cdf.dtype)
    theta = u[..., 0].astype(cdf.dtype) * totals
    ids = segment_ids(offsets, n)
    less = (cdf < jnp.take(theta, ids)).astype(jnp.int32)
    cnt = _segment_ends(segment_scan(less, offsets, **kw), offsets)
    lens = offsets[1:] - offsets[:-1]
    j = jnp.clip(cnt, 0, jnp.maximum(lens - 1, 0))
    pos = jnp.clip(offsets[:-1] + j, 0, max(n - 1, 0))
    tok = jnp.take(order, pos) - offsets[:-1]
    tok = jnp.where(lens > 0, tok, 0).astype(jnp.int32)
    if nonfinite == "sanitize":
        bad = jax.ops.segment_max(
            (~jnp.isfinite(probs)).astype(jnp.int32), ids,
            num_segments=num_segments, indices_are_sorted=True) > 0
        greedy = _segment_greedy(values, offsets, n, num_segments)
        tok = jnp.where(bad & (lens > 0), greedy, tok)
    return tok
