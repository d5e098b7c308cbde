"""Matmul-based parallel scan (prefix sum) — the paper's core contribution.

Implements, in pure JAX (lowering to the TPU MXU via ``jnp.dot``):

* ``ScanU``   (paper Alg. 1): one matmul ``A @ U_s`` computes ``s`` local scans of
  length ``s``; the row partials are then propagated.  On Ascend the propagation is a
  serial vector-core loop; on TPU we use a log-depth VPU cumsum over the ``s`` row sums
  (see DESIGN.md §2, "assumptions that changed").
* ``ScanUL1`` (paper Alg. 2 / Eq. 1): the full ``ℓ = s²`` tile scan as matmuls only::

      scan(z) = A @ U_s  +  L⁻_s @ A @ 1_s

  where ``A`` is the row-major ``s×s`` view of the tile, ``U_s`` the upper-triangular
  all-ones matrix (incl. diagonal) and ``L⁻_s`` the *strictly* lower-triangular
  all-ones matrix.
* A multi-level block scan (SSA structure, paper §2.1/§4.3) so arbitrary lengths run
  in linear work: tile-local scans (MXU) + a scan over the tile sums + broadcast add.

Dtype rules follow the paper's cube unit: ``int8 -> int32`` accumulation (mask scans),
``bf16/f16 -> f32`` accumulation, everything else accumulates in its own dtype.  See
:func:`accum_dtype_for`; the full paper-section-to-module map lives in
``docs/paper_map.md``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guards
from repro.core.autotune import maybe_resolve
from repro.core.precision import pdot, resolve_precision

__all__ = [
    "scan",
    "cumsum",
    "tile_scan_scanu",
    "tile_scan_scanul1",
    "upper_ones",
    "strictly_lower_ones",
    "accum_dtype_for",
]

METHODS = ("matmul", "vector", "kernel", "blocked")

# ---------------------------------------------------------------------------
# Constant matrices (paper notation: U_s, L_s, L⁻_s, 1_s)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _np_upper_ones(s: int) -> np.ndarray:
    return np.triu(np.ones((s, s), dtype=np.float32))


@functools.lru_cache(maxsize=None)
def _np_strictly_lower_ones(s: int) -> np.ndarray:
    return np.tril(np.ones((s, s), dtype=np.float32), k=-1)


def upper_ones(s: int, dtype=jnp.float32) -> jax.Array:
    """Build ``U_s`` — upper triangular all-ones (including the main diagonal).

    ``z @ U_s`` is the length-``s`` inclusive scan of a row vector ``z``; this
    is the constant operand of the paper's cube-unit matmuls (Alg. 1 line 5).

    Args:
        s: Matrix order (the paper's tile side; 128 matches the MXU).
        dtype: Element dtype the matrix is materialized in.

    Returns:
        An ``(s, s)`` array of the requested dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> upper_ones(3, jnp.int32).tolist()
        [[1, 1, 1], [0, 1, 1], [0, 0, 1]]
    """
    return jnp.asarray(_np_upper_ones(s), dtype=dtype)


def strictly_lower_ones(s: int, dtype=jnp.float32) -> jax.Array:
    """Build ``L⁻_s`` — strictly lower triangular all-ones (zero diagonal).

    ``L⁻_s @ v`` is the *exclusive* prefix of ``v``; it propagates row/tile
    partials entirely on the matrix engine (paper Eq. 1, Alg. 2 line 12).

    Args:
        s: Matrix order.
        dtype: Element dtype the matrix is materialized in.

    Returns:
        An ``(s, s)`` array of the requested dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> strictly_lower_ones(3, jnp.int32).tolist()
        [[0, 0, 0], [1, 0, 0], [1, 1, 0]]
    """
    return jnp.asarray(_np_strictly_lower_ones(s), dtype=dtype)


def accum_dtype_for(dtype) -> jnp.dtype:
    """Accumulation dtype mirroring the Ascend cube unit I/O types.

    The cube unit widens narrow inputs while accumulating: int8/uint8/int16 and
    bool inputs accumulate in int32 (the paper's mask-scan specialization used
    by ``split``/``compress``), and sub-fp32 floats (bf16/f16) accumulate in
    fp32.  Everything else accumulates in its own dtype.  Every ``scan``
    method — including the ``jnp.cumsum`` vector baseline — returns this
    dtype, which is what makes the methods bit-comparable.

    Args:
        dtype: Input element dtype (anything ``jnp.dtype`` accepts).

    Returns:
        The ``jnp.dtype`` scans over this input accumulate and return in.

    Example:
        >>> import jax.numpy as jnp
        >>> str(accum_dtype_for(jnp.int8)), str(accum_dtype_for(jnp.bfloat16))
        ('int32', 'float32')
        >>> str(accum_dtype_for(jnp.float32))
        'float32'
    """
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8), jnp.dtype(jnp.int16),
                 jnp.dtype(jnp.bool_)):
        return jnp.dtype(jnp.int32)
    if dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return jnp.dtype(jnp.float32)
    return dtype


# ---------------------------------------------------------------------------
# Tile-local scans (one ℓ = s² tile viewed as an s×s row-major matrix A)
# ---------------------------------------------------------------------------


def tile_scan_scanu(a: jax.Array, *, accum_dtype=None,
                    precision: str = "highest") -> jax.Array:
    """ScanU tile step (paper Alg. 1): ``A @ U_s`` + propagation of row partials.

    The matmul computes the ``s`` per-row local scans; propagation then adds
    the exclusive cumsum of the row sums — on TPU a log-depth VPU op rather
    than Ascend's serial vector loop.

    Args:
        a: ``(..., s, s)`` row-major tile(s); a tile is the ``s×s`` matrix view
            of ``ℓ = s²`` consecutive sequence elements.
        accum_dtype: Accumulation dtype override; defaults to
            ``accum_dtype_for(a.dtype)``.
        precision: Engine feed precision for the fp32 contraction
            (:mod:`repro.core.precision`); only affects fp32 tiles.

    Returns:
        The full inclusive tile scan, shape ``(..., s, s)``, in the
        accumulation dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> a = jnp.arange(1.0, 5.0).reshape(2, 2)   # the sequence 1,2,3,4
        >>> tile_scan_scanu(a).tolist()
        [[1.0, 3.0], [6.0, 10.0]]
    """
    s = a.shape[-1]
    acc = accum_dtype or accum_dtype_for(a.dtype)
    u = upper_ones(s, _operand_dtype(a.dtype))
    local = pdot(a, u, acc=acc, precision=precision, exact="right").astype(acc)
    row_sums = local[..., :, -1]
    row_prefix = jnp.cumsum(row_sums, axis=-1, dtype=acc) - row_sums  # exclusive
    return local + row_prefix[..., :, None]


def tile_scan_scanul1(a: jax.Array, *, accum_dtype=None,
                      precision: str = "highest") -> jax.Array:
    """ScanUL1 tile step (paper Alg. 2 / Eq. 1): ``A@U + L⁻ @ (A@1)`` — matmuls only.

    ``A @ 1_s`` is computed as a row-sum broadcast (identical result, avoids one
    explicit matmul operand load); the ``L⁻`` product runs on the MXU and plays the
    role of the cube accumulation-buffer step (Alg. 2 line 12).

    Args:
        a: ``(..., s, s)`` row-major tile(s).
        accum_dtype: Accumulation dtype override; defaults to
            ``accum_dtype_for(a.dtype)``.
        precision: Engine feed precision for the fp32 contractions
            (:mod:`repro.core.precision`); only affects fp32 tiles.

    Returns:
        The full inclusive tile scan, shape ``(..., s, s)``, in the
        accumulation dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> a = jnp.arange(1.0, 5.0).reshape(2, 2)
        >>> tile_scan_scanul1(a).tolist()
        [[1.0, 3.0], [6.0, 10.0]]
    """
    s = a.shape[-1]
    acc = accum_dtype or accum_dtype_for(a.dtype)
    od = _operand_dtype(a.dtype)
    u = upper_ones(s, od)
    lm = strictly_lower_ones(s, od)
    c2 = pdot(a, u, acc=acc, precision=precision, exact="right").astype(acc)
    # C1 = A @ 1_s  ==  row sums broadcast along columns.
    c1 = jnp.sum(a.astype(acc), axis=-1, keepdims=True) * jnp.ones((1, s), acc)
    c2 = c2 + pdot(lm.astype(acc), c1, acc=acc, precision=precision, exact="left")
    return c2


def _operand_dtype(dtype) -> jnp.dtype:
    """Dtype in which the constant matrices / matmul operands are fed to the MXU."""
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.bool_), jnp.dtype(jnp.uint8)):
        return jnp.dtype(jnp.int8)
    if dtype in (jnp.dtype(jnp.int16), jnp.dtype(jnp.int32)):
        return dtype
    if dtype == jnp.dtype(jnp.bfloat16):
        return dtype
    if dtype == jnp.dtype(jnp.float16):
        return dtype
    return jnp.dtype(jnp.float32)


_TILE_FNS = {"scanu": tile_scan_scanu, "scanul1": tile_scan_scanul1}


# ---------------------------------------------------------------------------
# Full scan over the last axis
# ---------------------------------------------------------------------------


def _scan_last_axis_matmul(x: jax.Array, s: int, variant: str, acc,
                           precision: str = "highest") -> jax.Array:
    """Multi-level SSA block scan over the last axis using matmul tile scans."""
    *lead, n = x.shape
    ell = s * s
    if n <= s:
        # Single row: one triangular matvec on the MXU.
        u = upper_ones(n, _operand_dtype(x.dtype)) if n > 1 else None
        if n == 1:
            return x.astype(acc)
        return pdot(x[..., None, :].astype(_operand_dtype(x.dtype)), u,
                    acc=acc, precision=precision,
                    exact="right")[..., 0, :].astype(acc)

    n_pad = (-n) % ell
    xp = jnp.pad(x, [(0, 0)] * len(lead) + [(0, n_pad)]) if n_pad else x
    nt = xp.shape[-1] // ell
    tiles = xp.reshape(*lead, nt, s, s)
    local = _TILE_FNS[variant](tiles, accum_dtype=acc,
                               precision=precision)             # (..., nt, s, s)
    tile_sums = local[..., -1, -1]                              # (..., nt)
    # Scan over the (much smaller) tile sums; recurse with the matmul method when the
    # tile-sum array itself is long enough to benefit.
    if nt > ell:
        tile_prefix = _scan_last_axis_matmul(tile_sums, s, variant, acc, precision)
    else:
        tile_prefix = jnp.cumsum(tile_sums, axis=-1, dtype=acc)
    tile_prefix = tile_prefix - tile_sums                       # exclusive
    out = local + tile_prefix[..., None, None]
    out = out.reshape(*lead, nt * ell)
    return out[..., :n] if n_pad else out


def scan(
    x: jax.Array,
    axis: int = -1,
    *,
    exclusive: bool = False,
    reverse: bool = False,
    method: str = "auto",
    precision: str = "highest",
    variant: str = "scanul1",
    tile_s: int = 128,
    block_tiles: int = 8,
    accum_dtype: Optional[jnp.dtype] = None,
    nonfinite: str = "propagate",
) -> jax.Array:
    """Inclusive (or exclusive) prefix sum along ``axis``.

    This is the framework-wide cumsum: the §5 operators (``split``, ``sort``,
    ``top_p_sample``, …), MoE dispatch and the SSM layers all route through it.
    The output dtype is always the accumulation dtype (``int8 -> int32``,
    ``bf16/f16 -> f32``; see :func:`accum_dtype_for`) regardless of method,
    which makes methods directly comparable.

    Args:
        x: Input array, any shape and any dtype :func:`accum_dtype_for` knows.
        axis: Axis to scan along (scans always execute over the last axis; other
            axes are moved there and back).
        exclusive: If true, shift the result right by one with a leading zero.
        reverse: If true, scan from the end (suffix sums).
        method: Execution strategy — ``"auto"`` (the default) resolves to one
            of ``METHODS`` per (op, length, dtype, backend) from the committed
            tuning table (:mod:`repro.core.autotune`; resolution is static, so
            the traced jaxpr is identical to passing the resolved method), or
            one of ``METHODS`` explicitly:

            * ``"matmul"`` — the paper's cube-unit algorithms (ScanU / ScanUL1
              per ``variant``) as XLA matmuls with SSA multi-level blocking.
            * ``"vector"`` — plain ``jnp.cumsum`` (the paper's vector-only
              baseline).
            * ``"kernel"`` — the fused sequential-grid Pallas kernel
              (``repro.kernels.scan_mm``): one launch, tiles walked in order
              with a VMEM-carried running partial.
            * ``"blocked"`` — the three-phase multi-core pipeline of paper §4
              (``repro.kernels.scan_pipeline``): parallel per-block partial
              scans, a block-sum carry scan, and a fused carry broadcast-add,
              so each element is read and written once.
        precision: Engine feed precision for the matmul methods
            (``"highest"``/``"compensated"``/``"fast"``), resolved pre-trace
            like ``method`` (:mod:`repro.core.precision`; ``precision_override``
            context > ``REPRO_SCAN_PRECISION`` env > this argument — dispatch
            rule 9).  ``"compensated"`` contracts fp32 inputs on the fp16
            engine via exact Ozaki high/low splits and matches
            ``method="vector"`` within the documented ulp bound; ``"fast"``
            feeds the bf16 engine (loose bound).  Only fp32 inputs are
            affected; integer scans stay exact.  Explicitly combining a
            non-default precision with ``method="vector"`` raises.
        variant: Tile algebra, ``"scanu"`` (Alg. 1, VPU row propagation) or
            ``"scanul1"`` (Alg. 2 / Eq. 1, propagation as an ``L⁻`` matmul).
        tile_s: Tile side ``s`` (a tile covers ``s²`` elements; 128 = MXU size).
        block_tiles: Tiles per block for ``method="blocked"`` (ignored
            otherwise); a block covers ``block_tiles * tile_s²`` elements.
        accum_dtype: Accumulation dtype override; defaults to
            ``accum_dtype_for(x.dtype)``.
        nonfinite: Non-finite input policy (:mod:`repro.core.guards`,
            dispatch rule 10), resolved pre-trace like ``method`` and
            ``precision`` (``nonfinite_override`` context > ``REPRO_NONFINITE``
            env > this argument).  ``"propagate"`` (default) keeps IEEE
            semantics and adds zero ops; ``"raise"`` rejects non-finite
            inputs (eagerly when concrete, as a checkified assertion under
            trace); ``"sanitize"`` replaces non-finite elements with 0 (the
            additive identity).  Integer scans are unaffected.

    Returns:
        The scanned array, same shape as ``x``, in the accumulation dtype.

    Raises:
        ValueError: If ``method``, ``precision``, ``variant`` or ``nonfinite``
            is unknown, ``axis`` is out of bounds, or an explicit non-default
            ``precision`` is combined with an explicit ``method="vector"``.

    Example:
        >>> import jax.numpy as jnp
        >>> [int(v) for v in scan(jnp.arange(1, 9, dtype=jnp.int32))]
        [1, 3, 6, 10, 15, 21, 28, 36]
        >>> out = scan(jnp.ones(10, jnp.int8), method="blocked", tile_s=8)
        >>> out.dtype.name, int(out[-1])
        ('int32', 10)
        >>> [int(v) for v in scan(jnp.arange(1, 5, dtype=jnp.int32), exclusive=True)]
        [0, 1, 3, 6]
    """
    if method != "auto" and method not in METHODS:
        raise ValueError(f"unknown scan method {method!r}; expected one of "
                         f"{METHODS + ('auto',)}")
    if variant not in _TILE_FNS:
        raise ValueError(f"unknown scan variant {variant!r}")
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else accum_dtype_for(x.dtype)

    axis = guards.validate_axis(axis, x.ndim, op="scan")
    explicit_method = method != "auto"
    method = maybe_resolve(method, "scan", x.shape[axis], x.dtype)
    precision = resolve_precision(precision, method=method,
                                  explicit_method=explicit_method)
    x = guards.apply_nonfinite(x, guards.resolve_nonfinite(nonfinite),
                               op="scan")
    if axis != x.ndim - 1:
        x = jnp.moveaxis(x, axis, -1)
    if reverse:
        x = jnp.flip(x, axis=-1)

    if method == "vector":
        out = jnp.cumsum(x, axis=-1, dtype=acc)
    elif method == "kernel":
        from repro.kernels import ops as _kops  # local import to avoid cycle
        out = _kops.scan_kernel(x, s=tile_s, variant=variant, accum_dtype=acc,
                                precision=precision)
    elif method == "blocked":
        from repro.kernels import ops as _kops  # local import to avoid cycle
        out = _kops.blocked_scan_kernel(x, s=tile_s, block_tiles=block_tiles,
                                        variant=variant, accum_dtype=acc,
                                        precision=precision)
    else:
        out = _scan_last_axis_matmul(x, tile_s, variant, acc, precision)

    if exclusive:
        pad = [(0, 0)] * (out.ndim - 1) + [(1, 0)]
        out = jnp.pad(out, pad)[..., :-1]
    if reverse:
        out = jnp.flip(out, axis=-1)
    if axis != x.ndim - 1:
        out = jnp.moveaxis(out, -1, axis)
    return out


def cumsum(x: jax.Array, axis: int = -1, **kw) -> jax.Array:
    """Drop-in ``jnp.cumsum`` replacement backed by the matmul scan.

    Args:
        x: Input array.
        axis: Axis to scan along.
        **kw: Forwarded to :func:`scan` (``method=``, ``variant=``, …).

    Returns:
        ``scan(x, axis=axis, **kw)`` — inclusive prefix sums in the
        accumulation dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> [int(v) for v in cumsum(jnp.asarray([1, 1, 2], jnp.int32))]
        [1, 2, 4]
    """
    return scan(x, axis=axis, **kw)
