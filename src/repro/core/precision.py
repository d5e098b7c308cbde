"""Precision policy for the matmul-engine scans (``precision="compensated"``).

The paper's cube unit earns its bandwidth by contracting in fp16/bf16, yet the
repo's matmul scan paths contract in fp32 (ROADMAP item 3).  This module adds
the missing axis — one shared split/accumulate helper reused by every
scan-triangle contraction (*SIMD²*'s "factor the engine trick once" argument),
implementing the Ozaki/Ootomo error-compensated split of *SGEMM-cube* (see
PAPERS.md):

* ``"highest"`` (default) — today's behaviour: operands feed the engine in
  fp32 (or their native dtype) and accumulate per ``preferred_element_type``.
* ``"compensated"`` — fp32 data operands split **exactly** into a fp16 high
  part plus a ``2^-11``-scaled fp16 low part after an exact per-slice
  power-of-two scaling; the cross terms contract on the fp16 engine with fp32
  accumulation and recombine to ~22 significand bits, matching the fp32
  ``"vector"`` path within the documented ulp bound
  (:mod:`repro.analysis.ulp`).  The ``lo×lo`` term (< 2^-22 relative) is
  dropped.
* ``"fast"`` — plain bf16 engine feed with fp32 accumulation; ~8 significand
  bits, loose bound, maximum throughput.

Resolution is static (pre-trace), mirroring ``method="auto"`` dispatch
(:mod:`repro.core.autotune`): an active :func:`precision_override` context
wins, else the ``REPRO_SCAN_PRECISION`` environment variable, else the
call-site argument — ``docs/architecture.md`` dispatch rule 9.  Two
interactions are fixed by :func:`resolve_precision`:

* an **explicit** ``method="vector"`` with an explicit non-default
  ``precision`` raises ``ValueError`` — the vector path never touches the
  matrix engine, so the request is unsatisfiable;
* when ``method="auto"`` (or an override/env var) lands on ``"vector"``, the
  precision silently degrades to ``"highest"`` — the vector path *is* the
  fp32 reference the compensated contract is stated against.

Only fp32 data operands are ever split: integer/bool contractions stay exact
and sub-fp32 float inputs (bf16/f16) already feed the engine natively, so for
those every precision is identical to ``"highest"`` by construction.

The exact power-of-two scaling here (``frexp``/``ldexp`` — no rounding) is the
same trick :func:`repro.core.linrec._pair_w` uses to keep windowed cumulative
products in range; its mantissa normalization lives here
(:func:`normalize_exponents`) so both users share one exponent-handling
implementation.
"""
from __future__ import annotations

import contextlib
import os
from typing import List, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "PRECISIONS", "SPLIT_SHIFT", "ENV_VAR",
    "resolve_precision", "precision_override",
    "split_f16", "normalize_exponents", "pdot",
]

PRECISIONS = ("highest", "compensated", "fast")
ENV_VAR = "REPRO_SCAN_PRECISION"

# fp16 carries 11 significand bits (incl. the implicit one): the low split
# part is pre-scaled by 2^SPLIT_SHIFT so its leading bits are exactly the
# residual bits the high part dropped.
SPLIT_SHIFT = 11

# Mantissas are normalized into [√½, √2) (not frexp's [½, 1)) so products and
# quotients of normalized values stay within one octave of 1 — shared with the
# linrec weighted-triangle construction (see module docstring).
_SQRT_HALF = 0.7071067811865476

_OVERRIDE: List[str] = []


@contextlib.contextmanager
def precision_override(precision: str):
    """Force every precision resolution to ``precision`` inside the block.

    The in-process analogue of the ``REPRO_SCAN_PRECISION`` environment
    variable (and it takes precedence over it) — the precision counterpart of
    :func:`repro.core.autotune.method_override`.  An override landing on a
    ``"vector"``-dispatched call degrades to ``"highest"`` silently (the
    vector path is the fp32 reference), and never affects integer/bool
    contractions (those stay exact by construction).

    Args:
        precision: One of ``PRECISIONS``.

    Raises:
        ValueError: If ``precision`` is not a known precision.

    Example:
        >>> with precision_override("compensated"):
        ...     resolve_precision("highest", method="matmul")
        'compensated'
    """
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; expected one of "
                         f"{PRECISIONS}")
    _OVERRIDE.append(precision)
    try:
        yield
    finally:
        _OVERRIDE.pop()


def _env_precision() -> Optional[str]:
    """The ``REPRO_SCAN_PRECISION`` forced precision, or ``None``."""
    p = os.environ.get(ENV_VAR)
    if not p:
        return None
    if p not in PRECISIONS:
        raise ValueError(f"{ENV_VAR}={p!r} is not a known precision; expected "
                         f"one of {PRECISIONS}")
    return p


def resolve_precision(precision: str = "highest", *, method: Optional[str] = None,
                      explicit_method: bool = True) -> str:
    """Resolve the effective precision for one operator call (pre-trace).

    Resolution order (``docs/architecture.md`` dispatch rule 9): an active
    :func:`precision_override` context wins, else ``REPRO_SCAN_PRECISION``,
    else the call-site ``precision`` argument.  Like ``method`` resolution
    this happens in Python before tracing, so the jaxpr of a call is
    identical to passing the resolved precision explicitly.

    Args:
        precision: The caller-supplied ``precision=`` argument.
        method: The **resolved** concrete method of the call (never
            ``"auto"``), used for the vector-path rules below; ``None`` skips
            them.
        explicit_method: Whether the caller named the method explicitly
            (``False`` when ``method="auto"`` resolution picked it).

    Returns:
        One of ``PRECISIONS``.

    Raises:
        ValueError: If ``precision`` (argument or environment) is unknown, or
            if an explicitly requested non-default precision is combined with
            an explicit ``method="vector"`` — the vector path never touches
            the matrix engine, so the request cannot be honoured.

    Example:
        >>> resolve_precision("compensated", method="kernel")
        'compensated'
        >>> resolve_precision("compensated", method="vector",
        ...                   explicit_method=False)  # auto picked vector
        'highest'
        >>> try:
        ...     resolve_precision("fast", method="vector")
        ... except ValueError:
        ...     print("rejected")
        rejected
    """
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; expected one of "
                         f"{PRECISIONS}")
    if method == "vector" and explicit_method and precision != "highest":
        raise ValueError(
            f"precision={precision!r} requires a matmul-engine method "
            "('matmul', 'kernel' or 'blocked'); method='vector' never touches "
            "the matrix engine.  Drop precision= (the vector path is the fp32 "
            "reference) or pick an engine method / method='auto'.")
    p = _OVERRIDE[-1] if _OVERRIDE else None
    if p is None:
        p = _env_precision()
    if p is None:
        p = precision
    if method == "vector" and p != "highest":
        # auto/override/env landed on the fp32 reference path: degrade.
        return "highest"
    return p


# ---------------------------------------------------------------------------
# Exact exponent handling (shared with the linrec weighted triangle)
# ---------------------------------------------------------------------------


def normalize_exponents(a, acc):
    """Split ``a`` exactly into mantissas in ``[√½, √2)`` and int32 exponents.

    ``a == a_norm · 2^e`` with no rounding (``frexp`` and the conditional
    doubling are power-of-two moves).  Centering mantissas on 1 (geometric
    mean of the interval endpoints) keeps window products of ``n`` of them
    within ``2^±(n/2)`` — the bound :func:`repro.core.linrec._pair_w` relies
    on for its tile-bounded cumulative products, and the reason the fp16
    split's per-slice scaling never overflows the half-precision range.

    Args:
        a: Float array (zeros map to ``(0, 0)`` like ``frexp``).
        acc: Dtype the mantissas are produced in.

    Returns:
        ``(a_norm, e)`` — mantissas in ``acc`` and int32 exponents.

    Example:
        >>> import jax.numpy as jnp
        >>> m, e = normalize_exponents(jnp.asarray([0.25, 3.0]), jnp.float32)
        >>> [float(v) for v in m], [int(v) for v in e]
        ([1.0, 0.75], [-2, 2])
    """
    m, e = jnp.frexp(a.astype(acc))                     # a = m·2^e, |m| ∈ [½,1)
    small = jnp.abs(m) < _SQRT_HALF
    a_norm = jnp.where(small, m * 2, m).astype(acc)
    es = jnp.where(small, e - 1, e).astype(jnp.int32)
    return a_norm, es


def split_f16(x, axis: int):
    """Exact per-slice scaled Ozaki split of fp32 ``x`` into fp16 high/low parts.

    Slices along ``axis`` (the contraction axis of the matmul the parts feed)
    are scaled by an exact power of two so their largest finite magnitude
    lands in ``[½, 1)`` — inside fp16's range whatever the fp32 exponents
    were (subnormal rows scale *up*, near-overflow rows scale *down*).  The
    high part is the fp16 rounding of the scaled slice; the residual
    (exact in fp32 by Sterbenz) is pre-scaled by ``2^SPLIT_SHIFT`` and
    rounded to fp16 as the low part::

        x ≈ ldexp(hi + ldexp(lo, -SPLIT_SHIFT), e)      (~22 significand bits)

    Non-finite values ride the high part unchanged (``ldexp`` preserves
    inf/nan) with the residual zeroed there, so inf/nan propagate through the
    compensated contraction exactly as through an fp32 one.

    Args:
        x: fp32 array.
        axis: Contraction axis — each slice along it shares one exponent.

    Returns:
        ``(hi, lo, e)`` — fp16 parts shaped like ``x`` and the int32 exponent
        with ``keepdims`` shape (broadcastable against the contraction
        output).

    Example:
        >>> import jax.numpy as jnp
        >>> x = jnp.asarray([[3.0, 0.0078125]])
        >>> hi, lo, e = split_f16(x, axis=-1)
        >>> recon = jnp.ldexp(hi.astype(jnp.float32)
        ...                   + jnp.ldexp(lo.astype(jnp.float32), -SPLIT_SHIFT), e)
        >>> bool(jnp.all(recon == x))
        True
    """
    f32 = jnp.float32
    finite = jnp.isfinite(x)
    mag = jnp.where(finite, jnp.abs(x), jnp.zeros((), f32))
    _, e = jnp.frexp(jnp.max(mag, axis=axis, keepdims=True))
    xs = jnp.ldexp(x, -e)                               # max finite |xs| ∈ [½, 1)
    hi = xs.astype(jnp.float16)
    r = jnp.where(finite, xs - hi.astype(f32), jnp.zeros((), f32))
    lo = jnp.ldexp(r, SPLIT_SHIFT).astype(jnp.float16)
    return hi, lo, e


# ---------------------------------------------------------------------------
# The one precision-dispatched contraction every scan triangle goes through
# ---------------------------------------------------------------------------


def _mm(a, b, acc):
    # fp32 operands contract at full fp32 precision: a TPU's default feeds
    # them to the matrix unit as single bf16 passes, which "highest" must not
    f32 = jnp.dtype(jnp.float32)
    full = jnp.dtype(a.dtype) == f32 or jnp.dtype(b.dtype) == f32
    return jnp.matmul(a, b, preferred_element_type=acc,
                      precision=jax.lax.Precision.HIGHEST if full else None)


def pdot(a, b, *, acc, precision: str, exact: str = "none"):
    """Precision-dispatched ``a @ b`` with ``acc`` accumulation.

    The single contraction helper behind every matmul-engine scan triangle
    (``scan_mm``, ``scan_pipeline``, ``linrec_mm``, ``segscan_mm`` and the
    pure-jnp tile scans).  ``"highest"`` is exactly
    ``jnp.matmul(..., preferred_element_type=acc)``, at
    ``Precision.HIGHEST`` when an operand is fp32 (a TPU otherwise rounds
    fp32 operands to one bf16 pass).

    Operands marked ``exact`` (the 0/1 triangular constants ``U_s``/``L⁻_s``
    and their masked segmented variants) are representable in fp16/bf16
    without rounding and are cast, never split.  If any *data* operand is not
    fp32 — integer mask scans, native bf16/f16 feeds — or ``acc`` is not
    fp32, the call falls through to ``"highest"`` (exactness of integer
    scans is unconditional; sub-fp32 floats already feed the engine
    natively).

    Args:
        a: Left operand, ``(..., m, k)``.
        b: Right operand, ``(..., k, n)``.
        acc: Accumulation dtype (``preferred_element_type``).
        precision: One of ``PRECISIONS`` (already resolved).
        exact: Which operand is an exact 0/1 constant: ``"left"``,
            ``"right"`` or ``"none"`` (both are data; 3 fp16 products, the
            ``lo×lo`` term dropped).

    Returns:
        The product in ``acc``.

    Example:
        >>> import jax.numpy as jnp
        >>> a = jnp.asarray([[1.5, 2.5]])
        >>> u = jnp.triu(jnp.ones((2, 2), jnp.float32))
        >>> hp = pdot(a, u, acc=jnp.float32, precision="highest", exact="right")
        >>> cp = pdot(a, u, acc=jnp.float32, precision="compensated", exact="right")
        >>> bool(jnp.all(hp == cp))   # exactly representable inputs: bit-equal
        True
    """
    acc = jnp.dtype(acc)
    f32 = jnp.dtype(jnp.float32)
    data_f32 = acc == f32
    if exact != "left":
        data_f32 = data_f32 and jnp.dtype(a.dtype) == f32
    if exact != "right":
        data_f32 = data_f32 and jnp.dtype(b.dtype) == f32
    if precision == "highest" or not data_f32:
        return _mm(a, b, acc)
    if precision == "fast":
        return _mm(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), acc).astype(acc)
    # compensated
    if exact == "right":
        hi, lo, e = split_f16(a, axis=-1)
        b16 = b.astype(jnp.float16)
        p = _mm(hi, b16, acc) + jnp.ldexp(_mm(lo, b16, acc), -SPLIT_SHIFT)
        return jnp.ldexp(p, e)
    if exact == "left":
        hi, lo, e = split_f16(b, axis=-2)
        a16 = a.astype(jnp.float16)
        p = _mm(a16, hi, acc) + jnp.ldexp(_mm(a16, lo, acc), -SPLIT_SHIFT)
        return jnp.ldexp(p, e)
    ah, al, ea = split_f16(a, axis=-1)
    bh, bl, eb = split_f16(b, axis=-2)
    p = _mm(ah, bh, acc) + jnp.ldexp(_mm(ah, bl, acc) + _mm(al, bh, acc),
                                     -SPLIT_SHIFT)
    return jnp.ldexp(p, ea + eb)
