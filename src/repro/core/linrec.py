"""Linear-recurrence scan (``y_t = a_t * y_{t-1} + b_t``) on the matmul tile machinery.

The paper's ScanU/ScanUL1 tile scans are the ``a ≡ 1`` special case of a more
general fact: any first-order linear recurrence is an associative scan that a
matrix engine can batch.  Where the prefix sum contracts a tile against the
*all-ones* upper-triangular ``U_s``, the linear recurrence contracts against
the **weighted** triangular matrix

    W[i, j] = Π_{k = j+1 .. i} a_k          (i >= j; 1 on the diagonal)

so one ``W @ b`` MXU contraction yields a whole tile row's recurrence — the
TCU scan formulation of Dakkak et al. and the SIMD² generalized-semiring view
(see PAPERS.md).  ``W`` is built in-register from cumulative products (the
log/product trick of :mod:`repro.core.ssd`): with ``p = cumprod(a')`` (zeros
replaced by 1), ``W[i, j] = p_i / p_j`` wherever no true zero of ``a`` lies in
``(j, i]`` — exactly-representable quotients divide exactly, which is what
keeps integer-valued payloads bit-identical across methods.

:func:`linear_scan` dispatches through the same ``method=`` table as
:func:`repro.core.scan.scan`:

* ``"matmul"`` — chunked ``W @ b`` contractions with a recursive cross-chunk
  affine carry scan (the SSA multi-level blocking of the prefix scan).
* ``"vector"`` — ``jax.lax.associative_scan`` over affine pairs
  ``(a, b) ⊕ (a', b') = (a·a', a'·b + b')`` (the correctness oracle).
* ``"kernel"`` — the fused sequential-grid Pallas kernel
  (:mod:`repro.kernels.linrec_mm`): tile scans with the running state carried
  in VMEM (the affine ``(Π a, sum)`` pair degenerates on a sequential walk).
* ``"blocked"`` — the §4 three-phase pipeline where phase 2 scans per-block
  ``(Π a, trailing affine sum)`` summaries, so multi-block inputs still read
  and write each element once.

Accumulation dtype (:func:`linrec_accum_dtype_for`): floats follow
``accum_dtype_for`` (bf16/f16 -> f32); integer and bool inputs accumulate in
**fp32** — the weighted-triangular construction divides cumulative products,
which needs a field, and exactness for integer-valued payloads is preserved
because exact quotients divide exactly.  This is the one documented deviation
from the prefix-scan dtype rule (int8 -> int32 there).

Numerical contract (enforced by ``tests/test_linrec.py``): every method is
bit-identical to ``"vector"`` for integer-valued payloads whose partial
products/sums stay exactly representable, and within tight ulp tolerance for
fp32/bf16 gated recurrences (``a = exp(a_log) ∈ (0, 1]``).  The in-register
products are exponent-normalized (see :func:`_pair_w`), so windowed products
never under- or overflow *internally* — ``W`` entries saturate to 0/inf only
when the true window product leaves the dtype's range, matching the vector
path's behaviour on the same inputs (no NaNs from ``0/0``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import guards
from repro.core.autotune import maybe_resolve
from repro.core.precision import normalize_exponents, pdot, resolve_precision
from repro.core.primitives import _register, dispatch
from repro.core.scan import METHODS, accum_dtype_for

__all__ = [
    "linear_scan", "cumprod", "cummax", "linrec_accum_dtype_for",
]


def linrec_accum_dtype_for(dtype) -> jnp.dtype:
    """Accumulation dtype for linear-recurrence scans.

    Floats follow :func:`repro.core.scan.accum_dtype_for` (bf16/f16 -> f32);
    integer and bool inputs accumulate in fp32 because the weighted-triangular
    matmul formulation divides cumulative products (a field operation) —
    integer-*valued* payloads stay exact, see the module docstring.

    Args:
        dtype: Input element dtype.

    Returns:
        The ``jnp.dtype`` linear scans over this input accumulate and return
        in.

    Example:
        >>> import jax.numpy as jnp
        >>> str(linrec_accum_dtype_for(jnp.int8)), str(linrec_accum_dtype_for(jnp.bfloat16))
        ('float32', 'float32')
        >>> str(linrec_accum_dtype_for(jnp.float32))
        'float32'
    """
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer) or dtype == jnp.dtype(jnp.bool_):
        return jnp.dtype(jnp.float32)
    return accum_dtype_for(dtype)


# ---------------------------------------------------------------------------
# In-register weighted-triangular algebra (shared with repro.kernels.linrec_mm)
# ---------------------------------------------------------------------------


# Longest axis _pair_w accepts: normalized mantissas lie in [√½, √2), so a
# cumulative product over n of them stays within 2^±(n/2) — safely inside
# fp32's exponent range for n ≤ 256.  Longer chains must be chunked through
# the recursive carry scan (as _linrec_matmul and _linrec_block do).
MAX_TILE = 256


def _pair_w(a: jax.Array, acc) -> jax.Array:
    """Weighted triangular operand ``W[..., i, j] = Π_{k=j+1..i} a_k``.

    The linear-recurrence analogue of the paper's ``U_s`` (which is the
    ``a ≡ 1`` case, transposed): ``(W @ b)[i]`` is the inclusive recurrence of
    row ``b`` under multipliers ``a``, so one batched MXU contraction scans a
    whole tile.  Built in-register from cumulative products of
    **exponent-normalized** multipliers: each ``a_k`` splits exactly into
    ``a_norm_k · 2^{e_k}`` with ``|a_norm_k| ∈ [√½, √2)``
    (:func:`repro.core.precision.normalize_exponents` — the same exact
    power-of-two machinery the compensated fp16 split scales its slices
    with; no rounding), the mantissa product/quotient
    never under- or overflows for tile-bounded windows, and the integer
    exponents travel through an exact ``cumsum``, re-applied per window with
    ``ldexp`` (which saturates gracefully to 0/inf only when the *true*
    window product does).  Zeros of ``a`` are replaced by 1 for the running
    product and re-imposed by masking every window that straddles one (a
    ``cummax`` of the last-zero position, exactly like the boundary masks of
    ``segscan_mm``).  Integer-valued payloads stay bit-exact: normalization
    only moves exponents, so quotients of exactly-representable products
    still divide exactly.
    """
    s = a.shape[-1]
    az = a == 0
    a1 = jnp.where(az, jnp.ones((), acc), a.astype(acc))
    a_norm, e = normalize_exponents(a1, acc)            # |a_norm| ∈ [√½, √2)
    es = jnp.cumsum(e, axis=-1)
    p = jnp.cumprod(a_norm, axis=-1)                    # |p| ∈ 2^±(s/2): safe
    pos = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    lastz = jax.lax.cummax(jnp.where(az, pos, -1), axis=a.ndim - 1)
    ri = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    cj = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    keep = (ri > cj) & (lastz[..., :, None] <= cj)
    ratio = p[..., :, None] / p[..., None, :]
    w = jnp.ldexp(ratio, es[..., :, None] - es[..., None, :])
    w = jnp.where(keep, w, jnp.zeros((), acc))
    return jnp.where(ri == cj, jnp.ones((), acc), w)


def _w_matvec(w: jax.Array, b: jax.Array, acc,
              precision: str = "highest") -> jax.Array:
    """Batched ``(..., s, s) @ (..., s)`` contraction in the accumulation dtype.

    The one data×data contraction of the subsystem: under
    ``precision="compensated"`` *both* operands Ozaki-split (``W`` per row,
    ``b`` per vector — 3 fp16 products, the ``lo×lo`` term dropped).
    """
    return pdot(w, b.astype(acc)[..., None], acc=acc, precision=precision,
                exact="none")[..., 0].astype(acc)


def _linrec_block(a2: jax.Array, b2: jax.Array, acc, precision: str = "highest"):
    """Linear recurrence of one ``(m, s)`` row-major block held in VMEM/registers.

    The ScanUL1 structure generalized to weighted triangles: per-row ``W @ b``
    contractions give the ``m`` row-local recurrences; rows are then chained
    through their affine summaries ``(row product, row-local last value)`` by
    a second weighted-triangular contraction over the ``m`` row products (the
    ``L⁻`` role of paper Eq. 1).  Returns ``(out, mult)`` where ``out`` is the
    block-local recurrence (zero incoming state) and ``mult[r, i] =
    Π a[block start .. (r, i)]`` is the multiplier an incoming carry picks up
    — plain cumulative products, zeros included exactly.
    """
    rowmult = jnp.cumprod(a2.astype(acc), axis=-1)       # (m, s)
    local = _w_matvec(_pair_w(a2, acc), b2, acc, precision)  # (m, s) row-local
    rp = rowmult[..., :, -1]                             # row products
    rl = local[..., :, -1]                               # row-local last values
    if rp.shape[-1] <= MAX_TILE:
        y_rows = _w_matvec(_pair_w(rp, acc), rl, acc, precision)
    else:  # tall blocks: chain the row summaries through the chunked scan
        y_rows = _linrec_matmul(rp, rl, method="matmul", tile_s=128,
                                block_tiles=0, accum_dtype=acc,
                                precision=precision)
    pad_row = [(0, 0)] * (y_rows.ndim - 1) + [(1, 0)]
    carry_rows = jnp.pad(y_rows, pad_row)[..., :-1]      # exclusive
    out = local + rowmult * carry_rows[..., :, None]
    rowprefix = jnp.pad(jnp.cumprod(rp, axis=-1),
                        pad_row, constant_values=1)[..., :-1]
    mult = rowmult * rowprefix[..., :, None]
    return out, mult


# ---------------------------------------------------------------------------
# Method implementations (registered in the shared dispatch table)
# ---------------------------------------------------------------------------


@_register("linear_scan", "vector")
def _linrec_vector(a, b, *, method, tile_s, block_tiles, accum_dtype,
                   precision="highest"):
    """Affine-pair ``associative_scan`` — the correctness oracle."""
    acc = accum_dtype
    av = a.astype(acc)
    # the b leaf's shape must be stable across combines -> broadcast it up
    # front; the (smaller) a leaf only ever combines with itself.
    bv = jnp.broadcast_to(b.astype(acc), jnp.broadcast_shapes(a.shape, b.shape))

    def comb(left, right):
        """Compose affine maps: (right ∘ left)(y) = a_r(a_l y + b_l) + b_r."""
        al, bl = left
        ar, br = right
        return al * ar, ar * bl + br

    _, out = jax.lax.associative_scan(comb, (av, bv), axis=-1)
    return out


@_register("linear_scan", "matmul")
def _linrec_matmul(a, b, *, method, tile_s, block_tiles, accum_dtype,
                   precision="highest"):
    """Chunked ``W @ b`` contractions + recursive cross-chunk affine carry scan.

    Chunks of ``tile_s`` elements each contract against their in-register
    ``W``; the per-chunk summaries ``(Π a, local last value)`` are themselves
    a linear recurrence one level up (the SSA blocking of the prefix scan),
    scanned by recursing until a single chunk remains.

    ``a`` and ``b`` may have broadcast leading dims (rank-aligned by
    ``linear_scan``, equal scan-axis length): ``W`` is built from the
    *unbroadcast* multipliers, so a decay shared across payload dims — the
    SSD cross-chunk case — gets ONE weighted triangle contracted against the
    whole payload batch instead of one triangle per payload element.
    """
    acc = accum_dtype
    q = tile_s
    n = a.shape[-1]
    if n <= q:
        return _w_matvec(_pair_w(a, acc), b, acc, precision)
    pad = (-n) % q
    if pad:  # identity affine element: a = 1, b = 0
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)], constant_values=1)
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    nc = a.shape[-1] // q
    ac = a.reshape(*a.shape[:-1], nc, q)
    bc = b.reshape(*b.shape[:-1], nc, q)
    local = _w_matvec(_pair_w(ac, acc), bc, acc, precision)  # (..., nc, q)
    mult = jnp.cumprod(ac.astype(acc), axis=-1)          # carry multipliers
    pa = mult[..., -1]                                   # chunk products
    sb = local[..., -1]                                  # chunk local lasts
    carry_inc = _linrec_matmul(pa, sb, method=method, tile_s=q,
                               block_tiles=block_tiles, accum_dtype=acc,
                               precision=precision)
    pad_c = [(0, 0)] * (carry_inc.ndim - 1) + [(1, 0)]
    carry_in = jnp.pad(carry_inc, pad_c)[..., :-1]       # exclusive
    out = local + mult * carry_in[..., None]
    out = out.reshape(*out.shape[:-2], nc * q)
    return out[..., :n] if pad else out


def _broadcast_pair(a, b):
    """Materialize the common shape (the Pallas wrappers flatten to rows)."""
    shp = jnp.broadcast_shapes(a.shape, b.shape)
    return jnp.broadcast_to(a, shp), jnp.broadcast_to(b, shp)


@_register("linear_scan", "kernel")
def _linrec_kernel(a, b, *, method, tile_s, block_tiles, accum_dtype,
                   precision="highest"):
    """Fused sequential-grid tile kernel with the VMEM running-state carry."""
    from repro.kernels import ops as _kops  # local import to avoid cycle
    a, b = _broadcast_pair(a, b)
    return _kops.linrec_kernel(a, b, s=tile_s, accum_dtype=accum_dtype,
                               precision=precision)


@_register("linear_scan", "blocked")
def _linrec_blocked(a, b, *, method, tile_s, block_tiles, accum_dtype,
                    precision="highest"):
    """§4 three-phase pipeline with an affine phase-2 carry scan."""
    from repro.kernels import ops as _kops  # local import to avoid cycle
    a, b = _broadcast_pair(a, b)
    return _kops.linrec_blocked_kernel(a, b, s=tile_s, block_tiles=block_tiles,
                                       accum_dtype=accum_dtype,
                                       precision=precision)


# ---------------------------------------------------------------------------
# Dispatch core with the analytic adjoint
# ---------------------------------------------------------------------------
#
# The VJP of a linear recurrence is itself a linear recurrence, run in
# reverse:  with  y_t = a_t y_{t-1} + b_t  and output cotangent ȳ,
#
#     λ_t = ȳ_t + a_{t+1} λ_{t+1},      b̄_t = λ_t,      ā_t = λ_t · y_{t-1}.
#
# Differentiating through the W construction instead would square tiny
# cumulative products in the quotient rule (NaN/inf for strongly decaying
# gates), and the Pallas methods have no autodiff at all — the custom VJP
# gives every method the same robust analytic gradient, computed by the very
# same dispatcher (the backward pass is one more method-matched scan).


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _linrec_core(a, b, method, tile_s, block_tiles, acc, precision):
    """Method-dispatched inclusive recurrence over the last axis (zero init)."""
    return dispatch("linear_scan", method)(
        a, b, method=method, tile_s=tile_s, block_tiles=block_tiles,
        accum_dtype=acc, precision=precision)


def _linrec_core_fwd(a, b, method, tile_s, block_tiles, acc, precision):
    """Forward pass; residuals are the multipliers and the output states."""
    y = _linrec_core(a, b, method, tile_s, block_tiles, acc, precision)
    return y, (a, y)


def _unbroadcast(x, shape):
    """Sum-reduce ``x`` back to a rank-aligned primal ``shape`` it broadcast from."""
    if x.shape == tuple(shape):
        return x
    axes = tuple(i for i, (xs, ps) in enumerate(zip(x.shape, shape))
                 if ps == 1 and xs != 1)
    return jnp.sum(x, axis=axes, keepdims=True)


def _linrec_core_bwd(method, tile_s, block_tiles, acc, precision, res, g):
    """Reverse-recurrence adjoint (module comment above), method-matched.

    ``b`` enters the core pre-broadcast to the output shape (public wrapper),
    so its cotangent is ``lam`` as-is; ``a`` may carry broadcast leading dims
    (shared decays) whose cotangent sum-reduces back to the primal shape.
    The backward recurrence reruns the dispatcher with the same ``precision``
    — a compensated forward pass gets a compensated adjoint.
    """
    a, y = res
    ash = jnp.concatenate([a[..., 1:], jnp.ones_like(a[..., :1])], axis=-1)
    lam = jnp.flip(
        _linrec_core(jnp.flip(ash, axis=-1), jnp.flip(g.astype(acc), axis=-1),
                     method, tile_s, block_tiles, acc, precision), axis=-1)
    y_prev = jnp.concatenate([jnp.zeros_like(y[..., :1]), y[..., :-1]], axis=-1)
    ga = _unbroadcast(lam * y_prev, a.shape).astype(a.dtype)
    return ga, lam.astype(acc)


_linrec_core.defvjp(_linrec_core_fwd, _linrec_core_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def linear_scan(
    a: jax.Array,
    b: jax.Array,
    *,
    axis: int = -1,
    exclusive: bool = False,
    reverse: bool = False,
    method: str = "auto",
    precision: str = "highest",
    initial=None,
    tile_s: int = 128,
    block_tiles: int = 8,
    accum_dtype: Optional[jnp.dtype] = None,
    nonfinite: str = "propagate",
) -> jax.Array:
    """First-order linear recurrence ``y_t = a_t * y_{t-1} + b_t`` along ``axis``.

    The recurrent analogue of :func:`repro.core.scan.scan`: same ``method=``
    table, same tile machinery, with the all-ones triangular operand replaced
    by the weighted triangle ``W`` (module docstring).  ``a ≡ 1`` recovers the
    prefix sum; ``b ≡ 0`` with ``initial=1`` recovers the cumulative product
    (:func:`cumprod`).  SSD/Mamba/xLSTM cross-chunk state propagation routes
    through here (:mod:`repro.core.ssd`).

    Args:
        a: Multipliers ``(..., n)`` — broadcast against ``b``.
        b: Additive inputs ``(..., n)`` — broadcast against ``a``.
        axis: Axis to scan along (scans execute over the last axis; others
            are moved there and back).
        exclusive: If true, return the state *entering* each step —
            ``out[t] = y_{t-1}`` with ``out[0] = initial`` (or 0).  Note the
            shift does not apply ``a_t``.
        reverse: Scan from the end (``y_t = a_t * y_{t+1} + b_t``).
        method: ``"auto"`` (default; resolved from the committed tuning table
            by :mod:`repro.core.autotune`) or one of ``METHODS`` (see module
            docstring for what runs).
        precision: Engine feed precision for the ``W @ b`` contractions
            (:mod:`repro.core.precision`, dispatch rule 9) — ``"highest"``
            (fp32, default), ``"compensated"`` (fp16 Ozaki splits of *both*
            operands, documented ulp bound vs ``"vector"``) or ``"fast"``
            (bf16, loose bound).  Applies to both the forward scan and its
            custom-VJP backward recurrence; only fp32 contractions are
            affected.  Explicit ``method="vector"`` rejects a non-default
            value.
        initial: Optional starting state ``y_{-1}`` (scalar or array
            broadcastable to ``a``/``b`` minus the scan axis).  Folded into
            the first step exactly (``b_0 + a_0 * initial``).  Length-1 scans
            then short-circuit to the direct fused multiply-add — bit-
            identical for every method, no kernel launch (the decode-step
            fast path).
        tile_s: Elements per tile row ``s``; a kernel tile covers ``s²``
            elements, the matmul path chunks ``s`` at a time.
        block_tiles: Tiles per block for ``method="blocked"``.
        accum_dtype: Accumulation dtype override; defaults to
            :func:`linrec_accum_dtype_for` of the broadcast input dtype.
        nonfinite: Non-finite input policy (:mod:`repro.core.guards`,
            dispatch rule 10; ``nonfinite_override`` context >
            ``REPRO_NONFINITE`` env > this argument).  ``"propagate"``
            (default) keeps IEEE semantics with zero added ops; ``"raise"``
            rejects non-finite operands (eagerly when concrete, checkified
            under trace); ``"sanitize"`` replaces non-finite elements with
            the affine identity — ``a -> 1``, ``b -> 0`` — so corrupted steps
            pass the running state through unchanged.

    Returns:
        The scanned array (broadcast shape of ``a`` and ``b``) in the
        accumulation dtype.

    Raises:
        ValueError: If ``method``, ``precision`` or ``nonfinite`` is unknown,
            ``axis`` is out of bounds, or an explicit non-default
            ``precision`` is combined with an explicit ``method="vector"``.

    Example:
        >>> import jax.numpy as jnp
        >>> a = jnp.asarray([1.0, 2.0, 0.0, 3.0])
        >>> b = jnp.asarray([1.0, 1.0, 5.0, 1.0])
        >>> [float(v) for v in linear_scan(a, b)]        # y = a*y_prev + b
        [1.0, 3.0, 5.0, 16.0]
        >>> [float(v) for v in linear_scan(jnp.ones(4), jnp.ones(4))]  # cumsum
        [1.0, 2.0, 3.0, 4.0]
        >>> [float(v) for v in linear_scan(a, b, exclusive=True, initial=7.0)]
        [7.0, 8.0, 17.0, 5.0]
    """
    if method != "auto" and method not in METHODS:
        raise ValueError(f"unknown scan method {method!r}; expected one of "
                         f"{METHODS + ('auto',)}")
    if not 2 <= tile_s <= MAX_TILE:
        raise ValueError(
            f"tile_s must be in [2, {MAX_TILE}] (the exponent-normalized "
            f"window-product range), got {tile_s}")
    # Rank-align WITHOUT materializing the broadcast: a decay shared across
    # payload dims (the SSD cross-chunk case) must reach the matmul path
    # unbroadcast so one weighted triangle serves the whole payload batch.
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    nd = max(a.ndim, b.ndim, 1)
    a = a.reshape((1,) * (nd - a.ndim) + a.shape)
    b = b.reshape((1,) * (nd - b.ndim) + b.shape)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else linrec_accum_dtype_for(jnp.result_type(a.dtype, b.dtype))

    orig_axis = guards.validate_axis(axis, nd, op="linear_scan")
    moved = orig_axis != nd - 1
    if moved:
        a = jnp.moveaxis(a, orig_axis, -1)
        b = jnp.moveaxis(b, orig_axis, -1)
    n = max(a.shape[-1], b.shape[-1])
    if a.shape[-1] != n:  # scan axis must be real on both operands
        a = jnp.broadcast_to(a, a.shape[:-1] + (n,))
    if b.shape[-1] != n:
        b = jnp.broadcast_to(b, b.shape[:-1] + (n,))
    explicit_method = method != "auto"
    method = maybe_resolve(method, "linear_scan", n,
                           jnp.result_type(a.dtype, b.dtype))
    precision = resolve_precision(precision, method=method,
                                  explicit_method=explicit_method)
    nonfinite = guards.resolve_nonfinite(nonfinite)
    a = guards.apply_nonfinite(a, nonfinite, op="linear_scan", identity=1.0)
    b = guards.apply_nonfinite(b, nonfinite, op="linear_scan", identity=0.0)
    full = jnp.broadcast_shapes(a.shape, b.shape)
    # b is output-sized anyway — materialize it (keeps the custom-VJP
    # cotangent shapes trivial); a stays unbroadcast for the shared-W saving.
    b = jnp.broadcast_to(b, full)
    if reverse:
        a = jnp.flip(a, axis=-1)
        b = jnp.flip(b, axis=-1)
    if n == 0:
        out = jnp.zeros(full, acc)
    else:
        a = a.astype(acc)  # float cotangents for the custom VJP below
        b = b.astype(acc)
        if initial is not None:
            init = jnp.asarray(initial, acc)
            b0 = jnp.broadcast_to(b[..., 0] + a[..., 0] * init, full[:-1])
            rest = jnp.broadcast_to(b[..., 1:], full[:-1] + (n - 1,))
            b = jnp.concatenate([b0[..., None], rest], axis=-1)
        if n == 1:
            # y_0 = a_0·initial + b_0 — already folded into b; every method
            # computes exactly this, so skip the dispatch (and any kernel
            # launch) for the stateful-decode single-step case.
            out = jnp.broadcast_to(b, full).astype(acc)
        else:
            out = _linrec_core(a, b, method, tile_s, block_tiles, acc,
                               precision)
        if exclusive:
            if initial is not None:
                init = jnp.asarray(initial, acc)
                init = init[..., None] if init.ndim else init  # + scan axis
                first = jnp.broadcast_to(init, out[..., :1].shape)
            else:
                first = jnp.zeros_like(out[..., :1])
            out = jnp.concatenate([first, out[..., :-1]], axis=-1)
    if reverse:
        out = jnp.flip(out, axis=-1)
    if moved:
        out = jnp.moveaxis(out, -1, orig_axis)
    return out


def cumprod(x: jax.Array, axis: int = -1, **kw) -> jax.Array:
    """Cumulative product along ``axis`` — ``linear_scan`` with ``b = 0``.

    ``y_t = x_t * y_{t-1}`` from ``initial = 1`` is exactly the cumulative
    product, so every ``method=`` runs it on the same tile machinery.

    Args:
        x: Input array.
        axis: Axis to scan along.
        **kw: Forwarded to :func:`linear_scan` (``method=``, ``reverse=``, …).

    Returns:
        Cumulative products in the linrec accumulation dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> [int(v) for v in cumprod(jnp.asarray([1, 2, 3, 4], jnp.int32))]
        [1, 2, 6, 24]
    """
    kw.setdefault("initial", 1.0)
    return linear_scan(x, jnp.zeros_like(x), axis=axis, **kw)


def cummax(x: jax.Array, axis: int = -1, *, method: str = "auto",
           reverse: bool = False, tile_s: int = 128,
           block_tiles: int = 8) -> jax.Array:
    """Cumulative maximum along ``axis`` under the same ``method=`` contract.

    The max-plus (tropical) semiring instance of the tile scan: within a
    chunk the running maximum is a masked ``(s, s)`` reduce (the tropical
    ``A @ U_s``), and chunk maxima propagate through an exclusive carry — the
    same two-level structure as the matmul prefix scan.  ``"vector"`` is
    ``jax.lax.cummax``; the other three methods share the chunked tropical
    contraction (max has no fused Pallas specialization yet — the kernel and
    blocked entries alias the matmul tiling, keeping the validation and
    dtype rules of the dispatch contract).  Output dtype equals the input
    dtype (maximum never widens), and every method is bit-identical.

    Args:
        x: Input array (any ordered dtype).
        axis: Axis to scan along.
        method: One of ``METHODS``.
        reverse: Scan from the end (suffix maxima).
        tile_s: Chunk length for the tropical contraction.
        block_tiles: Accepted for signature compatibility with the other
            dispatched scans; the tropical contraction has no blocked
            specialization, so it is unused.  Unsupported keywords (e.g.
            ``exclusive``) raise ``TypeError`` rather than being silently
            ignored.

    Returns:
        Running maxima, same shape and dtype as ``x``.

    Raises:
        ValueError: If ``method`` is unknown.

    Example:
        >>> import jax.numpy as jnp
        >>> cummax(jnp.asarray([1, 3, 2, 5, 4], jnp.int32)).tolist()
        [1, 3, 3, 5, 5]
    """
    if method != "auto" and method not in METHODS:
        raise ValueError(f"unknown scan method {method!r}; expected one of "
                         f"{METHODS + ('auto',)}")
    if x.ndim:
        method = maybe_resolve(method, "cummax", x.shape[axis % x.ndim], x.dtype)
    if x.dtype == jnp.bool_:  # lax.cummax rejects bool; max == prefix-any
        out = cummax(x.astype(jnp.int8), axis=axis, method=method,
                     reverse=reverse, tile_s=tile_s)
        return out > 0
    orig_axis = axis % max(x.ndim, 1)
    if x.ndim and orig_axis != x.ndim - 1:
        out = cummax(jnp.moveaxis(x, orig_axis, -1), method=method,
                     reverse=reverse, tile_s=tile_s)
        return jnp.moveaxis(out, -1, orig_axis)
    if reverse:
        return jnp.flip(cummax(jnp.flip(x, axis=-1), method=method,
                               tile_s=tile_s), axis=-1)
    n = x.shape[-1]
    if n == 0:
        return x
    if method == "vector":
        return jax.lax.cummax(x, axis=x.ndim - 1)
    lowest = (jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer)
              else jnp.finfo(x.dtype).min)
    q = tile_s
    *lead, _ = x.shape
    pad = (-n) % q
    xp = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad)],
                 constant_values=lowest) if pad else x
    nc = xp.shape[-1] // q
    xc = xp.reshape(*lead, nc, q)
    ri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    masked = jnp.where(cj <= ri, xc[..., None, :], jnp.asarray(lowest, x.dtype))
    local = jnp.max(masked, axis=-1)                     # tropical A @ U_s
    chunk_max = local[..., -1]
    carry = jax.lax.cummax(chunk_max, axis=chunk_max.ndim - 1)
    pad_c = [(0, 0)] * len(lead) + [(1, 0)]
    carry = jnp.pad(carry, pad_c, constant_values=lowest)[..., :-1]
    out = jnp.maximum(local, carry[..., None]).reshape(*lead, nc * q)
    return out[..., :n] if pad else out
