"""In-kernel tile algebra shared by the Pallas kernels.

Everything here runs inside a kernel body on 2-D ``(rows, s)`` values, with
``s`` the lane width (128 on a TPU), and uses only what Mosaic lowers:
matmuls on the MXU, elementwise ops, lane reductions, static slices and
``pltpu.roll``.  Mosaic has no cumulative primitives (``cumsum``,
``cumprod``, ``cummax``), no dynamic gather/scatter across a row and no
int32 matmul, so every prefix in this module is either a triangular matmul
(the paper's Eq. 1) or a log-depth Kogge-Stone doubling over rolled copies.

Carries between tiles are ``(1, s)`` rows holding one value in every lane
(:func:`last_as_row`): Mosaic broadcasts a ``(1, 1)`` value along lanes or
along sublanes, but not along both at once.

:func:`default_interpret` is the one place the interpret default is decided:
native Mosaic lowering on a TPU, the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import pdot

__all__ = ["default_interpret", "triangle", "last_as_row", "tile_scan",
           "sublane_cumsum", "affine_scan"]


def default_interpret(interpret: bool | None) -> bool:
    """``interpret`` if given, else interpret everywhere but a TPU.

    A TPU never gets interpret mode by default: a kernel that Mosaic refuses
    raises there instead of running interpreted.
    """
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def triangle(s: int, dtype, kind: str) -> jax.Array:
    """In-register 0/1 triangle: ``"upper"`` is ``U_s`` (``i <= j``),
    ``"strict_lower"`` is ``L⁻_s`` (``i > j``)."""
    ri = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    mask = ri <= ci if kind == "upper" else ri > ci
    return mask.astype(dtype)


def last_as_row(x: jax.Array) -> jax.Array:
    """The last element of a 2-D value as a ``(1, s)`` lane-broadcast row."""
    r, s = x.shape
    return jnp.broadcast_to(x[r - 1:r, s - 1:s], (1, s))


def sublane_cumsum(v: jax.Array) -> jax.Array:
    """Inclusive prefix sum down axis 0 by Kogge-Stone doubling (VPU/XLU)."""
    n = v.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    k = 1
    while k < n:
        v = v + jnp.where(row >= k, pltpu.roll(v, k, 0), jnp.zeros_like(v))
        k *= 2
    return v


def tile_scan(a: jax.Array, *, variant: str, acc, precision: str) -> jax.Array:
    """Inclusive row-major scan of one ``(r, s)`` tile, zero incoming carry.

    ``scanul1`` (paper Alg. 2, Eq. 1): ``A @ U_s`` gives the row-local
    scans and ``L⁻_r @ A`` the column sums of every earlier row, whose lane
    sum is the row's carry — two MXU products and one lane reduction.
    ``scanu`` (Alg. 1): one ``A @ U_s`` product; the row carries propagate on
    the vector unit (log-depth doubling instead of Ascend's serial loop).
    """
    r, s = a.shape
    local = pdot(a, triangle(s, a.dtype, "upper"), acc=acc,
                 precision=precision, exact="right").astype(acc)
    if variant == "scanul1":
        above = pdot(triangle(r, a.dtype, "strict_lower"), a, acc=acc,
                     precision=precision, exact="left").astype(acc)
        return local + jnp.sum(above, axis=1, keepdims=True)
    rows = jnp.broadcast_to(local[:, s - 1:s], (r, s))
    return local + (sublane_cumsum(rows) - rows)


def affine_scan(a: jax.Array, b: jax.Array, axis: int):
    """Inclusive scan of ``y = a*y + b`` along ``axis`` from a zero state.

    Kogge-Stone doubling over ``(a, b)`` pairs under affine composition.
    Returns ``(mult, y)``: ``mult[i]`` is the product of ``a`` from the start
    through ``i`` (what an incoming state is multiplied by), ``y[i]`` the
    recurrence value.
    """
    n = a.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    k = 1
    while k < n:
        live = idx >= k
        a_prev = pltpu.roll(a, k, axis)
        b_prev = pltpu.roll(b, k, axis)
        b = jnp.where(live, a * b_prev + b, b)
        a = jnp.where(live, a * a_prev, a)
        k *= 2
    return a, b
