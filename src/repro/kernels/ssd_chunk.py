"""Pallas TPU kernel for the chunked gated-linear-recurrence (SSD) scan.

The sequence is processed chunk-by-chunk on the sequential Pallas grid; the inter-chunk
state (the analogue of the paper's running ``partial``) lives in VMEM scratch, so — as
in ``scan_mm`` — the whole recurrence is one kernel with 2·(bytes of q,k,v,gates)
HBM traffic and *all* O(S·Q) work as MXU matmuls:

    cs      = cumsum of the log-decays         (triangle-masked reductions, Eq. 1 form)
    scores  = (C @ B^T) ∘ exp(cs_i - cs_j)     masked causal
    y       = scores @ X + (C ∘ exp(cs)) @ state
    state   = exp(cs_Q) * state + (B ∘ exp(cs_Q - cs))^T @ X
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile import default_interpret

__all__ = ["ssd_chunk_scan"]


def _kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, decay_ref, *,
            q: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)               # (Q, P)
    a = a_ref[0, 0].astype(jnp.float32)               # (1, Q) log decays
    bm = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)              # (Q, N)

    # cumsum of the log decays through the triangles of the paper's A @ U
    # identity, as a column (lane reduction) and as a row (sublane reduction)
    ri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ri >= ci
    cs_col = jnp.sum(jnp.where(causal, a, 0.0), axis=1, keepdims=True)
    a_col = jnp.sum(jnp.where(ri == ci, a, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(ri <= ci, a_col, 0.0), axis=0, keepdims=True)

    lmat = jnp.where(causal, jnp.exp(cs_col - cs_row), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * lmat
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    state = state_ref[...]                            # (N, P)
    y = y + jnp.dot(cm * jnp.exp(cs_col), state,
                    preferred_element_type=jnp.float32)

    total = cs_col[q - 1:q, :]                        # (1, 1)
    decay_to_end = jnp.exp(total - cs_col)            # (Q, 1)
    # the chunk decay crosses VMEM as a lane row: Mosaic cannot broadcast a
    # (1, 1) value over sublanes and lanes at once
    decay_ref[...] = jnp.broadcast_to(jnp.exp(total), decay_ref.shape)
    new_state = decay_ref[...] * state + jax.lax.dot_general(
        bm * decay_to_end, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = new_state
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_chunk_scan(x: jax.Array, a_log: jax.Array, b_mat: jax.Array,
                   c_mat: jax.Array, *, chunk: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    """x: (B,S,H,P); a_log: (B,S,H); b_mat/c_mat: (B,S,H,N) -> y: (B,S,H,P)."""
    interpret = default_interpret(interpret)
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // q

    def to_bh(t, feat):
        # (B,S,H,F) -> (B*H, nc, Q, F)
        t = jnp.moveaxis(t, 2, 1).reshape(bsz * h, sp, feat)
        return t.reshape(bsz * h, nc, q, feat)

    xb = to_bh(x, p)
    ab = to_bh(a_log[..., None], 1).reshape(bsz * h, nc, 1, q)
    bb = to_bh(b_mat, n)
    cb = to_bh(c_mat, n)
    out = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, nc, q, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32),
                        pltpu.VMEM((1, p), jnp.float32)],
        interpret=interpret,
        name=f"ssd_chunk_q{q}",
    )(xb, ab, bb, cb)

    y = out.reshape(bsz, h, sp, p)
    y = jnp.moveaxis(y, 1, 2)[:, :s]
    return y
