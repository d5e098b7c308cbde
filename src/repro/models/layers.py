"""Basic layers (explicit pytree params — no flax dependency)."""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.utils.sharding import constrain

_DT = threading.local()


def compute_dtype():
    return getattr(_DT, "dtype", jnp.bfloat16)


@contextlib.contextmanager
def use_compute_dtype(dt):
    prev = compute_dtype()
    _DT.dtype = jnp.dtype(dt)
    try:
        yield
    finally:
        _DT.dtype = prev


def ninit(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def linear_init(key, d_in, d_out, *, dtype=jnp.float32, scale=None):
    return {"w": ninit(key, (d_in, d_out), scale, dtype)}


# Inside ``fenced_rows()`` (a decode step) row-wise ops (matmuls, norms) run
# on one structure whatever the batch.  XLA compiles a one-row op unlike a
# batched one -- a TPU turns a one-row matmul into a VPU multiply-reduce
# instead of an MXU matmul and lays a one-row array out differently, so its
# reductions group the features differently; a CPU uses a matrix-vector
# kernel -- and it fuses neighbouring elementwise ops into the matmuls of
# different batches differently.  Each rounds differently.  Fenced, the rows
# are flattened, padded to a multiple of the TPU's 8-row sublane tile and
# wrapped in optimization barriers (which keep XLA from slicing the padding
# off or fusing across): a decode row then gets the same bits alone as in a
# batch -- what makes a continuously batched request replay its solo stream
# (dispatch rule 11).  Training and prefill take the plain path.
ROW_TILE = 8


@contextlib.contextmanager
def fenced_rows():
    """Trace the row-wise ops inside on the fenced decode structure."""
    prev = getattr(_DT, "fenced", False)
    _DT.fenced = True
    try:
        yield
    finally:
        _DT.fenced = prev


def _row_padded(fn, x):
    """``fn(x)`` for a row-wise ``fn`` over the last axis of ``x``; inside
    :func:`fenced_rows` the flattened rows are padded to a multiple of
    :data:`ROW_TILE` and fenced."""
    if not getattr(_DT, "fenced", False):
        return fn(x)
    rows = math.prod(x.shape[:-1])
    barrier = jax.lax.optimization_barrier
    y = barrier(fn(barrier(jnp.pad(x.reshape(rows, x.shape[-1]),
                                   ((0, -rows % ROW_TILE), (0, 0))))))
    return y[:rows].reshape(*x.shape[:-1], y.shape[-1])


def linear(p, x, cdt=None):
    # No f32 materialisation of the output: the TPU MXU accumulates bf16 matmuls
    # in f32 internally regardless, and a materialised f32 result DOUBLES the wire
    # bytes of every tensor-parallel all-reduce placed on it (§Perf I2).
    cdt = cdt or compute_dtype()
    w = p["w"].astype(cdt)
    return _row_padded(lambda v: jnp.matmul(v.astype(cdt), w), x)


def rmsnorm_init(d, dtype=jnp.float32):
    return {"g": jnp.zeros((d,), dtype)}


def rmsnorm(p, x, eps=1e-6):
    def norm(v):
        vf = v.astype(jnp.float32)
        var = jnp.mean(vf * vf, axis=-1, keepdims=True)
        g = 1.0 + p["g"].astype(jnp.float32)
        return (vf * jax.lax.rsqrt(var + eps) * g).astype(v.dtype)
    return _row_padded(norm, x)


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def embed_init(key, vocab, d, dtype=jnp.float32, scale=1.0):
    return {"embed": ninit(key, (vocab, d), scale=scale, dtype=dtype)}


def embed_lookup(p, tokens, cdt=None):
    cdt = cdt or compute_dtype()
    return jnp.take(p["embed"].astype(cdt), tokens, axis=0)


def unembed(p, x, cdt=None):
    cdt = cdt or compute_dtype()
    w = p["embed"].astype(cdt)
    return _row_padded(lambda v: jnp.matmul(
        v.astype(cdt), w.T, preferred_element_type=jnp.float32), x)


ACTS = {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu_nogate": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu}


def mlp_init(key, d, d_ff, *, gated=True, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w_up": ninit(ks[0], (d, d_ff), dtype=dtype),
         "w_down": ninit(ks[1], (d_ff, d), dtype=dtype)}
    if gated:
        p["w_gate"] = ninit(ks[2], (d, d_ff), dtype=dtype)
    return p


def mlp(p, x, act="silu"):
    up = linear({"w": p["w_up"]}, x)
    if "w_gate" in p:
        gate = linear({"w": p["w_gate"]}, x)
        h = ACTS[act](gate) * up
    else:
        h = ACTS[act](up)
    h = constrain(h, "dp", None, "model")
    return linear({"w": p["w_down"]}, h)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta=10000.0):
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    pos = positions.astype(jnp.float32)
    ang = pos[..., None] * freqs                       # (B, S, D/2)
    if ang.ndim == 2:                                  # (S, D/2) -> (1, S, D/2)
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq_len: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((seq_len, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)
