"""Sort-based nucleus (top-p) sampling, plainly, and the gap the check reads.

The semantics are those of the Llama 3 reference ``sample_top_p``: sort the
probabilities in descending order, keep every token whose preceding mass is
at most ``p``, and draw by inverse transform with the uniform ``u`` over the
kept mass.  Everything is float32 with a stable sort.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _sorted(logits, temperature):
    probs = jax.nn.softmax(logits.astype(F32) / temperature, axis=-1)
    order = jnp.argsort(-probs, axis=-1, stable=True)
    sp = jnp.take_along_axis(probs, order, -1)
    cum = jnp.cumsum(sp, axis=-1)
    return order, sp, cum


def _target(sp, cum, u, p):
    keep = (cum - sp) <= p
    kept = jnp.where(keep, sp, 0.0)
    cdf = jnp.cumsum(kept, axis=-1)
    return cdf, u * cdf[..., -1]


def sample(logits, u, *, p, temperature):
    """The token each row of ``logits`` (..., V) draws with its uniform ``u`` (...)."""
    order, sp, cum = _sorted(logits, temperature)
    cdf, theta = _target(sp, cum, u, p)
    idx = jnp.sum(cdf < theta[..., None], axis=-1)
    idx = jnp.minimum(idx, logits.shape[-1] - 1)
    return jnp.take_along_axis(order, idx[..., None], -1)[..., 0]


def logit_gap(logits, token, want):
    """How far apart the logits of ``token`` and ``want`` lie in each row."""
    pick = lambda t: jnp.take_along_axis(logits, t[..., None], -1)[..., 0]  # noqa: E731
    return jnp.abs(pick(token) - pick(want))
