"""Plain float32 reference of a sliding-window GQA decoder: its layers
alternate sliding-window attention (a position sees itself and the
``sliding_window - 1`` positions before it) and full causal attention, as the
file's ``layer_types`` list them; RoPE, dense SiLU MLPs, a tied head.

An architecture the ``decoder`` module does not cover, kept for the plug-in
test: it joins a copy of the benchmark as new files only, this module as
``reference/windowed.py`` beside a configuration, a traffic and a limits
file.  Weights are remade from the seed one layer at a time, by the program's
layout: with the cycle ``("local", "global")`` layer ``l`` is entry ``l // 2``
of the stack ``stack/sub{l % 2}``.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from chip import weights
from chip.counts import BYTES, head_dim, served
from chip.reference.decoder import F32, _mlp, _mm, padded_vocab, rmsnorm, rope

KINDS = {"sliding_attention": "local", "full_attention": "global"}


def layer_pattern(cfg: Dict):
    """The shortest cycle of the program's layer kinds that repeats into the
    file's ``layer_types``."""
    kinds = [KINDS[t] for t in cfg["layer_types"]]
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    return tuple(kinds[:p])


PUBLISHED = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "head_dim": head_dim, "d_ff": "intermediate_size",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps", "local_window": "sliding_window",
    "layer_pattern": layer_pattern,
    "qk_norm": lambda cfg: False,
    "tie_embeddings": lambda cfg: served(cfg, "tie_word_embeddings"),
}


def layer_params(cfg: Dict, layer: int, *, active: bool) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2 * d * q + 2 * d * kv + 3 * d * cfg["intermediate_size"]


def norm_params(cfg: Dict) -> int:
    return (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]


def cache_bytes_per_token(cfg: Dict) -> int:
    """The program keeps every position in every layer, windowed or not."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * cfg["num_hidden_layers"] * BYTES[cfg["torch_dtype"]])


def state_bytes_per_row(cfg: Dict) -> int:
    return 0


def attn_flops(cfg: Dict, context: int) -> int:
    """A windowed layer attends to at most ``sliding_window`` positions."""
    seen = sum(min(context, cfg["sliding_window"]) if t == "sliding_attention"
               else context for t in cfg["layer_types"])
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * seen


class Reference:
    """The reference model of one configuration file at one precision."""

    def __init__(self, cfg: Dict, quant: Optional[str] = None):
        if not served(cfg, "tie_word_embeddings"):
            raise NotImplementedError("the reference reads a tied output head")
        self.cfg = cfg
        self.quant = quant
        self._layer = jax.jit(self._layer_impl, static_argnums=(1, 3))
        self._embed = jax.jit(lambda key, t: self._table(key)[t])
        self._logits = jax.jit(self._logits_impl)

    def _leaf(self, key, path, layer, shape):
        return weights.leaf(key, path, layer, shape, F32)

    def _layer_impl(self, key, prefix, layer, window, x):
        cfg, quant = self.cfg, self.quant
        d, hd, f = cfg["hidden_size"], head_dim(cfg), cfg["intermediate_size"]
        nh, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        eps = cfg["rms_norm_eps"]
        b, t, _ = x.shape
        h = rmsnorm(x, self._leaf(key, f"{prefix}/norm1/g", layer, (d,)), eps)

        def proj(name, n):
            w = self._leaf(key, f"{prefix}/attn/{name}", layer, (d, n * hd))
            return _mm("btd,de->bte", h, w, quant).reshape(b, t, n, hd)

        q = rope(proj("wq", nh), cfg["rope_theta"])
        k = jnp.repeat(rope(proj("wk", nk), cfg["rope_theta"]), nh // nk, axis=2)
        v = jnp.repeat(proj("wv", nk), nh // nk, axis=2)
        s = _mm("bqhd,bkhd->bhqk", q, k, quant) * hd ** -0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (i - j < window)
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", pr, v, quant).reshape(b, t, nh * hd)
        wo = self._leaf(key, f"{prefix}/attn/wo", layer, (nh * hd, d))
        x = x + _mm("bte,ed->btd", o, wo, quant)
        h = rmsnorm(x, self._leaf(key, f"{prefix}/norm2/g", layer, (d,)), eps)
        return x + _mlp(h, self._leaf(key, f"{prefix}/mlp/w_gate", layer, (d, f)),
                        self._leaf(key, f"{prefix}/mlp/w_up", layer, (d, f)),
                        self._leaf(key, f"{prefix}/mlp/w_down", layer, (f, d)),
                        quant)

    def _table(self, key):
        cfg = self.cfg
        t = self._leaf(key, "embed/embed", None,
                       (padded_vocab(cfg), cfg["hidden_size"]))
        return t[: cfg["vocab_size"]]

    def _logits_impl(self, key, x, rows):
        cfg = self.cfg
        g = self._leaf(key, "final_norm/g", None, (cfg["hidden_size"],))
        h = jnp.take_along_axis(x, rows[..., None], axis=1)
        h = rmsnorm(h, g, cfg["rms_norm_eps"])
        return _mm("brd,vd->brv", h, self._table(key), self.quant)

    def logits(self, seed: int, tokens, rows):
        """Float32 logits (B, R, vocab) of ``tokens`` (B, T) read at the
        positions ``rows`` (B, R)."""
        cfg = self.cfg
        key = weights.seed_key(seed)
        x = self._embed(key, jnp.asarray(tokens, jnp.int32))
        cycle = layer_pattern(cfg)
        for layer, kind in enumerate(cfg["layer_types"]):
            window = cfg["sliding_window"] if KINDS[kind] == "local" else None
            x = self._layer(key, f"stack/sub{layer % len(cycle)}",
                            jnp.int32(layer // len(cycle)), window, x)
        return self._logits(key, x, jnp.asarray(rows, jnp.int32))
