"""Plain float32 reference forward passes: a GQA decoder with per-head q/k
RMSNorm (Qwen3) and a DeepSeek-MoE decoder (dense leading layers, 64 routed
experts with softmax top-k routing, shared experts).

Written from the published architectures in straightforward ``jax.numpy``,
with no kernels, no cache and no batching tricks: the whole sequence goes
through full causal attention, layer by layer.  Weights are made again from
the seed by :mod:`chip.weights`, one layer at a time, so the reference never
holds more than one layer's float32 weights and takes nothing the program
made.  Leaves are named by the program's parameter layout (the checkpoint
format), which is the only thing the two share.

``quant="fp8"`` rounds both operands of every matrix product to
``float8_e4m3fn`` with one absmax scale per tensor: the control, computed in
the precision below the bfloat16 that the configurations state.

A configuration file names this module with ``"reference": "decoder"``; the
module owns all that depends on the architecture: :data:`PUBLISHED` (what
``serve_as`` must equal), the counts behind :mod:`chip.counts`, and
:class:`Reference`.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from chip import weights
from chip.counts import BYTES, head_dim, served

# program field (``moe.<field>`` inside the MoE group) -> the published key
# whose value it must equal, or a function of the file giving that value
PUBLISHED = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "head_dim": head_dim, "d_ff": "intermediate_size",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps",
    "qk_norm": lambda cfg: cfg["model_type"] == "qwen3",
    "tie_embeddings": lambda cfg: served(cfg, "tie_word_embeddings"),
    "moe.n_experts": "n_routed_experts", "moe.top_k": "num_experts_per_tok",
    "moe.d_ff_expert": "moe_intermediate_size",
    "moe.n_shared": "n_shared_experts",
    "moe.first_k_dense": "first_k_dense_replace",
}


# ---- counts (see chip.counts) ----

def _attn_params(cfg: Dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def _is_moe_layer(cfg: Dict, layer: int) -> bool:
    return ("n_routed_experts" in cfg
            and layer >= cfg.get("first_k_dense_replace", 0)
            and layer % cfg.get("moe_layer_freq", 1) == 0)


def layer_params(cfg: Dict, layer: int, *, active: bool) -> int:
    """Matmul parameters of one layer (all experts, or only those a token
    runs through when ``active``)."""
    d = cfg["hidden_size"]
    n = _attn_params(cfg)
    if _is_moe_layer(cfg, layer):
        f = cfg["moe_intermediate_size"]
        routed = cfg["num_experts_per_tok"] if active else cfg["n_routed_experts"]
        n += d * cfg["n_routed_experts"]                       # router
        n += (routed + cfg.get("n_shared_experts", 0)) * 3 * d * f
    else:
        n += 3 * d * cfg["intermediate_size"]
    return n


def norm_params(cfg: Dict) -> int:
    """Every norm weight: two per layer (and Qwen3's per-head q/k norms),
    then the final norm."""
    qk = 2 * head_dim(cfg) if cfg["model_type"] == "qwen3" else 0
    return (cfg["num_hidden_layers"] * (2 * cfg["hidden_size"] + qk)
            + cfg["hidden_size"])


def cache_bytes_per_token(cfg: Dict) -> int:
    """Keys and values of one position in every layer."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * cfg["num_hidden_layers"] * BYTES[cfg["torch_dtype"]])


def state_bytes_per_row(cfg: Dict) -> int:
    """No recurrent state: every layer attends."""
    return 0


def attn_flops(cfg: Dict, context: int) -> int:
    """Score and value FLOPs of one token attending to ``context`` positions,
    over all layers."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg) * context
            * cfg["num_hidden_layers"])


# ---- the reference forward pass ----

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HIGHEST)


def rmsnorm(x, g, eps):
    """RMSNorm with the layout's stored weight ``g`` (the scale is ``1 + g``)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def rope(x, theta):
    """Rotary embedding, rotate-half convention; x: (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(x, wg, wu, wd, quant):
    gate = jax.nn.silu(_mm("...d,df->...f", x, wg, quant))
    return _mm("...f,fd->...d", gate * _mm("...d,df->...f", x, wu, quant), wd,
               quant)


def _layers(cfg: Dict):
    """(stack name, index in its stack, feed-forward kind) of every layer."""
    moe = "n_routed_experts" in cfg
    pre = cfg.get("first_k_dense_replace", 0) if moe else 0
    out = [("pre", i, "dense") for i in range(pre)]
    out += [("stack", i, "moe" if moe else "dense")
            for i in range(cfg["num_hidden_layers"] - pre)]
    return out


def padded_vocab(cfg: Dict) -> int:
    """The layout pads the embedding table's rows to a multiple of 256."""
    return -(-cfg["vocab_size"] // 256) * 256


class Reference:
    """The reference model of one configuration file at one precision."""

    def __init__(self, cfg: Dict, quant: Optional[str] = None):
        if not served(cfg, "tie_word_embeddings"):
            raise NotImplementedError("the reference reads a tied output head")
        self.cfg = cfg
        self.quant = quant
        self._layer = jax.jit(self._layer_impl, static_argnums=(1, 3))
        self._embed = jax.jit(self._embed_impl)
        self._logits = jax.jit(self._logits_impl)

    def _leaf(self, key, path, layer, shape):
        return weights.leaf(key, path, layer, shape, F32)

    def _attention(self, key, stack, layer, x):
        cfg, quant = self.cfg, self.quant
        d, hd = cfg["hidden_size"], head_dim(cfg)
        nh, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        eps = cfg["rms_norm_eps"]
        p = f"{stack}/sub0"
        b, t, _ = x.shape
        h = rmsnorm(x, self._leaf(key, f"{p}/norm1/g", layer, (d,)), eps)

        def proj(name, n):
            w = self._leaf(key, f"{p}/attn/{name}", layer, (d, n * hd))
            return _mm("btd,de->bte", h, w, quant).reshape(b, t, n, hd)

        q, k, v = proj("wq", nh), proj("wk", nk), proj("wv", nk)
        if cfg["model_type"] == "qwen3":
            q = rmsnorm(q, self._leaf(key, f"{p}/attn/q_norm/g", layer, (hd,)), eps)
            k = rmsnorm(k, self._leaf(key, f"{p}/attn/k_norm/g", layer, (hd,)), eps)
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
        k = jnp.repeat(k, nh // nk, axis=2)
        v = jnp.repeat(v, nh // nk, axis=2)
        s = _mm("bqhd,bkhd->bhqk", q, k, quant) * hd ** -0.5
        causal = jnp.tril(jnp.ones((t, t), bool))
        pr = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", pr, v, quant).reshape(b, t, nh * hd)
        wo = self._leaf(key, f"{p}/attn/wo", layer, (nh * hd, d))
        return x + _mm("bte,ed->btd", o, wo, quant)

    def _dense_ffn(self, key, stack, layer, x):
        cfg = self.cfg
        d, f = cfg["hidden_size"], cfg["intermediate_size"]
        p = f"{stack}/sub0"
        h = rmsnorm(x, self._leaf(key, f"{p}/norm2/g", layer, (d,)),
                    cfg["rms_norm_eps"])
        return x + _mlp(h, self._leaf(key, f"{p}/mlp/w_gate", layer, (d, f)),
                        self._leaf(key, f"{p}/mlp/w_up", layer, (d, f)),
                        self._leaf(key, f"{p}/mlp/w_down", layer, (f, d)),
                        self.quant)

    def _moe_ffn(self, key, stack, layer, x):
        """DeepSeek-MoE: a softmax router over every expert, the top ``k``
        weighted by their router probabilities, plus the shared experts."""
        cfg, quant = self.cfg, self.quant
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
        ns = cfg.get("n_shared_experts", 0)
        p = f"{stack}/sub0/moe"
        h = rmsnorm(x, self._leaf(key, f"{stack}/sub0/norm2/g", layer, (d,)),
                    cfg["rms_norm_eps"])
        router = self._leaf(key, f"{p}/router/w", layer, (d, e))
        probs = jax.nn.softmax(_mm("btd,de->bte", h, router, quant), axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        if served(cfg, "norm_topk_prob"):
            top = top / jnp.sum(top, -1, keepdims=True)
        combine = jnp.sum(jax.nn.one_hot(idx, e, dtype=F32) * top[..., None], -2)
        experts = tuple(self._leaf(key, f"{p}/experts/{n}", layer, shape)
                        for n, shape in (("w_gate", (e, d, f)), ("w_up", (e, d, f)),
                                         ("w_down", (e, f, d))))

        def one_expert(acc, xs):
            wg, wu, wd, c = xs
            return acc + c[..., None] * _mlp(h, wg, wu, wd, quant), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                              experts + (jnp.moveaxis(combine, -1, 0),))
        if ns:
            out = out + _mlp(
                h, self._leaf(key, f"{p}/shared/w_gate", layer, (d, ns * f)),
                self._leaf(key, f"{p}/shared/w_up", layer, (d, ns * f)),
                self._leaf(key, f"{p}/shared/w_down", layer, (ns * f, d)), quant)
        return x + out

    def _layer_impl(self, key, stack, layer, kind, x):
        x = self._attention(key, stack, layer, x)
        ffn = self._moe_ffn if kind == "moe" else self._dense_ffn
        return ffn(key, stack, layer, x)

    def _table(self, key):
        cfg = self.cfg
        t = self._leaf(key, "embed/embed", None,
                       (padded_vocab(cfg), cfg["hidden_size"]))
        return t[: cfg["vocab_size"]]

    def _embed_impl(self, key, tokens):
        return self._table(key)[tokens]

    def _logits_impl(self, key, x, rows):
        """Logits of the hidden states ``x`` (B, T, d) at positions ``rows``
        (B, R): the final norm, then the tied output head."""
        cfg = self.cfg
        g = self._leaf(key, "final_norm/g", None, (cfg["hidden_size"],))
        h = jnp.take_along_axis(x, rows[..., None], axis=1)
        h = rmsnorm(h, g, cfg["rms_norm_eps"])
        return _mm("brd,vd->brv", h, self._table(key), self.quant)

    def logits(self, seed: int, tokens, rows):
        """Float32 logits (B, R, vocab) of ``tokens`` (B, T) read at the
        positions ``rows`` (B, R)."""
        key = weights.seed_key(seed)
        x = self._embed(key, jnp.asarray(tokens, jnp.int32))
        for stack, i, kind in _layers(self.cfg):
            x = self._layer(key, stack, jnp.int32(i), kind, x)
        return self._logits(key, x, jnp.asarray(rows, jnp.int32))
