"""Operations and bytes from shapes, and the chip's published peaks.

Everything here is computed from a configuration file's published keys (the
Hugging Face ``config.json`` names) and the run's token counts, never from the
compiled program, so a change of implementation cannot move its own yardstick.
"""
from __future__ import annotations

from typing import Dict

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9},
}

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    """The row of :data:`PEAKS` for ``device_kind``; an unknown device raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def served(cfg: Dict, key: str):
    """A published key as the program serves it (a listed departure wins)."""
    dep = cfg.get("departures", {}).get(key)
    return dep["served"] if dep is not None else cfg[key]


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def _attn_params(cfg: Dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def _norm_params(cfg: Dict) -> int:
    qk = 2 * head_dim(cfg) if cfg["model_type"] == "qwen3" else 0
    return 2 * cfg["hidden_size"] + qk


def _is_moe_layer(cfg: Dict, layer: int) -> bool:
    return ("n_routed_experts" in cfg
            and layer >= cfg.get("first_k_dense_replace", 0)
            and layer % cfg.get("moe_layer_freq", 1) == 0)


def _layer_params(cfg: Dict, layer: int, *, active: bool) -> int:
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


def embed_params(cfg: Dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def param_count(cfg: Dict) -> int:
    """Parameters the configuration serves (tied head counted once)."""
    n = sum(_layer_params(cfg, i, active=False) + _norm_params(cfg)
            for i in range(cfg["num_hidden_layers"]))
    n += embed_params(cfg) * (1 if served(cfg, "tie_word_embeddings") else 2)
    return n + cfg["hidden_size"]                              # final norm


def weight_bytes(cfg: Dict) -> int:
    return param_count(cfg) * BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg: Dict) -> int:
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * cfg["num_hidden_layers"] * BYTES[cfg["torch_dtype"]])


def active_matmul_params(cfg: Dict) -> int:
    """Matmul parameters one token runs through: every layer's attention
    projections, the routed and shared experts it is sent to (or the dense
    MLP), the router, and the output head.  The embedding lookup is no matmul."""
    return (sum(_layer_params(cfg, i, active=True)
                for i in range(cfg["num_hidden_layers"]))
            + embed_params(cfg))


def attn_flops(cfg: Dict, context: int) -> int:
    """Score and value FLOPs of one token attending to ``context`` positions,
    over all layers."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg) * context
            * cfg["num_hidden_layers"])


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    """Model FLOPs of a causal prefill: token ``i`` attends to ``i + 1``."""
    ctx = prompt_len * (prompt_len + 1) // 2
    return (2.0 * active_matmul_params(cfg) * prompt_len
            + attn_flops(cfg, 1) * ctx)


def decode_flops(cfg: Dict, contexts) -> float:
    """Model FLOPs of decode tokens, one per entry of ``contexts``."""
    contexts = list(contexts)
    return (2.0 * active_matmul_params(cfg) * len(contexts)
            + attn_flops(cfg, 1) * sum(contexts))


def decode_step_bytes(cfg: Dict, contexts) -> float:
    """The least HBM bytes of one dense decode step: every weight once, and
    the keys and values of each active row's live positions."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(contexts)


def sampler_bytes(rows: int, vocab: int) -> int:
    """Top-p over ``rows`` fp32 logit rows must read the logits once."""
    return rows * vocab * 4
