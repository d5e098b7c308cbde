"""Operations and bytes from shapes, and the chip's published peaks.

Everything here is computed from a configuration file's published keys (the
Hugging Face ``config.json`` names) and the run's token counts, never from the
compiled program, so a change of implementation cannot move its own yardstick.
What depends on the architecture (the parameters of a layer, its norms, the
cache and recurrent state it keeps, its attention FLOPs) comes from the module
that the file names under ``"reference"``, ``reference/<module>.py``; this
module only adds them up.
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType
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


def arch(cfg: Dict) -> ModuleType:
    """The module that owns the configuration's architecture."""
    name = cfg["reference"]
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise ValueError(f"{cfg['name']}: reference {name!r} is no module name")
    return importlib.import_module(f"{__package__}.reference.{name}")


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def embed_params(cfg: Dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def param_count(cfg: Dict) -> int:
    """Parameters the configuration serves (tied head counted once)."""
    m = arch(cfg)
    n = sum(m.layer_params(cfg, i, active=False)
            for i in range(cfg["num_hidden_layers"]))
    n += m.norm_params(cfg)
    return n + embed_params(cfg) * (1 if served(cfg, "tie_word_embeddings") else 2)


def weight_bytes(cfg: Dict) -> int:
    return param_count(cfg) * BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg: Dict) -> int:
    """Cache bytes one position adds to a row, over all layers."""
    return arch(cfg).cache_bytes_per_token(cfg)


def state_bytes_per_row(cfg: Dict) -> int:
    """Bytes of one row's recurrent state, whatever its length, over all layers."""
    return arch(cfg).state_bytes_per_row(cfg)


def active_matmul_params(cfg: Dict) -> int:
    """Matmul parameters one token runs through: every layer's own (for MoE
    the router and the routed and shared experts it is sent to), and the
    output head.  The embedding lookup is no matmul."""
    m = arch(cfg)
    return (sum(m.layer_params(cfg, i, active=True)
                for i in range(cfg["num_hidden_layers"]))
            + embed_params(cfg))


def attn_flops(cfg: Dict, context: int) -> int:
    """Score and value FLOPs of one token attending to ``context`` positions,
    over all layers."""
    return arch(cfg).attn_flops(cfg, context)


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    """Model FLOPs of a causal prefill: token ``i`` attends to ``i + 1``."""
    m = arch(cfg)
    return (2.0 * active_matmul_params(cfg) * prompt_len
            + sum(m.attn_flops(cfg, c) for c in range(1, prompt_len + 1)))


def decode_flops(cfg: Dict, contexts) -> float:
    """Model FLOPs of decode tokens, one per entry of ``contexts``."""
    m = arch(cfg)
    contexts = list(contexts)
    return (2.0 * active_matmul_params(cfg) * len(contexts)
            + sum(m.attn_flops(cfg, c) for c in contexts))


def decode_step_bytes(cfg: Dict, contexts) -> float:
    """The least HBM bytes of one dense decode step: every weight once, the
    cache of each active row's live positions, and each row's recurrent state
    read and written once."""
    contexts = list(contexts)
    return (weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(contexts)
            + 2 * state_bytes_per_row(cfg) * len(contexts))


def sampler_bytes(rows: int, vocab: int) -> int:
    """Top-p over ``rows`` fp32 logit rows must read the logits once."""
    return rows * vocab * 4
