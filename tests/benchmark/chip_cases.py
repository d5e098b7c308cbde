"""Small configurations and traffic for the harness's CPU tests."""
from __future__ import annotations

import copy

QWEN = {
    "name": "tiny-qwen3", "reference": "decoder", "model_type": "qwen3",
    "departures": {},
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 500, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "serve_as": {"family": "decoder", "n_layers": 2, "d_model": 64, "n_heads": 4,
                 "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 500,
                 "qk_norm": True, "rope_theta": 1000000.0, "norm_eps": 1e-6,
                 "tie_embeddings": True, "act": "silu", "dtype": "bfloat16",
                 "remat": False},
}

MOE = {
    "name": "tiny-deepseek-moe", "reference": "decoder", "model_type": "deepseek",
    "departures": {"tie_word_embeddings": {"served": True, "why": ""},
                   "norm_topk_prob": {"served": True, "why": ""}},
    "hidden_size": 64, "intermediate_size": 256, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "norm_topk_prob": False,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "serve_as": {"family": "moe", "n_layers": 3, "d_model": 64, "n_heads": 4,
                 "n_kv_heads": 4, "head_dim": 16, "d_ff": 256, "vocab_size": 512,
                 "qk_norm": False, "rope_theta": 10000.0, "norm_eps": 1e-6,
                 "tie_embeddings": True, "act": "silu", "dtype": "bfloat16",
                 "remat": False,
                 "moe": {"n_experts": 8, "top_k": 2, "d_ff_expert": 32,
                         "n_shared": 2, "first_k_dense": 1,
                         "capacity_factor": 4.1}},
}

TRAFFIC = {
    "name": "tiny-chat",
    "engine": {"max_batch": 4, "page_size": 8, "max_len": 48, "n_pages": 25,
               "tick_tokens": 4, "bits_per_pass": 4, "sampler": "topp_scan",
               "top_p": 0.9, "temperature": 1.0},
    "prompt_len": {"values": [8, 16, 24], "weights": [3, 2, 1]},
    "output_len": {"values": [4, 8, 16], "weights": [1, 2, 1]},
    "load": 0.8, "initial_backlog": 4, "n_requests": 400, "schedule_seed": 5,
}


def traffic(sampler="topp_scan"):
    t = copy.deepcopy(TRAFFIC)
    if sampler == "greedy":
        t["engine"] = {k: v for k, v in t["engine"].items()
                       if k not in ("top_p", "temperature")}
    t["engine"]["sampler"] = sampler
    return t
