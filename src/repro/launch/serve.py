"""Serving launcher: batched generation with the paper's scan-based top-p sampler.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16 --sampler topp_scan
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.models.model import ARCHS, build_model, get_config, synth_batch
from repro.configs.base import ShapeConfig
from repro.serving.engine import ServeEngine
from repro.utils.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--top-p", type=float, default=0.9)
    ap.add_argument("--sampler", choices=["topp_scan", "topp_xla", "greedy"],
                    default="topp_scan")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")
    batch = synth_batch(cfg, shape, jax.random.PRNGKey(1))

    eng = ServeEngine(cfg, params,
                      max_len=args.prompt_len + args.new_tokens +
                      (cfg.n_img_tokens if cfg.family == "vlm" else 0),
                      top_p=args.top_p, sampler=args.sampler)
    t0 = time.perf_counter()
    toks = eng.generate(batch, args.new_tokens, jax.random.PRNGKey(2))
    dt = time.perf_counter() - t0
    toks = np.asarray(toks)
    print(f"[serve] generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s) sampler={args.sampler}")
    print(toks[:, :12])


if __name__ == "__main__":
    main()
