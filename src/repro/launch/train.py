"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real cluster the same entry point runs under ``jax.distributed.initialize``
with the production mesh; here the smoke configs exercise the full path on CPU.
Fault tolerance: checkpoint every ``--ckpt-every`` steps; re-running the same
command resumes from the latest checkpoint (restart-safe data pipeline).
"""
from __future__ import annotations

import argparse
import dataclasses


from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.model import ARCHS, get_config
from repro.training.optimizer import AdamWConfig
from repro.training.trainer import Trainer
from repro.utils.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="llama3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "debug", "prod", "prod-multi"],
                    default="none")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. ~100M example model)")
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    over = {}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.layers:
        over["n_layers"] = args.layers
    if over:
        cfg = dataclasses.replace(cfg, **over)

    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh()
    elif args.mesh.startswith("prod"):
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multi")

    trainer = Trainer(cfg, AdamWConfig(lr=args.lr, warmup_steps=20,
                                       total_steps=args.steps),
                      mesh=mesh, ckpt_dir=args.ckpt_dir,
                      grad_accum=args.grad_accum)
    src = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    out = trainer.fit(src, args.steps, log_every=10,
                      ckpt_every=args.ckpt_every if args.ckpt_dir else 0)
    print(f"[train] final loss {out['losses'][-1]:.4f} "
          f"(start {out['losses'][0]:.4f})")


if __name__ == "__main__":
    main()
