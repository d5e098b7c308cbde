#!/usr/bin/env python3
"""Readings for a cell's limit: the program's and the fp8 control's, per seed.

    python3 benchmarks/chip/control.py --workload qwen3-4b.chat-topp \\
        --seeds 1,2,3 --seconds 30

One process on the chip: for each seed a whole run of the cell (weights,
set-up, a window of ``--seconds`` at the cell's own load), then the check's
reference over the same sample, once in float32 and once with fp8 operands.
Prints one JSON line per seed with every number of both.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]
    import jax
    from chip import correct, traffic
    from chip import run as harness

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    cell, cfg, _, _ = harness.load_cell(args.workload)
    spec = traffic.load(cell["traffic"])
    limits = correct.limits(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, cfg, spec, [], [], seed=seed,
                               seconds=args.seconds, traced=False,
                               devices=jax.devices()[:cell["chips"]],
                               limits=limits, control=True)
        print(json.dumps({"seed": seed, "attempted": res["attempted"],
                          "failed": res["failed"], **res["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
