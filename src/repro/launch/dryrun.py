import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (architecture × input shape) against the
production meshes; record memory_analysis / cost_analysis / collective schedule.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/

train_4k lowers the *full train step* (loss + grad + AdamW update); prefill_32k the
prefill; decode_32k / long_500k the single-token ``serve_step`` against a full KV
cache (long_500k shards the cache sequence axis — SP — since batch == 1).
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs.base import SHAPES
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.sharding_plan import batch_specs, cache_specs
from repro.models.model import (ARCHS, build_model, cell_supported, get_config,
                                input_specs)
from repro.training import optimizer as opt_lib
from repro.utils.sharding import param_shardings, use_mesh


def abstract_state(model, opt: bool = True, param_dtype: str = "float32"):
    """ShapeDtypeStruct pytrees for params (+ opt state) — no allocation."""
    dt = jnp.dtype(param_dtype)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype=dt))
    if not opt:
        return params
    opt_state = jax.eval_shape(lambda p: opt_lib.adamw_init(p), params)
    return {"params": params, "opt": opt_state}


def _fit_sharding(mesh, sds, spec):
    """NamedSharding with non-divisible / missing axes dropped."""
    parts = []
    for dim, a in zip(sds.shape, tuple(spec) + (None,) * (len(sds.shape)
                                                          - len(spec))):
        if a is not None and a in mesh.axis_names and dim % mesh.shape[a] == 0:
            parts.append(a)
        else:
            parts.append(None)
    return NamedSharding(mesh, P(*parts))


def lower_cell(arch: str, shape_name: str, mesh, *, scan_method="matmul",
               scan_layers=False, overrides=None):
    """Returns (lowered, chips, n_params). Raises on sharding/compile errors.

    Layers are UNROLLED by default: XLA's cost_analysis counts while-loop bodies
    once, so scanned-layer modules under-report flops/bytes/collectives by ~n_layers
    — unrolling makes the roofline terms exact.  (Production training still scans;
    the lowered computation is identical per step.)
    """
    cfg = get_config(arch)
    over = dict(overrides or {})
    zero = over.pop("zero", False)                 # ZeRO-1: shard opt moments
    param_dtype = over.pop("param_dtype", "float32")
    cap = over.pop("moe_capacity", None)
    cfg = dataclasses.replace(cfg, scan_method=scan_method,
                              scan_layers=scan_layers, **over)
    if cap is not None and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=cap))
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        raise SkipCell(why)
    model = build_model(cfg)
    opt_cfg = opt_lib.AdamWConfig()
    chips = mesh.devices.size

    if shape.kind == "train":
        state = abstract_state(model, param_dtype=param_dtype)
        pspecs = param_shardings(mesh, state["params"])
        mspecs = param_shardings(mesh, state["opt"]["mu"])
        if zero:
            # ZeRO-1: additionally shard each moment over "data" along the first
            # free (and divisible) dimension.
            def zero_shard(sds, ns):
                parts = list(tuple(ns.spec) + (None,) * (len(sds.shape)
                                                         - len(ns.spec)))
                for i, (dim, a) in enumerate(zip(sds.shape, parts)):
                    if a is None and dim % mesh.shape["data"] == 0 \
                            and dim >= mesh.shape["data"]:
                        parts[i] = "data"
                        break
                return NamedSharding(mesh, P(*parts))
            mspecs = jax.tree.map(zero_shard, state["opt"]["mu"], mspecs)
        sspecs = {"params": pspecs,
                  "opt": {"mu": mspecs, "nu": mspecs,
                          "step": NamedSharding(mesh, P())}}
        batch = input_specs(cfg, shape)
        bspecs = batch_specs(mesh, batch)

        def train_step(st, b):
            with use_mesh(mesh):
                (loss, _), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(st["params"], b)
                new_p, new_o, _ = opt_lib.adamw_update(
                    opt_cfg, grads, st["opt"], st["params"])
                return {"params": new_p, "opt": new_o}, loss

        fn = jax.jit(train_step, in_shardings=(sspecs, bspecs),
                     out_shardings=(sspecs, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
        lowered = fn.lower(state, batch)
        n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
        return lowered, chips, n_params

    params = abstract_state(model, opt=False)
    pspecs = param_shardings(mesh, params)
    n_params = sum(x.size for x in jax.tree.leaves(params))

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        bspecs = batch_specs(mesh, batch)

        def prefill(p, b):
            with use_mesh(mesh):
                logits, caches = model.prefill(p, b, cache_len=shape.seq_len)
                return logits, caches
        # let XLA choose cache output shardings; inputs are what matter here
        fn = jax.jit(prefill, in_shardings=(pspecs, bspecs))
        return fn.lower(params, batch), chips, n_params

    # decode: one new token against a filled cache of seq_len
    b = shape.global_batch
    caches = jax.eval_shape(
        lambda: model.empty_caches(b, shape.seq_len))
    cspecs = cache_specs(mesh, caches, seq_sharded=b == 1)
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tspec = batch_specs(mesh, {"tokens": tokens})["tokens"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(p, t, c, pos):
        with use_mesh(mesh):
            logits, c = model.decode_step(p, t, c, pos)
            return jnp.argmax(logits, -1).astype(jnp.int32), c

    tok_out = NamedSharding(mesh, P(*tspec.spec[:1]))    # rank-1 sampled tokens
    fn = jax.jit(serve_step,
                 in_shardings=(pspecs, tspec, cspecs, NamedSharding(mesh, P())),
                 out_shardings=(tok_out, cspecs), donate_argnums=(2,))
    return fn.lower(params, tokens, caches, pos), chips, n_params


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, debug=False,
             scan_method="matmul", overrides=None, mesh_shape=None, tag=""):
    if mesh_shape is not None:
        d, m = mesh_shape
        from repro.utils.compat import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh_fn = make_debug_mesh if debug else make_production_mesh
        mesh = mesh_fn(multi_pod=mesh_kind == "multi")
    t0 = time.time()
    lowered, chips, n_params = lower_cell(arch, shape_name, mesh,
                                          scan_method=scan_method,
                                          overrides=overrides)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    mem = compiled.memory_analysis()
    # the dry run projects the v5e it was written for
    roof = rl.analyze(compiled, device_kind="TPU v5 lite", chips=chips)
    shape = SHAPES[shape_name]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mflops = rl.model_flops(n_params, tokens, shape.kind)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": chips,
        "tag": tag, "status": "ok",
        "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "n_params": n_params,
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "code": mem.generated_code_size_in_bytes,
        },
        "roofline": roof.to_dict(),
        "model_flops_global": mflops,
        "model_flops_per_chip": mflops / chips,
        "useful_fraction": (mflops / chips) / roof.flops if roof.flops else 0.0,
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON records here")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="8-device debug mesh (CI)")
    ap.add_argument("--scan-method", default="matmul",
                    choices=["matmul", "vector"])
    ap.add_argument("--zero", action="store_true", help="ZeRO-1 opt sharding")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--mesh-shape", default=None,
                    help="override logical mesh as DxM, e.g. 32x8")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--tag", default="", help="perf-iteration tag for the record")
    args = ap.parse_args()

    overrides = {}
    if args.zero:
        overrides["zero"] = True
    if args.param_dtype != "float32":
        overrides["param_dtype"] = args.param_dtype
    if args.capacity_factor is not None:
        overrides["moe_capacity"] = args.capacity_factor
    mesh_shape = None
    if args.mesh_shape:
        d, m = args.mesh_shape.split("x")
        mesh_shape = (int(d), int(m))

    cells = []
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for mk in meshes:
                cells.append((a, s, mk))

    records = []
    failed = 0
    for arch, shape, mk in cells:
        tag = f"{arch} × {shape} × {mk}"
        try:
            rec = run_cell(arch, shape, mk, debug=args.debug_mesh,
                           scan_method=args.scan_method, overrides=overrides,
                           mesh_shape=mesh_shape, tag=args.tag)
            r = rec["roofline"]
            print(f"[dryrun] OK  {tag}: compute {r['compute_s']*1e3:.2f}ms "
                  f"memory {r['memory_s']*1e3:.2f}ms collective "
                  f"{r['collective_s']*1e3:.2f}ms -> {r['bottleneck']}"
                  f" (compile {rec['compile_s']}s)", flush=True)
        except SkipCell as e:
            rec = {"arch": arch, "shape": shape, "mesh": mk,
                   "status": "skip", "reason": str(e)}
            print(f"[dryrun] SKIP {tag}: {e}", flush=True)
        except Exception as e:  # noqa
            rec = {"arch": arch, "shape": shape, "mesh": mk,
                   "status": "fail", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            print(f"[dryrun] FAIL {tag}: {type(e).__name__}: {e}", flush=True)
            failed += 1
        records.append(rec)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            suffix = f"_{args.tag}" if args.tag else ""
            fname = os.path.join(
                args.out,
                f"dryrun_{arch}_{shape}_{mk}{suffix}.json".replace("/", "_"))
            with open(fname, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"[dryrun] {len(records) - failed}/{len(records)} cells passed")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
