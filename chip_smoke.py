#!/usr/bin/env python3
"""Bring-up smoke run: the scan operators and qwen3-4b serving on one TPU.

    python chip_smoke.py [--seed N]          # one chip: operators + serving
    python chip_smoke.py --four-chips        # four chips: the sharded path only

One process drives the chip, and every phase is fatal: a failed check prints
``FAIL: ...`` and exits non-zero, and nothing is caught and ignored.  Without a
TPU, or without the rest of the repository beside it, the script exits
non-zero before any phase runs.  Lines before the last are information (smoke
timings are not metrics); the last line of standard output is one JSON object
naming the device.

Phases on one chip:

* operators, by explicit method so every kernel family really runs: scans at
  2^24 elements, the sort family and the top-p sampler on qwen3-4b's
  151936-wide vocabulary, a 2^22-key sort, compress and linear recurrences.
  Each kernel path's compiled HLO must hold a ``tpu_custom_call``;
* serving: qwen3-4b at its published widths in bfloat16 from a seed,
  ``ContinuousEngine`` with the paper's top-p sampler on 8 seeded requests,
  checked against a solo ``ServeEngine.generate`` stream.

``--four-chips`` runs ``mcscan``, ``dist_radix_sort`` and
``dist_top_p_sample`` on a 4-device mesh against their one-device siblings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
VOCAB = 151936                     # qwen3-4b
SCAN_N = 1 << 24                   # 64 MiB of fp32
SORT_N = 1 << 22


class SmokeFailure(Exception):
    """A smoke check failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok  {what}", flush=True)


def info(msg: str) -> None:
    print(f"  info {msg}", flush=True)


class Jobs:
    """Operator checks: compiled together, then run and verified in order.

    ``add`` queues one jitted call; its ``verify`` gets the output and the
    outputs of the jobs before it (by name).  ``run`` compiles every queued
    call on a thread pool — XLA compiles release the GIL, so independent
    compiles overlap — then runs and verifies them one by one.  A kernel
    job's compiled HLO must hold a Mosaic ``tpu_custom_call``.
    """

    def __init__(self, require_mosaic: bool):
        self.require_mosaic = require_mosaic
        self.jobs = []
        self.out = {}

    def add(self, name, fn, args, *, kernel: bool, verify=None):
        self.jobs.append((name, fn, args, kernel, verify))

    def run(self, workers: int = 8) -> None:
        import concurrent.futures as cf
        import jax

        def build(job):
            name, fn, args = job[:3]
            t0 = time.perf_counter()
            return jax.jit(fn).lower(*args).compile(), time.perf_counter() - t0

        t0 = time.perf_counter()
        with cf.ThreadPoolExecutor(workers) as pool:
            built = list(pool.map(build, self.jobs))
        info(f"compiled {len(self.jobs)} programs in "
             f"{time.perf_counter() - t0:.1f} s (longest "
             f"{max(sec for _, sec in built):.1f} s)")
        for (name, _, args, kernel, verify), (compiled, _) in zip(self.jobs,
                                                                  built):
            if kernel and self.require_mosaic:
                check("tpu_custom_call" in compiled.as_text(),
                      f"{name}: compiled HLO holds tpu_custom_call")
            self.out[name] = jax.block_until_ready(compiled(*args))
            if verify is not None:
                verify(self.out[name], self.out)
        self.jobs = []


# ---------------------------------------------------------------------------
# Phase: operators
# ---------------------------------------------------------------------------


def check_scans(jobs: Jobs, n: int, *, seed: int, s: int = 128) -> None:
    """Every scan method and variant vs the fp64 reference within the ulp
    bound, and exactly on integer payloads."""
    import jax.numpy as jnp
    from repro.analysis import ulp
    from repro.core import scan

    rng = np.random.default_rng(seed)
    x32 = rng.standard_normal(n).astype(np.float32)
    bound = ulp.ulp_bound("highest", n)
    ref, scale = ulp.scan_ref(x32), ulp.scan_scale(x32)
    xf = jnp.asarray(x32)

    def within(name, ref, scale, dtype=jnp.float32):
        def verify(got, _):
            mu = ulp.max_ulp(np.asarray(got), ref, scale)
            check(got.dtype == dtype and mu <= bound,
                  f"{name}: {jnp.dtype(dtype).name}, max ulp {mu:.3f} <= "
                  f"{bound:.1f}")
        return verify

    jobs.add("scan vector f32", lambda v: scan(v, method="vector"), (xf,),
             kernel=False, verify=within("scan vector f32", ref, scale))
    for method in ("matmul", "kernel", "blocked"):
        for variant in ("scanu", "scanul1"):
            name = f"scan {method}/{variant} f32 n={n}"
            jobs.add(name, lambda v, m=method, va=variant: scan(
                v, method=m, variant=va, tile_s=s), (xf,),
                kernel=method != "matmul", verify=within(name, ref, scale))

    xb = jnp.asarray(x32).astype(jnp.bfloat16)
    xb64 = np.asarray(xb.astype(jnp.float32), np.float64)
    ref_b, scale_b = ulp.scan_ref(xb64), ulp.scan_scale(xb64)
    for method in ("matmul", "kernel", "blocked"):
        name = f"scan {method} bf16 n={n}"
        jobs.add(name, lambda v, m=method: scan(v, method=m, tile_s=s), (xb,),
                 kernel=method != "matmul",
                 verify=within(name, ref_b, scale_b))

    x8 = rng.integers(-128, 128, n).astype(np.int8)
    ref8 = np.cumsum(x8.astype(np.int64))
    steps = rng.integers(-1, 2, n).astype(np.float32)     # integer-valued fp32
    refi = np.cumsum(steps.astype(np.float64))

    def exact(name, ref, dtype):
        def verify(got, _):
            check(got.dtype == dtype
                  and np.array_equal(np.asarray(got, ref.dtype), ref),
                  f"{name}: {jnp.dtype(dtype).name}, exact")
        return verify

    for method in ("vector", "matmul", "kernel", "blocked"):
        kern = method in ("kernel", "blocked")
        name = f"scan {method} int8 n={n}"
        jobs.add(name, lambda v, m=method: scan(v, method=m, tile_s=s),
                 (jnp.asarray(x8),), kernel=kern,
                 verify=exact(name, ref8, jnp.int32))
        name = f"scan {method} integer-valued f32 n={n}"
        jobs.add(name, lambda v, m=method: scan(v, method=m, tile_s=s),
                 (jnp.asarray(steps),), kernel=kern,
                 verify=exact(name, refi, jnp.float32))


def _same(got, want) -> bool:
    import jax
    return all(np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def check_sorts(jobs: Jobs, rows: int, vocab: int, sort_n: int, *, seed: int,
                s: int = 128) -> None:
    """The sort family and the top-p sampler: every method bit-identical to
    ``method="vector"``, sorted values equal to ``jnp.sort``."""
    import jax
    import jax.numpy as jnp
    from repro.core import radix_sort, sort, top_p_sample, topk

    rng = np.random.default_rng(seed + 1)
    logits = jnp.asarray(rng.standard_normal((rows, vocab)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal(sort_n), jnp.float32)
    # a peaked distribution: the nucleus is a few tokens whose cumulative
    # masses lie far apart next to any rounding of the prefix sums, so every
    # method must draw the same token
    peaked = logits * 6.0
    key = jax.random.PRNGKey(seed)
    k = 64

    cases = [
        ("radix_sort", lambda x, m: radix_sort(x, method=m, tile_s=s),
         (logits,), True),
        ("sort desc", lambda x, m: sort(x, descending=True, method=m,
                                        tile_s=s), (logits,), False),
        (f"topk k={k}", lambda x, m: topk(x, k, method=m, tile_s=s),
         (logits,), False),
        (f"sort n={sort_n}", lambda x, m: sort(x, method=m, tile_s=s),
         (keys,), True),
        ("top_p_sample p=0.9", lambda x, kk, m: top_p_sample(
            x, kk, p=0.9, method=m, tile_s=s), (peaked, key), False),
    ]
    for name, fn, args, ascending in cases:
        def vector_ok(got, _, name=name, x=args[0], ascending=ascending):
            if ascending:
                check(np.array_equal(np.asarray(got[0]),
                                     np.asarray(jnp.sort(x, axis=-1))),
                      f"{name} vector: values equal jnp.sort")
            else:
                info(f"{name} vector: reference computed")

        jobs.add(f"{name} vector", lambda *a, fn=fn: fn(*a, "vector"), args,
                 kernel=False, verify=vector_ok)
        for method in ("matmul", "kernel", "blocked"):
            def same_ok(got, out, name=name, method=method):
                check(_same(got, out[f"{name} vector"]),
                      f"{name} {method}: bit-identical to vector")
            jobs.add(f"{name} {method}", lambda *a, fn=fn, m=method: fn(*a, m),
                     args, kernel=method != "matmul", verify=same_ok)

    def in_vocab(got, _):
        toks = np.asarray(got)
        check(toks.shape == (rows,) and np.all((toks >= 0) & (toks < vocab)),
              "top_p_sample kernel: one in-vocabulary token per row")

    jobs.add("top_p_sample range", lambda x, kk: top_p_sample(
        x, kk, p=0.9, method="kernel", tile_s=s), (peaked, key), kernel=True,
        verify=in_vocab)


def check_compress_linrec(jobs: Jobs, n: int, *, seed: int,
                          s: int = 128) -> None:
    """compress by every method vs vector; linear_scan kernel/blocked vs the
    fp64 reference within the ulp bound and exactly on integer payloads."""
    import jax.numpy as jnp
    from repro.analysis import ulp
    from repro.core import compress, linear_scan

    rng = np.random.default_rng(seed + 2)
    x = jnp.asarray(rng.standard_normal((4, n)), jnp.float32)
    mask = jnp.asarray(rng.random((4, n)) < 0.3)
    jobs.add("compress vector", lambda v, mm: compress(v, mm, method="vector"),
             (x, mask), kernel=False)
    for method in ("matmul", "kernel", "blocked"):
        def same_ok(got, out, method=method):
            check(_same(got, out["compress vector"]),
                  f"compress {method}: bit-identical to vector")
        jobs.add(f"compress {method}", lambda v, mm, m=method: compress(
            v, mm, method=m, tile_s=s), (x, mask),
            kernel=method != "matmul", verify=same_ok)

    a = rng.uniform(0.9, 1.0, (2, n)).astype(np.float32)
    b = rng.standard_normal((2, n)).astype(np.float32)
    ref, scale = ulp.linrec_ref(a, b), ulp.linrec_scale(a, b)
    bound = ulp.ulp_bound("highest", n)
    ai = rng.integers(0, 2, (2, n)).astype(np.float32)   # resets: a in {0, 1}
    bi = rng.integers(-3, 4, (2, n)).astype(np.float32)
    ints = (jnp.asarray(ai), jnp.asarray(bi))
    jobs.add("linear_scan vector integer-valued",
             lambda u, v: linear_scan(u, v, method="vector"), ints,
             kernel=False)
    for method in ("kernel", "blocked"):
        name = f"linear_scan {method} f32 n={n}"

        def within(got, _, name=name):
            mu = ulp.max_ulp(np.asarray(got), ref, scale)
            check(mu <= bound, f"{name}: max ulp {mu:.3f} <= {bound:.1f}")

        def exact(got, out, method=method):
            check(_same(got, out["linear_scan vector integer-valued"]),
                  f"linear_scan {method} integer-valued: equal to vector")

        jobs.add(name, lambda u, v, m=method: linear_scan(u, v, method=m,
                                                          tile_s=s),
                 (jnp.asarray(a), jnp.asarray(b)), kernel=True, verify=within)
        jobs.add(f"linear_scan {method} integer-valued",
                 lambda u, v, m=method: linear_scan(u, v, method=m, tile_s=s),
                 ints, kernel=True, verify=exact)


def print_auto_resolution(vocab: int, scan_n: int) -> None:
    """What ``method="auto"`` resolves to on this backend (information)."""
    import warnings
    import jax.numpy as jnp
    from repro.core import resolve_method
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for op, n, dt in (("scan", scan_n, jnp.float32),
                          ("split", vocab, jnp.float32),
                          ("sort", vocab, jnp.bfloat16),
                          ("top_p_sample", vocab, jnp.float32),
                          ("linear_scan", scan_n, jnp.float32)):
            info(f"auto resolves {op} n={n} {jnp.dtype(dt).name} -> "
                 f"{resolve_method(op, n, dt)}")


def run_operators(*, seed: int, scan_n: int = SCAN_N, rows: int = 8,
                  vocab: int = VOCAB, sort_n: int = SORT_N, lin_n: int = 1 << 18,
                  s: int = 128, require_mosaic: bool = True) -> None:
    print("phase operators", flush=True)
    print_auto_resolution(vocab, scan_n)
    jobs = Jobs(require_mosaic)
    check_scans(jobs, scan_n, seed=seed, s=s)
    check_sorts(jobs, rows, vocab, sort_n, seed=seed, s=s)
    check_compress_linrec(jobs, lin_n, seed=seed, s=s)
    jobs.run()


# ---------------------------------------------------------------------------
# Phase: serving
# ---------------------------------------------------------------------------


def make_requests(n: int, *, seed: int, vocab: int, prompt_lens, new_tokens):
    """``n`` seeded requests, all arrived at step 0."""
    import jax
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(seed + 3)
    reqs = []
    for i in range(n):
        plen = int(rng.choice(prompt_lens))
        reqs.append(Request(
            rid=f"req{i}",
            tokens=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(new_tokens[0],
                                            new_tokens[1] + 1)),
            key=np.asarray(jax.random.PRNGKey(seed * 7919 + i))))
    return reqs


def run_serving(cfg, *, seed: int, n_requests: int = 8, max_batch: int = 4,
                prompt_lens=(128, 256, 512, 1024), new_tokens=(32, 64),
                page_size: int = 128, tick_tokens: int = 16) -> None:
    """``ContinuousEngine`` with the paper's top-p sampler on seeded requests."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import build_model
    from repro.serving import paged_kv
    from repro.serving.engine import ServeEngine
    from repro.serving.scheduler import ContinuousEngine

    print(f"phase serving {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}", flush=True)
    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: model.init(k, dtype=jnp.dtype(cfg.dtype)))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    info(f"weights: {n_params} parameters in {cfg.dtype}, built in "
         f"{time.perf_counter() - t0:.1f} s")

    reqs = make_requests(n_requests, seed=seed, vocab=cfg.vocab_size,
                         prompt_lens=prompt_lens, new_tokens=new_tokens)
    max_len = max(prompt_lens) + new_tokens[1]
    n_blocks = paged_kv.pages_needed(max_len, page_size)
    eng = ContinuousEngine(cfg, params, max_batch=max_batch,
                           page_size=page_size,
                           n_pages=1 + max_batch * n_blocks, max_len=max_len,
                           sampler="topp_scan", top_p=0.9,
                           tick_tokens=tick_tokens)
    t0 = time.perf_counter()
    res = eng.run(reqs)
    cold_s = time.perf_counter() - t0
    compiled_s = compile_s[0]
    t0 = time.perf_counter()
    again = eng.run(reqs)
    warm_s = time.perf_counter() - t0

    streams = res["streams"]
    check(len(streams) == n_requests, f"{n_requests} requests served")
    for r in reqs:
        toks = streams[r.rid]
        check(toks.shape == (r.max_new_tokens,)
              and np.all((toks >= 0) & (toks < cfg.vocab_size)),
              f"{r.rid}: {r.tokens.size}-token prompt, all "
              f"{r.max_new_tokens} tokens in [0, {cfg.vocab_size})")
    check(all(np.array_equal(streams[k], again["streams"][k])
              for k in streams), "a second run replays every stream")

    solo_req = reqs[0]
    solo = ServeEngine(cfg, params, max_len=n_blocks * page_size,
                       sampler="topp_scan", top_p=0.9)
    ref = np.asarray(solo.generate(
        {"tokens": jnp.asarray(solo_req.tokens)[None]},
        solo_req.max_new_tokens, jnp.asarray(solo_req.key, jnp.uint32)))[0]
    check(np.array_equal(ref, streams[solo_req.rid]),
          f"{solo_req.rid}: stream bitwise equal to a solo "
          "ServeEngine.generate with the same key")

    total = res["stats"]["total_tokens"]
    info(f"smoke timing (not a metric): {total} tokens; first run "
         f"{cold_s:.2f} s with compiles, second run {warm_s:.2f} s = "
         f"{total / warm_s:.1f} tokens/s")
    info(f"compile seconds (first serving run): {compiled_s:.2f}")
    stats = jax.devices()[0].memory_stats() or {}
    info(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------------------------
# Four chips: the sharded path and its one-device siblings
# ---------------------------------------------------------------------------


def run_four_chips(*, seed: int, devices, scan_n: int = SCAN_N,
                   sort_n: int = SORT_N, rows: int = 8, vocab: int = VOCAB,
                   s: int = 128) -> None:
    """``mcscan``, ``dist_radix_sort`` and ``dist_top_p_sample`` on a mesh of
    ``devices``, each bit-for-bit against its one-device sibling."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import dist_radix_sort, dist_top_p_sample, mcscan, scan
    from repro.core.primitives import radix_sort, top_p_sample

    n_dev = len(devices)
    print(f"phase four-chips: mesh of {n_dev} devices", flush=True)
    rng = np.random.default_rng(seed + 4)

    def spans_mesh(out, what):
        for leaf in jax.tree.leaves(out):
            check(len(leaf.sharding.device_set) == n_dev,
                  f"{what}: output spans {n_dev} devices")

    data = Mesh(np.asarray(devices), ("data",))
    model = Mesh(np.asarray(devices), ("model",))

    x8 = jnp.asarray(rng.integers(-128, 128, (2, scan_n)), jnp.int8)
    steps = jnp.asarray(rng.integers(-1, 2, (2, scan_n)), jnp.float32)
    for name, x in (("int8", x8), ("integer-valued f32", steps)):
        want = jax.jit(lambda v: scan(v, method="blocked", tile_s=s))(x)
        got = jax.jit(lambda v: mcscan(v, data, "data", method="blocked",
                                       tile_s=s))(x)
        spans_mesh(got, f"mcscan {name}")
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"mcscan {name} n={scan_n}: bit-identical to one-device scan")

    keys = jnp.asarray(rng.standard_normal(sort_n), jnp.float32)
    for method in ("matmul", "kernel"):
        want = jax.jit(lambda v, m=method: radix_sort(v, method=m,
                                                      tile_s=s))(keys)
        got = jax.jit(lambda v, m=method: dist_radix_sort(
            v, data, "data", method=m, tile_s=s))(keys)
        spans_mesh(got, f"dist_radix_sort {method}")
        check(all(np.array_equal(np.asarray(g), np.asarray(w))
                  for g, w in zip(got, want)),
              f"dist_radix_sort {method} n={sort_n}: bit-identical to "
              "radix_sort")

    # peaked logits: draws far from any nucleus boundary, where the sharded
    # sampler's documented-ulp contract is exact
    logits = jnp.asarray(rng.standard_normal((rows, vocab)) * 6.0, jnp.float32)
    u = jnp.asarray(rng.random((rows, 1)), jnp.float32)
    want = jax.jit(lambda lg: top_p_sample(lg, None, p=0.9, method="matmul",
                                           tile_s=s, u=u))(logits)
    got = jax.jit(lambda lg: dist_top_p_sample(lg, None, model, "model",
                                               p=0.9, method="matmul",
                                               tile_s=s, u=u))(logits)
    spans_mesh(got, "dist_top_p_sample")
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          f"dist_top_p_sample ({rows}, {vocab}): tokens equal top_p_sample")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-chip mesh")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("FAIL: chip_smoke.py must run from a checkout of the repository "
              f"(no src/repro beside {ROOT})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print(f"FAIL: no TPU (JAX sees {d0.platform}); this smoke run has no "
              "CPU fallback", file=sys.stderr)
        return 1

    from repro.utils.cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            if len(devices) < 4:
                raise SmokeFailure(f"--four-chips needs 4 devices, JAX sees "
                                   f"{len(devices)}")
            devices = devices[:4]
            device["count"] = len(devices)
            run_four_chips(seed=args.seed, devices=devices)
        else:
            from repro.models.model import get_config
            run_operators(seed=args.seed)
            run_serving(get_config("qwen3-4b"), seed=args.seed)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    info(f"wall seconds: {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
