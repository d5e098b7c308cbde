"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Timings are of whatever backend JAX
runs on; the committed ``benchmarks/baseline`` rows are CPU-host timings, which
validate relative behaviour — scan strategy ratios, traffic counts, operator
scaling — and are not device numbers.

  Fig 3  single-core scan: vector CumSum vs ScanU vs ScanUL1
  Fig 5  batched scan: ScanUL1/ScanU execution-time ratio grid
  Fig 8  MCScan bandwidth vs length (s = 32/64/128) + copy roofline  [>= 2 devices]
  Fig 9  MCScan int8 vs fp16 GElems/s                                [>= 2 devices]
  Fig 10 compress vs baseline masked-select
  Fig 11 radix sort vs jnp.sort (fp16)
  Fig 12 batched scan bandwidth vs batch size (len 65K)
  Fig 13 top-p sampling: baseline sort+cumsum vs radix+MCScan build

  scan_pipeline  blocked §4 pipeline: achieved bytes/s vs memcpy baseline
                 (the paper's headline 74.9%-of-memcpy metric) across methods
                 and dtypes -> BENCH_scan_pipeline.json
  sort           radix-2^k sweep: method × dtype × bits_per_pass with
                 pass-count and bytes-moved columns (plus a trace-only guard
                 that the fused sort runs ceil(bits/k) passes)
                 -> BENCH_sort.json
  segscan        segmented scan: segment-count × mean-segment-length × method
                 on ragged packed batches, vs the dense-pad baseline
                 -> BENCH_segscan.json
  linrec         linear-recurrence scan (y = a*y_prev + b): batch × length ×
                 method × dtype on gated-decay payloads — the recurrent-model
                 decode workload on the weighted-triangular matmul scan
                 -> BENCH_linrec.json
  precision      precision axis (highest/compensated/fast) on the matmul-
                 engine methods: time + max-ulp-vs-fp64 per op × method ×
                 precision, gated against the documented ulp bound
                 -> BENCH_precision.json
  serve          continuous batching under a seeded Poisson arrival trace:
                 paged-KV ContinuousEngine vs the dense sequential baseline,
                 tokens/s + p50/p99 per-token step latency + page-pool
                 utilization, plus a trace-only guard that decode_n stages
                 exactly one while_loop -> BENCH_serve.json
  dist           distributed operator family at 8 devices:
                 measured (HLO-parsed) vs modeled (closed-form) collective
                 traffic per dist_* op, gated exactly in-run
                 -> BENCH_dist.json                                [>= 2 devices]

Every section runs in this one process.  The multi-device sections build
their mesh from ``jax.devices()`` and print a skip line with fewer than 2; on
a CPU host give the command 8 virtual devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 python benchmarks/run.py
"""
from __future__ import annotations

import argparse
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import dump_json, row, timeit  # noqa: E402
from repro.core import accum_dtype_for, scan  # noqa: E402
from repro.core.autotune import resolve_method  # noqa: E402
from repro.utils.cache import enable_compile_cache  # noqa: E402
from repro.core.primitives import (compress, radix_sort, split,  # noqa: E402
                                   top_p_sample)

QUICK_LENS = [4096, 65536, 1 << 20]
FULL_LENS = [4096, 65536, 1 << 20, 1 << 23]
SMOKE_LENS = [2048, 16384]

# "auto" rows ride along in every sweep so tools/compare_bench.py can gate
# them against the per-row oracle (best measured concrete method); their
# derived column records what the tuning table resolved to.
OP_METHODS = ("vector", "matmul", "kernel", "auto")


def _resolved(op: str, n: int, dtype) -> str:
    """``;resolved=<m>`` derived-column suffix for a method="auto" row."""
    return f";resolved={resolve_method(op, n, dtype)}"


def fig3_single_scan(lens):
    """Paper Fig. 3: execution time of vector-only CumSum vs ScanU/ScanUL1."""
    for n in lens:
        x = jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32)
        fns = {
            "vec_only": jax.jit(lambda a: jnp.cumsum(a)),
            "scanu": jax.jit(functools.partial(scan, method="matmul",
                                               variant="scanu", tile_s=128)),
            "scanul1": jax.jit(functools.partial(scan, method="matmul",
                                                 variant="scanul1", tile_s=128)),
        }
        base = None
        for name, fn in fns.items():
            t = timeit(fn, x)
            base = base or t
            row(f"fig3/{name}/n={n}", t,
                f"speedup_vs_vec={base / t:.2f}x;GB/s={8 * n / t / 1e9:.2f}")


def fig5_batched_ratio():
    """Paper Fig. 5: ScanUL1 vs ScanU time ratio across (batch, length)."""
    for batch in (4, 16, 64):
        for n in (1024, 4096, 16384):
            x = jnp.asarray(
                np.random.default_rng(1).standard_normal((batch, n)), jnp.float32)
            tu = timeit(jax.jit(functools.partial(
                scan, method="matmul", variant="scanu", tile_s=32)), x)
            tl = timeit(jax.jit(functools.partial(
                scan, method="matmul", variant="scanul1", tile_s=32)), x)
            row(f"fig5/ratio/b={batch}/n={n}", tl,
                f"scanul1_over_scanu={tl / tu:.3f}")


def _skip_multi_device(section: str) -> bool:
    """Print why a multi-device section is skipped; True when it must be."""
    n = len(jax.devices())
    if n >= 2:
        return False
    print(f"# {section}: skipped — needs >= 2 devices, JAX sees {n} "
          "(on a CPU host set XLA_FLAGS=--xla_force_host_platform_device_"
          "count=8 on this command)", flush=True)
    return True


def fig8_fig9_mcscan(lens):
    """Paper Figs. 8/9: multi-device MCScan bandwidth + int8 vs fp16 elems/s.

    Runs in this process on a mesh of every device JAX sees (needs >= 2).
    """
    if _skip_multi_device("fig8"):
        return
    from repro.core import mcscan
    from repro.utils.compat import make_mesh
    mesh = make_mesh((len(jax.devices()),), ("data",))
    specs = [(n, s, "f32") for n in lens for s in (32, 64, 128)]
    specs += [(lens[-1], 128, "bf16"), (lens[-1], 128, "int8")]
    rng = np.random.default_rng(0)
    elems = {}
    for n, s, dt in specs:
        if dt == "int8":
            x = jnp.asarray(rng.integers(-3, 4, (1, n)), jnp.int8)
        else:
            x = jnp.asarray(rng.standard_normal((1, n)),
                            jnp.bfloat16 if dt == "bf16" else jnp.float32)
        fn = jax.jit(lambda a, s=s: mcscan(a, mesh, "data", tile_s=s))
        t = timeit(fn, x)
        nb = x.dtype.itemsize * n + jnp.dtype(accum_dtype_for(x.dtype)).itemsize * n
        row(f"fig8/mcscan/n={n}/s={s}/{dt}", t,
            f"GB/s={nb / t / 1e9:.2f};GElems/s={n / t / 1e9:.3f}")
        elems[dt] = n / t / 1e9
    for n in sorted(set(sp[0] for sp in specs)):
        x = jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
        t = timeit(jax.jit(lambda a: a + 0.0), x)
        row(f"fig8/copy/n={n}", t, f"GB/s={8 * n / t / 1e9:.2f}")
    if "int8" in elems and "bf16" in elems:
        row("fig9/int8_vs_fp16", 0.0,
            f"int8_GElems/s={elems['int8']:.3f};fp16_GElems/s={elems['bf16']:.3f};"
            f"ratio={elems['int8'] / max(elems['bf16'], 1e-9):.2f}x")


def fig10_compress(lens):
    """Paper Fig. 10: compress (scan-based) vs baseline masked-select."""
    for n in lens:
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        m = jnp.asarray(rng.random(n) < 0.5)
        # pinned: this section reproduces the paper's matmul-scan operator,
        # independent of what the tuning table would pick at this length
        ours = jax.jit(lambda a, f: compress(a, f, method="matmul")[0])
        base2 = jax.jit(lambda a, f: a[jnp.nonzero(f, size=n)[0]])
        t_ours = timeit(ours, x, m)
        t_nz = timeit(base2, x, m)
        row(f"fig10/compress/n={n}", t_ours,
            f"GB/s={8 * n / t_ours / 1e9:.2f};baseline_nonzero_us={t_nz * 1e6:.1f}")


def fig11_radix_sort(lens):
    """Paper Fig. 11: fp16 radix sort (scan splits) vs jnp.sort baseline.

    Pinned to ``bits_per_pass=1`` — this figure reproduces the paper's
    per-bit formulation; the multi-bit trajectory lives in the ``sort``
    section (BENCH_sort.json).
    """
    for n in lens:
        x = jnp.asarray(np.random.default_rng(3).standard_normal(n), jnp.float16)
        t_ours = timeit(jax.jit(lambda a: radix_sort(
            a, method="matmul", bits_per_pass=1)[0]), x)
        t_base = timeit(jax.jit(lambda a: jnp.sort(a)), x)
        row(f"fig11/radix_sort/n={n}", t_ours,
            f"baseline_us={t_base * 1e6:.1f};ratio={t_base / t_ours:.2f}x")


def fig12_batched_bandwidth():
    """Paper Fig. 12: batched scan bandwidth vs batch size (len 65K)."""
    n = 65536
    for batch in (1, 4, 16, 64):
        x = jnp.asarray(np.random.default_rng(4).standard_normal((batch, n)),
                        jnp.float32)
        for s in (16, 32, 64, 128):
            t = timeit(jax.jit(functools.partial(
                scan, method="matmul", variant="scanu", tile_s=s)), x)
            row(f"fig12/batched/b={batch}/s={s}", t,
                f"GB/s={8 * batch * n / t / 1e9:.2f}")


def fig13_top_p(quick=True):
    """Paper Fig. 13: llama3-style top-p sampling, baseline vs scan-based.

    Pinned to ``bits_per_pass=1`` so the row's ``scans_per_batch=17`` (16
    sort splits + 1 CDF scan) keeps meaning the paper's per-bit operator;
    the multi-bit trajectory lives in the ``sort`` section.
    """
    vocab = 32768 if quick else 131072
    for batch in (1, 4, 16):
        logits = jnp.asarray(
            np.random.default_rng(5).standard_normal((batch, vocab)) * 3,
            jnp.float32)
        key = jax.random.PRNGKey(0)
        ours = jax.jit(lambda l, k: top_p_sample(l, k, p=0.9, method="matmul",
                                                 sort_method="radix",
                                                 bits_per_pass=1))
        base = jax.jit(lambda l, k: top_p_sample(l, k, p=0.9, method="matmul",
                                                 sort_method="xla"))
        t_ours = timeit(ours, logits, key, repeats=3, warmup=1)
        t_base = timeit(base, logits, key, repeats=3, warmup=1)
        row(f"fig13/top_p/b={batch}/v={vocab}", t_ours,
            f"baseline_us={t_base * 1e6:.1f};scans_per_batch=17")


# ---------------------------------------------------------------------------
# Blocked pipeline sweep: large-N bandwidth vs memcpy (paper §4 headline)
# ---------------------------------------------------------------------------


def scan_pipeline_sweep(lens, smoke=False):
    """Paper §4 blocked pipeline: achieved bytes/s as a fraction of memcpy.

    The paper's headline multi-core metric is scan bandwidth relative to a
    memory copy (74.9% on 8 Ascend cores).  For each length and dtype we time
    a jitted copy as the roofline, then every scan method; ``memcpy_frac`` in
    the derived column (and in BENCH_scan_pipeline.json) is
    ``(scan bytes moved / t) / (copy bytes moved / t_copy)``.  Scan moves
    ``n * (in_itemsize + accum_itemsize)`` bytes — the accumulation dtype
    (int8 -> int32, bf16 -> f32) widens the write side.
    """
    dts = {"float32": jnp.float32} if smoke else \
        {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}
    methods = ("vector", "matmul", "kernel", "blocked", "auto")
    s = 32 if smoke else 128
    for dt_name, dt in dts.items():
        for n in lens:
            rng = np.random.default_rng(0)
            if dt_name == "int8":
                x = jnp.asarray(rng.integers(-3, 4, n), dt)
            else:
                x = jnp.asarray(rng.standard_normal(n), dt)
            cp = jax.jit(lambda a: a + jnp.zeros((), a.dtype))
            t_copy = timeit(cp, x, repeats=3, warmup=1)
            copy_bw = 2 * x.nbytes / t_copy
            row(f"scan_pipeline/memcpy/{dt_name}/n={n}", t_copy,
                f"GB/s={copy_bw / 1e9:.2f};memcpy_frac=1.000")
            nbytes = x.nbytes + n * jnp.dtype(accum_dtype_for(dt)).itemsize
            for m in methods:
                fn = jax.jit(functools.partial(scan, method=m, tile_s=s))
                t = timeit(fn, x, repeats=3, warmup=1)
                bw = nbytes / t
                extra = _resolved("scan", n, dt) if m == "auto" else ""
                row(f"scan_pipeline/{m}/{dt_name}/n={n}", t,
                    f"GB/s={bw / 1e9:.2f};memcpy_frac={bw / copy_bw:.3f}"
                    f"{extra}")


# ---------------------------------------------------------------------------
# sort: radix-2^k sweep — method × dtype × bits_per_pass (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------

SORT_BITS_PER_PASS = (1, 2, 4, 8)
_SORT_DTYPES = {  # dtype -> (sort bits, encoded key bytes)
    "float32": (32, 4),
    "bfloat16": (16, 2),
    "int8": (8, 1),
}


def _count_radix_pass_launches(fn, *args) -> int:
    """Count fused radix-pass ``pallas_call`` launches in ``fn``'s jaxpr.

    Walks the jaxpr recursively (pjit bodies included) and counts every
    pallas_call whose kernel name contains ``radix_pass`` — the guard that a
    ``bits_per_pass=k`` sort really executes ``ceil(bits / k)`` fused passes
    instead of silently falling back to per-bit splits.
    """
    def walk(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                nm = eqn.params.get("name_and_src_info",
                                    eqn.params.get("name", ""))
                if "radix_pass" in str(nm):
                    total += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):       # ClosedJaxpr param
                    total += walk(v.jaxpr)
                elif hasattr(v, "eqns"):      # raw Jaxpr param
                    total += walk(v)
        return total

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def sort_pass_count_guard():
    """Assert the fused sort's launch count is exactly ``ceil(bits / k)``.

    Trace-only (no execution), so it is cheap enough to run on every sweep;
    a mismatch aborts the benchmark run with a non-zero exit — this is the
    bench-smoke CI assertion against silent per-bit fallback.
    """
    x32 = jnp.asarray(np.random.default_rng(0).standard_normal(256),
                      jnp.float32)
    x16 = x32.astype(jnp.bfloat16)
    for dt_name, x, bits in (("float32", x32, 32), ("bfloat16", x16, 16)):
        for k in SORT_BITS_PER_PASS:
            want = -(-bits // k)
            got = _count_radix_pass_launches(
                lambda a, k=k: radix_sort(a, method="kernel",
                                          bits_per_pass=k)[0], x)
            row(f"sort/pass_count/{dt_name}/k={k}", 0.0,
                f"passes={got};expected={want}")
            if got != want:
                raise SystemExit(
                    f"sort pass-count guard: {dt_name} bits_per_pass={k} "
                    f"executed {got} fused passes, expected {want}")


def sort_sweep(lens):
    """Radix-2^k sort sweep: method × dtype × bits_per_pass -> BENCH_sort.json.

    ``passes`` is ``ceil(bits / k)``; ``bytes_moved`` models the HBM traffic
    of the chained passes — every pass reads and writes both the keys and the
    int32 permutation, i.e. ``passes * n * (key_bytes + 4) * 2`` — so
    ``bits_per_pass=4`` shows the ~4x traffic cut over per-bit splits on the
    same row.  The trace-only pass-count guard runs first.
    """
    sort_pass_count_guard()
    methods = ("vector", "matmul", "kernel", "auto")
    for dt_name, (bits, key_bytes) in _SORT_DTYPES.items():
        for n in lens:
            x = _op_payload(dt_name, n, seed=6)
            for m in methods:
                base = None
                for k in SORT_BITS_PER_PASS:
                    passes = -(-bits // k)
                    bytes_moved = passes * n * (key_bytes + 4) * 2
                    fn = jax.jit(lambda a, m=m, k=k: radix_sort(
                        a, method=m, bits_per_pass=k)[0])
                    t = timeit(fn, x, repeats=3, warmup=1)
                    base = base or t
                    extra = _resolved("radix_sort", n, dt_name) \
                        if m == "auto" else ""
                    row(f"sort/{dt_name}/n={n}/{m}/k={k}", t,
                        f"passes={passes};bytes_moved={bytes_moved};"
                        f"GB/s={bytes_moved / t / 1e9:.2f};"
                        f"speedup_vs_k1={base / t:.2f}x{extra}")


# ---------------------------------------------------------------------------
# segscan: segmented scan over ragged packed batches (ISSUE 4 tentpole)
# ---------------------------------------------------------------------------


def segscan_sweep(smoke=False):
    """Segmented scan: segment-count × mean-segment-length × method sweep.

    Ragged offsets are drawn deterministically (uniform cuts, so empty and
    tiny segments occur); every method scans the same fp32 packed batch and
    the derived column reports throughput plus ``pad_waste`` — the fraction of
    extra elements a dense ``(segments, max_len)`` padding of the same batch
    would read/write, i.e. the traffic the packed layout avoids.
    """
    from repro.core.segmented import segment_scan
    methods = ("vector", "matmul", "kernel", "blocked", "auto")
    s = 16 if smoke else 128
    grid = ((4, 128), (16, 256)) if smoke else \
        ((8, 512), (64, 1024), (512, 2048))
    for num_segs, mean_len in grid:
        n = num_segs * mean_len
        rng = np.random.default_rng(7)
        cuts = np.sort(rng.integers(0, n + 1, num_segs - 1))
        offsets = jnp.asarray(np.concatenate([[0], cuts, [n]]), jnp.int32)
        lens = np.diff(np.asarray(offsets))
        pad_waste = (num_segs * int(lens.max()) - n) / n
        x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        base = None
        for m in methods:
            fn = jax.jit(lambda v, o, m=m: segment_scan(v, o, method=m,
                                                        tile_s=s))
            t = timeit(fn, x, offsets, repeats=3, warmup=1)
            base = base or t
            extra = _resolved("segment_scan", n, jnp.float32) \
                if m == "auto" else ""
            row(f"segscan/{m}/S={num_segs}/L={mean_len}", t,
                f"n={n};GB/s={8 * n / t / 1e9:.2f};"
                f"pad_waste={pad_waste:.2f};"
                f"speedup_vs_vector={base / t:.2f}x{extra}")


# ---------------------------------------------------------------------------
# linrec: linear-recurrence scan over gated decays (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------


def linrec_sweep(smoke=False):
    """Linear recurrence ``y = a*y_prev + b``: S × L × method × dtype sweep.

    Payloads model the recurrent-decode workload: multipliers are gated
    decays ``a = exp(-|g|) ∈ (0, 1]``, inputs Gaussian.  Every method scans
    the same batch; the derived column reports throughput (three streams:
    read ``a``, read ``b``, write ``y`` in the accumulation dtype) and the
    speedup over the affine-pair ``associative_scan`` vector baseline.
    """
    from repro.core.linrec import linear_scan, linrec_accum_dtype_for
    methods = ("vector", "matmul", "kernel", "blocked", "auto")
    dts = {"float32": jnp.float32} if smoke else \
        {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    s = 16 if smoke else 128
    grid = ((2, 1024), (8, 4096)) if smoke else \
        ((4, 16384), (16, 65536))
    for num_rows, length in grid:
        rng = np.random.default_rng(8)
        a_np = np.exp(-np.abs(rng.standard_normal((num_rows, length))) * 0.05)
        b_np = rng.standard_normal((num_rows, length))
        for dt_name, dt in dts.items():
            a = jnp.asarray(a_np, dt)
            b = jnp.asarray(b_np, dt)
            n = num_rows * length
            nbytes = 2 * a.dtype.itemsize * n + \
                jnp.dtype(linrec_accum_dtype_for(dt)).itemsize * n
            base = None
            for m in methods:
                fn = jax.jit(lambda a, b, m=m: linear_scan(a, b, method=m,
                                                           tile_s=s))
                t = timeit(fn, a, b, repeats=3, warmup=1)
                base = base or t
                extra = _resolved("linear_scan", length, dt) \
                    if m == "auto" else ""
                row(f"linrec/{m}/{dt_name}/S={num_rows}/L={length}", t,
                    f"n={n};GB/s={nbytes / t / 1e9:.2f};"
                    f"speedup_vs_vector={base / t:.2f}x{extra}")


# ---------------------------------------------------------------------------
# precision: fp16/bf16 matmul-engine scans + ulp accuracy (ISSUE 7 tentpole)
# ---------------------------------------------------------------------------


def precision_sweep(smoke=False):
    """Precision axis sweep: time + max-ulp per op × engine method × precision.

    Every row runs one scan-family op at ``precision in ("highest",
    "compensated", "fast")`` on the same payload and scores the result against
    the fp64 sequential reference of :mod:`repro.analysis.ulp` — the derived
    column carries ``max_ulp`` (in fp32 ulps at the conditioning scale) and
    ``ulp_bound`` (the documented contract), which ``tools/compare_bench.py``
    gates: ``max_ulp <= ulp_bound`` always, and bounded drift vs baseline.

    ``time_vs_highest`` records the speed ratio against the fp32 path of the
    same method.  On the CPU test backend XLA contracts fp16/bf16 through the
    same fp32 units, so the split's extra products make compensated ~parity to
    ~3x slower here; on an fp16-native matrix engine (the paper's target) the
    two-to-three fp16 products replace one fp32 product at twice the MAC rate
    — the documented-speedup column is measured, not modelled, so the CPU
    baseline records parity and a hardware runner records the gain.
    """
    from repro.analysis import ulp
    from repro.core.linrec import linear_scan
    from repro.core.segmented import segment_scan
    methods = ("matmul", "kernel", "blocked")
    precisions = ("highest", "compensated", "fast")
    s = 32 if smoke else 128
    sweep_lens = (2048,) if smoke else (16384, 65536)
    rng = np.random.default_rng(9)
    for n in sweep_lens:
        x = np.asarray(rng.standard_normal(n), np.float32)
        a = np.asarray(np.exp(-np.abs(rng.standard_normal((4, n))) * 0.05),
                       np.float32)
        b = np.asarray(rng.standard_normal((4, n)), np.float32)
        cuts = np.sort(rng.integers(0, n + 1, max(1, n // 512)))
        off = np.concatenate([[0], cuts, [n]]).astype(np.int32)
        cases = (
            ("scan",
             lambda m, p: jax.jit(functools.partial(
                 scan, method=m, precision=p, tile_s=s)),
             (jnp.asarray(x),), ulp.scan_ref(x), ulp.scan_scale(x)),
            ("linrec",
             lambda m, p: jax.jit(lambda u, v: linear_scan(
                 u, v, method=m, precision=p, tile_s=s)),
             (jnp.asarray(a), jnp.asarray(b)),
             ulp.linrec_ref(a, b), ulp.linrec_scale(a, b)),
            ("segscan",
             lambda m, p: jax.jit(lambda v, o: segment_scan(
                 v, o, method=m, precision=p, tile_s=s)),
             (jnp.asarray(x), jnp.asarray(off)),
             ulp.segment_scan_ref(x, off), ulp.segment_scan_scale(x, off)),
        )
        for op, make, args_, ref, scale in cases:
            base = None
            for m in methods:
                for p in precisions:
                    fn = make(m, p)
                    t = timeit(fn, *args_, repeats=3, warmup=1)
                    if p == "highest":
                        base = t
                    mu = ulp.max_ulp(np.asarray(fn(*args_)), ref, scale)
                    row(f"precision/{op}/{m}/{p}/n={n}", t,
                        f"n={n};max_ulp={mu:.2f};"
                        f"ulp_bound={ulp.ulp_bound(p, n):.1f};"
                        f"time_vs_highest={t / base:.2f}x")


# ---------------------------------------------------------------------------
# Operator benchmarks: split / sort / top-p across methods and dtypes
# (tracks the fused-kernel trajectory, not just raw scan — ISSUE 1 tentpole)
# ---------------------------------------------------------------------------

_OP_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}


def _op_payload(dtype_name, shape, seed=0):
    rng = np.random.default_rng(seed)
    if dtype_name == "int8":
        return jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), _OP_DTYPES[dtype_name])


def ops_split(n: int):
    """SplitInd across methods × payload dtypes (kernel = fused Pallas launch)."""
    f = jnp.asarray(np.random.default_rng(1).random(n) < 0.5)
    for dt in _OP_DTYPES:
        x = _op_payload(dt, n)
        base = None
        for m in OP_METHODS:
            fn = jax.jit(lambda a, fl, m=m: split(a, fl, method=m)[0])
            t = timeit(fn, x, f, repeats=3, warmup=1)
            base = base or t
            extra = _resolved("split", n, dt) if m == "auto" else ""
            row(f"ops/split/{dt}/n={n}/{m}", t,
                f"speedup_vs_vector={base / t:.2f}x{extra}")


def ops_sort(n: int, dtypes=("bfloat16", "float32")):
    """Radix sort as shipped (default ``bits_per_pass=4``) across methods × key widths."""
    for dt in dtypes:
        x = _op_payload(dt, n, seed=2)
        bits = 16 if dt == "bfloat16" else 32
        base = None
        for m in OP_METHODS:
            fn = jax.jit(lambda a, m=m: radix_sort(a, method=m)[0])
            t = timeit(fn, x, repeats=3, warmup=1)
            base = base or t
            extra = _resolved("radix_sort", n, dt) if m == "auto" else ""
            row(f"ops/sort/{dt}/n={n}/{m}", t,
                f"bits={bits};speedup_vs_vector={base / t:.2f}x{extra}")


def ops_top_p(vocab: int, batch: int = 4):
    """Nucleus sampling across methods (kernel = fused radix + one-launch tail)."""
    logits = jnp.asarray(
        np.random.default_rng(3).standard_normal((batch, vocab)) * 3,
        jnp.float32)
    key = jax.random.PRNGKey(0)
    base = None
    for m in OP_METHODS:
        fn = jax.jit(lambda l, k, m=m: top_p_sample(l, k, p=0.9, method=m))
        t = timeit(fn, logits, key, repeats=3, warmup=1)
        base = base or t
        extra = _resolved("top_p_sample", vocab, jnp.float32) \
            if m == "auto" else ""
        row(f"ops/top_p/b={batch}/v={vocab}/{m}", t,
            f"speedup_vs_vector={base / t:.2f}x{extra}")


def ops_operators(smoke: bool):
    n = 2048 if smoke else 16384
    ops_split(n)
    ops_sort(n // 2 if smoke else n, dtypes=("bfloat16",) if smoke
             else ("bfloat16", "float32"))
    ops_top_p(1024 if smoke else 16384, batch=2 if smoke else 4)


# ---------------------------------------------------------------------------
# serve: continuous batching under Poisson arrivals (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------


def serve_sweep(smoke=False):
    """Continuous-batching serve sweep under Poisson arrivals -> BENCH_serve.json.

    A seeded ragged request trace is served by ``ContinuousEngine`` (paged KV
    + in-graph ``decode_n``), against the dense sequential baseline (each
    request alone through ``ServeEngine.generate`` — the ``kv_layout="dense"``
    path).  The trace-only launch guard asserts ``decode_n`` stages exactly
    one ``while_loop`` (no per-token dispatch) and aborts the run otherwise —
    the bench-smoke CI gate.  With ``eos_id=None`` every schedule-derived
    metric (tokens, steps, peak pages, p50/p99 step latencies) is a pure
    function of the seeded trace — independent of model numerics — so
    ``tools/compare_bench.py`` gates them exactly; tokens/s stays a timing.
    """
    import time

    from repro.models.model import build_model, get_config
    from repro.serving.engine import ServeEngine
    from repro.serving.scheduler import (ContinuousEngine, count_while_loops,
                                         poisson_trace)

    cfg = get_config("llama3-8b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    page_size = 8
    grids = [(3, 13, 4, 0.4, 8)] if smoke else \
        [(4, 25, 8, 0.5, 16), (8, 49, 8, 1.0, 24)]
    for max_batch, n_pages, tick, rate, n_reqs in grids:
        eng = ContinuousEngine(cfg, params, max_batch=max_batch,
                               page_size=page_size, n_pages=n_pages,
                               max_len=32, sampler="greedy", tick_tokens=tick)
        n_while = count_while_loops(eng.decode_n_jaxpr(tick))
        row(f"serve/trace_guard/decode_n/B={max_batch}", 0.0,
            f"while_loops={n_while};expected=1")
        if n_while != 1:
            raise SystemExit(
                f"serve launch guard: decode_n staged {n_while} while_loops, "
                "expected exactly 1 (multi-token decode must be one in-graph "
                "loop, not per-token dispatch)")
        trace = poisson_trace(n_reqs, rate=rate, vocab_size=cfg.vocab_size,
                              seed=17, prompt_len=(3, 10), max_new=(2, 8))
        eng.run(trace)                  # warmup: compile prefill/decode
        t0 = time.perf_counter()
        res = eng.run(trace)
        dt = time.perf_counter() - t0
        st = res["stats"]
        lat = np.asarray(sorted(r["per_token_latency_steps"]
                                for r in res["requests"].values()))
        row(f"serve/continuous/B={max_batch}/pages={n_pages}/rate={rate}", dt,
            f"tokens={st['total_tokens']};reqs={st['reqs']};"
            f"steps={st['steps']};peak_pages={st['peak_pages']};"
            f"util={st['peak_util']:.3f};"
            f"p50_steps={np.percentile(lat, 50):.3f};"
            f"p99_steps={np.percentile(lat, 99):.3f};"
            f"tokens_per_s={st['total_tokens'] / dt:.1f}")
        dense = ServeEngine(cfg, params, max_len=eng.n_blocks * page_size,
                            sampler="greedy")
        for r in trace:                 # warmup compiles per prompt length
            dense.generate({"tokens": jnp.asarray(r.tokens)[None]},
                           r.max_new_tokens, jnp.asarray(r.key))
        t0 = time.perf_counter()
        total = 0
        for r in trace:
            total += dense.generate(
                {"tokens": jnp.asarray(r.tokens)[None]}, r.max_new_tokens,
                jnp.asarray(r.key)).shape[1]
        dt_d = time.perf_counter() - t0
        row(f"serve/dense_sequential/B={max_batch}/rate={rate}", dt_d,
            f"tokens={total};reqs={n_reqs};"
            f"tokens_per_s={total / dt_d:.1f};"
            f"continuous_speedup={dt_d / dt:.2f}x")


def _dist_case(op, mesh, n, bpp, rng):
    """``(fn, args, dtype name)`` for one distributed operator row."""
    from repro.core import (dist_linear_scan, dist_radix_sort,
                            dist_segment_scan, dist_top_p_sample)
    if op == "dist_sort":
        x = jnp.asarray(rng.normal(size=(2, n)), jnp.bfloat16)
        return (lambda v: dist_radix_sort(
            v, mesh, "data", method="matmul", tile_s=32,
            bits_per_pass=bpp)), (x,), "bfloat16"
    if op == "dist_top_p_sample":
        lg = jnp.asarray(rng.normal(size=(2, n)) * 3, jnp.float32)
        return (lambda v, k: dist_top_p_sample(
            v, k, mesh, "data", p=0.9, method="matmul", tile_s=32,
            bits_per_pass=bpp)), (lg, jax.random.PRNGKey(0)), "float32"
    if op == "dist_linear_scan":
        a = jnp.asarray(rng.uniform(0.8, 1.2, size=(2, n)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(2, n)), jnp.float32)
        return (lambda u, v: dist_linear_scan(
            u, v, mesh, "data", method="matmul", tile_s=32)), (a, b), "float32"
    xs = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    off = jnp.asarray([0, n // 3, n], jnp.int32)
    return (lambda v, o: dist_segment_scan(
        v, o, mesh, "data", method="matmul", tile_s=32)), (xs, off), "float32"


def dist_sweep(smoke=False):
    """Distributed operator family: measured-vs-modeled traffic -> BENCH_dist.json.

    Every ``dist_*`` operator is lowered at 8 (and, non-smoke, 2) devices of
    this process — rows whose device count exceeds what JAX sees are
    skipped with a printed line; the post-SPMD HLO is parsed for collectives
    (:func:`repro.analysis.roofline.summarize_collectives`) and compared —
    in-run, aborting on mismatch — against the closed forms of
    :func:`repro.analysis.collectives.modeled_dist_traffic`
    (docs/distributed.md §Traffic).  Collective counts and operand bytes are
    both shape-derived, so the gate is **exact**: the committed
    ``bytes_measured`` must equal ``bytes_modeled`` on every row, and
    ``tools/compare_bench.py`` re-gates all three derived columns against the
    committed baseline.  Timings ride along informationally.
    """
    if _skip_multi_device("dist"):
        return
    from repro.analysis.collectives import modeled_dist_traffic
    from repro.analysis.roofline import summarize_collectives
    from repro.utils.compat import make_mesh
    n = 256 if smoke else 2048
    specs = [("dist_sort", 8, n, 8), ("dist_top_p_sample", 8, n, 8),
             ("dist_linear_scan", 8, n, 8), ("dist_segment_scan", 8, n, 8)]
    if not smoke:
        specs += [("dist_sort", 2, n, 4), ("dist_linear_scan", 2, n, 4)]
    rng = np.random.default_rng(0)
    for op, d, nn, bpp in specs:
        if d > len(jax.devices()):
            print(f"# dist/{op}/d={d}: skipped — JAX sees "
                  f"{len(jax.devices())} devices", flush=True)
            continue
        mesh = make_mesh((d,), ("data",))
        fn, args, dt = _dist_case(op, mesh, nn, bpp, rng)
        compiled = jax.jit(fn).lower(*args).compile()
        meas = summarize_collectives(compiled.as_text())
        t = timeit(compiled, *args, repeats=3, warmup=1)
        mc, mb = meas["collective_count"], float(meas["operand_bytes"])
        mod = modeled_dist_traffic(op, d=d, n=nn, batch=1 if op ==
                                   "dist_segment_scan" else 2, dtype=dt,
                                   bits_per_pass=bpp)
        row(f"dist/{op}/matmul/{dt}/d={d}/n={nn}", t,
            f"collective_count={mc};bytes_measured={mb:.0f};"
            f"bytes_modeled={mod['operand_bytes']:.0f}")
        if mc != mod["collective_count"] or mb != mod["operand_bytes"]:
            raise SystemExit(
                f"dist traffic guard: {op} d={d} n={nn} measured "
                f"{mc} collectives / {mb:.0f} operand bytes, model says "
                f"{mod['collective_count']} / {mod['operand_bytes']:.0f} — "
                "the lowered HLO no longer matches docs/distributed.md "
                "§Traffic")


def guards_identity_guard():
    """Assert guards-off traces are byte-identical to ``guards_disabled``.

    Rule 10's zero-overhead contract: with ``REPRO_CHECKS`` unset, every
    guarded operator must stage the exact jaxpr it staged before the guards
    layer existed.  Trace-only (no execution); a mismatch aborts the run with
    a non-zero exit — the bench-smoke CI gate against guard ops leaking into
    the default trace.
    """
    import re

    from repro.core import guards
    from repro.core.linrec import linear_scan
    from repro.core.primitives import weighted_sample
    from repro.core.segmented import segment_scan, segment_top_p_sample

    x = jnp.asarray(np.random.default_rng(0).standard_normal(5), jnp.float32)
    off = jnp.asarray([0, 3, 5], jnp.int32)
    cases = {
        "scan": lambda v: scan(v),
        "linrec": lambda v: linear_scan(v, v),
        "segment_scan": lambda v: segment_scan(v, off),
        "weighted_sample": lambda v: weighted_sample(
            v, None, u=jnp.asarray(0.5)),
        "top_p": lambda v: top_p_sample(v[None], None, p=0.9,
                                        u=jnp.asarray([[0.5]])),
        "segment_top_p": lambda v: segment_top_p_sample(
            v, off, p=0.9, u=jnp.asarray([[0.5], [0.5]])),
    }

    def trace(fn):
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(x)))

    for name, fn in cases.items():
        with guards.checks(False):
            guarded = trace(fn)
        with guards.guards_disabled():
            bare = trace(fn)
        same = guarded == bare
        row(f"guards/jaxpr_identity/{name}", 0.0, f"identical={same}")
        if not same:
            raise SystemExit(
                f"guards jaxpr-identity guard: {name} traces differently "
                "with the guards layer active (checks off) vs disabled")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger sizes")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + fast sections only (CI)")
    ap.add_argument("--only", default=None,
                    help="comma list of section ids, e.g. fig3,ops")
    ap.add_argument("--json-out", default=None, metavar="DIR",
                    help="write BENCH_<section>.json row files to DIR")
    args = ap.parse_args()
    enable_compile_cache()
    lens = SMOKE_LENS if args.smoke else (FULL_LENS if args.full else QUICK_LENS)
    sections = {
        "fig3": lambda: fig3_single_scan(lens),
        "fig5": fig5_batched_ratio,
        "fig8": lambda: fig8_fig9_mcscan(lens),
        "fig10": lambda: fig10_compress(lens[:2]),
        "fig11": lambda: fig11_radix_sort(lens[:2]),
        "fig12": fig12_batched_bandwidth,
        "fig13": lambda: fig13_top_p(quick=not args.full),
        "scan_pipeline": lambda: scan_pipeline_sweep(lens, smoke=args.smoke),
        "sort": lambda: sort_sweep([512] if args.smoke else lens[:2]),
        "segscan": lambda: segscan_sweep(smoke=args.smoke),
        "linrec": lambda: linrec_sweep(smoke=args.smoke),
        "precision": lambda: precision_sweep(smoke=args.smoke),
        "ops": lambda: ops_operators(smoke=args.smoke),
        "serve": lambda: serve_sweep(smoke=args.smoke),
        "dist": lambda: dist_sweep(smoke=args.smoke),
        "guards": guards_identity_guard,
    }
    only = set(args.only.split(",")) if args.only else None
    if args.smoke and only is None:
        # fast sections (sort carries the pass-count guard, serve the
        # while-loop launch guard, guards the jaxpr-identity guard, dist the
        # measured-vs-modeled traffic guard)
        only = {"fig3", "fig10", "fig11", "scan_pipeline", "sort", "segscan",
                "linrec", "precision", "ops", "serve", "dist", "guards"}
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if only and name not in only:
            continue
        fn()
    if args.json_out:
        for p in dump_json(args.json_out):
            print(f"# wrote {p}", flush=True)


if __name__ == "__main__":
    main()
