#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed 7 \\
        --seconds 30 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration file,
``configs/<config>.json``, which names the module of its architecture,
``reference/<module>.py``, and a traffic file, ``traffic/<mix>.json``; with
``--trace 1`` each per-layer metric of the cell is read by its own module,
``metrics/<metric>.py``.  One process: weights made on the chip from the seed,
set-up (every program the window uses compiled or loaded from the
persistent cache in ``<checkout>/.jax_cache``), one measured window, then the
check of the served tokens against the plain reference.  Lines before the
last are information; the last line of standard output is the result, and
the numbers compared for ``correct`` are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, or
without the program beside the benchmark, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import typing  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SAMPLER_CALLS = 10


def info(msg: str) -> None:
    print(msg, flush=True)


def load_cell(name: str):
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(ROOT / conf["file"]) as f:
        cfg = json.load(f)
    listed = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return (cell, cfg, [m for m in bench["end_to_end"] if listed(m)],
            [m for m in bench["per_layer"] if listed(m)])


def _published(cfg, sa, table):
    """The names of the ``serve_as`` fields (``group.field`` inside a nested
    group) that differ from what ``table`` says the file publishes: a key,
    whose value in the file is the served one (a ``reduced`` key's cut
    value), or a function of the file.  A field that neither side has is
    no part of the architecture and is skipped."""
    missing = object()
    bad = []
    for field, want in table.items():
        got = sa
        for part in field.split("."):
            got = got.get(part, missing) if isinstance(got, dict) else missing
        want = want(cfg) if callable(want) else cfg.get(want, missing)
        if got is missing and want is missing:
            continue
        got, want = (tuple(v) if isinstance(v, list) else v for v in (got, want))
        if got is missing or want is missing or got != want:
            bad.append(field)
    return bad


def _dataclass(cls, values, where=""):
    """``cls(**values)``, a nested dict made into the sub-configuration its
    field's type names and a list into a tuple; an unknown key raises."""
    hints = typing.get_type_hints(cls)
    kw = {}
    for k, v in values.items():
        if k not in hints:
            raise ValueError(f"serve_as key {where}{k} is no field of {cls.__name__}")
        if isinstance(v, dict):
            sub = [t for t in (hints[k], *typing.get_args(hints[k]))
                   if dataclasses.is_dataclass(t)]
            if not sub:
                raise ValueError(f"serve_as key {where}{k} holds a group, but "
                                 f"{cls.__name__}.{k} is no sub-configuration")
            v = _dataclass(sub[0], v, f"{where}{k}.")
        elif isinstance(v, list):
            v = tuple(v)
        kw[k] = v
    return cls(**kw)


def model_config(cfg):
    """The program's ``ModelConfig`` from the file's ``serve_as``, checked
    against the published keys it stands for (the table ``PUBLISHED`` of the
    file's own ``reference`` module)."""
    from repro.configs.base import ModelConfig
    from chip.counts import arch
    table = arch(cfg).PUBLISHED
    published = cfg.get("published", {})
    reduced = cfg.get("reduced", [])
    for k in reduced:
        if k not in published:
            raise ValueError(f"{cfg['name']}: reduced key {k} has no source "
                             "value under published")
        if k not in table.values():
            raise ValueError(f"{cfg['name']}: reduced key {k} is not checked "
                             "against serve_as by the reference module")
    extra = set(published) - set(reduced)
    if extra:
        raise ValueError(f"{cfg['name']}: published keys {sorted(extra)} are "
                         "not listed under reduced")
    bad = _published(cfg, cfg["serve_as"], table)
    if bad:
        raise ValueError(f"{cfg['name']}: serve_as differs from the published "
                         f"keys in {bad}")
    return _dataclass(ModelConfig, {**cfg["serve_as"], "name": cfg["name"]})


def p95(xs):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), 95)) if xs else None


def read_metric(name: str, run) -> float | None:
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def sampler_probe(engine, seed: int, rows: int, width: int):
    """One call of the engine's ``_sample_rows``, jitted alone on seeded fp32
    logits of shape (rows, width), that waits for its result."""
    import jax
    import numpy as np
    from chip import weights
    k = weights.seed_key(seed)
    logits = jax.random.normal(jax.random.fold_in(k, 11), (rows, width), np.float32)
    keys = jax.random.split(jax.random.fold_in(k, 12), rows)

    def sampler_probe(logits, keys):
        return engine._sample_rows(logits, keys)

    fn = jax.jit(sampler_probe)
    return lambda: jax.block_until_ready(fn(logits, keys))


def run_cell(cell, cfg, spec, e2e, per_layer, *, seed: int,
             seconds: float, traced: bool, devices, limits: dict,
             engine_hook=None, control: bool = False, peak_row=None):
    """One run of a cell on ``devices``: set-up, the window, the check.

    Returns the result dict whose JSON is the last line of a run.
    ``engine_hook(engine)`` may replace parts of the engine before set-up (the
    fault tests break the timed path with it); ``control=True`` puts the fp8
    reference's own choices in the program's place for the check;
    ``peak_row`` stands in for the peaks of a device the table lacks (tests).
    """
    import jax
    import numpy as np
    from chip import correct, counts, serve, trace, traffic, weights
    from repro.models.model import build_model
    from repro.serving.scheduler import ContinuousEngine

    d0 = devices[0]
    pk = peak_row or counts.peaks(d0.device_kind)
    compiles = [0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    mc = model_config(cfg)
    model = build_model(mc)
    layout = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               dtype=np.dtype(mc.dtype)))
    params = weights.build(layout, seed)
    jax.block_until_ready(params)
    info(f"weights: {counts.param_count(cfg)} parameters served, "
         f"{counts.weight_bytes(cfg)} bytes, made at "
         f"{time.perf_counter() - T_START:.2f} s")
    engine = ContinuousEngine(mc, params, **spec["engine"])
    serve.check_wrappable(engine)
    if engine_hook is not None:
        engine_hook(engine)
    stream = traffic.generate(spec, cfg["vocab_size"], seed)
    logits_width = layout["embed"]["embed"].shape[0]

    probe = None
    if traced and spec["engine"]["sampler"] != "greedy":
        probe = sampler_probe(engine, seed, spec["engine"]["max_batch"], logits_width)
    serve.warm_up(engine, traffic.warm_requests(spec), cfg["vocab_size"], probe)

    log_dir = tempfile.mkdtemp(prefix="chip-trace-") if traced else None
    opened, span = [], []

    def on_open():
        if traced:
            jax.profiler.start_trace(log_dir)
            span.append(jax.profiler.TraceAnnotation(trace.WINDOW_SPAN))
            span[0].__enter__()
        opened.append(compiles[0])

    win = serve.Window(engine, stream, seconds, on_open=on_open)
    win.run()
    compiles_in_window = compiles[0] - opened[0]
    sampler_s, red = [], None
    if traced:
        span[0].__exit__(None, None, None)
        if probe is not None:
            with jax.profiler.TraceAnnotation("sampler/solo"):
                for _ in range(SAMPLER_CALLS):
                    probe()
        jax.profiler.stop_trace()
        red = trace.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        if probe is not None:
            sampler_s = trace.durations(red, "jit_sampler_probe")
    setup_s = win.open_t - T_START
    summ = win.summary()
    stats = d0.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    info(f"window: {summ['window_s']:.3f} s, {summ['decode_steps']} decode steps, "
         f"{summ['output_tokens']} output tokens, {len(summ['prefills'])} prefills")
    ticks = summ["ticks"]
    if ticks:
        # a stall shows as one slow tick (device or sync) or one long gap
        # between ticks (the host: admissions, prefills, the harness)
        per_step = sorted((t["t1"] - t["t0"]) / max(t["n"], 1) for t in ticks)
        gaps = [b["t0"] - a["t1"] for a, b in zip(ticks, ticks[1:])]
        info(f"ticks: {len(ticks)}, per step median {1e3 * per_step[len(per_step) // 2]:.2f} "
             f"ms, slowest {1e3 * per_step[-1]:.2f} ms; longest gap between ticks "
             f"{max(gaps, default=0.0):.3f} s, all gaps {sum(gaps):.3f} s")
    info(f"tails: ttft over {len(summ['ttft_s'])} requests, tpot over "
         f"{len(summ['tpot_s'])} requests")
    info(f"compiles inside the window: {compiles_in_window}")
    info(f"peak_bytes_in_use: {peak}")

    served = {i: list(win.req[i]["tokens"]) for i in win.req}
    finished = summ["finished"]
    failed = sum(correct.malformed(stream[i], served[i], cfg["vocab_size"])
                 for i in finished)
    del engine, params, win, probe
    gc.collect()

    ids = correct.pick_sample(finished, stream, served, seed)
    got = (correct.readings(cfg, spec["engine"], stream, served, ids, seed,
                            control=control) if ids else {})
    side = "_control" if control else ""
    checks = {n: {"value": got.get(n + side), "limit": lim}
              for n, lim in limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())

    run = {"cfg": cfg, "peaks": pk, "window": summ, "trace": red,
           "max_batch": spec["engine"]["max_batch"], "chips": len(devices),
           "logits_width": logits_width, "sampler_s": sampler_s}
    metrics = {}
    if traced:
        for m in per_layer:
            v = read_metric(m["name"], run)
            if v is None:
                # listed for this cell, so its reader must find what it reads:
                # a program renamed or taken off the path fails here, loudly
                raise RuntimeError(
                    f"per-layer metric {m['name']} found nothing to read in "
                    f"this traced run of {cell.get('name')}; see "
                    f"benchmarks/chip/metrics/{m['name']}.py")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"output_tokens_per_s": summ["output_tokens"] / summ["window_s"],
                  "ttft_p95_ms": p95([1e3 * x for x in summ["ttft_s"]]),
                  "tpot_p95_ms": p95([1e3 * x for x in summ["tpot_s"]]),
                  "setup_s": setup_s}
        for m in e2e:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    info(f"setup_s: {setup_s}")
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": len(finished), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace.busy_seconds(red)
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace.breakdown(red)
    result["checks"] = checks
    if control:
        result["readings"] = got
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program beside the benchmark (no src/repro under {ROOT})",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]
    cell, cfg, e2e, per_layer = load_cell(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    d0 = devices[0]
    info(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devices)}")
    if d0.platform != "tpu":
        print(f"no TPU: JAX sees {d0.platform}; the benchmark never times the CPU",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from chip import correct, traffic
    spec = traffic.load(cell["traffic"])
    result = run_cell(cell, cfg, spec, e2e, per_layer,
                      seed=args.seed, seconds=args.seconds,
                      traced=bool(args.trace), devices=devices[:cell["chips"]],
                      limits=correct.limits(args.workload))
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
