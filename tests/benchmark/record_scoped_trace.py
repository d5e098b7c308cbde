"""Record the scoped TPU trace that ``test_bench_spans.py`` and
``test_bench_scopes.py`` read.

    python3 tests/benchmark/record_scoped_trace.py OUT_DIR

On a TPU: the small qwen3 configuration of the harness's CPU tests
(``chip_cases.QWEN``) served by ``ContinuousEngine`` with the top-p sampler,
six requests of two prompt lengths with staggered arrivals through a batch of
four (so two wait in the queue), inside a ``bench/window`` span.  The
program's own named scopes and ``engine/*`` spans are in the trace.  Writes
``OUT_DIR/scoped.xplane.pb.gz`` and, beside it, the compiled text of the decode
tick and of each prefill shape (``scoped.hlo.json.gz``, the harness's
``scopes.decode_text`` and ``scopes.prefill_text`` on the engine), then
prints the reductions of ``spans.py`` and ``scopes.py`` on it.
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import tempfile
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "benchmarks", Path(__file__).resolve().parent):
    sys.path.insert(0, str(p))


def requests(vocab: int):
    import numpy as np
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(0)
    shapes = [(8, 6, 0), (16, 5, 0), (8, 7, 1), (16, 4, 2), (8, 6, 3), (16, 5, 3)]
    return [Request(rid=f"req{i}", tokens=rng.integers(0, vocab, p, dtype=np.int32),
                    max_new_tokens=o, key=np.array([0, i], np.uint32), arrival_step=a)
            for i, (p, o, a) in enumerate(shapes)]


def main(out: str) -> int:
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    import chip_cases
    from chip import run as harness
    from chip import scopes, spans, trace
    from repro.models.model import build_model
    from repro.serving.scheduler import ContinuousEngine

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    mc = harness.model_config(chip_cases.QWEN)
    params = build_model(mc).init(jax.random.PRNGKey(0), dtype=np.dtype(mc.dtype))
    eng = ContinuousEngine(mc, params, **chip_cases.TRAFFIC["engine"])
    reqs = requests(mc.vocab_size)
    res = eng.run(reqs)                                 # compile every shape
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        res = eng.run(reqs)
    jax.profiler.stop_trace()
    path = trace.find_xspace(log_dir)
    outd = Path(out)
    outd.mkdir(parents=True, exist_ok=True)
    with open(path, "rb") as f, gzip.open(outd / "scoped.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)

    win = types.SimpleNamespace(engine=eng, _decode=eng._decode_n, _prefill=eng._prefill)
    pd = ProfileData.from_file(path)
    red = trace.reduce_trace(pd)
    tl = spans.reduce(pd, red)
    shapes = sorted({(s[3]["prompt"], s[3]["pages"] * eng.page_size)
                     for s in tl["spans"] if s[0] == spans.ADMIT})
    hlo = {"decode": scopes.decode_text(win),
           "prefill": {f"{p},{c}": scopes.prefill_text(win, p, c) for p, c in shapes}}
    with gzip.open(outd / "scoped.hlo.json.gz", "wt") as f:
        json.dump(hlo, f)
    summ = {"decode_steps": sum(s[3]["steps"] for s in tl["spans"]
                                if s[0] == "engine/harvest")}
    program = dict(tl, scopes=scopes.reduce(red, tl, win, summ))
    del program["timelines"]
    for line in spans.report(program):
        print(line)
    print("stats", res["stats"])
    print("programs", {k: len(v) for k, v in red["devices"][0]["programs"].items()})
    print("modules", sorted({e.name for p in pd.planes if trace.DEVICE_PLANE.match(p.name)
                             for ln in p.lines if ln.name == trace.MODULES_LINE
                             for e in ln.events})[:20])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
