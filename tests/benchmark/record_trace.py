"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python3 tests/benchmark/record_trace.py OUT_DIR

On a TPU: two jitted programs (``jit_probe_step``, a matmul and a reduction,
and ``jit_probe_loop``, a ``while`` loop of matmuls) run inside the harness's
host spans and a ``bench/window`` span, with a host-only pause between them
that the reduction must report as idle.  Writes ``OUT_DIR/small.xplane.pb``
and prints what the trace holds, plane by plane.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1

    def probe_step(x):
        return jnp.tanh(x @ x).sum(0)

    def probe_loop(x):
        return jax.lax.fori_loop(0, 4, lambda i, y: jnp.tanh(y @ x), x)

    step, loop = jax.jit(probe_step), jax.jit(probe_loop)
    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    jax.block_until_ready((step(x), loop(x)))
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench/window"):
        with jax.profiler.TraceAnnotation("admit/prefill"):
            jax.block_until_ready(step(x))
        time.sleep(0.05)
        with jax.profiler.TraceAnnotation("tick/decode_n"):
            jax.block_until_ready(loop(x))
    jax.profiler.stop_trace()
    found = list(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(found[0], Path(out) / "small.xplane.pb")
    pd = ProfileData.from_file(str(found[0]))
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", line.name, len(evs))
            for e in evs[:6]:
                print("    ", e.name, e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:6]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
