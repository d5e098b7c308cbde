"""Model step: device time of the ``_prefill_impl`` programs in the window per
thousand prompt tokens prefilled in it."""
from chip import trace


def read(run):
    if run["trace"] is None:
        return None
    tokens = sum(p["prompt"] for p in run["window"]["prefills"])
    secs = trace.program_seconds(run["trace"], "jit__prefill_impl")
    if not tokens or secs <= 0:
        return None
    return 1e3 * secs / tokens * 1e3
