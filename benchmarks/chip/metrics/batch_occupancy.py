"""Scheduler: decode tokens emitted over decode steps times ``max_batch``, in
the window (counted by the harness's ``_decode_n`` wrapper)."""


def read(run):
    w = run["window"]
    if not w["decode_steps"]:
        return None
    return 100.0 * w["decode_tokens"] / (w["decode_steps"] * run["max_batch"])
