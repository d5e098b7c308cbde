"""Scheduler: the 95th percentile, over the requests admitted in the window,
of the time from a request's ``engine/arrive`` span (the first pass of the
engine's loop at which its step clock reached the arrival) to the start of
its admission (``engine/admit``), from the engine's own spans."""
import numpy as np


def read(run):
    waits = run.get("queue_wait_s")
    if not waits:
        return None
    return float(np.percentile(1e3 * np.asarray(list(waits.values()), float), 95))
