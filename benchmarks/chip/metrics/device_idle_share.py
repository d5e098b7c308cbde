"""Device: the share of the window in which no operation ran on the chip
(1 - the union of the trace's device-op intervals over the window)."""
from chip import trace


def read(run):
    red = run["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(red) / red["window_s"])
