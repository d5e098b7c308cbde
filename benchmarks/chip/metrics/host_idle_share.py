"""Scheduler host loop: the share of the window in which the chip is idle
while the host is outside any program and outside ``engine/decode`` and
``engine/prefill`` (admission, insert, release, staging, harvest, or no engine
span at all), on the clock ``clock.py`` aligns; classes in ``spans.py``.  Over
the part of the window the chip's trace records: where a long trace fills the
profiler's buffer, the tail after the chip's events end is left out."""


def read(run):
    idle, red = run.get("idle"), run.get("trace")
    if not idle or not run.get("engine_spans") or not red:
        return None
    recorded = red["window_s"] - idle["lost_tail"]
    return 100.0 * idle["host"] / recorded if recorded > 0 else None
