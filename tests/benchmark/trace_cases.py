"""The traces recorded on a chip that the harness's CPU tests read, and the
same traces with events taken out."""
from __future__ import annotations

import gzip
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def recorded_trace(name: str):
    """A trace under ``data/`` (gzipped or not) as ``ProfileData``."""
    from jax.profiler import ProfileData
    if name.endswith(".gz"):
        with gzip.open(DATA / name, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(DATA / name))


class _Ev:
    def __init__(self, e):
        self.name, self.start_ns, self.duration_ns = e.name, e.start_ns, e.duration_ns
        self.stats = list(e.stats)


def filter_events(pd, keep):
    """The trace with only the events for which ``keep(plane, event)``."""
    return type("PD", (), {"planes": [
        type("Plane", (), {"name": p.name, "lines": [
            type("Line", (), {"name": ln.name,
                              "events": [_Ev(e) for e in ln.events if keep(p, e)]})()
            for ln in p.lines]})()
        for p in pd.planes]})()


def cut_chip_events(pd, at_ns):
    """The trace as if the profiler's buffer had filled at chip time
    ``at_ns``: the chip's events from then on are gone, the host's kept."""
    return filter_events(
        pd, lambda p, e: not p.name.startswith("/device:TPU:") or e.start_ns < at_ns)
