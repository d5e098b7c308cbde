"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU trace has one
plane per chip (``/device:TPU:<n>``) whose line ``XLA Modules`` holds one
event per execution of a compiled program (``jit__decode_n_impl(...)``) and
whose line ``XLA Ops`` holds one event per operation, and a host plane
(``/host:CPU``) whose lines hold the host's spans, the harness's
``TraceAnnotation`` spans among them.  Every time here is clipped to the
host span that marks the measured window (``bench/window``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench/window"
HOST_SPANS = ("admit/prefill", "tick/decode_n", "sampler/solo")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xspace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def program_name(event_name: str) -> str:
    """``jit__decode_n_impl(123)`` -> ``jit__decode_n_impl``."""
    return event_name.split("(", 1)[0].strip()


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for e in line.events:
            yield e.name, e.start_ns, e.start_ns + e.duration_ns


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _leaves(ops):
    """The ops that hold no other op: a ``while`` or a call spans the ops of
    its body, which the trace lists too."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if not (i + 1 < len(ops) and ops[i + 1][1] < o[2] and ops[i + 1][2] <= o[2])]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if min(b, hi) > max(a, lo)]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(mid: float, spans: List[Tuple[str, float, float]]) -> str:
    for name, a, b in spans:
        if a <= mid <= b:
            return name
    return "tick/host"


def reduce_trace(pd) -> Dict:
    """The reduced trace of a ``ProfileData``: the window, and per device its
    busy time, each program's run times, per-op time, and idle gaps."""
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    spans = [(n, a, b) for p in host for n, a, b in _events(p)
             if n in HOST_SPANS or n == WINDOW_SPAN]
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = win[0]
    spans = sorted((s for s in spans if s[0] != WINDOW_SPAN), key=lambda s: s[1])
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    per_device = []
    for plane in devices:
        mods = [(program_name(n), a, b) for n, a, b in _events(plane, MODULES_LINE)]
        ops = [(op_name(n), a, b) for n, a, b in _events(plane, OPS_LINE)] or mods
        busy = _union(_clip([(a, b) for _, a, b in ops], lo, hi))
        programs: Dict[str, List[float]] = defaultdict(list)
        for name, a, b in mods:
            programs[name].append((b - a) * 1e-9)
        win_programs: Dict[str, float] = defaultdict(float)
        for name, a, b in mods:
            for ca, cb in _clip([(a, b)], lo, hi):
                win_programs[name] += (cb - ca) * 1e-9
        op_time: Dict[str, float] = defaultdict(float)
        mods_sorted = sorted(mods, key=lambda m: m[1])
        j = 0
        for name, a, b in _leaves(ops):
            for ca, cb in _clip([(a, b)], lo, hi):
                while j + 1 < len(mods_sorted) and mods_sorted[j][2] < a:
                    j += 1
                owner = (mods_sorted[j][0] if mods_sorted
                         and mods_sorted[j][1] <= a <= mods_sorted[j][2] else "")
                op_time[f"{owner}/{name}" if owner else name] += (cb - ca) * 1e-9
        gaps = []
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label((a + b) / 2, spans), (b - a) * 1e-9))
        per_device.append({
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "programs": dict(programs), "programs_in_window_s": dict(win_programs),
            "op_seconds": dict(op_time), "gaps": gaps})
    return {"window_s": (hi - lo) * 1e-9, "devices": per_device}


def load(log_dir: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_trace(ProfileData.from_file(find_xspace(log_dir)))


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The device operations that took most time and the idle time by what
    the host was doing, summed over the chips used."""
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for d in red["devices"]:
        for name, s in d["op_seconds"].items():
            ops[name] += s
        for name, s in d["gaps"]:
            idle[name] += s
    rank = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])
    return {"device_ops": rank(ops)[:top], "idle_gaps": rank(idle)[:top]}


def program_seconds(red: Dict, prefix: str) -> float:
    """Device seconds, within the window, of programs whose name starts with
    ``prefix``, averaged over chips."""
    return sum(s for d in red["devices"]
               for name, s in d["programs_in_window_s"].items()
               if name.startswith(prefix)) / len(red["devices"])


def busy_seconds(red: Dict) -> float:
    return sum(d["busy_s"] for d in red["devices"]) / len(red["devices"])


def durations(red: Dict, prefix: str) -> List[float]:
    """Device seconds of every execution of programs whose name starts with
    ``prefix``, on the first chip, window or not."""
    return [s for name, ds in red["devices"][0]["programs"].items()
            if name.startswith(prefix) for s in ds]
