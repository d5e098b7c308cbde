"""One clock for the host's spans and the chip's events in a profiler trace.

A TPU trace times the chip's events (``XLA Modules``, ``XLA Ops``) on the
chip's clock and the host's spans on the host's; the two disagree by about a
millisecond.  Each execution of a program carries a ``run_id`` on its
``XLA Modules`` event, and the host's ``DoEnqueueProgram`` (the program is
handed to the chip) and ``CompleteCallbacks`` (the host learns it is done)
carry the same ``run_id``.  So with ``host = chip + delta``::

    DoEnqueueProgram start  <=  chip start + delta
    chip end + delta        <=  CompleteCallbacks start

Every execution bounds ``delta`` from both sides.  The two clocks also drift
apart, by some tens of microseconds over a minute on a v5e host (two 51 s
traced runs found no single offset), so the executions are taken in order and
cut into the fewest stretches over which one offset fits them all; each
stretch takes the middle of its feasible interval, whose width says how well
the trace pins it.  An execution whose own bounds contradict each other
raises, and so do more stretches, or a larger jump between two adjacent
ones, than drift explains: a host mark joined to the wrong execution is off
by a whole execution, and breaks the offset into many stretches far apart.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

from chip import trace

ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
INF = float("inf")
# drift on a v5e host: two 51 s traced runs needed 0.062 and 0.072 ms between
# their first and last executions; four found one offset
MAX_STRETCHES = 16
MAX_JUMP_NS = 250_000.0

# a stretch of one chip's executions: the chip time its first one starts and
# the interval (lo, hi) of offsets, in ns, that fits each of them
Stretch = Tuple[float, float, float]


def host_marks(pd):
    """``(device ordinal, run_id)`` -> earliest enqueue start, and -> latest
    completion start, from the host planes."""
    enq: Dict[Tuple[int, int], float] = {}
    done: Dict[Tuple[int, int], float] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name not in (ENQUEUE, COMPLETE):
                    continue
                st = dict(e.stats)
                if "run_id" not in st:
                    continue
                k = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                if e.name == ENQUEUE:
                    enq[k] = min(enq.get(k, e.start_ns), e.start_ns)
                else:
                    done[k] = max(done.get(k, e.start_ns), e.start_ns)
    return enq, done


def stretches(bounds: List[Tuple[float, float, float]]) -> List[Stretch]:
    """Cut executions (chip start, lo, hi), in order of start, into the
    fewest consecutive stretches whose intervals of offsets are not empty.
    A stretch not bounded on both sides is left out: its executions take
    the offset of the stretch before.  Raises past :data:`MAX_STRETCHES`
    stretches, or where two adjacent ones lie :data:`MAX_JUMP_NS` apart."""
    out: List[List[float]] = []
    for t, lo, hi in bounds:
        if lo > hi:
            raise ValueError(f"the execution at chip time {t} ns allows no clock "
                             f"offset: >= {lo} ns and <= {hi} ns")
        if out and max(out[-1][1], lo) <= min(out[-1][2], hi):
            out[-1][1], out[-1][2] = max(out[-1][1], lo), min(out[-1][2], hi)
        else:
            out.append([t, lo, hi])
    got = [(t, lo, hi) for t, lo, hi in out if lo > -INF and hi < INF]
    if len(got) > MAX_STRETCHES:
        raise ValueError(f"{len(got)} clock stretches, more than drift explains "
                         f"({MAX_STRETCHES})")
    for (t0, lo0, hi0), (t1, lo1, hi1) in zip(got, got[1:]):
        if max(lo1 - hi0, lo0 - hi1) > MAX_JUMP_NS:
            raise ValueError(f"the clock offset jumps by more than {MAX_JUMP_NS} ns "
                             f"between the stretches at chip times {t0} and {t1} ns")
    return got


def intervals(pd, marks=None) -> Dict[int, List[Stretch]]:
    """Per chip (the ``n`` of ``/device:TPU:<n>``), its stretches.  A chip
    that no execution bounds from both sides is left out.  ``marks`` is
    :func:`host_marks` of the same trace, where already read."""
    enq, done = marks or host_marks(pd)
    out = {}
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        n = int(plane.name.rsplit(":", 1)[1])
        bounds = []
        for line in plane.lines:
            if line.name != trace.MODULES_LINE:
                continue
            for e in line.events:
                run_id = dict(e.stats).get("run_id")
                k = (n, int(run_id)) if run_id is not None else None
                if k in enq or k in done:
                    lo = enq[k] - e.start_ns if k in enq else -INF
                    hi = done[k] - e.start_ns - e.duration_ns if k in done else INF
                    bounds.append((e.start_ns, lo, hi))
        got = stretches(sorted(bounds))
        if got:
            out[n] = got
    return out


def offset_at(chip: List[Stretch], t: float) -> float:
    """The offset, in ns, to add to chip time ``t`` to read it on the host's
    clock: the middle of the stretch ``t`` falls in (the first, before it)."""
    i = max(bisect.bisect_right([s[0] for s in chip], t) - 1, 0)
    return (chip[i][1] + chip[i][2]) / 2
