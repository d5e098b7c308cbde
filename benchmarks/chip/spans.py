"""The chip's idle time in the window, named by what the host was doing, and
each request's wait in the queue, from the engine's own spans.

``ContinuousEngine.run`` leaves host spans under the profiler
(``engine/arrive``, ``engine/admit`` around ``engine/prefill`` and
``engine/insert``, ``engine/release``, ``engine/stage``, ``engine/decode``,
``engine/harvest``), each with its request id ``rid`` and counts.  Read on
one clock with the chip's events (``clock.py``), every idle stretch of the
window falls in one of four classes:

* ``in_program``: inside a program's execution, between its ops;
* ``lost_events``: inside an execution that has no op event at all, or
  after the chip's events end while the host still enqueues programs on it
  (a long trace fills the profiler's buffer: that time was not idle);
* ``dispatch``: outside every program while the host is in
  ``engine/decode`` or ``engine/prefill``: the host handing the program over,
  or waiting for its result;
* ``host``: any other time, by the innermost engine span the host was in
  (``none`` outside all of them).

The classes split the idle time ``trace.busy_seconds`` leaves exactly: the
stretches are those of ``trace`` (the chip's clock, cut to the window span),
and only what lies outside programs is moved onto the host's clock.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chip import clock, trace

ENGINE = "engine/"
ARRIVE, ADMIT, PREFILL = "engine/arrive", "engine/admit", "engine/prefill"
DISPATCH = ("engine/decode", PREFILL)
# the spans every window of a serving run holds (a release may fall outside)
WINDOW_SPANS = (ARRIVE, ADMIT, PREFILL, "engine/insert", "engine/stage", "engine/decode",
                "engine/harvest")
# per-layer metrics that read the program's scopes, or its engine spans
SCOPE_METRICS = ("attention_step_ms", "mlp_step_ms", "sampler_step_ms", "step_other_ms")
SPAN_METRICS = ("host_idle_share", "queue_wait_p95_ms")


def host_spans(pd) -> List[Tuple[str, float, float, Dict]]:
    """Every ``engine/*`` span and the window span of the host planes, as
    (name, start ns, end ns, stats), in order of start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ENGINE) or e.name == trace.WINDOW_SPAN:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def device_timeline(plane):
    """A chip plane's executions (name, start, end, run_id) and ops (name,
    start, end), each in order of start."""
    mods, ops = [], []
    for line in plane.lines:
        if line.name == trace.MODULES_LINE:
            mods += [(trace.program_name(e.name), e.start_ns,
                      e.start_ns + e.duration_ns, dict(e.stats).get("run_id"))
                     for e in line.events]
        elif line.name == trace.OPS_LINE:
            ops += [(trace.op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return sorted(mods, key=lambda m: m[1]), sorted(ops, key=lambda o: o[1])


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) stretches, each named by the innermost of
    the (properly nested) spans covering it; instants are left out."""
    segs, stack, t = [], [], None
    for name, a, b, _ in spans:
        if b <= a or name == ARRIVE:
            continue
        while stack and stack[-1][1] <= a:
            top, end = stack.pop()
            if end > t:
                segs.append((t, end, top))
                t = end
        if stack and a > t:
            segs.append((t, a, stack[-1][0]))
        t = a
        stack.append((name, b))
    while stack:
        top, end = stack.pop()
        if end > t:
            segs.append((t, end, top))
            t = end
    return segs


def overlap(pieces, segs):
    """Split sorted disjoint ``pieces`` over sorted disjoint labelled ``segs``:
    the overlap per label, and what no segment covers."""
    by: Dict[str, float] = defaultdict(float)
    rest, j = [], 0
    for a, b in pieces:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, label = segs[k]
            if s0 > t:
                rest.append((t, s0))
            hi = min(s1, b)
            if hi > max(s0, t):
                by[label] += hi - max(s0, t)
                t = hi
            k += 1
        if t < b:
            rest.append((t, b))
    return by, rest


def unrecorded(mods, enqueued: Dict[int, float], stretches) -> List[float]:
    """The programs the host enqueued on a chip after the last execution the
    chip's trace shows, and that it never shows: their enqueue times on the
    chip's clock, in order (``enqueued``: run_id -> host time).  The first
    is where the chip's events end early; none if the trace runs to the
    end."""
    seen = {m[3] for m in mods}
    last = mods[-1][1] if mods else float("-inf")
    late = [t - (clock.offset_at(stretches, t) if stretches else 0.0)
            for run_id, t in enqueued.items() if run_id not in seen]
    return sorted(t for t in late if t > last)


def idle_classes(mods, ops, lo, hi, stretches, segs, cut=None):
    """The idle stretches of one chip in the window [lo, hi] (chip clock),
    by class: seconds per class, ``host`` by span, ``dispatch`` by the program
    the chip ran next, and how many executions in the window have no op
    event.  ``stretches`` are the chip's from ``clock.intervals`` (none: the
    two clocks are taken as one); from ``cut`` on (:func:`unrecorded`) the
    chip's events are missing, and ``lost_tail`` is that part of the
    window."""
    busy = trace._union(trace._clip([(a, b) for _, a, b in ops], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    starts = [a for _, a, _ in ops]
    prog, lost = [], 0
    for _, a, b, _ in mods:
        if b <= lo or a >= hi:
            continue
        i = bisect.bisect_left(starts, a)
        has_ops = i < len(starts) and starts[i] <= b
        lost += not has_ops
        a = max(a, prog[-1][1]) if prog else a
        if b > a:
            prog.append((a, b, "in_program" if has_ops else "lost_events"))
    tail = 0.0
    if cut is not None and cut < hi:
        tail = hi - max(cut, lo)
        a = max(cut, prog[-1][1]) if prog else cut
        prog.append((a, max(a, hi), "lost_events"))
    by, rest = overlap(gaps, prog)
    # what lies outside programs goes onto the host's clock, grouped by the
    # program the chip ran next (before it, or after the one before it)
    mod_starts = [m[1] for m in mods]
    after: Dict[str, list] = defaultdict(list)
    for a, b in rest:
        d = clock.offset_at(stretches, (a + b) / 2) if stretches else 0.0
        i = bisect.bisect_left(mod_starts, b)
        after[mods[i][0] if i < len(mods) else "none"].append((a + d, b + d))
    named: Dict[str, float] = defaultdict(float)
    dispatch_by_next, none = {}, 0.0
    for nxt, pieces in after.items():
        got, uncovered = overlap(pieces, segs)
        for k, v in got.items():
            named[k] += v
        dispatch_by_next[nxt] = sum(got[k] for k in DISPATCH) * 1e-9
        none += sum(b - a for a, b in uncovered)
    host = {k: v * 1e-9 for k, v in named.items() if k not in DISPATCH}
    host["none"] = none * 1e-9
    return {"in_program": by["in_program"] * 1e-9,
            "lost_events": by["lost_events"] * 1e-9,
            "dispatch": sum(named[k] for k in DISPATCH) * 1e-9,
            "host": sum(host.values()), "host_by_span": host,
            "dispatch_by_next": {k: v for k, v in dispatch_by_next.items() if v},
            "lost_executions": lost, "lost_tail": tail * 1e-9}


def queue_waits(spans, lo, hi) -> Dict[str, float]:
    """Seconds from ``engine/arrive`` to the request's admission (its last
    ``engine/admit``), for every request admitted in the window [lo, hi]."""
    arrive, admit = {}, {}
    for name, a, _, st in spans:
        if name == ARRIVE:
            arrive.setdefault(st.get("rid"), a)
        elif name == ADMIT:
            admit[st.get("rid")] = a
    return {rid: (a - arrive[rid]) * 1e-9 for rid, a in admit.items()
            if rid in arrive and lo <= a <= hi}


def reduce(pd, red) -> Dict:
    """Clock intervals, idle classes (averaged over chips, as
    ``trace.busy_seconds`` is) and queue waits of one traced window; ``red``
    is ``trace.reduce_trace`` of the same trace, whose idle time the classes
    must add up to."""
    marked = host_spans(pd)
    win = [(a, b) for n, a, b, _ in marked if n == trace.WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} host span")
    lo, hi = win[0]
    engine = [s for s in marked if s[0] != trace.WINDOW_SPAN]
    missing = set(WINDOW_SPANS) - {s[0] for s in engine if lo <= s[1] <= hi}
    if engine and missing:
        raise ValueError(f"the window holds engine spans but none named {sorted(missing)}")
    marks = clock.host_marks(pd)
    iv = clock.intervals(pd, marks)
    segs = innermost(engine)
    idle: Dict = defaultdict(float)
    by_span = {"host_by_span": defaultdict(float), "dispatch_by_next": defaultdict(float)}
    timelines = {}
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        n = int(plane.name.rsplit(":", 1)[1])
        timelines[n] = device_timeline(plane)
        late = unrecorded(timelines[n][0], {r: t for (d, r), t in marks[0].items()
                                            if d == n}, iv.get(n))
        got = idle_classes(*timelines[n], lo, hi, iv.get(n), segs,
                           late[0] if late else None)
        got["unrecorded"] = sum(t < hi for t in late)
        for key, acc in by_span.items():
            for k, v in got.pop(key).items():
                acc[k] += v
        for k, v in got.items():
            idle[k] += v
    out = {"clock_ns": iv, "window_ns": (lo, hi),
           "engine_spans": bool(engine), "spans": engine, "timelines": timelines,
           "enqueue_ns": marks[0], "queue_wait_s": queue_waits(engine, lo, hi)}
    if timelines:
        k = len(timelines)
        idle = {c: v if c in ("lost_executions", "unrecorded") else v / k
                for c, v in idle.items()}
        for key, acc in by_span.items():
            idle[key] = {c: v / k for c, v in acc.items()}
        want = red["window_s"] - trace.busy_seconds(red)
        got = sum(idle[c] for c in ("in_program", "lost_events", "dispatch", "host"))
        if abs(got - want) > 0.01 * max(want, 1e-9):
            raise ValueError(f"idle classes add up to {got} s, not the window's "
                             f"idle {want} s")
        out["idle"] = idle
    return out


def unread(program: Dict) -> Tuple[str, ...]:
    """The per-layer metrics (``metrics/<name>.py``) a traced run of this
    program gives nothing to read, so that a run leaves them out: those of the scopes where its compiled decode program carries none
    of them, those of the engine spans where it leaves none (a program built
    before them).  Any other absence raises in ``scopes.reduce`` or
    :func:`reduce`."""
    return ((() if program.get("scopes") else SCOPE_METRICS)
            + (() if program.get("engine_spans") else SPAN_METRICS))


def report(program: Dict) -> List[str]:
    """Lines for the run's output: the clock, the idle classes, the queue
    waits and the scopes; none for an untraced run."""
    if not program:
        return []
    lines = []
    for n, chip in sorted(program.get("clock_ns", {}).items()):
        ranges = "; ".join(f"[{lo * 1e-6:.4f}, {hi * 1e-6:.4f}]" for _, lo, hi in chip)
        lines.append(f"clock: chip {n} -> host offset in {ranges} ms over {len(chip)} "
                     f"stretch(es), widest {max(hi - lo for _, lo, hi in chip) * 1e-6:.4f}"
                     " ms")
    idle = program.get("idle")
    if idle:
        desc = lambda d: ", ".join(f"{k} {v:.4f}" for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1]))
        lines.append(f"idle (s): in_program {idle['in_program']:.4f}, dispatch "
                     f"{idle['dispatch']:.4f} (by the program run next: "
                     f"{desc(idle['dispatch_by_next'])}), host {idle['host']:.4f} "
                     f"({desc(idle['host_by_span'])}), lost_events "
                     f"{idle['lost_events']:.4f} ({idle['lost_executions']:g} "
                     f"executions with no op event, {idle['unrecorded']:g} enqueued "
                     f"in the window after the chip's events end, which leaves "
                     f"{idle['lost_tail']:.4f} s of it unrecorded)")
    waits = sorted(program.get("queue_wait_s", {}).values())
    if waits:
        lines.append(f"queue waits: {len(waits)} requests admitted in the window, "
                     f"median {1e3 * waits[len(waits) // 2]:.1f} ms, longest "
                     f"{1e3 * waits[-1]:.1f} ms")
    if not program.get("engine_spans"):
        lines.append("engine spans: none in the trace")
    units = {"jit__prefill_impl": "ms per 1000 prompt tokens"}
    for prog, by in program.get("scopes", {}).items():
        lines.append(f"scopes {prog} ({units.get(prog, 'ms per decode step')}): "
                     + ", ".join(f"{k} {v:.4f}" for k, v in by.items()))
    if not program.get("scopes"):
        lines.append("scopes: none in the compiled decode program")
    return lines
