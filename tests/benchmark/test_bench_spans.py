"""One clock for host and chip, and the idle time split by what the host was
doing: on the small v5e trace (``record_trace.py``: a 50 ms host-only pause
between two programs, no engine spans) and on the scoped one
(``record_scoped_trace.py``: ``ContinuousEngine`` with its own spans)."""
import pytest

from chip import clock, spans, trace
from trace_cases import cut_chip_events, filter_events, recorded_trace

CLASSES = ("in_program", "lost_events", "dispatch", "host")


def _load(name):
    pd = recorded_trace(name)
    red = trace.reduce_trace(pd)
    return pd, red, spans.reduce(pd, red)


@pytest.fixture(scope="module")
def small():
    return _load("small.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return _load("scoped.xplane.pb.gz")


def test_clock_offset_from_run_ids(small):
    pd, _, got = small
    # DoEnqueueProgram of run 6 starts 1.339 ms after the chip starts it (on
    # their raw clocks); CompleteCallbacks of run 6 starts 1.793 ms after the
    # chip ends it; run 7 bounds the offset more loosely on both sides
    (chip,) = clock.intervals(pd).values()
    (t, lo, hi), = chip
    assert lo == pytest.approx(1.339192e6) and hi == pytest.approx(1.793150e6)
    assert clock.offset_at(chip, 0.0) == clock.offset_at(chip, 1e12) == (lo + hi) / 2
    assert got["clock_ns"] == {0: chip}


def test_drifting_clocks_are_cut_into_stretches():
    # the offset fits [10, 20] for the first two executions and [30, 40] for
    # the last: no single offset fits all three, one per stretch does
    got = clock.stretches([(0, 10, 25), (5, 5, 20), (9, 30, 40)])
    assert got == [(0, 10, 20), (9, 30, 40)]
    assert clock.offset_at(got, 7) == 15 and clock.offset_at(got, 50) == 35
    # a stretch with one side open is left out
    assert clock.stretches([(0, float("-inf"), 3), (1, 5, 8)]) == [(1, 5, 8)]


def test_more_drift_than_a_clock_has_raises():
    """A host mark joined to the wrong execution is off by a whole execution:
    the offset jumps further, or breaks into more stretches, than drift
    explains."""
    far = clock.MAX_JUMP_NS + 1
    with pytest.raises(ValueError, match="jumps by more than"):
        clock.stretches([(0, 0, 10), (5, far + 10, far + 20)])
    assert len(clock.stretches([(0, 0, 10), (5, far, far + 10)])) == 2
    many = [(i, 20 * i, 20 * i + 10) for i in range(clock.MAX_STRETCHES + 1)]
    with pytest.raises(ValueError, match="more than drift explains"):
        clock.stretches(many)
    assert len(clock.stretches(many[:-1])) == clock.MAX_STRETCHES


def test_empty_offset_interval_raises():
    class Ev:
        def __init__(self, name, start, dur, **stats):
            self.name, self.start_ns, self.duration_ns = name, start, dur
            self.stats = list(stats.items())

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class PD:
        planes = [Plane("/device:TPU:0", [Line(trace.MODULES_LINE,
                                               [Ev("jit_f(1)", 100.0, 50.0, run_id=3)])]),
                  # enqueued 40 ns after the chip starts (offset >= 40), done 5 ns
                  # after it ends (offset <= 5): the execution contradicts itself
                  Plane("/host:CPU", [Line("main", [
                      Ev(clock.ENQUEUE, 140.0, 1.0, run_id=3, device_ordinal=0),
                      Ev(clock.COMPLETE, 155.0, 1.0, run_id=3, device_ordinal=0)])])]

    with pytest.raises(ValueError, match="allows no clock offset"):
        clock.intervals(PD())


@pytest.mark.parametrize("which", ["small", "scoped"])
def test_idle_classes_add_up_to_window_less_busy(which, request):
    _, red, got = request.getfixturevalue(which)
    idle = got["idle"]
    want = red["window_s"] - trace.busy_seconds(red)
    assert sum(idle[c] for c in CLASSES) == pytest.approx(want, rel=1e-9)
    assert idle["host"] == pytest.approx(sum(idle["host_by_span"].values()), rel=1e-9)
    assert all(idle[c] >= 0 for c in CLASSES)


def test_the_pause_is_host_time(small):
    _, _, got = small
    idle = got["idle"]
    assert not got["engine_spans"]
    assert idle["host_by_span"] == {"none": pytest.approx(idle["host"])}
    assert idle["host"] == pytest.approx(0.0506, abs=0.003)
    assert idle["dispatch"] == 0 and idle["lost_events"] == 0
    assert idle["lost_executions"] == 0 and idle["dispatch_by_next"] == {}


def test_engine_spans_on_the_scoped_trace(scoped):
    _, _, got = scoped
    assert got["engine_spans"]
    names = {s[0] for s in got["spans"]}
    assert {"engine/arrive", "engine/admit", "engine/prefill", "engine/insert",
            "engine/release", "engine/stage", "engine/decode",
            "engine/harvest"} <= names
    # six requests through four rows: every one waited in the queue for
    # zero or more, and the two admitted last for longer than the first
    waits = got["queue_wait_s"]
    assert sorted(waits) == [f"req{i}" for i in range(6)]
    assert all(w >= 0 for w in waits.values())
    assert min(waits["req4"], waits["req5"]) > max(waits["req0"], waits["req1"])
    assert set(got["idle"]["host_by_span"]) <= names | {"none"}
    idle = got["idle"]
    assert sum(idle["dispatch_by_next"].values()) == pytest.approx(idle["dispatch"])


def test_innermost_span_names_each_stretch():
    s = [("engine/admit", 0, 10, {}), ("engine/prefill", 2, 5, {}),
         ("engine/arrive", 6, 6, {}), ("engine/insert", 6, 8, {}),
         ("engine/decode", 12, 20, {})]
    assert spans.innermost(s) == [(0, 2, "engine/admit"), (2, 5, "engine/prefill"),
                                  (5, 6, "engine/admit"), (6, 8, "engine/insert"),
                                  (8, 10, "engine/admit"), (12, 20, "engine/decode")]
    by, rest = spans.overlap([(1, 3), (9, 14), (25, 30)], spans.innermost(s))
    assert dict(by) == {"engine/admit": 2, "engine/prefill": 1, "engine/decode": 2}
    assert rest == [(10, 12), (25, 30)]


def test_chip_events_that_end_early_are_lost_not_idle(scoped):
    pd, red, full = scoped
    from chip import scopes
    (mods, _), = full["timelines"].values()
    last_decode = [m for m in mods if m[0] == scopes.DECODE][-1]
    cut = cut_chip_events(pd, last_decode[1])
    red_cut = trace.reduce_trace(cut)
    got = spans.reduce(cut, red_cut)
    idle = got["idle"]
    # the chip looks idle from the cut to the window's end; the host kept
    # enqueuing there, so that time is lost events, not dispatch or host
    lo, hi = got["window_ns"]
    assert idle["unrecorded"] >= 1
    assert idle["lost_events"] >= 0.9 * (hi - last_decode[1]) * 1e-9
    assert idle["dispatch"] <= full["idle"]["dispatch"] + 1e-9
    assert sum(idle[c] for c in CLASSES) == pytest.approx(
        red_cut["window_s"] - trace.busy_seconds(red_cut), rel=1e-9)
    # two of the three ticks' decode executions are in the trace
    assert scopes.recorded_share(got) == pytest.approx(2 / 3)
    assert scopes.recorded_share(full) == 1.0
    # the tail from the first program enqueued after the cut is unrecorded
    late = [m for m in mods if m[1] >= last_decode[1]]
    assert full["idle"]["lost_tail"] == 0
    assert 0 < idle["lost_tail"] <= (hi - late[0][1]) * 1e-9 + 2e-3


def test_host_idle_share_is_over_the_recorded_window(scoped):
    """Host idle in the part of the window the chip's trace records, over
    that part: a tail lost to a full profiler buffer moves neither."""
    from chip.run import read_metric
    pd, red, full = scoped
    (mods, _), = full["timelines"].values()
    cut_at = mods[-1][1]
    cut = cut_chip_events(pd, cut_at)
    red_cut = trace.reduce_trace(cut)
    got = spans.reduce(cut, red_cut)
    share = read_metric("host_idle_share", dict(got, trace=red_cut))
    assert share == pytest.approx(100 * got["idle"]["host"]
                                  / (red_cut["window_s"] - got["idle"]["lost_tail"]))
    assert share > 100 * got["idle"]["host"] / red_cut["window_s"]


@pytest.mark.parametrize("name", spans.WINDOW_SPANS)
def test_a_window_without_one_engine_span_raises(scoped, name):
    """A renamed span would move its time to another class or empty a
    reader; only a program with no engine span at all is read as one
    built before them."""
    pd, red, _ = scoped
    dropped = filter_events(pd, lambda p, e: e.name != name)
    with pytest.raises(ValueError, match=name):
        spans.reduce(dropped, red)
