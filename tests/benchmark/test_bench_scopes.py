"""The chip's op time joined to the program's named scopes, and the six
readers of the program's scopes and spans, on the scoped v5e trace
(``record_scoped_trace.py``) and the compiled text of its programs."""
import gzip
import json
import re
import types

import pytest

from chip import scopes, spans, trace
from chip.run import read_metric as _read
from trace_cases import DATA, cut_chip_events, recorded_trace

PAGE_SIZE = 8                    # chip_cases.TRAFFIC's engine


@pytest.fixture(scope="module")
def hlo():
    with gzip.open(DATA / "scoped.hlo.json.gz", "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced():
    pd = recorded_trace("scoped.xplane.pb.gz")
    red = trace.reduce_trace(pd)
    return red, spans.reduce(pd, red)


def _steps(tl):
    return sum(s[3]["steps"] for s in tl["spans"] if s[0] == "engine/harvest")


def _reduce(traced, hlo, monkeypatch, decode=None):
    red, tl = traced
    monkeypatch.setattr(scopes, "decode_text", lambda win: decode or hlo["decode"])
    monkeypatch.setattr(scopes, "prefill_text",
                        lambda win, p, c: hlo["prefill"][f"{p},{c}"])
    win = types.SimpleNamespace(engine=types.SimpleNamespace(page_size=PAGE_SIZE))
    return scopes.reduce(red, tl, win, {"decode_steps": _steps(tl)})


def test_scope_of_takes_the_innermost():
    assert scopes.scope_of("jit(f)/while/body/attention/dot_general") == "attention"
    assert scopes.scope_of("jit(f)/mlp/sampler/sort") == "sampler"
    assert scopes.scope_of("jit(f)/while/body/dynamic_slice") == "unscoped"
    assert scopes.scope_of(None) == "unscoped"


def test_compiled_decode_names_every_scope(hlo):
    names = scopes.instruction_scopes(hlo["decode"])
    assert set(names.values()) == set(scopes.SCOPES) | {"unscoped"}
    # the join reads the same names the text declares
    assert len(names) == len(re.findall(r"^\s*(?:ROOT )?%\S+ = ", hlo["decode"], re.M))


def _called(text):
    """Each instruction that calls a computation -> the op_names on that
    computation's instructions and, in turn, on those of the computations
    they call."""
    comps, calls, cur = {}, {}, None
    for line in text.splitlines():
        h = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if h:
            cur = comps.setdefault(h.group(1), [])
            continue
        m = re.match(r"^\s*(?:ROOT )?%(\S+) = ", line)
        if m:
            cur.append(line)
            c = re.search(r"calls=%([^\s,]+)", line)
            if c:
                calls[m.group(1)] = c.group(1)

    def names(comp):
        out = []
        for line in comps[comp]:
            out += re.findall(r'op_name="([^"]*)"', line)
            c = re.search(r"calls=%([^\s,]+)", line)
            out += names(c.group(1)) if c else []
        return out
    return {i: names(c) for i, c in calls.items()}


def test_a_fusion_without_op_name_takes_its_computations_scope(hlo):
    """The compiler leaves some fusions without an ``op_name``; one whose
    called computations carry a single scope takes it (``fusion.483``, a
    scatter of the top-p sampler's keys, carries ``sampler`` only inside)."""
    names = scopes.instruction_scopes(hlo["decode"])
    inherited = 0
    for name, inside in _called(hlo["decode"]).items():
        got = {scopes.scope_of(o) for o in inside}
        if f"%{name} = " in hlo["decode"] and len(got) == 1 and got <= set(scopes.SCOPES):
            line = next(ln for ln in hlo["decode"].splitlines() if f"%{name} = " in ln)
            if "op_name=" not in line:
                inherited += 1
                assert names[name] == got.pop(), name
    assert inherited >= 20 and names["fusion.483"] == "sampler"


def test_data_movement_without_op_name_takes_its_operands_scope(hlo):
    """A sort or copy the compiler left without an ``op_name`` and with no
    scoped computation of its own takes its operands' scope; the whole K/V
    page pools copied from the program's arguments stay ``unscoped``."""
    names = scopes.instruction_scopes(hlo["decode"])
    for name in ("sort.6", "sort.7", "sort.8", "copy.184"):
        line = next(ln for ln in hlo["decode"].splitlines() if f"%{name} = " in ln)
        assert "op_name=" not in line and names[name] == "sampler", name
    assert names["copy.87"] == names["copy.88"] == "unscoped"


def test_trace_ops_join_to_the_compiled_program(traced, hlo):
    red, _ = traced
    ops = {k.split("/", 1)[1]: s for k, s in red["devices"][0]["op_seconds"].items()
           if k.startswith(scopes.DECODE + "/")}
    by = scopes.join(ops, scopes.instruction_scopes(hlo["decode"]))
    assert by["unjoined"] <= scopes.UNJOINED_MAX * sum(by.values())
    assert sum(by.values()) == pytest.approx(sum(ops.values()))
    assert by["sampler"] > 0 and by["attention"] > 0 and by["mlp"] > 0


def test_scopes_key_per_program(traced, hlo, monkeypatch):
    got = _reduce(traced, hlo, monkeypatch)
    assert set(got) == {scopes.DECODE, scopes.PREFILL}
    for prog in got.values():
        parts = sum(v for k, v in prog.items() if k != "total")
        assert prog["total"] == pytest.approx(parts) and prog["total"] > 0
        assert prog["unjoined"] <= scopes.UNJOINED_MAX * prog["total"]
    red, tl = traced
    dec_s = sum(s for k, s in red["devices"][0]["op_seconds"].items()
                if k.startswith(scopes.DECODE + "/"))
    assert got[scopes.DECODE]["total"] == pytest.approx(1e3 * dec_s / _steps(tl))


def test_a_trace_that_names_no_instruction_raises(traced, hlo, monkeypatch):
    renamed = re.sub(r"%fusion", "%fusion_renamed", hlo["decode"])
    with pytest.raises(ValueError, match="trace and the compile disagree"):
        _reduce(traced, hlo, monkeypatch, decode=renamed)


def test_six_readers(traced, hlo, monkeypatch):
    red, tl = traced
    run = dict(tl, trace=red, scopes=_reduce(traced, hlo, monkeypatch))
    got = {n: _read(n, run) for n in spans.SCOPE_METRICS + spans.SPAN_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    dec = run["scopes"][scopes.DECODE]
    assert (got["attention_step_ms"] + got["mlp_step_ms"] + got["sampler_step_ms"]
            + got["step_other_ms"]) == pytest.approx(dec["total"], rel=1e-9)
    assert got["step_other_ms"] == pytest.approx(dec["head"] + dec["unscoped"]
                                                 + dec["unjoined"])
    assert run["idle"]["lost_tail"] == 0
    assert got["host_idle_share"] == pytest.approx(
        100 * run["idle"]["host"] / red["window_s"])
    waits = sorted(1e3 * w for w in run["queue_wait_s"].values())
    assert waits[-2] <= got["queue_wait_p95_ms"] <= waits[-1]
    assert spans.unread(run) == ()


def test_a_program_without_scopes_or_spans_is_left_unread(traced, hlo, monkeypatch):
    """A program built before the scopes and spans gives the six readers
    nothing, and a traced run leaves them out rather than failing."""
    bare = re.sub(r'op_name="[^"]*"', 'op_name="jit(f)/dot_general"', hlo["decode"])
    red, tl = traced
    run = dict(tl, trace=red, engine_spans=False, queue_wait_s={},
               scopes=_reduce(traced, hlo, monkeypatch, decode=bare))
    assert run["scopes"] == {}
    assert set(spans.unread(run)) == set(spans.SCOPE_METRICS + spans.SPAN_METRICS)
    assert all(_read(n, run) is None for n in spans.unread(run))
    assert spans.report({}) == []


def test_a_program_missing_one_scope_raises(traced, hlo, monkeypatch):
    """A scope renamed or taken out of the program would read 0 ms, like a
    whole gain: where the program has the others, that raises."""
    renamed = hlo["decode"].replace("/sampler/", "/picker/")
    with pytest.raises(ValueError, match="sampler"):
        _reduce(traced, hlo, monkeypatch, decode=renamed)


def test_a_window_without_the_decode_program_fails_its_readers(traced, hlo, monkeypatch):
    """A scoped program whose decode ticks the trace does not show (the jit
    renamed) leaves the four metrics nothing to read, and the run fails on
    them rather than leaving them out."""
    red, tl = traced
    gone = dict(red, devices=[
        dict(d, op_seconds={k: v for k, v in d["op_seconds"].items()
                            if not k.startswith(scopes.DECODE + "/")})
        for d in red["devices"]])
    run = dict(tl, trace=gone, scopes=_reduce((gone, tl), hlo, monkeypatch))
    assert run["scopes"] == {scopes.DECODE: {}}
    assert not set(spans.SCOPE_METRICS) & set(spans.unread(run))
    assert all(_read(n, run) is None for n in spans.SCOPE_METRICS)


def test_steps_the_trace_lost_do_not_dilute_the_step(traced, hlo, monkeypatch):
    """Where the chip's events end before the window does, the op time per
    step counts only the ticks whose decode execution the trace shows."""
    red, tl = traced
    pd = recorded_trace("scoped.xplane.pb.gz")
    (mods, _), = tl["timelines"].values()
    last = [m for m in mods if m[0] == scopes.DECODE][-1]
    cut = cut_chip_events(pd, last[1])
    red_cut = trace.reduce_trace(cut)
    full = _reduce(traced, hlo, monkeypatch)[scopes.DECODE]["total"]
    got = _reduce((red_cut, spans.reduce(cut, red_cut)), hlo, monkeypatch)
    assert got[scopes.DECODE]["total"] == pytest.approx(full, rel=0.05)
