"""The chip's op time in the window, by the program's named scopes.

The program names its layers with ``jax.named_scope``: ``attention`` and
``mlp`` in every block, ``head`` (final norm and unembedding) and
``sampler``.  A scope lives in the ``op_name`` metadata of each instruction of
the compiled program, which the trace's op events do not carry; they carry
the instruction's name.  So after a traced window :func:`reduce` compiles the
programs again from the engine's own jitted functions, on the shapes they
ran with (the persistent cache makes that a load), reads each instruction's
innermost scope from ``as_text()``, and joins the trace's ops to it by name.
An instruction outside the four is ``unscoped`` (the embedding, the layer
loop's slicing, copies the compiler adds).

The decode program has one shape.  Prefill compiles per (prompt, cache)
length, and every shape's program has the same name in the trace, so each
prefill execution is joined through its ``run_id`` to the host's
``DoEnqueueProgram``, and through that to the ``engine/admit`` span it fell
in, which gives the request's lengths.  (The enqueue may come after the
``engine/prefill`` span: dispatch returns before the runtime enqueues.)
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict
from typing import Dict, Optional

from chip import spans, trace

SCOPES = ("attention", "mlp", "head", "sampler")
DECODE, PREFILL = "jit__decode_n_impl", "jit__prefill_impl"
UNJOINED_MAX = 0.01
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,{}]+)")
_OPERANDS = re.compile(r"\s[a-z][\w-]*\(([^)]*)\)")   # the opcode's operand list
_NAME = re.compile(r"%([^\s,)]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")


def scope_of(op_name: Optional[str]) -> str:
    """The innermost of :data:`SCOPES` on an ``op_name`` path."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return "unscoped"


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """Each instruction name of a compiled program's text -> its scope.

    The compiler leaves some instructions without an ``op_name`` of their
    own: a fusion whose root it added, such as a bitcast, or data movement
    such as a concatenation, a copy or a slice.  Such an instruction takes
    the scope its called computation's instructions carry or, where they
    carry none, the scope of its operands: theirs where they agree, else the
    one most of them carry, else ``unscoped``."""
    own: Dict[str, Optional[str]] = {}       # instruction -> scope of its op_name
    calls: Dict[str, str] = {}               # instruction -> computation it calls
    args: Dict[str, list] = {}               # instruction -> its operands
    body: Dict[str, list] = defaultdict(list)  # computation -> its instructions
    comp = None
    for line in hlo_text.splitlines():
        h = _COMPUTATION.match(line)
        if h:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        o = _OP_NAME.search(line)
        own[name] = scope_of(o.group(1)) if o else None
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        a = _OPERANDS.search(line, m.end())
        args[name] = _NAME.findall(a.group(1)) if a else []
        body[comp].append(name)

    done = set()

    def vote(names) -> Optional[str]:
        votes = Counter(filter(None, map(resolve, names)))
        if not votes:
            return None
        (top, n), *rest = votes.most_common(2)
        return top if not rest or rest[0][1] < n else "unscoped"

    def resolve(name: str) -> Optional[str]:
        if name in own and name not in done:
            done.add(name)
            if own[name] is None and name in calls:
                own[name] = vote(body[calls[name]])
            if own[name] is None:
                own[name] = vote(args[name])
        return own.get(name)

    return {name: resolve(name) or "unscoped" for name in list(own)}


def join(op_seconds: Dict[str, float], names: Dict[str, str]) -> Dict[str, float]:
    """Op seconds by scope; ops that name no instruction of the program go
    to ``unjoined``."""
    out = dict.fromkeys(SCOPES + ("unscoped", "unjoined"), 0.0)
    for op, s in op_seconds.items():
        out[names.get(op, "unjoined")] += s
    return out


def _spec(x):
    import jax
    committed = getattr(x, "committed", False)
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=x.sharding if committed else None)


def decode_text(win) -> str:
    """The compiled text of the engine's decode tick, as the window ran it."""
    import jax
    import jax.numpy as jnp
    eng = win.engine
    b = eng.max_batch
    row = lambda dt, *s: jax.ShapeDtypeStruct((b,) + s, dt)  # noqa: E731
    args = jax.tree.map(_spec, (eng.params, eng.caches)) + (
        row(jnp.int32), row(jnp.int32), row(jnp.uint32, 2), row(jnp.bool_),
        row(jnp.int32), row(jnp.int32))
    return win._decode.lower(*args, eng.tick_tokens).compile().as_text()


def prefill_text(win, prompt: int, cache_len: int) -> str:
    import jax
    import jax.numpy as jnp
    eng = win.engine
    return win._prefill.lower(
        jax.tree.map(_spec, eng.params), jax.ShapeDtypeStruct((1, prompt), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), cache_len).compile().as_text()


def _check_joined(program: str, by: Dict[str, float]) -> None:
    total = sum(by.values())
    if total > 0 and by["unjoined"] > UNJOINED_MAX * total:
        raise ValueError(f"{program}: {by['unjoined']:.6f} of {total:.6f} s of op "
                         "time name no instruction of the compiled program: the "
                         "trace and the compile disagree")


def _prefill_seconds(tl: Dict):
    """Per chip, each prefill execution in the window joined to its request:
    (rid, {op: seconds of its leaf ops in the window}).  ``tl`` is
    ``spans.reduce`` of the trace."""
    lo, hi = tl["window_ns"]
    enq = tl["enqueue_ns"]
    admits = [s for s in tl["spans"] if s[0] == spans.ADMIT]
    starts = [s[1] for s in admits]
    out = []
    for n, (mods, ops) in tl["timelines"].items():
        leaves = trace._leaves(ops)
        lstarts = [a for _, a, _ in leaves]
        for name, a, b, run_id in mods:
            if name != PREFILL or b <= lo or a >= hi or (n, run_id) not in enq:
                continue
            t = enq[(n, run_id)]
            i = bisect.bisect_right(starts, t) - 1
            if i < 0 or not admits[i][1] <= t <= admits[i][2]:
                continue
            secs: Dict[str, float] = defaultdict(float)
            for j in range(bisect.bisect_left(lstarts, a), len(leaves)):
                op, oa, ob = leaves[j]
                if oa > b:
                    break
                for ca, cb in trace._clip([(oa, ob)], lo, hi):
                    secs[op] += (cb - ca) * 1e-9
            out.append((admits[i][3].get("rid"), secs))
    return out


def recorded_share(tl: Dict) -> float:
    """The share of the window's ``engine/decode`` spans whose decode
    execution the chip's trace shows (averaged over chips): below 1 where a
    long trace filled the profiler's buffer and the chip's events end before
    the window does.  1 where there is nothing to tell."""
    lo, hi = tl["window_ns"]
    ticks = [s for s in tl["spans"] if s[0] == "engine/decode" and lo <= s[1] <= hi]
    if not ticks or not tl["timelines"]:
        return 1.0
    starts = [s[1] for s in ticks]
    shares = []
    for n, (mods, _) in tl["timelines"].items():
        shown = set()
        for name, _, _, run_id in mods:
            t = tl["enqueue_ns"].get((n, run_id))
            if name != DECODE or t is None:
                continue
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and ticks[i][1] <= t <= ticks[i][2]:
                shown.add(i)
        shares.append(len(shown) / len(ticks))
    return sum(shares) / len(shares) or 1.0


def reduce(red, tl: Dict, win, summ: Dict) -> Dict[str, Dict[str, float]]:
    """``{program: {scope: ms}}`` in the window: per decode step for the
    decode program, per 1000 prompt tokens for prefill.  Empty only where the
    compiled decode program holds none of the four scopes (a program built
    before them); raises where it holds some but not all.  Where the window's
    trace shows no decode op, the decode program's entry is empty: its
    readers read nothing, and a run that lists them fails.  ``tl`` is
    ``spans.reduce`` of the same trace.  The steps are the window's, times
    :func:`recorded_share`: the op time holds only the executions the trace
    shows."""
    names = instruction_scopes(decode_text(win))
    held = set(names.values()) & set(SCOPES)
    if not held:
        return {}
    if held != set(SCOPES):
        raise ValueError(f"{DECODE}: no instruction of the compiled program is under "
                         f"{sorted(set(SCOPES) - held)}")
    n_dev = len(red["devices"])
    dec_ops: Dict[str, float] = defaultdict(float)
    for d in red["devices"]:
        for key, s in d["op_seconds"].items():
            prog, _, op = key.partition("/")
            if prog == DECODE:
                dec_ops[op] += s / n_dev
    if not dec_ops or not summ["decode_steps"]:
        return {DECODE: {}}
    by = join(dec_ops, names)
    _check_joined(DECODE, by)
    per = 1e3 / (summ["decode_steps"] * recorded_share(tl))
    out = {DECODE: {k: v * per for k, v in by.items()}}
    out[DECODE]["total"] = sum(by.values()) * per
    lengths = {s[3].get("rid"): (s[3]["prompt"], s[3]["pages"] * win.engine.page_size)
               for s in tl["spans"] if s[0] == spans.ADMIT}
    texts: Dict = {}
    pre = dict.fromkeys(SCOPES + ("unscoped", "unjoined"), 0.0)
    tokens = 0
    for rid, secs in _prefill_seconds(tl):
        if rid not in lengths:
            continue
        if lengths[rid] not in texts:
            texts[lengths[rid]] = instruction_scopes(prefill_text(win, *lengths[rid]))
        got = join(secs, texts[lengths[rid]])
        _check_joined(PREFILL, got)
        for k, v in got.items():
            pre[k] += v / n_dev
        tokens += lengths[rid][0] / n_dev
    if tokens:
        per = 1e6 / tokens
        out[PREFILL] = {k: v * per for k, v in pre.items()}
        out[PREFILL]["total"] = sum(pre.values()) * per
    return out
