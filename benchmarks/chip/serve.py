"""Drive the program's ``ContinuousEngine`` through one cell's window.

The engine has no wall-clock events of its own, so the harness wraps two
attributes of the engine *instance*, ``_prefill`` and ``_decode_n`` (a traced
top-p run also times the third, ``_sample_rows``, jitted alone), and keeps its
own mirror of the scheduler's slots and decode-step clock:

* ``_prefill`` is called once per admission, in the engine's strict FCFS
  order, so the ``k``-th call is the ``k``-th request of the queue.  The
  wrapper waits for the first token, which ``run()`` reads right after.
* ``_decode_n`` is one tick.  The wrapper waits for the tick's outputs, which
  ``run()`` copies to the host right after, so neither wrapper adds a host
  sync.  Every tick checks the mirror against the rows the engine returns.

Each wrapper writes a ``jax.profiler.TraceAnnotation`` host span
(``admit/prefill``, ``tick/decode_n``), which a traced run uses to name the
device's idle gaps.  The window opens at the first tick after the first
``max_batch`` admissions and closes at the end of the first tick that ends
``seconds`` after it opened; the wrapper then stops the engine by raising
:class:`WindowClosed`, and the engine is dropped.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

WRAPPED = ("_prefill", "_decode_n", "_sample_rows")


class WindowClosed(Exception):
    """Raised inside ``ContinuousEngine.run`` to end the measured window."""


def check_wrappable(engine) -> None:
    """Fail loudly where an attribute the harness wraps has gone."""
    missing = [a for a in WRAPPED if not callable(getattr(engine, a, None))]
    if missing:
        raise RuntimeError(f"ContinuousEngine has no {missing}: the harness "
                           "times the engine through these attributes")


def to_requests(stream: List[Dict]):
    """The engine's requests for a stream; none stops early on an EOS id."""
    from repro.serving.scheduler import Request
    return [Request(rid=r["rid"], tokens=r["tokens"],
                    max_new_tokens=r["max_new_tokens"], key=r["key"],
                    eos_id=None, arrival_step=r["arrival_step"])
            for r in stream]


def warm_up(engine, shapes, vocab_size: int, sampler_probe: Optional[Callable]):
    """Compile every program the window uses: each (prompt, cache) prefill
    shape of the mix, their page scatters, and one decode tick, by running
    the engine on one request per shape until its second tick begins."""
    from repro.serving.scheduler import Request
    b = engine.max_batch
    rng = np.random.default_rng(0)
    for lo in range(0, len(shapes), b):
        reqs = [Request(rid=f"warm{i}",
                        tokens=rng.integers(0, vocab_size, p, dtype=np.int32),
                        max_new_tokens=o,
                        key=np.array([0, i], np.uint32))
                for i, (p, o) in enumerate(shapes[lo:lo + b])]
        orig = engine._decode_n
        calls = [0]

        def decode_once(*args):
            calls[0] += 1
            if calls[0] > 1:
                raise WindowClosed
            return orig(*args)

        engine._decode_n = decode_once
        try:
            engine.run(reqs)
        except WindowClosed:
            pass
        finally:
            engine._decode_n = orig
    if sampler_probe is not None:
        sampler_probe()


class Window:
    """Times one ``engine.run(stream)`` and records what it served."""

    def __init__(self, engine, stream: List[Dict], seconds: float,
                 on_open: Optional[Callable] = None):
        check_wrappable(engine)
        self.engine = engine
        self.stream = stream
        self.seconds = seconds
        self.on_open = on_open
        self.queue = sorted(range(len(stream)),
                            key=lambda i: (stream[i]["arrival_step"], i))
        self.n_admitted = 0
        self.slots: List[Optional[int]] = [None] * engine.max_batch
        self.step = 0
        self.run_t0 = None
        self.open_t = None
        self.close_t = None
        # per tick: first step, steps, wall start and end, tokens emitted,
        # and the context each emitted token attended to
        self.ticks: List[Dict] = []
        self.req: Dict[int, Dict] = {}
        self.prefills: List[Dict] = []
        self._prefill = engine._prefill
        self._decode = engine._decode_n
        engine._prefill = self._wrapped_prefill
        engine._decode_n = self._wrapped_decode

    # ---- the decode-step clock, mapped onto the wall clock ----
    def _wall_of_step(self, a: int) -> float:
        for t in reversed(self.ticks):
            if t["base"] <= a <= t["base"] + t["n"]:
                return t["t0"] + (a - t["base"]) / max(t["n"], 1) * (t["t1"] - t["t0"])
            if t["base"] + t["n"] < a:
                break
        return self.run_t0

    def _wrapped_prefill(self, params, tokens, key, cache_len):
        i = self.queue[self.n_admitted]
        r = self.stream[i]
        if tokens.shape[-1] != r["tokens"].size:
            raise RuntimeError("harness out of step with the engine's admission "
                               f"order: prefill of {tokens.shape[-1]} tokens "
                               f"where {r['rid']} has {r['tokens'].size}")
        t_call = time.perf_counter()
        if r["arrival_step"] > self.step:       # the engine idled up to it
            self.step = r["arrival_step"]
            arrival_t = t_call
        else:
            arrival_t = self._wall_of_step(r["arrival_step"])
        slot = self.slots.index(None)
        with jax.profiler.TraceAnnotation("admit/prefill"):
            out = self._prefill(params, tokens, key, cache_len)
            first = int(out[0])
        t = time.perf_counter()
        self.slots[slot] = i
        self.n_admitted += 1
        self.req[i] = {"arrival_t": arrival_t, "first_t": t, "last_t": t,
                       "tokens": [first], "finish_t": None}
        self.prefills.append({"t0": t_call, "t1": t, "prompt": r["tokens"].size})
        return out

    def _wrapped_decode(self, params, caches, tok, pos, keys, done, rem, eos,
                        n_steps):
        if self.open_t is None and self.n_admitted >= self.engine.max_batch:
            if self.on_open is not None:
                self.on_open()
            self.open_t = time.perf_counter()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("tick/decode_n"):
            out = self._decode(params, caches, tok, pos, keys, done, rem, eos,
                               n_steps)
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        # run() copies these to the host right after; a jax.Array keeps its
        # host copy, so reading them here costs no second transfer
        toks, n = np.asarray(out[0]), int(out[1])
        done_out, rem_out = np.asarray(out[6]), np.asarray(out[7])
        tick = {"base": self.step, "n": n, "t0": t0, "t1": t1, "tokens": 0,
                "contexts": []}
        self.step += n
        for s, i in enumerate(self.slots):
            if i is None:
                if not done_out[s]:
                    raise RuntimeError(f"harness out of step with the engine: "
                                       f"slot {s} is idle in the mirror only")
                continue
            st, r = self.req[i], self.stream[i]
            had = len(st["tokens"])
            emitted = r["max_new_tokens"] - had - int(rem_out[s])
            if not 0 <= emitted <= n:
                raise RuntimeError(f"harness out of step with the engine: slot "
                                   f"{s} emitted {emitted} of {n} steps")
            st["tokens"].extend(int(x) for x in toks[s, :emitted])
            st["last_t"] = t1
            tick["tokens"] += emitted
            # the row writes at position prompt + had - 1 and attends to it
            # and everything before
            pos0 = r["tokens"].size + had - 1
            tick["contexts"].extend(pos0 + 1 + j for j in range(emitted))
            if done_out[s]:
                st["finish_t"] = t1
                self.slots[s] = None
        self.ticks.append(tick)
        if self.open_t is not None and t1 - self.open_t >= self.seconds:
            self.close_t = t1
            raise WindowClosed
        return out

    def run(self) -> None:
        reqs = to_requests(self.stream)
        self.run_t0 = time.perf_counter()
        try:
            self.engine.run(reqs)
        except WindowClosed:
            return
        raise RuntimeError("the request stream ran dry before the window "
                           "closed: the traffic file needs more requests")

    # ---- what the window saw ----
    def in_window(self, t: float) -> bool:
        return self.open_t <= t <= self.close_t

    def summary(self) -> Dict:
        """The end-to-end readings and the counts behind them."""
        w = self.close_t - self.open_t
        win_ticks = [t for t in self.ticks if t["t0"] >= self.open_t]
        first_in = [i for i, s in self.req.items() if self.in_window(s["first_t"])]
        finished = [i for i, s in self.req.items()
                    if s["finish_t"] is not None and self.in_window(s["finish_t"])]
        out_tokens = sum(t["tokens"] for t in win_ticks) + len(first_in)
        ttft = [self.req[i]["first_t"] - self.req[i]["arrival_t"] for i in first_in]
        tpot = [(self.req[i]["last_t"] - self.req[i]["first_t"])
                / (len(self.req[i]["tokens"]) - 1)
                for i in finished if len(self.req[i]["tokens"]) > 1]
        return {"window_s": w, "output_tokens": out_tokens,
                "ttft_s": ttft, "tpot_s": tpot, "finished": finished,
                "ticks": win_ticks,
                "prefills": [p for p in self.prefills if self.in_window(p["t1"])],
                "decode_steps": sum(t["n"] for t in win_ticks),
                "decode_tokens": sum(t["tokens"] for t in win_ticks)}
