"""Continuous-batching scheduler: FCFS admission, paged KV, in-graph decode.

``ContinuousEngine.run(requests)`` serves a ragged trace of variable-length
requests through a fixed decode batch of ``max_batch`` rows:

  * **admission** — strict FCFS over arrived requests; a request is admitted
    when a batch row is free and the :class:`~repro.serving.paged_kv.
    PageAllocator` can cover ``prompt + max_new_tokens`` positions (the head
    of the queue never gets bypassed, so admission order is reproducible
    under budget pressure);
  * **prefill** — each admitted request prefills alone (batch 1) and its
    dense cache is scattered into its allocated pages;
  * **decode** — all running rows step together through ``decode_n``, one
    ``lax.while_loop`` staging up to ``tick_tokens`` model steps with the
    all-rows-done predicate *inside* the graph — one host sync per tick, not
    per token;
  * **eviction** — rows that emit their eos or exhaust their budget release
    their pages at the tick boundary and the row is refilled FCFS.

Exact-stream contract (the acceptance bar): a request served continuously
emits the byte-for-byte token stream :class:`~repro.serving.engine.
ServeEngine` ``generate`` emits for it alone, given the same sampler, the
same per-request PRNG key, and a dense ``max_len`` equal to this engine's
``n_blocks * page_size`` (equal attention length — rule 11).  This works
because batch rows are computationally independent (decode runs its row-wise
ops fenced, ``layers.fenced_rows``, and the sampler pads its rows, so a row
rounds the same alone and in a batch), the paged gather reproduces the dense
cache view bitwise, and each row carries its own PRNG chain split exactly
like the solo loop (``key, k = split(key)`` per token).

Time is virtual: the clock advances one unit per decode iteration, so
arrival traces, latencies, and the whole schedule replay deterministically.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guards
from repro.core.dist_ops import dist_top_p_sample
from repro.models.model import build_model
from repro.serving import paged_kv
from repro.serving.engine import sample_padded_rows
from repro.utils.sharding import use_mesh


@dataclasses.dataclass
class Request:
    """One serving request.

    ``key`` is the request's own PRNG key (uint32 ``(2,)``, e.g.
    ``jax.random.PRNGKey(i)``) — the same key handed to a solo
    ``ServeEngine.generate`` call reproduces the same stream.
    ``arrival_step`` is in virtual decode steps.
    """
    rid: str
    tokens: np.ndarray
    max_new_tokens: int
    key: np.ndarray
    eos_id: Optional[int] = None
    arrival_step: int = 0


@dataclasses.dataclass
class RequestState:
    """Scheduler-side state of an admitted request."""
    request: Request
    slot: int
    page_ids: np.ndarray
    admit_step: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish_step: Optional[int] = None


def _span(name: str, **stats):
    """A profiler host span ``name`` carrying ``stats`` (the request id
    ``rid`` and the counts known at that boundary).  It costs about a
    microsecond when no trace is running; under ``jax.profiler`` it lands in
    the trace beside the device's events."""
    return jax.profiler.TraceAnnotation(name, **stats)


def count_while_loops(jaxpr) -> int:
    """Count ``while`` equations in a (closed) jaxpr, nested ones included.

    The trace-only launch guard: ``decode_n`` must stage exactly one —
    multi-token decode is one ``lax.while_loop``, not per-token dispatch.
    """
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jx.eqns:
        if eqn.primitive.name == "while":
            n += 1
        for val in eqn.params.values():
            for v in val if isinstance(val, (tuple, list)) else (val,):
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    n += count_while_loops(v)
    return n


def poisson_trace(n_requests: int, *, rate: float, vocab_size: int, seed: int,
                  prompt_len=(4, 12), max_new=(2, 8),
                  eos_id: Optional[int] = None) -> List[Request]:
    """Synthetic Poisson arrival trace (deterministic in ``seed``).

    Inter-arrival gaps are exponential with mean ``1/rate`` (in virtual
    decode steps); prompt lengths and decode budgets are uniform over the
    given inclusive ranges.
    """
    guards.validate_positive(n_requests, name="n_requests", op="poisson_trace")
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng.exponential(1.0 / rate,
                                                  n_requests))).astype(int)
    reqs = []
    for i in range(n_requests):
        s = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        n = int(rng.integers(max_new[0], max_new[1] + 1))
        toks = rng.integers(0, vocab_size, size=s).astype(np.int32)
        reqs.append(Request(
            rid=f"req{i}", tokens=toks, max_new_tokens=n,
            key=np.asarray(jax.random.PRNGKey(seed * 7919 + i)),
            eos_id=eos_id, arrival_step=int(arrivals[i])))
    return reqs


class ContinuousEngine:
    """Continuous-batching engine over a paged KV cache.

    Restricted to attention-only decoder stacks (dense/local/global/moe
    layers) — the paged layout pages the attention time axis; recurrent
    state (SSM/xLSTM), MLA latents, and cross-attention caches have no
    page-table form here and are rejected at construction.
    """

    SAMPLERS = ("greedy", "topp_scan", "topp_sharded", "topp_xla")
    _KINDS = frozenset({"dense", "local", "global", "moe"})

    def __init__(self, cfg, params, *, mesh=None, max_batch: int = 4,
                 page_size: int = 8, n_pages: int = 64,
                 max_len: Optional[int] = None, top_p: float = 0.9,
                 temperature: float = 1.0, sampler: str = "greedy",
                 bits_per_pass: int = 4, tick_tokens: int = 8):
        op = "ContinuousEngine"
        self.sampler = guards.validate_choice(sampler, self.SAMPLERS,
                                              name="sampler", op=op)
        guards.validate_probability(top_p, name="top_p", op=op)
        guards.validate_temperature(temperature, op=op)
        self.bits_per_pass = guards.validate_bits_per_pass(bits_per_pass,
                                                           op=op)
        self.max_batch = guards.validate_positive(max_batch, name="max_batch",
                                                  op=op)
        self.page_size = guards.validate_positive(page_size, name="page_size",
                                                  op=op)
        self.tick_tokens = guards.validate_positive(tick_tokens,
                                                    name="tick_tokens", op=op)
        self.alloc = paged_kv.PageAllocator(n_pages)
        self.n_pages = self.alloc.n_pages
        if max_len is None:
            max_len = self.alloc.capacity * self.page_size
        self.max_len = guards.validate_positive(max_len, name="max_len", op=op)
        self.n_blocks = paged_kv.pages_needed(self.max_len, self.page_size)
        self.top_p = top_p
        self.temperature = temperature
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.model = build_model(cfg)
        kinds = set(getattr(self.model, "pattern", ()))
        if cfg.family not in ("decoder", "moe") or not kinds <= self._KINDS:
            raise ValueError(
                f"{op}: {cfg.name!r} (family={cfg.family!r}, "
                f"pattern={sorted(kinds)}) is not an attention-only decoder "
                "stack — the paged KV layout pages the attention time axis "
                "only; serve it with the dense ServeEngine instead")
        self.caches = paged_kv.build_paged_caches(
            self.model, self.max_batch, self.n_pages, self.page_size,
            self.n_blocks)
        self._prefill = jax.jit(self._prefill_impl, static_argnums=(3,))
        if guards.checks_enabled():
            # checkify does not compose with donated buffers (see ServeEngine)
            from jax.experimental import checkify
            cdec = jax.jit(checkify.checkify(self._decode_n_impl,
                                             errors=checkify.user_checks),
                           static_argnums=(8,))

            def _decode_checked(*args):
                err, out = cdec(*args)
                err.throw()
                return out

            self._decode_n = _decode_checked
        else:
            self._decode_n = jax.jit(self._decode_n_impl, donate_argnums=(1,),
                                     static_argnums=(8,))

    # ---- sampling: per-row key chains, same operators as ServeEngine ----
    @jax.named_scope("sampler")
    def _sample_rows(self, logits, keys):
        """Sample one token per row, row ``r`` from ``keys[r]``.

        The per-row PRNG chains enter through the sampler's ``u=``
        override: one uniform per row from that row's key — the same single
        draw (identical bits) a solo ``ServeEngine._sample`` takes from it —
        then one batched call on the same padded rows the solo sampler
        uses, which is what makes continuous streams replay solo ones.
        """
        if self.sampler == "greedy":
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        u = jax.vmap(lambda k: jax.random.uniform(k, (1,), jnp.float32))(keys)
        if (self.sampler == "topp_sharded" and self.mesh is not None
                and "model" in self.mesh.shape
                and self.mesh.shape["model"] > 1):
            return dist_top_p_sample(
                logits, None, self.mesh, "model", p=self.top_p,
                temperature=self.temperature, method="matmul",
                bits_per_pass=self.bits_per_pass, u=u).astype(jnp.int32)
        return sample_padded_rows(
            logits, u, p=self.top_p, temperature=self.temperature,
            method="matmul",
            sort_method="xla" if self.sampler == "topp_xla" else "radix",
            bits_per_pass=self.bits_per_pass)

    # ---- prefill (one request alone, batch 1) ----
    def _prefill_impl(self, params, tokens, key, cache_len):
        with use_mesh(self.mesh):
            last_logits, caches = self.model.prefill(params,
                                                     {"tokens": tokens},
                                                     cache_len=cache_len)
            tok = self._sample_rows(last_logits, key[None, :])
            return tok[0], caches

    # ---- decode_n: the in-graph multi-token loop ----
    def _decode_n_impl(self, params, caches, tok, pos, keys, done, rem, eos,
                       n_steps):
        """Up to ``n_steps`` decode iterations in one ``lax.while_loop``.

        Carry per row: current token, write position, PRNG chain key, done
        flag, remaining token budget.  The loop exits early on-device when
        every row is done — no per-token host syncs.  ``eos`` is per-row
        (-1 = no eos).  Done rows keep stepping (their position frozen, so
        they only rewrite their own last slot / the scratch page) and their
        emitted slots are padded; callers harvest ``out[r, :emitted]`` via
        the returned ``rem``.
        """
        with use_mesh(self.mesh):
            cap = self.n_blocks * self.page_size
            guards.guard_check(
                lambda: jnp.all(jnp.where(done, 0,
                                          pos + jnp.minimum(rem, n_steps))
                                <= cap),
                "decode_n: a row's write positions would overrun its page "
                "budget (n_blocks * page_size) — admission must bound "
                "prompt + max_new_tokens by max_len")
            b = tok.shape[0]
            out0 = jnp.zeros((b, n_steps), jnp.int32)

            def cond(carry):
                i, done = carry[0], carry[6]
                return (i < n_steps) & jnp.logical_not(jnp.all(done))

            def body(carry):
                i, out, tok, caches, pos, keys, done, rem = carry
                ks = jax.vmap(jax.random.split)(keys)   # (B, 2, 2)
                keys2, kstep = ks[:, 0], ks[:, 1]
                logits, caches = self.model.decode_step(params, tok[:, None],
                                                        caches, pos)
                new = self._sample_rows(logits, kstep)
                new = jnp.where(done, jnp.maximum(eos, 0), new)
                out = out.at[:, i].set(new)
                rem2 = jnp.where(done, rem, rem - 1)
                done2 = done | ((new == eos) & (eos >= 0)) | (rem2 <= 0)
                pos2 = jnp.where(done2, pos, pos + 1)
                return (i + 1, out, new, caches, pos2, keys2, done2, rem2)

            carry = (jnp.zeros((), jnp.int32), out0, tok, caches, pos, keys,
                     done, rem)
            i, out, tok, caches, pos, keys, done, rem = jax.lax.while_loop(
                cond, body, carry)
            return out, i, tok, caches, pos, keys, done, rem

    def decode_n_jaxpr(self, n_steps: Optional[int] = None):
        """Trace-only: the jaxpr ``decode_n`` stages (for launch guards)."""
        n = n_steps or self.tick_tokens
        b = self.max_batch
        return jax.make_jaxpr(
            lambda p, c, t, ps, k, d, r, e:
            self._decode_n_impl(p, c, t, ps, k, d, r, e, n))(
                self.params, self.caches,
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b, 2), jnp.uint32), jnp.zeros((b,), bool),
                jnp.ones((b,), jnp.int32), jnp.full((b,), -1, jnp.int32))

    # ---- request validation (eager: fail before touching the model) ----
    def _validate(self, req: Request) -> np.ndarray:
        toks = np.asarray(req.tokens, np.int32)
        if toks.ndim != 1 or toks.size == 0:
            raise ValueError(f"run: request {req.rid!r} has a zero-length or "
                             f"non-1D prompt (shape {toks.shape}) — every "
                             "request needs at least one prompt token")
        if req.max_new_tokens < 1:
            raise ValueError(f"run: request {req.rid!r} asks for "
                             f"{req.max_new_tokens} tokens; continuous "
                             "batching serves requests with "
                             "max_new_tokens >= 1")
        total = toks.size + req.max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"run: request {req.rid!r} needs {total} positions "
                f"(prompt {toks.size} + max_new_tokens "
                f"{req.max_new_tokens}) > max_len={self.max_len} — it can "
                "never be admitted; raise max_len/n_pages or shorten it")
        if paged_kv.pages_needed(total, self.page_size) > self.alloc.capacity:
            raise ValueError(
                f"run: request {req.rid!r} needs "
                f"{paged_kv.pages_needed(total, self.page_size)} pages > "
                f"pool capacity {self.alloc.capacity}")
        return toks

    # ---- the driver ----
    def run(self, requests: Sequence[Request], *,
            max_ticks: int = 100_000) -> Dict:
        """Serve ``requests`` to completion; returns streams + schedule stats.

        One host sync per decode tick (plus one per admission).  Replaying
        the same trace on the same engine yields the identical result dict
        (virtual-time clock, FCFS admission, lowest-page-first allocation).

        Under ``jax.profiler`` the loop leaves host spans (:func:`_span`):
        ``engine/arrive`` (an instant, on the first pass at which the step
        clock reaches the request's arrival), ``engine/admit`` (allocation,
        prefill and insert; a failed allocation ends it early, so a request's
        last one is its admission) around ``engine/prefill`` and
        ``engine/insert``, ``engine/release``, and per tick ``engine/stage``,
        ``engine/decode`` and ``engine/harvest``.
        """
        reqs = [(self._validate(r), r) for r in requests]
        order = sorted(range(len(reqs)),
                       key=lambda i: (reqs[i][1].arrival_step, i))
        queue = [reqs[i] for i in order]
        arrivals = [r for _, r in queue]

        b = self.max_batch
        # reset page tables: stale tables from a previous run must not alias
        # freshly allocated pages
        for r in range(b):
            self.caches = paged_kv.clear_page_table(self.caches, r)
        self.alloc = paged_kv.PageAllocator(self.n_pages)

        slots: List[Optional[RequestState]] = [None] * b
        tok = np.zeros(b, np.int32)
        pos = np.zeros(b, np.int32)
        keys = np.zeros((b, 2), np.uint32)
        done = np.ones(b, bool)                 # idle rows count as done
        rem = np.zeros(b, np.int32)
        eos = np.full(b, -1, np.int32)
        step = 0
        ticks = 0
        n_arrived = 0
        finished: List[RequestState] = []

        def release(st):
            with _span("engine/release", rid=st.request.rid,
                       pages=len(st.page_ids)):
                self.alloc.release(st.page_ids)
                self.caches = paged_kv.clear_page_table(self.caches, st.slot)
            finished.append(st)

        def admit(toks_np, req):
            total = toks_np.size + req.max_new_tokens
            m = paged_kv.pages_needed(total, self.page_size)
            slot = next((i for i, s in enumerate(slots) if s is None), None)
            if slot is None:
                return False
            with _span("engine/admit", rid=req.rid, prompt=toks_np.size,
                       pages=m):
                pages = self.alloc.alloc(m)
                if pages is None:
                    return False
                key = jnp.asarray(np.asarray(req.key), jnp.uint32)
                key, k0 = jax.random.split(key)
                with _span("engine/prefill", rid=req.rid):
                    t0, dense = self._prefill(self.params,
                                              jnp.asarray(toks_np)[None, :], k0,
                                              m * self.page_size)
                with _span("engine/insert", rid=req.rid):
                    self.caches = paged_kv.insert_request(self.caches, dense,
                                                          slot, pages)
                st = RequestState(request=req, slot=slot, page_ids=pages,
                                  admit_step=step, tokens=[int(t0)])
            e = -1 if req.eos_id is None else int(req.eos_id)
            fin = ((e >= 0 and st.tokens[0] == e)
                   or req.max_new_tokens <= 1)
            if fin:
                st.finish_step = step
                release(st)
                return True
            slots[slot] = st
            tok[slot] = st.tokens[0]
            pos[slot] = toks_np.size
            keys[slot] = np.asarray(key)
            done[slot] = False
            rem[slot] = req.max_new_tokens - 1
            eos[slot] = e
            return True

        while queue or any(s is not None for s in slots):
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"run: exceeded max_ticks={max_ticks} — "
                                   "scheduler is not draining")
            while (n_arrived < len(arrivals)
                   and arrivals[n_arrived].arrival_step <= step):
                with _span("engine/arrive", rid=arrivals[n_arrived].rid):
                    pass
                n_arrived += 1
            # strict FCFS admission of arrived requests
            while queue and queue[0][1].arrival_step <= step:
                if not admit(*queue[0]):
                    break
                queue.pop(0)
            if all(s is None for s in slots):
                if queue:       # idle: fast-forward to the next arrival
                    step = max(step, queue[0][1].arrival_step)
                continue

            rem_before = rem.copy()
            rows = [r for r, st in enumerate(slots) if st is not None]
            with _span("engine/stage"):
                staged = [jnp.asarray(x) for x in (tok, pos, keys, done, rem, eos)]
            with _span("engine/decode", rows=len(rows)):
                out, nsteps, tok_d, self.caches, pos_d, keys_d, done_d, rem_d = \
                    self._decode_n(self.params, self.caches, *staged,
                                   self.tick_tokens)
                # ONE host sync for the whole tick (np.array: device_get views
                # can be read-only, and admission mutates these in place)
                out, nsteps, tok, pos, keys, done, rem = [
                    np.array(x) for x in jax.device_get(
                        (out, nsteps, tok_d, pos_d, keys_d, done_d, rem_d))]
            base = step
            step += int(nsteps)
            emitted = {r: int(rem_before[r] - rem[r]) for r in rows}
            with _span("engine/harvest", steps=int(nsteps),
                       tokens=sum(emitted.values()), rows=len(rows),
                       pages=self.alloc.in_use):
                for r in rows:
                    st = slots[r]
                    st.tokens.extend(int(t) for t in out[r, :emitted[r]])
                    if done[r]:
                        st.finish_step = base + emitted[r]
                        release(st)
                        slots[r] = None

        finished.sort(key=lambda st: (st.finish_step, st.request.rid))
        total_tokens = sum(len(st.tokens) for st in finished)
        return {
            "streams": {st.request.rid: np.asarray(st.tokens, np.int32)
                        for st in finished},
            "requests": {st.request.rid: {
                "arrival_step": st.request.arrival_step,
                "admit_step": st.admit_step,
                "finish_step": st.finish_step,
                "n_tokens": len(st.tokens),
                "latency_steps": st.finish_step - st.request.arrival_step,
                "per_token_latency_steps":
                    (st.finish_step - st.request.arrival_step)
                    / max(len(st.tokens), 1),
            } for st in finished},
            "stats": {
                "steps": step,
                "ticks": ticks,
                "total_tokens": total_tokens,
                "reqs": len(finished),
                "peak_pages": self.alloc.peak_in_use,
                "pool_capacity": self.alloc.capacity,
                "peak_util": self.alloc.peak_in_use / self.alloc.capacity,
            },
        }
