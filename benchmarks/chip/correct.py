"""The comparison that decides ``correct``: served tokens against the plain
float32 reference, on a sample of the requests the window finished.  The
reference is the ``Reference`` of the module that the configuration file
names under ``"reference"`` (``reference/<module>.py``).

The sample is drawn from the seed and always holds the longest finished
request.  For each sampled request the reference runs once over its prompt
and its served tokens (teacher-forced) and reads the logits that chose each
served token, the prefill's first token among them.  The number compared is
``logit_gap``, the widest gap over all sampled tokens between the reference
logit (over the temperature) of the served token and that of the token the
reference itself chooses there:

* greedy traffic: the reference's best token, so the gap is how far the
  served token lies below the best;
* top-p traffic: the token the reference's sort-based top-p draws with the
  request's own uniform for that token, replayed from the request's key
  exactly as the engine splits it.  The program sorts on bf16-rounded
  probabilities, so it may draw a neighbour of nearly equal probability;
  such a neighbour lies a small logit gap away, where a wrong token lies
  about a whole spread of the logits away.

``mismatch_share`` is the share of the sampled tokens that are not the
reference's own choice.  It is the number of the MoE cell: there a bf16
rounding now and then flips one of the 6 routing choices of 64, which moves
that token's logits a long way, so the widest gap swings from seed to seed
while the share of flipped tokens stays small.

A cell's limits file (``limits/<workload>.json``) names the numbers it
compares, each with its limit.  The control puts the same reference, computed
with fp8 operands, in the program's place: at every position it chooses its
own token (its best, or its own draw with the same uniform) and is read by
the same numbers.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chip import counts
from chip.reference import topp

HERE = Path(__file__).resolve().parent
SAMPLE = 4
NUMBERS = ("logit_gap", "mismatch_share")


def limits(workload: str) -> Dict[str, float]:
    """The numbers a cell compares, each with its limit."""
    with open(HERE / "limits" / f"{workload}.json") as f:
        lim = json.load(f)
    unknown = set(lim) - set(NUMBERS)
    if unknown:
        raise ValueError(f"limits/{workload}.json names unknown numbers {unknown}")
    return lim


def pick_sample(finished: List[int], stream: List[Dict], served: Dict[int, List[int]],
                seed: int) -> List[int]:
    """The longest finished request and ``SAMPLE - 1`` others drawn from the seed."""
    k = SAMPLE
    if not finished:
        return []
    size = {i: stream[i]["tokens"].size + len(served[i]) for i in finished}
    longest = max(sorted(finished), key=lambda i: size[i])
    rest = sorted(i for i in finished if i != longest)
    rng = np.random.default_rng([seed, 3])
    extra = rng.choice(rest, size=min(k - 1, len(rest)), replace=False) if rest else []
    return [longest] + sorted(int(i) for i in extra)


@jax.jit
def _uniforms(key, dummy):
    """The engine's uniform for every token of one request: the prefill's
    from ``split(key)[1]``, then one per decode step from the split chain."""
    key, k0 = jax.random.split(key)

    def step(key, _):
        ks = jax.random.split(key)
        return ks[0], jax.random.uniform(ks[1], (1,), jnp.float32)[0]

    _, us = jax.lax.scan(step, key, dummy)
    return jnp.concatenate([jax.random.uniform(k0, (1,), jnp.float32), us])


def malformed(stream_req: Dict, tokens: List[int], vocab: int) -> bool:
    """A finished request whose stream is not its whole budget of valid ids."""
    t = np.asarray(tokens)
    return (t.size != stream_req["max_new_tokens"]
            or bool(np.any((t < 0) | (t >= vocab))))


def _batch(stream, served, ids, max_len):
    """Teacher-forced inputs: prompt + served[:-1], padded to ``max_len``, and
    the rows whose logits chose each served token, padded with the last."""
    r = max(len(served[i]) for i in ids)
    toks = np.zeros((len(ids), max_len), np.int32)
    rows = np.zeros((len(ids), r), np.int32)
    got = np.zeros((len(ids), r), np.int32)
    mask = np.zeros((len(ids), r), bool)
    for j, i in enumerate(ids):
        p, s = stream[i]["tokens"], np.asarray(served[i], np.int32)
        seq = np.concatenate([p, s[:-1]])
        toks[j, :seq.size] = seq
        n = s.size
        rows[j, :n] = p.size - 1 + np.arange(n)
        rows[j, n:] = rows[j, n - 1]
        got[j, :n] = s
        got[j, n:] = s[-1]
        mask[j, :n] = True
    return toks, rows, got, mask


def readings(cfg: Dict, engine_spec: Dict, stream: List[Dict],
             served: Dict[int, List[int]], ids: List[int], seed: int,
             control: bool = False) -> Dict[str, float]:
    """``logit_gap`` and ``mismatch_share`` of the program's served tokens
    against the float32 reference, and with ``control`` the same of the fp8
    reference's own choices (``<name>_control``)."""
    toks, rows, got, mask = _batch(stream, served, ids, engine_spec["max_len"])
    greedy = engine_spec["sampler"] == "greedy"
    us = np.zeros(got.shape, np.float32)
    if not greedy:
        for j, i in enumerate(ids):
            n = int(mask[j].sum())
            u = np.asarray(_uniforms(jnp.asarray(stream[i]["key"], jnp.uint32),
                                     jnp.zeros(n - 1)))
            us[j, :n], us[j, n:] = u, u[-1]
    temp = 1.0 if greedy else engine_spec["temperature"]
    pick_of = (jax.jit(lambda lg, u: jnp.argmax(lg, -1)) if greedy else
               jax.jit(lambda lg, u: topp.sample(lg, u, p=engine_spec["top_p"],
                                                 temperature=temp)))
    gap_of = jax.jit(lambda lg, a, b: topp.logit_gap(lg, a, b) / temp)
    reference = counts.arch(cfg).Reference
    ref = reference(cfg).logits(seed, toks, rows)
    ctl = reference(cfg, "fp8").logits(seed, toks, rows) if control else None
    sides = {"": [], "_control": []}
    for j in range(len(ids)):
        u = jnp.asarray(us[j])
        want = pick_of(ref[j], u)
        chosen = {"": jnp.asarray(got[j])}
        if control:
            chosen["_control"] = pick_of(ctl[j], u)
        for side, tok in chosen.items():
            gap = np.asarray(gap_of(ref[j], tok, want))[mask[j]]
            same = np.asarray(tok == want)[mask[j]]
            sides[side].append((gap, same))
    out = {}
    for side, parts in sides.items():
        if parts:
            gaps = np.concatenate([g for g, _ in parts])
            same = np.concatenate([m for _, m in parts])
            out[f"logit_gap{side}"] = float(gaps.max())
            out[f"mismatch_share{side}"] = float(1.0 - same.mean())
    return out
