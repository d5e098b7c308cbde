"""Seeded request streams for the serving cells, read from a traffic file.

A traffic file (``traffic/<mix>.json``) holds only parameters: the engine's
settings, the prompt and output length distributions as values with integer
weights, the offered load as a share of what the batch's slots can retire,
the size of the backlog at the start, and the stream's length.

The work is the same for every seed: the lengths and the arrival gaps are
drawn from the file's own ``schedule_seed``, and ``--seed`` draws the prompts'
token ids and each request's sampling key.  A window of the slowest cell
holds only a dozen arrivals, so a schedule that changed with the seed made
runs of different seeds differ far more than two runs of one seed.  Within
the schedule, each aligned block of ``sum(weights)`` requests holds every
length exactly as often as its weight says, and each block of ``GAP_BLOCK``
arrival gaps is the same set of exponential quantiles, shuffled, so the mean
rate is exactly the stated one.

Arrivals are open loop on the engine's decode-step clock (``arrival_step``):
the first ``initial_backlog`` requests arrive at step 0, a server that is busy
when the window opens, and the rest follow with exponential gaps of mean
``1 / rate``.  Nothing here imports the program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
GAP_BLOCK = 10


def load(name: str) -> Dict:
    """The traffic file ``traffic/<name>.json``."""
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def mean_of(dist: Dict) -> float:
    v, w = np.asarray(dist["values"], float), np.asarray(dist["weights"], float)
    return float((v * w).sum() / w.sum())


def arrival_rate(spec: Dict) -> float:
    """Requests per decode step: ``load`` of what ``max_batch`` slots retire."""
    return spec["load"] * spec["engine"]["max_batch"] / mean_of(spec["output_len"])


def cache_len(prompt_len: int, output_len: int, page_size: int) -> int:
    """The prefill cache length the engine asks for: whole pages covering
    the prompt and the output budget."""
    return math.ceil((prompt_len + output_len) / page_size) * page_size


def _stratified(dist: Dict, n: int, rng) -> np.ndarray:
    block = np.repeat(np.asarray(dist["values"], np.int64),
                      np.asarray(dist["weights"], np.int64))
    reps = -(-n // block.size)
    out = np.concatenate([rng.permutation(block) for _ in range(reps)])
    return out[:n]


def _gaps(n: int, mean: float, rng) -> np.ndarray:
    q = (np.arange(GAP_BLOCK) + 0.5) / GAP_BLOCK
    block = -np.log1p(-q)
    block = block / block.mean() * mean
    reps = -(-n // GAP_BLOCK)
    return np.concatenate([rng.permutation(block) for _ in range(reps)])[:n]


def generate(spec: Dict, vocab_size: int, seed: int) -> List[Dict]:
    """The stream of ``spec["n_requests"]`` requests for ``seed``.

    Each request is a dict: ``rid``, ``tokens`` (int32 prompt), ``max_new_tokens``,
    ``key`` (uint32 pair, the request's own PRNG key), ``arrival_step``.
    """
    n = spec["n_requests"]
    sched = np.random.default_rng([spec["schedule_seed"], 1])
    plen = _stratified(spec["prompt_len"], n, sched)
    olen = _stratified(spec["output_len"], n, sched)
    backlog = min(spec.get("initial_backlog", 0), n)
    gaps = _gaps(n - backlog, 1.0 / arrival_rate(spec), sched)
    arrivals = np.concatenate([np.zeros(backlog),
                               np.floor(np.cumsum(gaps))]).astype(np.int64)
    keys = np.random.default_rng([seed, 1]).integers(0, 2 ** 32, size=(n, 2),
                                                     dtype=np.uint32)
    tok_rng = np.random.default_rng([seed, 2])
    return [{"rid": f"r{i}",
             "tokens": tok_rng.integers(0, vocab_size, int(plen[i]),
                                        dtype=np.int32),
             "max_new_tokens": int(olen[i]),
             "key": keys[i],
             "arrival_step": int(arrivals[i])} for i in range(n)]


def warm_requests(spec: Dict) -> List[Tuple[int, int]]:
    """One (prompt_len, output_len) per distinct (prompt_len, cache_len)
    the mix can draw: the shapes set-up compiles."""
    page = spec["engine"]["page_size"]
    seen = {}
    for p in spec["prompt_len"]["values"]:
        for o in spec["output_len"]["values"]:
            seen.setdefault((p, cache_len(p, o, page)), (p, o))
    return sorted(seen.values())
