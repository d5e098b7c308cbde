"""Continuous-batching subsystem: allocator, paged-vs-dense parity, the
scheduler state machine, and the exact-stream contract vs solo ServeEngine."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import guards
from repro.models.model import build_model, get_config
from repro.serving import paged_kv
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import (ContinuousEngine, Request,
                                     count_while_loops, poisson_trace)

PS = 8  # page size used throughout


@functools.lru_cache(maxsize=None)
def _cfg_params(name="llama3-8b"):
    cfg = get_config(name, smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _engine(sampler="greedy", **kw):
    cfg, params = _cfg_params()
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", PS)
    kw.setdefault("n_pages", 9)
    kw.setdefault("max_len", 24)
    kw.setdefault("tick_tokens", 4)
    return ContinuousEngine(cfg, params, sampler=sampler, top_p=0.9, **kw)


def _req(rid, tokens, n, seed, eos_id=None, arrival=0):
    return Request(rid=rid, tokens=np.asarray(tokens, np.int32),
                   max_new_tokens=n, key=np.asarray(jax.random.PRNGKey(seed)),
                   eos_id=eos_id, arrival_step=arrival)


# ---------------------------------------------------------------------------
# page allocator (free-list via the paper's compress)
# ---------------------------------------------------------------------------


def test_allocator_lowest_free_first_and_reuse():
    al = paged_kv.PageAllocator(8)          # capacity 7, page 0 reserved
    a = al.alloc(3)
    np.testing.assert_array_equal(a, [1, 2, 3])   # never hands out page 0
    b = al.alloc(4)
    np.testing.assert_array_equal(b, [4, 5, 6, 7])
    assert al.alloc(1) is None and al.in_use == 7 == al.peak_in_use
    al.release(a)
    c = al.alloc(2)                         # freed pages come back, lowest id
    np.testing.assert_array_equal(c, [1, 2])


def test_allocator_rejects_double_free_and_bad_ids():
    al = paged_kv.PageAllocator(4)
    ids = al.alloc(2)
    al.release(ids)
    with pytest.raises(ValueError, match="double free"):
        al.release(ids)
    with pytest.raises(ValueError, match="outside"):
        al.release([0])                     # scratch page is not releasable
    with pytest.raises(ValueError):
        paged_kv.PageAllocator(1)           # nothing left after the scratch


# ---------------------------------------------------------------------------
# paged layout parity (rule 11): the gathered view IS the dense cache
# ---------------------------------------------------------------------------


def test_insert_then_gather_matches_dense_prefill_cache():
    cfg, params = _cfg_params()
    model = build_model(cfg)
    nblk = 3
    caches = paged_kv.build_paged_caches(model, 2, 9, PS, nblk)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 10)))
    _, dense = model.prefill(params, {"tokens": toks}, cache_len=2 * PS)
    caches = paged_kv.insert_request(caches, dense, 1, np.asarray([4, 2]))
    view = paged_kv.gather_dense(caches)

    def check(v, d):
        got = v["k"][:, 1, :2 * PS]          # row 1, first 2 blocks
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(d["k"][:, 0]))
    jax.tree.map(check, view, dense,
                 is_leaf=lambda n: isinstance(n, dict) and "k" in n)


def test_build_paged_caches_rejects_non_attention_models():
    cfg = get_config("zamba2-1.2b", smoke=True)
    with pytest.raises(ValueError, match="attention"):
        paged_kv.build_paged_caches(build_model(cfg), 2, 8, PS, 2)


def test_continuous_engine_rejects_non_attention_stacks():
    for name in ("minicpm3-4b", "zamba2-1.2b"):
        with pytest.raises(ValueError, match="attention-only"):
            ContinuousEngine(get_config(name, smoke=True), None)


# ---------------------------------------------------------------------------
# the exact-stream contract: continuous == solo ServeEngine, per request
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampler",
                         ["greedy", "topp_scan", "topp_sharded", "topp_xla"])
def test_continuous_matches_solo_streams_across_samplers(sampler):
    eng = _engine(sampler)
    cfg, params = _cfg_params()
    rng = np.random.default_rng(3)
    reqs = [_req(f"r{i}", rng.integers(0, cfg.vocab_size, s), n, 60 + i,
                 arrival=i)
            for i, (s, n) in enumerate([(4, 6), (7, 4), (4, 5)])]
    res = eng.run(reqs)
    solo = ServeEngine(cfg, params, max_len=eng.n_blocks * PS,
                       sampler=sampler, top_p=0.9)
    for r in reqs:
        ref = np.asarray(solo.generate({"tokens": jnp.asarray(r.tokens)[None]},
                                       r.max_new_tokens,
                                       jnp.asarray(r.key)))[0]
        np.testing.assert_array_equal(res["streams"][r.rid], ref, err_msg=r.rid)


def test_continuous_eos_stream_matches_solo():
    eng = _engine()
    cfg, params = _cfg_params()
    solo = ServeEngine(cfg, params, max_len=eng.n_blocks * PS,
                       sampler="greedy")
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size,
                                             5).astype(np.int32)
    key = np.asarray(jax.random.PRNGKey(7))
    full = np.asarray(solo.generate({"tokens": jnp.asarray(toks)[None]}, 8,
                                    jnp.asarray(key)))[0]
    eos = int(full[2])
    ref = np.asarray(solo.generate({"tokens": jnp.asarray(toks)[None]}, 8,
                                   jnp.asarray(key), eos_id=eos))[0]
    res = eng.run([_req("e0", toks, 8, 0, eos_id=eos)])
    np.testing.assert_array_equal(res["streams"]["e0"], ref)
    assert res["streams"]["e0"][-1] == eos and len(ref) < 8


# ---------------------------------------------------------------------------
# scheduler state machine
# ---------------------------------------------------------------------------


def test_fcfs_admission_blocks_under_page_pressure():
    """A later small request must NOT bypass a blocked earlier big one."""
    eng = _engine(page_size=4, n_pages=5, max_len=12, tick_tokens=2)
    # capacity 4 pages of 4: A and B need 3 pages each, C needs 1
    reqs = [_req("A", [1, 2, 3, 4], 8, 0, arrival=0),
            _req("B", [1, 2, 3, 4], 8, 1, arrival=1),
            _req("C", [1, 2], 2, 2, arrival=1)]
    res = eng.run(reqs)
    info = res["requests"]
    assert info["A"]["admit_step"] == 0
    # B blocked on pages until A finished; C (1 page, free slot available the
    # whole time) still waits behind B — strict FCFS
    assert info["B"]["admit_step"] >= info["A"]["finish_step"]
    assert info["C"]["admit_step"] >= info["B"]["admit_step"]
    assert res["stats"]["peak_pages"] <= 4


def test_eviction_reclaims_pages_for_later_requests():
    """More total pages than the pool holds — only works with eviction."""
    eng = _engine(page_size=4, n_pages=4, max_len=12, max_batch=1,
                  tick_tokens=4)
    cfg, _ = _cfg_params()
    rng = np.random.default_rng(0)
    reqs = [_req(f"r{i}", rng.integers(0, cfg.vocab_size, 5), 6, i)
            for i in range(4)]           # 3 pages each, 12 total vs pool of 3
    res = eng.run(reqs)
    assert len(res["streams"]) == 4
    assert res["stats"]["peak_pages"] <= eng.alloc.capacity == 3
    assert all(len(s) == 6 for s in res["streams"].values())


def test_zero_length_and_over_budget_rejected_eagerly():
    eng = _engine()
    with pytest.raises(ValueError, match="zero-length"):
        eng.run([_req("z", np.zeros(0, np.int32), 2, 0)])
    with pytest.raises(ValueError, match="max_len"):
        eng.run([_req("b", np.ones(30, np.int32), 10, 0)])
    with pytest.raises(ValueError, match="max_new_tokens >= 1"):
        eng.run([_req("n", [1, 2], 0, 0)])


def test_arrival_trace_replays_deterministically():
    eng = _engine()
    cfg, _ = _cfg_params()
    reqs = poisson_trace(5, rate=0.4, vocab_size=cfg.vocab_size, seed=11,
                         prompt_len=(3, 8), max_new=(2, 5))
    r1, r2 = eng.run(reqs), eng.run(reqs)
    assert r1["stats"] == r2["stats"]
    assert r1["requests"] == r2["requests"]
    for k in r1["streams"]:
        np.testing.assert_array_equal(r1["streams"][k], r2["streams"][k])
    # and the trace itself is a pure function of the seed
    again = poisson_trace(5, rate=0.4, vocab_size=cfg.vocab_size, seed=11,
                          prompt_len=(3, 8), max_new=(2, 5))
    for a, b in zip(reqs, again):
        assert a.arrival_step == b.arrival_step
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_decode_n_stages_exactly_one_while_loop():
    """Trace-only launch guard: multi-token decode is ONE while_loop."""
    eng = _engine()
    assert count_while_loops(eng.decode_n_jaxpr(4)) == 1
    assert count_while_loops(eng.decode_n_jaxpr(eng.tick_tokens)) == 1


def test_page_budget_guard_fires_under_checks():
    from jax.experimental.checkify import JaxRuntimeError
    with guards.checks():
        eng = _engine()
        b = eng.max_batch
        bad_pos = jnp.full((b,), eng.n_blocks * PS, jnp.int32)  # past budget
        with pytest.raises(JaxRuntimeError, match="page budget"):
            eng._decode_n(eng.params, eng.caches, jnp.zeros((b,), jnp.int32),
                          bad_pos, jnp.zeros((b, 2), jnp.uint32),
                          jnp.zeros((b,), bool), jnp.ones((b,), jnp.int32),
                          jnp.full((b,), -1, jnp.int32), 2)


def test_padded_sampler_row_matches_its_batch():
    """``sample_padded_rows`` draws a row's token the same alone and among
    other rows (both engines sample through it: rule 11)."""
    from repro.serving.engine import sample_padded_rows
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((5, 300)) * 3.0, jnp.float32)
    u = jnp.asarray(rng.random((5, 1)), jnp.float32)
    kw = dict(p=0.9, temperature=1.0, method="matmul", sort_method="radix",
              bits_per_pass=4)
    batch = np.asarray(sample_padded_rows(logits, u, **kw))
    for r in range(5):
        alone = sample_padded_rows(logits[r:r + 1], u[r:r + 1], **kw)
        assert int(alone[0]) == int(batch[r])
