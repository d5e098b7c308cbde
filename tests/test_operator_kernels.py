"""Fused operator kernels (split_mm) vs the unfused paths.

Acceptance: ``method="kernel"`` is bit-identical to ``method="vector"`` for
split / radix_sort / topk on CPU interpret mode, across fp32 / bf16 / int8
payloads and odd lengths (non-multiples of s²); top_p_sample draws the same
tokens on these inputs, where no draw sits within rounding of a threshold.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import compress, radix_sort, sort, split, top_p_sample, topk
from repro.core.primitives import dispatch

S = 16                       # kernel mask-scan row width (small: interpret speed)
ODD_LENS = [5, 37, 333]      # none is a multiple of S² = 256


def _payload(dtype, n, seed):
    rng = np.random.default_rng(seed)
    if dtype == "int8":
        return jnp.asarray(rng.integers(-128, 128, n), jnp.int8)
    return jnp.asarray(rng.standard_normal(n), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("n", ODD_LENS)
def test_split_parity(dtype, n):
    x = _payload(dtype, n, n)
    f = jnp.asarray(np.random.default_rng(n + 1).random(n) < 0.4)
    zv, iv, cv = split(x, f, method="vector", tile_s=S)
    zk, ik, ck = split(x, f, method="kernel", tile_s=S)
    np.testing.assert_array_equal(np.asarray(zv), np.asarray(zk))
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(ik))
    assert int(cv) == int(ck)


def test_split_parity_batched():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 77)), jnp.float32)
    f = jnp.asarray(rng.random((4, 77)) < 0.5)
    zv, iv, cv = split(x, f, method="vector", tile_s=S)
    zk, ik, ck = split(x, f, method="kernel", tile_s=S)
    np.testing.assert_array_equal(np.asarray(zv), np.asarray(zk))
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(ik))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(ck))


def test_split_all_true_all_false_shorter_than_s():
    for flags in (np.ones(5, bool), np.zeros(5, bool)):
        x = jnp.asarray(np.arange(5), jnp.float32)
        zv, iv, cv = split(x, jnp.asarray(flags), method="vector", tile_s=S)
        zk, ik, ck = split(x, jnp.asarray(flags), method="kernel", tile_s=S)
        np.testing.assert_array_equal(np.asarray(zv), np.asarray(zk))
        np.testing.assert_array_equal(np.asarray(iv), np.asarray(ik))
        assert int(cv) == int(ck)


def test_compress_parity():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(201), jnp.float32)
    m = jnp.asarray(rng.random(201) < 0.3)
    vv, cv = compress(x, m, method="vector", tile_s=S)
    vk, ck = compress(x, m, method="kernel", tile_s=S)
    np.testing.assert_array_equal(np.asarray(vv), np.asarray(vk))
    assert int(cv) == int(ck)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("n", [37, 333])
@pytest.mark.parametrize("bits_per_pass", [1, 4])
def test_radix_sort_parity(dtype, n, bits_per_pass):
    x = _payload(dtype, n, 7 * n)
    vv, iv = radix_sort(x, method="vector", tile_s=S,
                        bits_per_pass=bits_per_pass)
    vk, ik = radix_sort(x, method="kernel", tile_s=S,
                        bits_per_pass=bits_per_pass)
    np.testing.assert_array_equal(np.asarray(vv), np.asarray(vk))
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(ik))


def test_radix_sort_kernel_correct_vs_numpy():
    rng = np.random.default_rng(3)
    x = rng.standard_normal(300).astype(np.float32)
    v, idx = radix_sort(jnp.asarray(x), method="kernel", tile_s=S)
    np.testing.assert_array_equal(np.asarray(v), np.sort(x, kind="stable"))
    np.testing.assert_array_equal(x[np.asarray(idx)], np.asarray(v))


def test_radix_sort_descending_and_batched_kernel():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((3, 129)), jnp.bfloat16)
    vv, iv = sort(x, descending=True, method="vector", tile_s=S)
    vk, ik = sort(x, descending=True, method="kernel", tile_s=S)
    np.testing.assert_array_equal(np.asarray(vv.astype(jnp.float32)),
                                  np.asarray(vk.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(ik))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_topk_parity(dtype):
    x = _payload(dtype, 211, 11)
    vv, iv = topk(x, 9, method="vector", tile_s=S)
    vk, ik = topk(x, 9, method="kernel", tile_s=S)
    np.testing.assert_array_equal(np.asarray(vv), np.asarray(vk))
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(ik))


@pytest.mark.parametrize("n", [100, 257])
def test_top_p_parity(n):
    rng = np.random.default_rng(n)
    logits = jnp.asarray(rng.standard_normal((3, n)) * 2, jnp.float32)
    for i in range(3):
        key = jax.random.PRNGKey(i)
        tv = top_p_sample(logits, key, p=0.9, method="vector", tile_s=S)
        tk = top_p_sample(logits, key, p=0.9, method="kernel", tile_s=S)
        np.testing.assert_array_equal(np.asarray(tv), np.asarray(tk))


def test_top_p_kernel_restricts_to_nucleus():
    logits = jnp.asarray(np.r_[10.0, np.zeros(63)], jnp.float32)[None, :]
    keys = jax.random.split(jax.random.PRNGKey(1), 25)
    toks = np.asarray(jax.vmap(
        lambda k: top_p_sample(logits, k, p=0.5, method="kernel",
                               tile_s=S))(keys))
    assert np.all(toks == 0)


def test_dispatch_rejects_unknown_method():
    x = jnp.zeros(8)
    f = jnp.zeros(8, bool)
    with pytest.raises(ValueError):
        split(x, f, method="cube")
    with pytest.raises(ValueError):
        dispatch("split", "nope")
    with pytest.raises(ValueError):
        dispatch("no_such_op", "kernel")


def test_serving_engine_kernel_sampler():
    """The fused sampler slots into ServeEngine and matches the scan sampler."""
    from repro.models.model import get_config
    from repro.serving.engine import ServeEngine

    cfg = get_config("llama3-8b", smoke=True)
    key = jax.random.PRNGKey(0)
    eng_scan = ServeEngine(cfg, None, sampler="topp_scan")
    eng_kern = ServeEngine(cfg, None, sampler="topp_kernel")
    logits = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, cfg.vocab_size)) * 3,
        jnp.float32)
    a = eng_scan._sample(logits, key)
    b = eng_kern._sample(logits, key)
    assert a.shape == b.shape == (2,)
    assert np.all(np.asarray(b) >= 0) and np.all(np.asarray(b) < cfg.vocab_size)
