"""Per-architecture smoke tests (reduced configs): one forward + train step on CPU,
output shapes + no NaNs; prefill/decode consistency vs teacher forcing."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SMOKE_SHAPE
from repro.models.model import ARCHS, build_model, get_config, synth_batch

ALL = list(ARCHS)


@pytest.mark.parametrize("arch", ALL)
def test_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    batch = synth_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(2))
    logits = m.forward(params, batch)
    s_total = batch["tokens"].shape[1] + (cfg.n_img_tokens
                                          if cfg.family == "vlm" else 0)
    assert logits.shape == (SMOKE_SHAPE.global_batch, s_total, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    (loss, metrics), grads = jax.value_and_grad(m.loss, has_aux=True)(
        params, batch)
    assert np.isfinite(float(loss))
    gnorm = np.sqrt(sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                        for g in jax.tree.leaves(grads)))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ALL)
def test_prefill_decode_consistency(arch):
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    batch = synth_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(2))
    s = batch["tokens"].shape[1]
    off = cfg.n_img_tokens if cfg.family == "vlm" else 0
    cache_len = off + s + 2
    full = np.asarray(m.forward(params, batch), np.float32)
    s0 = s - 2
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :s0]
    last, caches = m.prefill(params, pre, cache_len=cache_len)
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               full[:, off + s0 - 1], rtol=2e-3, atol=2e-3)
    for t in range(2):
        tok = batch["tokens"][:, s0 + t][:, None]
        logits, caches = m.decode_step(params, tok, caches,
                                       jnp.asarray(off + s0 + t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   full[:, off + s0 + t], rtol=5e-3, atol=5e-3)


def test_unrolled_matches_scanned_layers():
    """scan-over-layers and unrolled layers are the same computation."""
    import dataclasses
    cfg = get_config("qwen3-4b", smoke=True)
    m1 = build_model(cfg)
    m2 = build_model(dataclasses.replace(cfg, scan_layers=False))
    params = m1.init(jax.random.PRNGKey(0))
    batch = synth_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(1))
    a = np.asarray(m1.forward(params, batch), np.float32)
    b = np.asarray(m2.forward(params, batch), np.float32)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_scan_method_toggle_matches_vector_baseline():
    """The paper's matmul scan inside MoE dispatch == the vector baseline."""
    import dataclasses
    cfg = get_config("deepseek-moe-16b", smoke=True)
    m1 = build_model(cfg)                                 # matmul scan
    m2 = build_model(dataclasses.replace(cfg, scan_method="vector"))
    params = m1.init(jax.random.PRNGKey(0))
    batch = synth_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(1))
    a = np.asarray(m1.forward(params, batch), np.float32)
    b = np.asarray(m2.forward(params, batch), np.float32)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_local_window_masks_gemma2():
    """gemma2 local layers must not attend beyond the window."""
    cfg = get_config("gemma2-2b", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 200, (1, 48)), jnp.int32)
    base = np.asarray(m.forward(params, {"tokens": toks}), np.float32)
    # perturbing a token beyond every window+global reach changes logits;
    # sanity: outputs differ when early token changes (global layers attend)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 7) % 200)
    pert = np.asarray(m.forward(params, {"tokens": toks2}), np.float32)
    assert not np.allclose(base[0, -1], pert[0, -1])


@pytest.mark.parametrize("batch", [4, 8, 16])
def test_decode_row_bits_do_not_depend_on_batch(batch):
    """A decode row computes the same bits alone and in a batch (decode
    steps run their row-wise ops fenced: ``layers.fenced_rows``) — what lets
    a continuously batched request replay its solo stream (rule 11)."""
    cfg = get_config("qwen3-4b", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    c1 = jax.tree.map(lambda c: jnp.asarray(rng.standard_normal(c.shape),
                                            c.dtype), m.empty_caches(1, 16))
    cb = jax.tree.map(lambda c: jnp.repeat(c, batch, axis=-4), c1)
    dec = jax.jit(m.decode_step)
    solo, _ = dec(params, jnp.asarray([[5]], jnp.int32), c1, jnp.int32(7))
    rows, _ = dec(params, jnp.full((batch, 1), 5, jnp.int32), cb,
                  jnp.full((batch,), 7, jnp.int32))
    for r in range(batch):
        np.testing.assert_array_equal(np.asarray(rows[r]), np.asarray(solo[0]))


SCOPES = ("attention", "mlp", "head", "sampler")


def _fusion_scopes(hlo_text):
    """(scope or None, op_name) of every fusion in an optimized HLO text that
    carries an ``op_name``: the innermost of :data:`SCOPES` on its path."""
    import re
    out = []
    for line in hlo_text.splitlines():
        m = re.search(r' fusion\(.*op_name="([^"]*)"', line)
        if m:
            path = m.group(1).split("/")
            out.append((next((p for p in reversed(path) if p in SCOPES), None),
                        m.group(1)))
    return out


@pytest.mark.parametrize("arch,sampler", [("qwen3-4b", "topp_scan"),
                                          ("qwen3-4b", "greedy"),
                                          ("deepseek-moe-16b", "greedy")])
def test_decode_step_fusions_carry_named_scopes(arch, sampler):
    """The compiled decode tick names its layers through ``jax.named_scope``
    (``attention``, ``mlp``, ``head``, ``sampler``) in each fusion's
    ``op_name``, which is how a device trace's ops are joined to layers.
    Inside the layer loop every fusion is scoped but the loop's own slicing
    of the stacked weights and caches and its counter."""
    from repro.serving.scheduler import ContinuousEngine
    cfg = get_config(arch, smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, sampler=sampler, max_batch=2,
                           page_size=8, n_pages=9, max_len=24, tick_tokens=4)
    b = eng.max_batch
    text = eng._decode_n.lower(
        eng.params, eng.caches, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, 2), jnp.uint32),
        jnp.zeros((b,), bool), jnp.ones((b,), jnp.int32),
        jnp.full((b,), -1, jnp.int32), 4).compile().as_text()
    fusions = _fusion_scopes(text)
    assert {s for s, _ in fusions} >= set(SCOPES)
    layer_loop = "jit(_decode_n_impl)/while/body/while/body/"
    in_layers = [(s, n) for s, n in fusions if n.startswith(layer_loop)]
    loop_own = {"dynamic_slice", "dynamic_update_slice", "add"}
    stray = [n for s, n in in_layers
             if s is None and n[len(layer_loop):] not in loop_own]
    assert in_layers and not stray, stray
