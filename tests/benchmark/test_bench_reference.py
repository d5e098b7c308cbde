"""The plain references against ``ContinuousEngine`` at small sizes on the CPU:
prefill and decode logits through the paged cache, and the sampler's draws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import correct, weights
from chip.reference import topp
from chip.reference.decoder import Reference
from chip_cases import traffic


def _engine(cfg, sampler, seed):
    from repro.models.model import build_model
    from repro.serving.scheduler import ContinuousEngine
    from chip import run as harness
    mc = harness.model_config(cfg)
    layout = jax.eval_shape(lambda: build_model(mc).init(jax.random.PRNGKey(0)))
    eng_kw = dict(traffic(sampler)["engine"], max_batch=2)
    return ContinuousEngine(mc, weights.build(layout, seed), **eng_kw), eng_kw


def _served_logits(eng):
    """Wrap the engine's sampler so every call also hands its logits to the host."""
    seen = []
    orig = eng._sample_rows

    def record(logits, keys):
        jax.experimental.io_callback(lambda lg: seen.append(np.asarray(lg)), None,
                                     logits, ordered=True)
        return orig(logits, keys)

    eng._sample_rows = record
    return seen


def _fp32(cfg):
    cfg["torch_dtype"] = "float32"
    cfg["serve_as"]["dtype"] = "float32"
    return cfg


@pytest.mark.parametrize("which", ["qwen", "moe"])
def test_prefill_and_paged_decode_logits(which, qwen, moe):
    from repro.serving.scheduler import Request
    cfg = _fp32(qwen if which == "qwen" else moe)
    seed = 2**31 + 3
    eng, _ = _engine(cfg, "greedy", seed)
    seen = _served_logits(eng)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], 13, dtype=np.int32)
    res = eng.run([Request("a", prompt, 9, np.array([0, 1], np.uint32))])
    toks = res["streams"]["a"]
    # the prefill's row, then row 0 of each decode step
    got = np.stack([seen[0][0]] + [s[0] for s in seen[1:len(toks)]])
    seq = np.concatenate([prompt, toks[:-1]])[None]
    rows = (prompt.size - 1 + np.arange(toks.size))[None]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(Reference(cfg).logits(seed, seq, rows))[0]
    vocab = cfg["vocab_size"]
    np.testing.assert_allclose(got[:, :vocab], want, rtol=0, atol=2e-4 * np.abs(want).max())
    assert np.array_equal(toks, want.argmax(-1))


def test_topp_draws_match_the_reference_sampler(qwen):
    """The engine's top-p draws against the sort-based reference on the
    engine's own logits, with the uniforms replayed from the request key."""
    from repro.serving.scheduler import Request
    cfg = _fp32(qwen)
    eng, kw = _engine(cfg, "topp_scan", 5)
    seen = _served_logits(eng)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg["vocab_size"], 9, dtype=np.int32)
    key = np.array([7, 2**31 + 1], np.uint32)
    toks = eng.run([Request("a", prompt, 12, key)])["streams"]["a"]
    logits = jnp.asarray(np.stack([seen[0][0]] + [s[0] for s in seen[1:len(toks)]]))
    u = correct._uniforms(jnp.asarray(key), jnp.zeros(toks.size - 1))
    want = topp.sample(logits, u, p=kw["top_p"], temperature=kw["temperature"])
    gap = topp.logit_gap(logits, jnp.asarray(toks), want)
    # the program sorts on bf16-rounded probabilities, so it may draw a
    # neighbour of nearly equal probability: a 2**-7 band in logit
    assert float(gap.max()) <= 2.0 ** -7


def test_topp_reference_semantics():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    # kept: preceding mass <= 0.6 -> tokens 0 and 1 (0.8 precedes token 2);
    # the draw point is u times the kept mass 0.8
    for u, tok in ((0.0, 0), (0.6, 0), (0.7, 1), (0.99, 1)):
        assert int(topp.sample(logits, jnp.asarray([u]), p=0.6, temperature=1.0)[0]) == tok


def test_weights_per_layer_match_the_stacked_build(qwen):
    from repro.models.model import build_model
    from chip import run as harness
    mc = harness.model_config(qwen)
    layout = jax.eval_shape(lambda: build_model(mc).init(jax.random.PRNGKey(0),
                                                         dtype=jnp.bfloat16))
    params = weights.build(layout, 2**33 + 1)
    key = weights.seed_key(2**33 + 1)
    w = params["stack"]["sub0"]["mlp"]["w_up"]
    for layer in range(w.shape[0]):
        one = weights.leaf(key, "stack/sub0/mlp/w_up", layer, w.shape[1:])
        assert np.array_equal(np.asarray(w[layer], np.float32), np.asarray(one))
    vals = np.asarray(w, np.float32) / weights.SCALE
    assert vals.min() >= -62 and vals.max() <= 62 and np.all(vals == np.round(vals))
    # zero mean: a biased generator collapses every position onto one output
    assert abs(vals.mean()) < 4 * 18.5 / vals.size ** 0.5 and 17 < vals.std() < 20


# correct.readings of a fixed sample (with the fp8 control) as the harness
# read them before the reference came from the configuration's own module
READINGS = {
    ("QWEN", "greedy"): (1.2611780166625977, 1.0, 0.0, 0.0),
    ("QWEN", "topp_scan"): (0.46996042132377625, 1.0, 0.031070958822965622,
                            0.9310344827586207),
    ("MOE", "greedy"): (1.1207995414733887, 1.0, 0.0, 0.0),
    ("MOE", "topp_scan"): (0.44767871499061584, 1.0, 0.019267559051513672,
                           0.8620689655172413),
}


@pytest.mark.parametrize("case, sampler", sorted(READINGS))
def test_readings_pinned(case, sampler):
    import chip_cases
    rng = np.random.default_rng(7)
    stream, served = [], {}
    for i, (p, n) in enumerate(((8, 4), (13, 6), (5, 3), (24, 16))):
        stream.append({"tokens": rng.integers(0, 500, p, dtype=np.int32),
                       "key": np.array([i, 2**31 + i], np.uint32),
                       "max_new_tokens": n})
        served[i] = [int(x) for x in rng.integers(0, 500, n)]
    got = correct.readings(getattr(chip_cases, case), traffic(sampler)["engine"],
                           stream, served, [0, 1, 2, 3], 2**31 + 5, control=True)
    assert (got["logit_gap"], got["mismatch_share"], got["logit_gap_control"],
            got["mismatch_share_control"]) == READINGS[case, sampler]
