"""The traffic generator: seeded, stratified, the issue's rates and lengths,
and a schedule that holds the batch near four fifths full."""
import collections

import numpy as np
import pytest

from chip import serve, traffic, weights


@pytest.mark.parametrize("mix", ["chat-topp", "chat-greedy"])
def test_parameters(mix):
    spec = traffic.load(mix)
    assert traffic.mean_of(spec["prompt_len"]) == pytest.approx(332.8)
    assert traffic.mean_of(spec["output_len"]) == pytest.approx(147.2)
    assert traffic.arrival_rate(spec) == pytest.approx(0.8 * 8 / 147.2)
    assert spec["engine"]["max_batch"] == 8 and spec["engine"]["tick_tokens"] == 16
    assert spec["engine"]["n_pages"] == 81 and spec["engine"]["max_len"] == 1280
    # the source's median output is 129 tokens; the budgets' median is 128
    o = spec["output_len"]
    cum = np.cumsum(o["weights"])
    assert o["values"][int(np.searchsorted(cum, cum[-1] / 2))] == 128
    assert "Azure" in spec["source"] and "eos" not in spec


def test_same_seed_same_stream():
    spec = traffic.load("chat-topp")
    a, b = traffic.generate(spec, 1000, 2**31 + 5), traffic.generate(spec, 1000, 2**31 + 5)
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert np.array_equal(x["key"], y["key"])
        assert (x["max_new_tokens"], x["arrival_step"]) == (y["max_new_tokens"], y["arrival_step"])
    c = traffic.generate(spec, 1000, 2**31 + 6)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    assert not np.array_equal(a[0]["key"], c[0]["key"])


def test_every_seed_gets_the_same_work():
    spec = traffic.load("chat-topp")
    first = traffic.generate(spec, 1000, 1)
    for seed in (1, 2**31 + 9):
        s = traffic.generate(spec, 1000, seed)
        assert [(r["tokens"].size, r["max_new_tokens"], r["arrival_step"]) for r in s] == \
            [(r["tokens"].size, r["max_new_tokens"], r["arrival_step"]) for r in first]
        for lo in range(0, 600, 10):
            blk = s[lo:lo + 10]
            assert collections.Counter(r["tokens"].size for r in blk) == \
                {128: 4, 256: 3, 512: 2, 1024: 1}
            assert collections.Counter(r["max_new_tokens"] for r in blk) == \
                {64: 3, 128: 4, 256: 3}
        assert all(r["arrival_step"] == 0 for r in s[:8])
        steps = s[-1]["arrival_step"]
        assert (len(s) - 8) / steps == pytest.approx(traffic.arrival_rate(spec), rel=0.01)


def test_warm_set_covers_every_draw():
    spec = traffic.load("chat-topp")
    page = spec["engine"]["page_size"]
    warm = {(p, traffic.cache_len(p, o, page)) for p, o in traffic.warm_requests(spec)}
    assert len(warm) == 8
    for r in traffic.generate(spec, 1000, 3):
        p = r["tokens"].size
        assert (p, traffic.cache_len(p, r["max_new_tokens"], page)) in warm
        assert p + r["max_new_tokens"] <= spec["engine"]["max_len"]


def test_schedule_holds_four_fifths_occupancy(qwen):
    """The mix's schedule through the real engine at a small model width:
    the decode-step clock makes it independent of the model's speed."""
    import jax
    from repro.models.model import build_model
    from repro.serving.scheduler import ContinuousEngine
    from chip import run as harness

    qwen["torch_dtype"] = "float32"
    qwen["serve_as"]["dtype"] = "float32"
    spec = traffic.load("chat-greedy")
    spec["n_requests"] = 120
    mc = harness.model_config(qwen)
    layout = jax.eval_shape(lambda: build_model(mc).init(jax.random.PRNGKey(0)))
    eng = ContinuousEngine(mc, weights.build(layout, 0), **spec["engine"])
    stream = traffic.generate(spec, qwen["vocab_size"], 11)
    win = serve.Window(eng, stream, seconds=1e9)
    res = eng.run(serve.to_requests(stream))
    assert len(res["streams"]) == 120
    steps = sum(t["n"] for t in win.ticks)
    occ = sum(t["tokens"] for t in win.ticks) / (steps * 8)
    assert 0.7 <= occ <= 0.95
    waits = [r["admit_step"] - r["arrival_step"] for r in
             (res["requests"][s["rid"]] for s in stream)]
    # the queue does not grow: the last third waits no longer than the first
    # third plus one request's service time
    third = len(waits) // 3
    assert np.mean(waits[-third:]) <= np.mean(waits[:third]) + 147.2
