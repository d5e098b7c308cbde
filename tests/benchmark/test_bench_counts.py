"""Counts from shapes, checked against values worked out by hand."""
import json
from pathlib import Path

import pytest

from chip import counts

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / "configs"


def _cfg(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


def test_qwen3_4b_weights():
    c = _cfg("qwen3-4b")
    # per layer: q,o 2560x4096 twice, k,v 2560x1024 twice, MLP 3x2560x9728,
    # two 2560 norms and two 128 q/k norms; 36 layers, a tied 151936x2560
    # table and the final norm
    layer = 2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728 + 2 * 2560 + 2 * 128
    assert layer == 100_930_816
    assert counts.param_count(c) == 36 * layer + 151936 * 2560 + 2560 == 4_022_468_096
    assert counts.weight_bytes(c) == 8_044_936_192


def test_deepseek_moe_9_layers_weights():
    c = _cfg("deepseek-moe-16b")
    dense = 4 * 2048 * 2048 + 3 * 2048 * 10944 + 2 * 2048
    moe = 4 * 2048 * 2048 + 2048 * 64 + (64 + 2) * 3 * 2048 * 1408 + 2 * 2048
    assert (dense, moe) == (84_021_248, 587_862_016)
    total = dense + 8 * moe + 102400 * 2048 + 2048
    assert counts.param_count(c) == total == 4_996_634_624
    assert counts.weight_bytes(c) == pytest.approx(10.0e9, rel=1e-3)


def test_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(_cfg("qwen3-4b")) == 2 * 8 * 128 * 36 * 2 == 147_456
    assert counts.kv_bytes_per_token(_cfg("deepseek-moe-16b")) == 2 * 16 * 128 * 9 * 2 == 73_728


def test_moe_active_flops():
    c = _cfg("deepseek-moe-16b")
    # a token runs through the dense layer, then per MoE layer attention,
    # the router, 6 routed and 2 shared experts; then the tied head
    dense = 4 * 2048 * 2048 + 3 * 2048 * 10944
    moe = 4 * 2048 * 2048 + 2048 * 64 + (6 + 2) * 3 * 2048 * 1408
    active = dense + 8 * moe + 102400 * 2048
    assert counts.active_matmul_params(c) == active == 982_646_784
    # one decode token at context 100: 2 flops per active parameter, plus
    # scores and values over 100 positions, 16 heads of 128, 9 layers
    assert counts.decode_flops(c, [100]) == 2 * active + 4 * 16 * 128 * 100 * 9


def test_prefill_flops_are_causal():
    c = _cfg("qwen3-4b")
    a = counts.attn_flops(c, 1)
    assert counts.prefill_flops(c, 4) == 2.0 * counts.active_matmul_params(c) * 4 + a * 10


def test_sampler_bytes_bound():
    # 8 rows of 152064 fp32 logits (the table's padded width) read once
    assert counts.sampler_bytes(8, 152064) == 4_866_048
    assert counts.sampler_bytes(8, 152064) / counts.peaks("TPU v5 lite")["hbm_bw"] \
        == pytest.approx(5.94e-6, rel=1e-3)


def test_peaks_table_refuses_unknown_devices():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        counts.peaks("cpu")


# Every public count of the two committed configurations, as the harness
# computed them before the architecture moved into the reference module.
PINNED = {
    "qwen3-4b": {
        "param_count": 4_022_468_096, "weight_bytes": 8_044_936_192,
        "kv_bytes_per_token": 147_456, "state_bytes_per_row": 0,
        "active_matmul_params": 4_022_272_000, "embed_params": 388_956_160,
        "head_dim": 128,
        "attn_flops": {1: 589_824, 100: 58_982_400, 1280: 754_974_720},
        "prefill_flops": {1: 8045133824.0, 128: 1034571218944.0,
                          1024: 8547152691200.0},
        "decode_flops": {(1,): 8045133824.0, (100, 1280, 5): 24950538240.0},
        "decode_step_bytes": {(1,): 8_045_083_648, (100, 1280, 5): 8_249_162_752},
    },
    "deepseek-moe-16b": {
        "param_count": 4_996_634_624, "weight_bytes": 9_993_269_248,
        "kv_bytes_per_token": 73_728, "state_bytes_per_row": 0,
        "active_matmul_params": 982_646_784, "embed_params": 209_715_200,
        "head_dim": 128,
        "attn_flops": {1: 73_728, 100: 7_372_800, 1280: 94_371_840},
        "prefill_flops": {1: 1965367296.0, 128: 252166275072.0,
                          1024: 2051153068032.0},
        "decode_flops": {(1,): 1965367296.0, (100, 1280, 5): 5997993984.0},
        "decode_step_bytes": {(1,): 9_993_342_976, (100, 1280, 5): 10_095_382_528},
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_pinned(name):
    c, want = _cfg(name), PINNED[name]
    for fn, value in want.items():
        if isinstance(value, dict):
            got = {arg: getattr(counts, fn)(c, list(arg) if isinstance(arg, tuple)
                                            else arg) for arg in value}
        else:
            got = getattr(counts, fn)(c)
        assert got == value, fn


def test_counts_follow_the_named_module(qwen, monkeypatch):
    """Each count comes from the module the file names: one more parameter
    per layer there moves the parameter count, a recurrent state the bytes
    of a decode step."""
    from chip.reference import decoder
    base = decoder.layer_params(qwen, 0, active=False)
    layer_params = decoder.layer_params
    monkeypatch.setattr(decoder, "layer_params", lambda cfg, layer, *, active:
                        layer_params(cfg, layer, active=active) + 1)
    monkeypatch.setattr(decoder, "state_bytes_per_row", lambda cfg: 10)
    assert counts.param_count(qwen) - counts.embed_params(qwen) \
        == 2 * (base + 1) + decoder.norm_params(qwen)
    assert counts.state_bytes_per_row(qwen) == 10
    assert counts.decode_step_bytes(qwen, [3, 4]) == (
        counts.weight_bytes(qwen) + counts.kv_bytes_per_token(qwen) * 7 + 2 * 10 * 2)


def test_reference_must_be_a_module_name(qwen):
    qwen["reference"] = "../decoder"
    with pytest.raises(ValueError, match="no module name"):
        counts.param_count(qwen)
