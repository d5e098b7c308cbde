"""The check that decides ``correct``, driven through a whole run on the CPU
at a small size (the harness's look for a chip skipped): a sound run passes,
the fp8 control in the program's place fails, and a token altered where the
engine produces it fails."""
import copy
from pathlib import Path

import jax
import pytest

import chip_cases
from chip import correct, serve
from chip import run as harness

PEAKS = {"bf16_flops": 1e12, "hbm_bw": 1e11}
# the small cells' limits, set between the program's and the control's
# readings at this size on the CPU, 61 requests in the window (greedy:
# program 0, control 0.026 to 0.065; top-p: program 0.006 to 0.009, control
# 0.041 to 0.053)
LIMIT = {"greedy": 0.01, "topp_scan": 0.02}


class _Clock:
    """A clock that moves 10 ms at every reading: the window then holds the
    same ticks, and the check reads the same requests, however busy the CPU."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 0.01
        return self.t


def _cell(sampler, monkeypatch):
    monkeypatch.setattr(serve, "time", _Clock())
    cfg = copy.deepcopy(chip_cases.QWEN)
    cfg.update(hidden_size=128, intermediate_size=256, vocab_size=2048,
               num_hidden_layers=4)
    cfg["serve_as"].update(d_model=128, d_ff=256, vocab_size=2048, n_layers=4)
    if sampler == "greedy":
        # fp8 flips a greedy choice only now and then at this width: read
        # every finished request, not a sample of four
        monkeypatch.setattr(correct, "SAMPLE", 1000)
    return cfg, chip_cases.traffic(sampler)


def _run(sampler, seed, monkeypatch, **kw):
    cfg, spec = _cell(sampler, monkeypatch)
    return harness.run_cell({"chips": 1}, cfg, spec, [], [], seed=seed,
                            seconds=2.0, traced=False, devices=jax.devices(),
                            limits={"logit_gap": LIMIT[sampler]}, peak_row=PEAKS, **kw)


@pytest.mark.parametrize("sampler", ["greedy", "topp_scan"])
@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_program_passes_and_control_fails(sampler, seed, monkeypatch):
    res = _run(sampler, seed, monkeypatch, control=True)
    got = res["readings"]
    program, control = got["logit_gap"], got["logit_gap_control"]
    assert program <= LIMIT[sampler] < control, (program, control)
    if sampler == "greedy":
        # top-p draws on a small vocabulary tie in bf16 keys all the time, so
        # the share of differing draws says nothing there
        assert got["mismatch_share"] < got["mismatch_share_control"]
    assert res["correct"] is False


@pytest.mark.parametrize("sampler", ["greedy", "topp_scan"])
def test_altered_token_fails(sampler, monkeypatch):
    def alter(engine):
        orig = engine._sample_rows
        engine._sample_rows = lambda logits, keys: (orig(logits, keys) + 1) % 2048

    assert _run(sampler, 21, monkeypatch)["correct"] is True
    res = _run(sampler, 21, monkeypatch, engine_hook=alter)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > LIMIT[sampler]


def test_listed_metric_with_nothing_to_read_fails(monkeypatch):
    """A traced run ends without a result where a per-layer metric that the
    cell lists finds nothing to read: here the window's trace is the small
    recorded v5e trace, which holds no ``_prefill_impl`` program."""
    from jax.profiler import ProfileData
    from chip import trace
    small = trace.reduce_trace(ProfileData.from_file(
        str(Path(__file__).resolve().parent / "data" / "small.xplane.pb")))
    monkeypatch.setattr(trace, "load", lambda log_dir: small)
    cfg, spec = _cell("topp_scan", monkeypatch)
    metric = {"name": "prefill_ms_per_ktok", "unit": "ms"}
    with pytest.raises(RuntimeError, match="prefill_ms_per_ktok found nothing"):
        harness.run_cell({"chips": 1, "name": "tiny"}, cfg, spec, [], [metric],
                         seed=31, seconds=1.0, traced=True, devices=jax.devices(),
                         limits={"logit_gap": LIMIT["topp_scan"]}, peak_row=PEAKS)
