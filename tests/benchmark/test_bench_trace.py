"""The trace reduction against a small trace recorded on a TPU v5e
(``record_trace.py``): ``jit_probe_step`` inside ``admit/prefill``, a 50 ms
host-only pause, then ``jit_probe_loop`` (a ``while`` of four matmuls) inside
``tick/decode_n``, all inside ``bench/window``."""
from pathlib import Path

import pytest

from chip import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return trace.reduce_trace(ProfileData.from_file(str(DATA)))


def test_window_and_programs(red):
    assert red["window_s"] == pytest.approx(0.052862891)
    d = red["devices"]
    assert len(d) == 1
    progs = d[0]["programs"]
    assert set(progs) == {"jit_probe_step", "jit_probe_loop"}
    assert progs["jit_probe_loop"] == [pytest.approx(61.276e-6)]
    assert progs["jit_probe_step"] == [pytest.approx(17.682e-6)]
    # the chip's clock in this trace runs about a millisecond ahead of the
    # host's, so the program dispatched first in the window reads as before it
    assert trace.program_seconds(red, "jit_probe_loop") == pytest.approx(61.276e-6)
    assert trace.program_seconds(red, "jit_probe_step") == 0
    assert trace.durations(red, "jit_probe_loop") == [pytest.approx(61.276e-6)]


def test_busy_is_the_union_of_leaf_ops(red):
    # the while op spans its body's ops: busy counts that time once
    assert trace.busy_seconds(red) == pytest.approx(61.265e-6)
    ops = red["devices"][0]["op_seconds"]
    assert "jit_probe_loop/while" not in ops
    assert ops["jit_probe_loop/convolution_tanh_fusion.2"] == pytest.approx(4 * 11.0635e-6,
                                                                            rel=1e-3)
    assert sum(ops.values()) <= trace.busy_seconds(red) * (1 + 1e-9)


def test_idle_gaps_are_named_by_the_host_span(red):
    b = trace.breakdown(red)
    name, secs = b["idle_gaps"][0]
    assert name == "tick/host" and secs == pytest.approx(0.0506, abs=5e-4)
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        red["window_s"] - trace.busy_seconds(red))
    assert b["device_ops"][0][0] == "jit_probe_loop/convolution_tanh_fusion.2"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_names():
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x)") == "fusion.3"
    assert trace.program_name("jit__decode_n_impl(123)") == "jit__decode_n_impl"
