"""Compile the main-path Pallas kernels for a TPU v5e chip, with no chip.

Each kernel is compiled with ``interpret=False`` at a real width against a
described ``v5e:2x2`` topology, so Mosaic (the TPU kernel compiler) lowers it
exactly as it would on the chip, and the compiled HLO must hold the kernel as
a ``tpu_custom_call``.  The topology is described inside a module fixture,
never at import time.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

VOCAB = 151936          # qwen3-4b's vocabulary: the sampler's row width
LOGITS_WIDTH = 152064   # qwen3-4b's padded vocabulary: the served logits' width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


CASES = {
    "scan_tiles_s128": (
        lambda x: ops.scan_kernel(x, s=128, interpret=False),
        [((1 << 20,), jnp.float32)]),
    "blocked_scan_2p24": (
        lambda x: ops.blocked_scan_kernel(x, s=128, interpret=False),
        [((1 << 24,), jnp.float32)]),
    "radix_sort_enc_vocab": (
        lambda e: ops.radix_sort_enc_kernel(e, bits=16, bits_per_pass=4,
                                            interpret=False),
        [((8, VOCAB), jnp.uint32)]),
    "topp_mask_sample_vocab": (
        lambda p, u: ops.topp_mask_sample_kernel(p, u, p=0.9,
                                                 interpret=False),
        [((8, VOCAB), jnp.float32), ((8, 1), jnp.float32)]),
    "linrec_kernel": (
        lambda a, b: ops.linrec_kernel(a, b, s=128, interpret=False),
        [((4, 1 << 18), jnp.float32), ((4, 1 << 18), jnp.float32)]),
    "split_kernel_vocab": (
        lambda x, f: ops.split_kernel(x, f, s=128, interpret=False),
        [((8, VOCAB), jnp.float32), ((8, VOCAB), jnp.int8)]),
    "seg_blocked_scan_kernel": (
        lambda x, f: ops.seg_blocked_scan_kernel(x, f, s=128,
                                                 interpret=False),
        [((1 << 20,), jnp.int32), ((1 << 20,), jnp.int8)]),
    "ssd_kernel": (
        lambda x, a, b, c: ops.ssd_kernel(x, a, b, c, chunk=128,
                                          interpret=False),
        [((1, 1024, 4, 64), jnp.float32), ((1, 1024, 4), jnp.float32),
         ((1, 1024, 4, 64), jnp.float32), ((1, 1024, 4, 64), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


@pytest.mark.parametrize("width", [9728, VOCAB])
def test_one_row_projection_stays_a_matmul_on_v5e(width, one_chip):
    """In a decode step a one-row projection compiles to the same 8-row MXU
    convolution as a batch of rows (``layers.fenced_rows``): unfenced, the
    TPU compiler turns it into a VPU multiply-reduce, or slices the padding
    off again, and a decode row alone would not match the same row in a
    batch."""
    from repro.models import layers

    def project(e, v):
        with layers.fenced_rows():
            return layers.unembed({"embed": e}, v)

    w = jax.ShapeDtypeStruct((width, 2560), jnp.bfloat16, sharding=one_chip)
    x = jax.ShapeDtypeStruct((1, 1, 2560), jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit(project).lower(w, x).compile().as_text()
    assert f"f32[8,{width}]" in hlo and " convolution(" in hlo
    assert " reduce(" not in hlo


def test_sampler_moves_each_radix_pass_once_on_v5e(one_chip):
    """The serving sampler at the chat-topp settings, compiled for the chip:
    each radix pass moves one packed payload with one sort of each row by
    its destinations and reads its bucket offsets without a gather, so the
    whole sampler holds one data movement per pass plus the two gathers of
    the sampling tail (20 such ops, among them 3 sorts of the flattened
    batch, when every pass moved keys and permutation apart and gathered
    its offsets)."""
    from repro.serving.engine import sample_padded_rows

    bits_per_pass = 4
    passes = 16 // bits_per_pass              # bf16-rounded keys: 16 bits

    def sample(logits, u):
        return sample_padded_rows(logits, u, p=0.9, temperature=1.0,
                                  method="matmul", sort_method="radix",
                                  bits_per_pass=bits_per_pass)

    logits = jax.ShapeDtypeStruct((8, LOGITS_WIDTH), jnp.float32,
                                  sharding=one_chip)
    u = jax.ShapeDtypeStruct((8, 1), jnp.float32, sharding=one_chip)
    hlo = jax.jit(sample).lower(logits, u).compile().as_text()
    moves = re.findall(r"\s(gather|scatter|sort)\(", hlo)
    assert 0 < len(moves) <= passes + 2, moves
    assert moves.count("sort") == passes and "scatter" not in moves, moves
    sorts = [ln for ln in hlo.splitlines() if " sort(" in ln]
    assert all(f"u32[8,{LOGITS_WIDTH}]" in ln for ln in sorts), sorts
