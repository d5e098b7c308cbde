"""Segmented & ragged scan subsystem (ISSUE 4 acceptance contract).

Every segmented op must be bit-identical to looping the existing 1-D op over
each segment slice, for all registered methods × {fp32, bf16, int8} × ragged
segment layouts (including empty and length-1 segments); `moe_apply`'s
segmented dispatch and `ServeEngine(sampler="topp_segmented")` must produce
outputs identical to their existing paths on equivalent inputs.

Float caveat (architecture.md dispatch rule 2/6): integer paths — offsets,
permutations, counts, sampled indices — are exact unconditionally; float
*sums* are bit-identical when exactly representable (the payloads used here),
and the sampler comparisons are pinned at scales where no fp32 rounding flip
occurs (at large batch×vocab a flat packed scan can round differently from
per-row scans near a threshold).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # property tests skip (not error) in minimal environments
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import compress, radix_sort, scan, top_p_sample, topk
from repro.core.segmented import (
    SegmentedBatch, boundary_flags, segment_compress, segment_cumsum,
    segment_ids, segment_scan, segment_sort, segment_sums, segment_topk,
    segment_top_p_sample,
)

S = 8                        # tile side (small: interpret speed)
BT = 2                       # block_tiles for method="blocked"
METHODS_ALL = ["vector", "matmul", "kernel", "blocked"]
KW = dict(tile_s=S, block_tiles=BT)

# ragged layouts: empty segments (incl. leading/trailing/consecutive),
# length-1 segments, a segment crossing tile and block boundaries
LAYOUTS = {
    "ragged": np.asarray([0, 0, 3, 4, 4, 4, 19, 20, 33], np.int32),
    "single": np.asarray([0, 13], np.int32),
    "unit_segs": np.asarray([0, 1, 2, 3, 4], np.int32),
}

_PAYLOADS = {
    # integer-valued floats: sums exactly representable => bit-parity holds
    "float32": lambda rng, n: jnp.asarray(rng.integers(-4, 5, n), jnp.float32),
    "bfloat16": lambda rng, n: jnp.asarray(rng.integers(-4, 5, n), jnp.bfloat16),
    "int8": lambda rng, n: jnp.asarray(rng.integers(-4, 5, n), jnp.int8),
}


def _loop_segments(offsets):
    off = np.asarray(offsets)
    return [(off[i], off[i + 1]) for i in range(off.shape[0] - 1)
            if off[i + 1] > off[i]]


def _loop_scan(x, offsets, **kw):
    """Oracle: the existing 1-D scan looped over every nonempty segment."""
    outs = [np.asarray(scan(x[a:b], **kw)) for a, b in _loop_segments(offsets)]
    return np.concatenate(outs) if outs else np.zeros((0,))


# ---------------------------------------------------------------------------
# segment_scan: the acceptance parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS_ALL)
@pytest.mark.parametrize("dtype", list(_PAYLOADS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_segment_scan_parity(method, dtype, layout):
    offsets = LAYOUTS[layout]
    n = int(offsets[-1])
    x = _PAYLOADS[dtype](np.random.default_rng(n), n)
    for exclusive in (False, True):
        got = segment_scan(x, jnp.asarray(offsets), method=method,
                           exclusive=exclusive, **KW)
        want = _loop_scan(x, offsets, method=method, exclusive=exclusive, **KW)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert got.dtype == jnp.asarray(want).dtype


@pytest.mark.parametrize("method", ["vector", "kernel", "blocked"])
def test_segment_scan_reverse(method):
    offsets = LAYOUTS["ragged"]
    x = jnp.asarray(np.random.default_rng(5).integers(-3, 4, 33), jnp.int32)
    got = segment_scan(x, jnp.asarray(offsets), method=method, reverse=True,
                       **KW)
    want = _loop_scan(x, offsets, method=method, reverse=True, **KW)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("method", METHODS_ALL)
def test_segment_scan_batched_leading_dims(method):
    """(B, n) payloads share the offsets — the MoE one-hot layout."""
    offsets = LAYOUTS["ragged"]
    xb = jnp.asarray(np.random.default_rng(6).integers(0, 2, (5, 33)), jnp.int8)
    got = np.asarray(segment_scan(xb, jnp.asarray(offsets), method=method,
                                  exclusive=True, **KW))
    for r in range(xb.shape[0]):
        want = _loop_scan(xb[r], offsets, method=method, exclusive=True, **KW)
        np.testing.assert_array_equal(got[r], want)


def test_segment_scan_long_input_crosses_blocks():
    """n >> block_len: the segmented phase-2 carry scan actually engages."""
    rng = np.random.default_rng(7)
    n = 4 * BT * S * S + 11                   # several blocks + ragged tail
    cuts = np.sort(rng.integers(0, n + 1, 6))
    offsets = np.concatenate([[0], cuts, [n]]).astype(np.int32)
    x = jnp.asarray(rng.integers(-3, 4, n), jnp.int32)
    want = _loop_scan(x, offsets, method="vector")
    for method in ("kernel", "blocked"):
        got = segment_scan(x, jnp.asarray(offsets), method=method, **KW)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_all_empty_batch_every_op():
    """n == 0 with num_segments > 0: every op returns its documented zeros."""
    sb = SegmentedBatch.from_ragged([[], []])
    assert segment_scan(sb.values, sb.offsets).shape == (0,)
    np.testing.assert_array_equal(
        np.asarray(segment_sums(sb.values, sb.offsets)), [0, 0])
    z, c = segment_compress(sb.values, jnp.zeros((0,), bool), sb.offsets)
    assert z.shape == (0,) and np.asarray(c).tolist() == [0, 0]
    v, i = segment_sort(sb.values, sb.offsets)
    assert v.shape == (0,) and i.shape == (0,)
    tv, ti, tc = segment_topk(sb.values, sb.offsets, k=2, fill_value=-1)
    assert tv.shape == (2, 2) and np.all(np.asarray(ti) == -1)
    assert np.asarray(tc).tolist() == [0, 0]
    tok = segment_top_p_sample(sb.values.astype(jnp.float32), sb.offsets,
                               jax.random.PRNGKey(0))
    assert np.asarray(tok).tolist() == [0, 0]


def test_segment_scan_validation_and_empty():
    x = jnp.arange(4, dtype=jnp.int32)
    with pytest.raises(ValueError):
        segment_scan(x, jnp.asarray([0, 4]), method="cube")
    with pytest.raises(ValueError):
        segment_scan(x)                        # offsets required
    out = segment_scan(jnp.zeros((0,), jnp.int8), jnp.asarray([0, 0, 0]))
    assert out.shape == (0,) and out.dtype == jnp.int32


# ---------------------------------------------------------------------------
# container + boundary structure
# ---------------------------------------------------------------------------


def test_segmented_batch_roundtrip_and_pytree():
    segs = [[1, 2, 3], [], [4], [], [5, 6]]
    sb = SegmentedBatch.from_ragged(segs)
    assert sb.num_segments == 5
    assert sb.lengths.tolist() == [3, 0, 1, 0, 2]
    assert [s.tolist() for s in sb.to_ragged()] == segs
    # pytree: survives jit boundaries
    out = jax.jit(lambda b: SegmentedBatch(b.values * 2, b.offsets))(sb)
    assert isinstance(out, SegmentedBatch)
    assert np.asarray(out.values).tolist() == [2, 4, 6, 8, 10, 12]
    dense, mask = sb.to_dense(fill_value=-1)
    assert dense.shape == (5, 3)
    np.testing.assert_array_equal(dense[0], [1, 2, 3])
    np.testing.assert_array_equal(mask.sum(axis=1), [3, 0, 1, 0, 2])


def test_boundary_flags_and_segment_ids():
    offsets = jnp.asarray([0, 0, 3, 4, 4, 6], jnp.int32)
    np.testing.assert_array_equal(np.asarray(boundary_flags(offsets, 6)),
                                  [1, 0, 0, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(segment_ids(offsets, 6)),
                                  [1, 1, 1, 2, 4, 4])
    # ids respect every method of the counting scan
    np.testing.assert_array_equal(
        np.asarray(segment_ids(offsets, 6, method="matmul", tile_s=S)),
        [1, 1, 1, 2, 4, 4])


@pytest.mark.parametrize("method", METHODS_ALL)
def test_segment_sums(method):
    offsets = LAYOUTS["ragged"]
    x = jnp.asarray(np.random.default_rng(8).integers(0, 3, 33), jnp.int8)
    got = np.asarray(segment_sums(x, jnp.asarray(offsets), method=method, **KW))
    want = [int(np.asarray(x)[a:b].astype(np.int64).sum())
            for a, b in zip(offsets[:-1], offsets[1:])]
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32


# ---------------------------------------------------------------------------
# segment_compress
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS_ALL)
@pytest.mark.parametrize("dtype", list(_PAYLOADS))
def test_segment_compress_parity(method, dtype):
    offsets = LAYOUTS["ragged"]
    rng = np.random.default_rng(9)
    x = _PAYLOADS[dtype](rng, 33)
    m = jnp.asarray(rng.random(33) < 0.4)
    z, c = segment_compress(x, m, jnp.asarray(offsets), method=method, **KW)
    want_z, want_c = [], []
    for i in range(offsets.shape[0] - 1):
        a, b = offsets[i], offsets[i + 1]
        if b > a:
            zi, ci = compress(x[a:b], m[a:b], method=method, tile_s=S)
            want_z.append(np.asarray(zi))
            want_c.append(int(ci))
        else:
            want_c.append(0)
    np.testing.assert_array_equal(np.asarray(z), np.concatenate(want_z))
    np.testing.assert_array_equal(np.asarray(c), want_c)


def test_segment_compress_edge_masks():
    offsets = jnp.asarray([0, 2, 5], jnp.int32)
    x = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
    z, c = segment_compress(x, jnp.zeros(5, bool), offsets, fill_value=-7)
    np.testing.assert_array_equal(np.asarray(z), [-7] * 5)
    assert np.asarray(c).tolist() == [0, 0]
    z, c = segment_compress(x, jnp.ones(5, bool), offsets)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x))
    assert np.asarray(c).tolist() == [2, 3]


# ---------------------------------------------------------------------------
# segment_sort / segment_topk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS_ALL)
@pytest.mark.parametrize("k", [1, 4, 8])
def test_segment_sort_parity_fp32(method, k):
    """Arbitrary (non-integer) keys: offsets are exact, so parity is exact."""
    offsets = LAYOUTS["ragged"]
    x = jnp.asarray(np.random.default_rng(10).standard_normal(33), jnp.float32)
    v, i = segment_sort(x, jnp.asarray(offsets), method=method,
                        bits_per_pass=k, **KW)
    want_v, want_i = [], []
    for a, b in _loop_segments(offsets):
        vv, ii = radix_sort(x[a:b], method=method, bits_per_pass=k, tile_s=S)
        want_v.append(np.asarray(vv))
        want_i.append(np.asarray(ii) + a)
    np.testing.assert_array_equal(np.asarray(v), np.concatenate(want_v))
    np.testing.assert_array_equal(np.asarray(i), np.concatenate(want_i))


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("method", METHODS_ALL)
def test_segment_sort_dtypes_descending(dtype, method):
    offsets = LAYOUTS["ragged"]
    rng = np.random.default_rng(11)
    x = (jnp.asarray(rng.integers(-128, 128, 33), jnp.int8) if dtype == "int8"
         else jnp.asarray(rng.standard_normal(33), jnp.bfloat16))
    v, i = segment_sort(x, jnp.asarray(offsets), descending=True,
                        method=method, **KW)
    xs = np.asarray(x.astype(jnp.float32))
    for a, b in _loop_segments(offsets):
        seg = np.asarray(v.astype(jnp.float32))[a:b]
        np.testing.assert_array_equal(seg, np.sort(xs[a:b], kind="stable")[::-1])
    np.testing.assert_array_equal(xs[np.asarray(i)],
                                  np.asarray(v.astype(jnp.float32)))
    with pytest.raises(ValueError):
        segment_sort(x, jnp.asarray(offsets), bits_per_pass=0)


@pytest.mark.parametrize("method", ["vector", "kernel"])
def test_segment_topk_parity(method):
    offsets = LAYOUTS["ragged"]
    x = jnp.asarray(np.random.default_rng(12).standard_normal(33), jnp.float32)
    k = 3
    v, i, c = segment_topk(x, jnp.asarray(offsets), k=k, method=method, **KW)
    assert v.shape == (8, k) and i.shape == (8, k) and c.shape == (8,)
    for s_, (a, b) in enumerate(zip(offsets[:-1], offsets[1:])):
        kk = min(k, b - a)
        assert int(c[s_]) == kk
        if kk:
            tv, ti = topk(x[a:b], kk, method=method, tile_s=S)
            np.testing.assert_array_equal(np.asarray(v)[s_, :kk], np.asarray(tv))
            np.testing.assert_array_equal(np.asarray(i)[s_, :kk], np.asarray(ti))
        assert np.all(np.asarray(i)[s_, kk:] == -1)


# ---------------------------------------------------------------------------
# segment_top_p_sample: ragged nucleus sampling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["vector", "matmul", "blocked"])
def test_segment_top_p_parity_vs_loop(method):
    """Same per-segment uniforms => same sampled (segment-local) token ids."""
    offsets = np.asarray([0, 3, 4, 23, 33], np.int32)
    logits = jnp.asarray(
        np.random.default_rng(13).standard_normal(33) * 2, jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(3), (4, 1), dtype=jnp.float32)
    got = segment_top_p_sample(logits, jnp.asarray(offsets), None, p=0.9,
                               method=method, u=u, **KW)
    want = [int(top_p_sample(logits[a:b], None, p=0.9, method=method, u=u[s_],
                             tile_s=S))
            for s_, (a, b) in enumerate(zip(offsets[:-1], offsets[1:]))]
    assert np.asarray(got).tolist() == want


def test_segment_top_p_empty_segment_and_batch_input():
    sb = SegmentedBatch.from_ragged(
        [np.asarray([0.0, 9.0]), np.asarray([], np.float32),
         np.asarray([9.0, 0.0, 0.0])])
    tok = segment_top_p_sample(sb, key=jax.random.PRNGKey(0), p=0.9, tile_s=S)
    assert np.asarray(tok).tolist() == [1, 0, 0]


# ---------------------------------------------------------------------------
# wiring: serving engine + MoE dispatch + data pipeline
# ---------------------------------------------------------------------------


def test_serving_topp_segmented_matches_topp_scan():
    from repro.models.model import get_config
    from repro.serving.engine import ServeEngine

    cfg = get_config("llama3-8b", smoke=True)
    for seed in range(3):
        logits = jnp.asarray(
            np.random.default_rng(seed).standard_normal((3, cfg.vocab_size)) * 3,
            jnp.float32)
        key = jax.random.PRNGKey(seed)
        ref = ServeEngine(cfg, None, sampler="topp_scan")._sample(logits, key)
        got = ServeEngine(cfg, None, sampler="topp_segmented")._sample(logits,
                                                                       key)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_serving_sample_packed_ragged():
    from repro.models.model import get_config
    from repro.serving.engine import ServeEngine

    cfg = get_config("llama3-8b", smoke=True)
    eng = ServeEngine(cfg, None, sampler="topp_segmented")
    rng = np.random.default_rng(14)
    segs = [rng.standard_normal(40).astype(np.float32),
            rng.standard_normal(7).astype(np.float32),
            np.asarray([0.0, 50.0, 0.0], np.float32)]
    tok = eng.sample_packed(SegmentedBatch.from_ragged(segs),
                            jax.random.PRNGKey(0))
    assert tok.shape == (3,) and tok.dtype == jnp.int32
    assert all(0 <= int(t) < len(s) for t, s in zip(tok, segs))
    assert int(tok[2]) == 1                     # all mass on one token


def test_moe_segmented_dispatch_matches_grouped():
    from repro.models.model import get_config
    from repro.models.moe import moe_apply, moe_init

    cfg = get_config("deepseek-moe-16b", smoke=True)
    params = moe_init(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(
        np.random.default_rng(15).standard_normal((2, 8, cfg.d_model)),
        jnp.float32)
    y_seg, aux_seg = moe_apply(params, x, cfg, dispatch_mode="segmented")
    y_grp, aux_grp = moe_apply(params, x, cfg, dispatch_mode="grouped")
    y_auto, _ = moe_apply(params, x, cfg)
    np.testing.assert_array_equal(np.asarray(y_seg), np.asarray(y_grp))
    np.testing.assert_array_equal(np.asarray(y_seg), np.asarray(y_auto))
    assert float(aux_seg) == float(aux_grp)


def test_packed_synthetic_lm():
    from repro.data.pipeline import PackedSyntheticLM, pack_ragged

    src = PackedSyntheticLM(vocab_size=64, tokens_per_batch=96, num_docs=7,
                            seed=3)
    b1, b2 = src.batch_at(5), src.batch_at(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])   # deterministic
    np.testing.assert_array_equal(b1["offsets"], b2["offsets"])
    assert b1["tokens"].shape == (96,) and b1["offsets"].shape == (8,)
    off = b1["offsets"]
    assert off[0] == 0 and off[-1] == 96 and np.all(np.diff(off) >= 0)
    np.testing.assert_array_equal(
        b1["segment_ids"], np.repeat(np.arange(7), np.diff(off)))
    assert not np.array_equal(b1["tokens"], src.batch_at(6)["tokens"])
    # the packed batch feeds the subsystem directly
    sums = segment_sums(jnp.asarray(b1["tokens"]), jnp.asarray(off))
    assert int(np.asarray(sums).sum()) == int(b1["tokens"].sum())
    p = pack_ragged([[1, 2], [], [3]])
    assert p["tokens"].tolist() == [1, 2, 3]
    assert p["offsets"].tolist() == [0, 2, 2, 3]
    assert p["segment_ids"].tolist() == [0, 0, 2]


# ---------------------------------------------------------------------------
# launch-count guards (mirrors the multisplit jaxpr guard)
# ---------------------------------------------------------------------------


def _count_pallas_launches(fn, substr, *args) -> int:
    def walk(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                nm = eqn.params.get("name_and_src_info",
                                    eqn.params.get("name", ""))
                if substr in str(nm):
                    total += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    total += walk(v.jaxpr)
                elif hasattr(v, "eqns"):
                    total += walk(v)
        return total

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def test_segment_scan_kernel_launch_counts():
    x = jnp.asarray(np.random.default_rng(16).integers(0, 3, 3 * BT * S * S),
                    jnp.int32)
    offsets = jnp.asarray([0, 5, 3 * BT * S * S], jnp.int32)
    got = _count_pallas_launches(
        lambda v, o: segment_scan(v, o, method="kernel", **KW), "segscan_mm",
        x, offsets)
    assert got == 1                 # the whole segmented scan is one launch
    got = _count_pallas_launches(
        lambda v, o: segment_scan(v, o, method="blocked", **KW),
        "segscan_pipeline", x, offsets)
    assert got == 2                 # summaries + fused 1+3 (the segmented
    #                                 carry scan over nb summaries is XLA)


# ---------------------------------------------------------------------------
# property-based (hypothesis): random ragged layouts vs the loop oracle
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 9), min_size=1, max_size=60),
           st.lists(st.integers(0, 60), min_size=0, max_size=6),
           st.sampled_from(["vector", "matmul"]))
    def test_segment_scan_property(values, cuts, method):
        x = jnp.asarray(values, jnp.int32)
        n = x.shape[0]
        offsets = np.concatenate(
            [[0], np.sort(np.clip(cuts, 0, n)), [n]]).astype(np.int32)
        got = segment_scan(x, jnp.asarray(offsets), method=method, **KW)
        want = _loop_scan(x, offsets, method=method, **KW)
        np.testing.assert_array_equal(np.asarray(got), want)
        # segment totals recompose to the global total
        sums = segment_sums(x, jnp.asarray(offsets), method=method, **KW)
        assert int(np.asarray(sums).sum()) == int(np.asarray(x).sum())

else:  # pragma: no cover

    @pytest.mark.skip(reason="hypothesis not installed — property tests skipped")
    def test_segment_scan_property_placeholder():
        pass  # visible placeholder so missing hypothesis shows as a skip
