"""Precision axis (``precision="compensated"``): resolution, split math, ulp gates.

The contract under test (docs/architecture.md dispatch rule 9 +
:mod:`repro.analysis.ulp`):

* resolution is pre-trace with the chain override > ``REPRO_SCAN_PRECISION``
  env > call-site argument; explicit ``method="vector"`` + explicit
  non-default precision raises; auto/override/env landing on vector silently
  degrades to ``"highest"`` (the vector path *is* the fp32 reference);
* ``precision="highest"`` traces exactly like a ``Precision.HIGHEST`` matmul;
* the Ozaki split is exact (``x == ldexp(hi + ldexp(lo, -SPLIT_SHIFT), e)``
  whenever the per-slice dynamic range fits the ~22-bit window);
* measured max ulp at the conditioning scale stays under
  ``ULP_COEFF[precision] * sqrt(n)`` for scan / linear_scan / segment_scan on
  every matmul-engine method — including subnormal, near-fp16-overflow and
  non-finite inputs;
* integer scans are bit-exact under every precision.

Sweeps run twice: a seeded deterministic sweep that always runs, and a
hypothesis property sweep that activates when hypothesis is installed (the
container gates it; profiles live in ``conftest.py``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ulp
from repro.core import precision as prec
from repro.core.linrec import cumprod, linear_scan
from repro.core.precision import (
    ENV_VAR, PRECISIONS, SPLIT_SHIFT, normalize_exponents, pdot,
    precision_override, resolve_precision, split_f16,
)
from repro.core.scan import cumsum, scan
from repro.core.segmented import segment_scan
from repro.core.ssd import ssd_scan, ssd_scan_ref
from ulp_oracle import (
    assert_within_bound, linrec_case, scan_case, segment_scan_case,
)

try:
    from hypothesis import given, settings, strategies as st
    from hypothesis.extra import numpy as hnp
    HAS_HYPOTHESIS = True
except ImportError:  # container without hypothesis: the seeded sweeps cover
    HAS_HYPOTHESIS = False

ENGINE_METHODS = ("matmul", "kernel", "blocked")


@pytest.fixture(autouse=True)
def _no_env_precision(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)


# ---------------------------------------------------------------------------
# resolution: override > env > argument; the vector-path rules
# ---------------------------------------------------------------------------


def test_resolution_chain(monkeypatch):
    assert resolve_precision("compensated", method="matmul") == "compensated"
    monkeypatch.setenv(ENV_VAR, "fast")
    assert resolve_precision("compensated", method="matmul") == "fast"
    with precision_override("compensated"):
        assert resolve_precision("highest", method="kernel") == "compensated"
    monkeypatch.setenv(ENV_VAR, "nonsense")
    with pytest.raises(ValueError, match="nonsense"):
        resolve_precision("highest", method="matmul")


def test_unknown_precision_rejected():
    with pytest.raises(ValueError, match="unknown precision"):
        resolve_precision("double", method="matmul")
    with pytest.raises(ValueError):
        with precision_override("double"):
            pass


def test_explicit_vector_with_precision_raises():
    x = jnp.ones(64, jnp.float32)
    for fn in (lambda: scan(x, method="vector", precision="compensated"),
               lambda: cumsum(x, method="vector", precision="fast"),
               lambda: linear_scan(x, x, method="vector",
                                   precision="compensated"),
               lambda: segment_scan(x, jnp.asarray([0, 64]), method="vector",
                                    precision="compensated")):
        with pytest.raises(ValueError, match="matmul-engine"):
            fn()


def test_vector_with_default_precision_fine():
    x = jnp.ones(64, jnp.float32)
    assert scan(x, method="vector", precision="highest").shape == (64,)


def test_auto_landing_on_vector_degrades_silently():
    # n=64 fp32 resolves to vector on the committed cpu table
    from repro.core.autotune import resolve_method
    x = jnp.ones(64, jnp.float32)
    if resolve_method("scan", 64, jnp.float32) == "vector":
        out = scan(x, method="auto", precision="compensated")
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(scan(x, method="vector")))


def test_override_degrades_on_vector_path():
    x = jnp.arange(32, dtype=jnp.float32)
    with precision_override("fast"):
        out = scan(x, method="vector")  # never touches the engine
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(scan(x, method="vector")))


def test_env_precision_changes_resolution_pre_trace(monkeypatch):
    x = jnp.asarray(np.random.default_rng(0).standard_normal(256), jnp.float32)
    monkeypatch.setenv(ENV_VAR, "fast")
    got_env = scan(x, method="matmul", tile_s=16)
    monkeypatch.delenv(ENV_VAR)
    got_arg = scan(x, method="matmul", tile_s=16, precision="fast")
    np.testing.assert_array_equal(np.asarray(got_env), np.asarray(got_arg))


# ---------------------------------------------------------------------------
# highest is the identity: pdot traces exactly like a HIGHEST jnp.matmul
# ---------------------------------------------------------------------------


def _jaxpr(fn, *args):
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def test_pdot_highest_is_plain_matmul():
    a = jnp.ones((4, 8), jnp.float32)
    b = jnp.ones((8, 4), jnp.float32)
    for exact in ("none", "left", "right"):
        assert _jaxpr(lambda u, v: pdot(u, v, acc=jnp.float32,
                                        precision="highest", exact=exact),
                      a, b) == \
            _jaxpr(lambda u, v: jnp.matmul(
                u, v, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST), a, b)


def test_pdot_non_f32_data_falls_through():
    a = jnp.ones((4, 8), jnp.int8)
    b = jnp.ones((8, 4), jnp.int8)
    for p in PRECISIONS:
        out = pdot(a, b, acc=jnp.int32, precision=p)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), 8)


# ---------------------------------------------------------------------------
# the split itself: exactness and exponent handling
# ---------------------------------------------------------------------------


def _reconstruct(hi, lo, e):
    return np.ldexp(np.asarray(hi, np.float32)
                    + np.ldexp(np.asarray(lo, np.float32), -SPLIT_SHIFT),
                    np.asarray(e))


def _assert_split_window(x):
    """Split/reconstruct ``x``: error < 2^-22 of the slice max, exactly 0 for
    values whose mantissa fits 22 bits."""
    hi, lo, e = split_f16(jnp.asarray(x, jnp.float32), axis=-1)
    recon = _reconstruct(hi, lo, e)
    xs = np.asarray(x, np.float64)
    slice_max = np.max(np.abs(xs), axis=-1, keepdims=True)
    err = np.abs(recon.astype(np.float64) - xs)
    assert np.all(err <= slice_max * 2.0 ** -22 + 0.0), np.max(err / slice_max)


def test_split_exact_for_22bit_mantissas():
    rng = np.random.default_rng(1)
    # 22-bit integers scaled by powers of two: exactly representable by hi+lo
    ints = rng.integers(-(1 << 21), 1 << 21, (4, 64)).astype(np.float64)
    x = ints * 2.0 ** rng.integers(-30, 30, (4, 1))
    hi, lo, e = split_f16(jnp.asarray(x, jnp.float32), axis=-1)
    np.testing.assert_array_equal(_reconstruct(hi, lo, e),
                                  np.asarray(x, np.float32))


def test_split_window_random_and_extreme_rows():
    rng = np.random.default_rng(2)
    sgn = rng.choice([-1.0, 1.0], (4, 32))
    mag = 0.5 + np.abs(rng.standard_normal((4, 32)))   # normal-range mantissas
    _assert_split_window(rng.standard_normal((8, 32)))
    _assert_split_window(sgn * mag * 1e30)             # near fp32 overflow
    _assert_split_window(sgn * mag * 1e-33)            # near the normal floor


def test_split_flushes_subnormals_to_zero():
    # XLA flushes subnormal operands in the scaling multiplies themselves, so
    # subnormal inputs become exact zeros — the documented backend floor
    # shared by every precision (no nan/inf, no garbage).
    x = jnp.asarray([[1e-40, -1e-39, 0.0, 1e-44]], jnp.float32)
    hi, lo, _ = split_f16(x, axis=-1)
    np.testing.assert_array_equal(np.asarray(hi, np.float32), 0.0)
    np.testing.assert_array_equal(np.asarray(lo, np.float32), 0.0)


def test_split_propagates_nonfinite_and_zero_rows():
    x = jnp.asarray([[1.0, np.inf, -3.0, np.nan],
                     [0.0, 0.0, 0.0, 0.0]], jnp.float32)
    hi, lo, e = split_f16(x, axis=-1)
    h = np.asarray(hi, np.float32)
    assert np.isposinf(h[0, 1]) and np.isnan(h[0, 3])
    assert np.all(np.asarray(lo, np.float32)[0, [1, 3]] == 0)
    np.testing.assert_array_equal(h[1], 0)
    np.testing.assert_array_equal(np.asarray(lo)[1], 0)


def test_normalize_exponents_exact():
    rng = np.random.default_rng(3)
    a = rng.standard_normal(256) * 10.0 ** rng.integers(-30, 30, 256)
    m, e = normalize_exponents(jnp.asarray(a, jnp.float32), jnp.float32)
    m = np.asarray(m, np.float64)
    nz = np.asarray(a, np.float32) != 0
    assert np.all((np.abs(m[nz]) >= prec._SQRT_HALF - 1e-9)
                  & (np.abs(m[nz]) < np.sqrt(2) + 1e-9))
    np.testing.assert_array_equal(
        np.ldexp(m, np.asarray(e)).astype(np.float32), np.asarray(a, np.float32))


# ---------------------------------------------------------------------------
# ulp gates: the documented bound across op x method x precision x n
# ---------------------------------------------------------------------------


def _cases(rng, n):
    x = rng.standard_normal(n) * np.exp(rng.standard_normal(n))
    a = np.exp(-np.abs(rng.standard_normal(n)))          # decays in (0, 1]
    b = rng.standard_normal(n)
    k = max(1, n // 7)
    starts = np.sort(rng.choice(n, size=k, replace=False))
    starts[0] = 0
    offsets = np.concatenate([starts, [n]]).astype(np.int32)
    return x, a, b, offsets


@pytest.mark.parametrize("method", ENGINE_METHODS)
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("n", [5, 97, 600])
def test_ulp_bound_seeded_sweep(method, precision, n):
    rng = np.random.default_rng(n * 7 + len(method))
    x, a, b, offsets = _cases(rng, n)
    for rep in (scan_case(x, method=method, precision=precision, tile_s=8),
                linrec_case(a, b, method=method, precision=precision,
                            tile_s=8),
                segment_scan_case(x, offsets, method=method,
                                  precision=precision, tile_s=8)):
        assert_within_bound(rep)


@pytest.mark.parametrize("method", ENGINE_METHODS)
def test_compensated_tracks_fp32_vector(method):
    # the recovery claim head-on: compensated within a small ulp distance of
    # the fp32 vector reference itself, at the vector result's own scale
    rng = np.random.default_rng(11)
    x = rng.standard_normal(512)
    ref = np.asarray(scan(jnp.asarray(x, jnp.float32), method="vector"),
                     np.float64)
    got = scan(jnp.asarray(x, jnp.float32), method=method, tile_s=8,
               precision="compensated")
    mu = ulp.max_ulp(np.asarray(got), ref, ulp.scan_scale(x))
    assert mu <= ulp.ulp_bound("compensated", 512), mu


def test_subnormal_inputs_flush_deterministically():
    rng = np.random.default_rng(5)
    # every input a fp32 subnormal: XLA flushes them in matmul and in the
    # split's scaling multiplies alike, so all engine paths produce exact
    # zeros — deterministic, finite, and identical across precisions (the
    # documented proviso: the ulp bounds assume normal-range inputs).
    x = (rng.standard_normal(256) * 1e-40).astype(np.float32).astype(np.float64)
    assert np.all(np.abs(x[x != 0]) < np.finfo(np.float32).tiny)
    for p in ("highest", "compensated"):
        got = np.asarray(scan(jnp.asarray(x, jnp.float32), method="kernel",
                              tile_s=8, precision=p))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, 0.0)


def test_near_tiny_normal_inputs_within_bound():
    rng = np.random.default_rng(9)
    # normal-range values just above the subnormal floor: the exact
    # power-of-two slice scaling makes the bound exponent-independent
    x = (0.5 + np.abs(rng.standard_normal(256))) * 1e-35 \
        * rng.choice([-1.0, 1.0], 256)
    for p in ("highest", "compensated"):
        assert_within_bound(scan_case(x, method="kernel", precision=p,
                                      tile_s=8))


def test_near_fp16_overflow_within_bound():
    rng = np.random.default_rng(6)
    # far outside fp16 range (max ~65504): the exact scaling brings each
    # slice back into range, so the bound must hold unchanged
    x = rng.standard_normal(256) * 1e30
    for p in ("highest", "compensated"):
        assert_within_bound(scan_case(x, method="blocked", precision=p,
                                      tile_s=8))


def test_extreme_intra_slice_range_bounded_at_final_scale():
    # elements below ~2^-35 of their slice max are lost by the split (below
    # fp32 significance at the slice scale); the documented guarantee there
    # is at the end-of-scan conditioning scale, not per element
    x = np.ones(64)
    x[37] = 1e30
    got = scan(jnp.asarray(x, jnp.float32), method="kernel", tile_s=8,
               precision="compensated")
    ref, scale = ulp.scan_ref(x), ulp.scan_scale(x)
    mu = ulp.max_ulp(np.asarray(got), ref, scale[-1:])
    assert mu <= ulp.ulp_bound("compensated", 64), mu


@pytest.mark.parametrize("precision", ("compensated", "fast"))
@pytest.mark.parametrize("method", ENGINE_METHODS)
def test_nonfinite_propagation_matches_engine_reference(method, precision):
    # the contract: non-finites ride the split's high part unchanged, so
    # inf/nan propagate exactly as through the fp32 engine ("highest") on the
    # SAME method — not as the vector cumsum, because any matmul formulation
    # spreads nan within a tile via inf * 0 against the triangular zeros.
    x = np.ones(48)
    x[10], x[30] = np.inf, np.nan
    xj = jnp.asarray(x, jnp.float32)
    got = np.asarray(scan(xj, method=method, tile_s=4, precision=precision),
                     np.float64)
    ref = np.asarray(scan(xj, method=method, tile_s=4, precision="highest"),
                     np.float64)
    np.testing.assert_array_equal(np.isnan(got), np.isnan(ref))
    fin = np.isfinite(ref)
    np.testing.assert_array_equal(got[~fin & ~np.isnan(ref)],
                                  ref[~fin & ~np.isnan(ref)])
    # every element at/after the nan is non-finite on every path
    assert not np.isfinite(got[30:]).any()


@pytest.mark.parametrize("precision", PRECISIONS)
def test_integer_scans_bit_exact(precision):
    rng = np.random.default_rng(7)
    xi = rng.integers(-100, 100, 300).astype(np.int32)
    ref = np.cumsum(xi)
    for method in ENGINE_METHODS:
        got = scan(jnp.asarray(xi), method=method, tile_s=8,
                   precision=precision)
        np.testing.assert_array_equal(np.asarray(got), ref)
    off = np.asarray([0, 150, 300], np.int32)
    seg = segment_scan(jnp.asarray(xi), jnp.asarray(off), method="kernel",
                       tile_s=8, precision=precision)
    assert np.array_equal(np.asarray(seg)[:150], np.cumsum(xi[:150]))


def test_cumprod_and_ssd_accept_precision():
    rng = np.random.default_rng(8)
    a = np.exp(rng.standard_normal(128) * 0.1)
    got = cumprod(jnp.asarray(a, jnp.float32), method="matmul", tile_s=8,
                  precision="compensated")
    ref = np.cumprod(a)
    np.testing.assert_allclose(np.asarray(got, np.float64), ref, rtol=1e-5)
    x = jnp.asarray(rng.standard_normal((1, 32, 2, 4)), jnp.float32)
    al = jnp.asarray(-np.abs(rng.standard_normal((1, 32, 2))), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((1, 32, 2, 3)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((1, 32, 2, 3)), jnp.float32)
    y = ssd_scan(x, al, bm, cm, chunk=16, scan_method="matmul",
                 precision="compensated")
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        ssd_scan_ref(x, al, bm, cm)), atol=1e-4)


def test_linrec_grad_runs_under_compensated():
    a = jnp.full((64,), 0.9, jnp.float32)
    b = jnp.ones((64,), jnp.float32)
    g = jax.grad(lambda u, v: jnp.sum(linear_scan(
        u, v, method="matmul", tile_s=8, precision="compensated")))(a, b)
    gref = jax.grad(lambda u, v: jnp.sum(linear_scan(
        u, v, method="matmul", tile_s=8)))(a, b)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# hypothesis property sweeps (gated: activate where hypothesis is installed)
# ---------------------------------------------------------------------------


if HAS_HYPOTHESIS:
    # bounds written as the float32 values they stand for: the installed
    # hypothesis rejects width=32 bounds that float32 cannot represent
    F32_1E30 = float(np.float32(1e30))
    finite_f32 = st.floats(min_value=-F32_1E30, max_value=F32_1E30, width=32,
                           allow_nan=False, allow_infinity=False,
                           allow_subnormal=True)

    def _flush(x):
        # the documented backend floor: subnormal inputs flush to exact zero
        # on every engine path, so the fp64 oracle is stated on FTZ(x)
        return np.where(np.abs(x) < np.finfo(np.float32).tiny, 0.0,
                        np.asarray(x, np.float64))

    @given(x=hnp.arrays(np.float32, st.integers(1, 300), elements=finite_f32),
           method=st.sampled_from(ENGINE_METHODS),
           precision=st.sampled_from(PRECISIONS))
    @settings(deadline=None)
    def test_hyp_scan_final_scale_bound(x, method, precision):
        got = scan(jnp.asarray(x), method=method, tile_s=8,
                   precision=precision)
        xf = _flush(x)
        mu = ulp.max_ulp(np.asarray(got), ulp.scan_ref(xf),
                         ulp.scan_scale(xf)[-1:])
        assert mu <= ulp.ulp_bound(precision, x.shape[0]), mu

    @given(x=hnp.arrays(np.float32, st.integers(1, 200),
                        elements=st.floats(-100, 100, width=32)),
           precision=st.sampled_from(PRECISIONS))
    @settings(deadline=None)
    def test_hyp_moderate_range_per_element_bound(x, precision):
        assert_within_bound(scan_case(_flush(x), method="kernel",
                                      precision=precision, tile_s=8))

    @given(hi=hnp.arrays(np.float32, 64,
                         elements=st.floats(-F32_1E30, F32_1E30, width=32,
                                            allow_subnormal=True)))
    @settings(deadline=None)
    def test_hyp_split_window(hi):
        _assert_split_window(hi[None, :])
