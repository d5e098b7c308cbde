"""Guardrails layer (ISSUE 8): resolution order, checks, probes, validators.

The three contracts under test, in the order ``docs/architecture.md`` rule 10
documents them:

1. the ``nonfinite`` policy resolves ctx > env > call-site, pre-trace;
2. staged checks are a Python no-op when off — guarded operators trace to
   jaxprs **identical** to :func:`repro.core.guards.guards_disabled`;
3. ``kernel``/``blocked`` dispatch probes lowering once and degrades through
   the tuning-table fallbacks with a warn-once ``ProbeFallbackWarning``.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import checkify

from repro.core import guards
from repro.core.autotune import _WARNED
from repro.core.linrec import linear_scan
from repro.core.primitives import radix_sort, split, top_p_sample, \
    weighted_sample
from repro.core.scan import scan
from repro.core.segmented import segment_scan, segment_top_p_sample


OFF = jnp.asarray([0, 3, 5])
X5 = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])


def _jaxpr(fn, *args):
    """Jaxpr text with object ids stripped (stable across traces)."""
    return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))


# ---------------------------------------------------------------------------
# nonfinite policy resolution (rule 10 mirrors rules 8/9)
# ---------------------------------------------------------------------------


def test_resolution_order_ctx_beats_env_beats_arg(monkeypatch):
    monkeypatch.setenv(guards.ENV_VAR, "raise")
    assert guards.resolve_nonfinite("propagate") == "raise"   # env > arg
    with guards.nonfinite_override("sanitize"):               # ctx > env
        assert guards.resolve_nonfinite("propagate") == "sanitize"
    monkeypatch.delenv(guards.ENV_VAR)
    assert guards.resolve_nonfinite("sanitize") == "sanitize"  # arg
    assert guards.resolve_nonfinite() == "propagate"


def test_unknown_policy_rejected_everywhere(monkeypatch):
    with pytest.raises(ValueError, match="nonfinite"):
        guards.resolve_nonfinite("explode")
    with pytest.raises(ValueError, match="nonfinite"):
        with guards.nonfinite_override("explode"):
            pass
    monkeypatch.setenv(guards.ENV_VAR, "explode")
    with pytest.raises(ValueError, match=guards.ENV_VAR):
        guards.resolve_nonfinite()


def test_guards_disabled_forces_propagate_and_no_checks(monkeypatch):
    monkeypatch.setenv(guards.CHECKS_ENV_VAR, "1")
    with guards.nonfinite_override("raise"):
        with guards.guards_disabled():
            assert guards.resolve_nonfinite() == "propagate"
            assert not guards.checks_enabled()
            assert not guards.guards_active()
        assert guards.resolve_nonfinite() == "raise"
    assert guards.checks_enabled()


def test_env_var_drives_operator_behaviour(monkeypatch):
    bad = jnp.asarray([1.0, jnp.nan, 3.0])
    monkeypatch.setenv(guards.ENV_VAR, "sanitize")
    assert scan(bad).tolist() == [1.0, 1.0, 4.0]
    monkeypatch.setenv(guards.ENV_VAR, "raise")
    with pytest.raises(guards.NonFiniteError):
        scan(bad)


# ---------------------------------------------------------------------------
# checks: eager + staged assertions
# ---------------------------------------------------------------------------


def test_guard_check_noop_when_off():
    guard_thunk_ran = []
    with guards.checks(False):   # pin off even on the REPRO_CHECKS=1 CI leg
        guards.guard_check(lambda: guard_thunk_ran.append(1),
                           "never evaluated")
    assert not guard_thunk_ran


def test_guard_check_eager_concrete_raises():
    with guards.checks():
        with pytest.raises(checkify.JaxRuntimeError, match="bad scalar"):
            guards.guard_check(False, "bad scalar")
        guards.guard_check(True, "fine")


def test_guard_check_staged_fires_through_checked():
    def f(x):
        guards.guard_check(lambda: jnp.all(x > 0), "x must be positive")
        return x * 2

    with guards.checks():
        out = guards.checked(f)(jnp.asarray([1.0, 2.0]))
        assert out.tolist() == [2.0, 4.0]
        with pytest.raises(checkify.JaxRuntimeError, match="positive"):
            guards.checked(f)(jnp.asarray([1.0, -2.0]))


def test_traced_offsets_csr_check_fires_in_jit():
    # jit makes the offsets genuine tracers; concrete offsets are caught
    # eagerly by the ValueError path instead (test_validate_offsets_concrete)
    def f(values, offsets):
        return segment_scan(values, offsets)

    with guards.checks():
        cf = guards.checked(jax.jit(f))
        good = cf(X5, OFF)
        assert good.shape == X5.shape
        with pytest.raises(checkify.JaxRuntimeError, match="CSR"):
            cf(X5, jnp.asarray([0, 4, 2]))


def test_checks_env_var(monkeypatch):
    monkeypatch.setenv(guards.CHECKS_ENV_VAR, "1")
    assert guards.checks_enabled()
    with guards.checks(False):   # ctx wins over env
        assert not guards.checks_enabled()
    monkeypatch.delenv(guards.CHECKS_ENV_VAR)
    assert not guards.checks_enabled()


# ---------------------------------------------------------------------------
# jaxpr identity: guarded defaults == guards_disabled (zero-overhead gate)
# ---------------------------------------------------------------------------


IDENTITY_CASES = [
    ("scan", lambda x: scan(x), X5),
    ("linrec", lambda x: linear_scan(x, x), X5),
    ("segment_scan", lambda x: segment_scan(x, OFF), X5),
    ("weighted_sample",
     lambda x: weighted_sample(x, None, u=jnp.asarray(0.5)), X5),
    ("top_p",
     lambda x: top_p_sample(x[None], None, p=0.9,
                            u=jnp.asarray([[0.5]])), X5),
    ("segment_top_p",
     lambda x: segment_top_p_sample(x, OFF, p=0.9,
                                    u=jnp.asarray([[0.5], [0.5]])), X5),
]


@pytest.mark.parametrize("name,fn,arg",
                         IDENTITY_CASES, ids=[c[0] for c in IDENTITY_CASES])
def test_jaxpr_identity_guarded_vs_disabled(name, fn, arg):
    with guards.checks(False):   # the documented checks-off contract
        guarded = _jaxpr(fn, arg)
    with guards.guards_disabled():
        bare = _jaxpr(fn, arg)
    assert guarded == bare, f"{name}: guards added ops to the default trace"


# ---------------------------------------------------------------------------
# backend capability probe
# ---------------------------------------------------------------------------


def test_probe_lowering_succeeds_and_caches():
    backend = jax.default_backend()
    assert guards.probe_lowering("scan", "kernel", backend=backend)
    assert (backend, "scan", "kernel") in guards._PROBE_CACHE


def test_forced_probe_failure_degrades_with_single_warning():
    _WARNED.clear()
    with guards.force_probe_failure("scan", "kernel"):
        with pytest.warns(guards.ProbeFallbackWarning, match="rule 10"):
            assert guards.ensure_available("kernel", "scan") == "vector"
        # warn-once: a second degrade of the same key is silent
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            assert guards.ensure_available("kernel", "scan") == "vector"
    _WARNED.clear()
    # outside the block the real (passing) probe result is restored
    assert guards.ensure_available("kernel", "scan") == "kernel"


def test_forced_probe_failure_through_public_entry():
    _WARNED.clear()
    x = jnp.asarray([3, 1, 4, 1, 5], jnp.int32)
    f = jnp.asarray([1, 0, 1, 0, 1], jnp.int8)
    with guards.force_probe_failure():
        with pytest.warns(guards.ProbeFallbackWarning):
            z, ind, cnt = split(x, f, method="kernel", tile_s=8)
    _WARNED.clear()
    zr, indr, cntr = split(x, f, method="vector", tile_s=8)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(zr))
    assert int(cnt) == int(cntr)


def test_real_lowering_failure_raises_on_tpu_and_degrades_elsewhere(
        monkeypatch):
    def refuse(fam, method, s=8):
        raise NotImplementedError("refused by the kernel compiler")

    monkeypatch.setattr(guards, "_probe_lower", refuse)
    guards._reset_probes_for_testing()
    try:
        with pytest.raises(RuntimeError, match="does not lower on the TPU"):
            guards.probe_lowering("linear_scan", "kernel", backend="tpu")
        assert ("tpu", "linear_scan", "kernel") not in guards._PROBE_CACHE
        assert not guards.probe_lowering("linear_scan", "kernel",
                                         backend="gpu")
    finally:
        guards._reset_probes_for_testing()


def test_probe_bypassed_under_guards_disabled():
    with guards.force_probe_failure():
        with guards.guards_disabled():
            assert guards.ensure_available("kernel", "scan") == "kernel"


def test_probe_family_collapse():
    assert guards._probe_family("sort", "blocked") == "scan"
    assert guards._probe_family("radix_sort", "kernel") == "sort"
    assert guards._probe_family("linear_scan", "blocked") == "linear_scan"


# ---------------------------------------------------------------------------
# validators
# ---------------------------------------------------------------------------


def test_validate_axis_rejects_out_of_bounds():
    assert guards.validate_axis(-1, 2, op="scan") == 1
    with pytest.raises(ValueError, match="axis"):
        guards.validate_axis(5, 2, op="scan")
    with pytest.raises(ValueError, match="axis"):
        scan(jnp.ones((2, 3)), axis=7)
    with pytest.raises(ValueError, match="axis"):
        linear_scan(jnp.ones(4), jnp.ones(4), axis=-2)


def test_validate_bits_per_pass():
    with pytest.raises(ValueError, match="bits_per_pass"):
        radix_sort(jnp.asarray([3, 1, 2], jnp.int32), bits_per_pass=0)
    with pytest.raises(ValueError, match="bits_per_pass"):
        radix_sort(jnp.asarray([3, 1, 2], jnp.int32), bits_per_pass=9)


@pytest.mark.parametrize("bad,err", [
    ([1, 3, 5], ValueError),          # offsets[0] != 0
    ([0, 3, 9], ValueError),          # offsets[-1] != n
    ([0, 4, 2, 5], ValueError),       # decreasing
    ([[0, 3, 5]], ValueError),        # 2-D
])
def test_validate_offsets_concrete(bad, err):
    with pytest.raises(err):
        segment_scan(X5, jnp.asarray(bad))


def test_validate_offsets_traced_pass_through():
    out = jax.jit(lambda v, o: segment_scan(v, o))(X5, OFF)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(segment_scan(X5, OFF)))


def test_sampler_param_validation():
    logits = jnp.asarray([[0.0, 1.0, 2.0]])
    u = jnp.asarray([[0.5]])
    with pytest.raises(ValueError, match="p must"):
        top_p_sample(logits, None, p=1.5, u=u)
    with pytest.raises(ValueError, match="p must"):
        top_p_sample(logits, None, p=float("nan"), u=u)
    with pytest.raises(ValueError, match="temperature"):
        top_p_sample(logits, None, temperature=-1.0, u=u)
    with pytest.raises(ValueError, match="temperature"):
        top_p_sample(logits, None, temperature=float("inf"), u=u)


def test_kernel_entry_validators():
    from repro.kernels.scan_mm import scan_tiles
    from repro.kernels.split_mm import multi_split_tiles, split_tiles

    with pytest.raises(ValueError, match="variant"):
        scan_tiles(jnp.ones(8), variant="scanul3", s=2)
    with pytest.raises(ValueError, match="must match"):
        split_tiles(jnp.ones(8), jnp.ones(7), s=2)
    with pytest.raises(ValueError, match="num_buckets"):
        multi_split_tiles(jnp.ones(8), jnp.zeros(8, jnp.int32),
                          num_buckets=0, s=2)


def test_apply_nonfinite_policies():
    x = jnp.asarray([1.0, jnp.inf, jnp.nan])
    assert guards.apply_nonfinite(x, "propagate", op="t") is x
    assert guards.apply_nonfinite(
        x, "sanitize", op="t", identity=7.0).tolist() == [1.0, 7.0, 7.0]
    with pytest.raises(guards.NonFiniteError):
        guards.apply_nonfinite(x, "raise", op="t")
    ints = jnp.asarray([1, 2, 3], jnp.int32)
    assert guards.apply_nonfinite(ints, "raise", op="t") is ints
