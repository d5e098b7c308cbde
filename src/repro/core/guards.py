"""Guardrails & graceful degradation — ``docs/architecture.md`` dispatch rule 10.

Every public operator (and the serving path) routes through this module's
three layers, in order:

1. **Pre-trace validation** — plain-Python checks on static information
   (axis/ndim bounds, ``bits_per_pass``, probabilities, temperatures,
   ``SegmentedBatch`` offset monotonicity/bounds when the offsets are
   concrete).  These raise ``ValueError``/``TypeError`` *before* tracing, so a
   bad call fails at the call site instead of deep inside a kernel, and they
   add nothing to the traced jaxpr.
2. **The non-finite policy** — ``nonfinite="propagate" | "raise" | "sanitize"``
   on the scan/sampler family, resolved statically exactly like ``method``
   (rule 8) and ``precision`` (rule 9): an active :func:`nonfinite_override`
   context wins, else the ``REPRO_NONFINITE`` environment variable, else the
   call-site argument.  ``"propagate"`` (the default) is PR 7's documented
   IEEE semantics and traces to a jaxpr **identical** to pre-guard code;
   ``"raise"`` rejects non-finite payloads (eagerly when concrete, as a
   checkified assertion under trace); ``"sanitize"`` replaces non-finite
   elements with the operator's identity — and maps all-masked / all-``-inf``
   sampler rows to a **deterministic greedy fallback** instead of undefined
   samples.
3. **Opt-in in-jit assertions** — :func:`guard_check` stages
   ``jax.experimental.checkify`` assertions (offsets sorted, decode
   ``pos < max_len``, finite CDF before the inverse-transform sample) only
   when ``REPRO_CHECKS=1`` or a :func:`checks` context is active.  With checks
   off, :func:`guard_check` is a Python no-op — zero ops in the jaxpr, which
   is what the bench-smoke jaxpr-identity gate asserts.  Staged checks fire
   through :func:`checked`; under a plain ``jax.jit`` they compile to nothing
   (``checkify.debug_check`` semantics), so enabling checks never breaks an
   existing jit call site.

Backend capability probing (:func:`ensure_available`) extends the
warn-once degradation chain of :mod:`repro.core.autotune`: the first
``kernel``/``blocked`` dispatch per (backend, op family, method) lowers a
tiny probe kernel once and, on failure, degrades through the tuning table's
``fallbacks`` entry (else ``"vector"``) with an :class:`ProbeFallbackWarning`
— the same script runs unmodified on CPU/GPU/TPU.

The fault-injection harness (:mod:`repro.analysis.faults`,
``tests/test_faults.py``) asserts that every injected fault lands on one of
the documented contracts above: propagate, eager ``ValueError``, checkified
error, or warn-once degrade.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import (
    CONCRETE_METHODS, OP_ALIASES, TUNED_OPS, AutotuneFallbackWarning,
    _warn_once, load_table,
)

__all__ = [
    "NONFINITE", "ENV_VAR", "CHECKS_ENV_VAR",
    "NonFiniteError", "ProbeFallbackWarning",
    "resolve_nonfinite", "nonfinite_override", "apply_nonfinite",
    "checks", "checks_enabled", "guard_check", "checked",
    "guards_disabled", "guards_active",
    "validate_axis", "validate_bits_per_pass", "validate_probability",
    "validate_temperature", "validate_offsets", "validate_same_shape",
    "validate_positive", "validate_choice", "validate_broadcastable_to",
    "ensure_available", "probe_lowering", "force_probe_failure",
]

NONFINITE = ("propagate", "raise", "sanitize")
ENV_VAR = "REPRO_NONFINITE"
CHECKS_ENV_VAR = "REPRO_CHECKS"


class NonFiniteError(ValueError):
    """Raised by ``nonfinite="raise"`` when a concrete payload is non-finite."""


class ProbeFallbackWarning(AutotuneFallbackWarning):
    """Raised (once per key) when a lowering probe fails and dispatch degrades."""


_NONFINITE_OVERRIDE: List[str] = []
_CHECKS_OVERRIDE: List[bool] = []
_BYPASS: List[bool] = []


def is_concrete(x) -> bool:
    """True when ``x`` is a plain Python value or a non-traced array.

    Concrete values can be validated eagerly (raising at the call site);
    tracers can only be checked in-jit via :func:`guard_check`.

    Example:
        >>> is_concrete(3.5), is_concrete(jnp.asarray([1.0]))
        (True, True)
        >>> bool(jax.jit(is_concrete)(jnp.asarray([1.0])))
        False
    """
    return not isinstance(x, jax.core.Tracer)


def guards_active() -> bool:
    """False inside a :func:`guards_disabled` block, else True."""
    return not _BYPASS


@contextlib.contextmanager
def guards_disabled():
    """Disable the whole guard layer inside the block (bench/test hook).

    Validation helpers, the non-finite policy, staged checks and lowering
    probes all become no-ops, reproducing pre-guard dispatch exactly.  The
    bench-smoke jaxpr-identity gate traces every guarded operator once
    normally and once under this context and asserts the jaxprs are equal —
    the "zero steady-state overhead" acceptance criterion.

    Example:
        >>> with guards_disabled():
        ...     guards_active()
        False
    """
    _BYPASS.append(True)
    try:
        yield
    finally:
        _BYPASS.pop()


# ---------------------------------------------------------------------------
# Non-finite policy (dispatch rule 10, resolution mirrors rules 8/9)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def nonfinite_override(policy: str):
    """Force every non-finite-policy resolution to ``policy`` inside the block.

    The in-process analogue of the ``REPRO_NONFINITE`` environment variable
    (and it takes precedence over it) — the non-finite counterpart of
    :func:`repro.core.autotune.method_override` and
    :func:`repro.core.precision.precision_override`.

    Args:
        policy: One of ``NONFINITE``.

    Raises:
        ValueError: If ``policy`` is not a known policy.

    Example:
        >>> with nonfinite_override("sanitize"):
        ...     resolve_nonfinite("propagate")
        'sanitize'
    """
    if policy not in NONFINITE:
        raise ValueError(f"unknown nonfinite policy {policy!r}; expected one "
                         f"of {NONFINITE}")
    _NONFINITE_OVERRIDE.append(policy)
    try:
        yield
    finally:
        _NONFINITE_OVERRIDE.pop()


def _env_nonfinite() -> Optional[str]:
    """The ``REPRO_NONFINITE`` forced policy, or ``None``."""
    p = os.environ.get(ENV_VAR)
    if not p:
        return None
    if p not in NONFINITE:
        raise ValueError(f"{ENV_VAR}={p!r} is not a known nonfinite policy; "
                         f"expected one of {NONFINITE}")
    return p


def resolve_nonfinite(policy: str = "propagate") -> str:
    """Resolve the effective non-finite policy for one call (pre-trace).

    Resolution order (``docs/architecture.md`` dispatch rule 10): an active
    :func:`nonfinite_override` context wins, else ``REPRO_NONFINITE``, else
    the call-site ``nonfinite`` argument.  Resolution happens in Python
    before tracing, so the jaxpr of a call is identical to passing the
    resolved policy explicitly.

    Args:
        policy: The caller-supplied ``nonfinite=`` argument.

    Returns:
        One of ``NONFINITE`` (``"propagate"`` inside :func:`guards_disabled`).

    Raises:
        ValueError: If ``policy`` (argument or environment) is unknown.

    Example:
        >>> resolve_nonfinite()
        'propagate'
        >>> resolve_nonfinite("sanitize")
        'sanitize'
    """
    if policy not in NONFINITE:
        raise ValueError(f"unknown nonfinite policy {policy!r}; expected one "
                         f"of {NONFINITE}")
    if _BYPASS:
        return "propagate"
    p = _NONFINITE_OVERRIDE[-1] if _NONFINITE_OVERRIDE else None
    if p is None:
        p = _env_nonfinite()
    if p is None:
        p = policy
    return p


def apply_nonfinite(x: jax.Array, policy: str, *, op: str,
                    identity: float = 0.0) -> jax.Array:
    """Apply a resolved non-finite policy to a float payload.

    * ``"propagate"`` — return ``x`` untouched (adds **zero** ops; PR 7's
      documented IEEE semantics).
    * ``"raise"`` — when ``x`` is concrete, raise :class:`NonFiniteError`
      eagerly if any element is non-finite; under trace, stage a checkified
      assertion (fires through :func:`checked` / a checkified caller, and
      compiles to nothing under a plain ``jit`` — ``debug_check`` semantics).
    * ``"sanitize"`` — replace non-finite elements with ``identity`` (0 for
      additive scans; the linear-recurrence entry passes the affine identity
      per operand: ``a -> 1``, ``b -> 0``).

    Integer/bool payloads are always finite and are returned unchanged under
    every policy.

    Args:
        x: The operator's payload array.
        policy: A **resolved** policy (one of ``NONFINITE``).
        op: Operator name for error messages.
        identity: Replacement value for ``"sanitize"``.

    Returns:
        ``x``, possibly sanitized.

    Raises:
        NonFiniteError: Policy ``"raise"`` with a concrete non-finite payload.

    Example:
        >>> x = jnp.asarray([1.0, jnp.inf, jnp.nan])
        >>> apply_nonfinite(x, "sanitize", op="scan").tolist()
        [1.0, 0.0, 0.0]
        >>> try:
        ...     apply_nonfinite(x, "raise", op="scan")
        ... except NonFiniteError:
        ...     print("rejected")
        rejected
    """
    if _BYPASS or policy == "propagate" \
            or not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return x
    if policy == "raise":
        if is_concrete(x):
            if not bool(np.isfinite(np.asarray(x)).all()):
                raise NonFiniteError(
                    f"{op}: non-finite input under nonfinite='raise' "
                    "(use 'propagate' for IEEE semantics or 'sanitize' for "
                    "the identity-element fallback)")
        else:
            from jax.experimental import checkify
            checkify.debug_check(
                jnp.all(jnp.isfinite(x)),
                f"{op}: non-finite input under nonfinite='raise'")
        return x
    # sanitize
    return jnp.where(jnp.isfinite(x), x, jnp.asarray(identity, x.dtype))


# ---------------------------------------------------------------------------
# Opt-in in-jit assertions (checkify behind REPRO_CHECKS=1 / checks())
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def checks(enable: bool = True):
    """Enable (or force-disable) staged in-jit assertions inside the block.

    The in-process analogue of ``REPRO_CHECKS=1`` (and it takes precedence
    over it).

    Example:
        >>> with checks():
        ...     checks_enabled()
        True
    """
    _CHECKS_OVERRIDE.append(bool(enable))
    try:
        yield
    finally:
        _CHECKS_OVERRIDE.pop()


def checks_enabled() -> bool:
    """Whether :func:`guard_check` assertions are active.

    An active :func:`checks` context wins, else the ``REPRO_CHECKS``
    environment variable (``"1"`` enables); :func:`guards_disabled` forces
    off.

    Example:
        >>> checks_enabled()
        False
    """
    if _BYPASS:
        return False
    if _CHECKS_OVERRIDE:
        return _CHECKS_OVERRIDE[-1]
    return os.environ.get(CHECKS_ENV_VAR, "") == "1"


def guard_check(pred, msg: str) -> None:
    """Assert ``pred`` when checks are enabled; a Python no-op otherwise.

    Pass the predicate as a **thunk** (zero-argument callable) whenever
    computing it would add ops: with checks off, guard_check returns before
    calling it, so the traced jaxpr carries zero extra equations (the
    bench-smoke identity gate relies on this — dead equations are *not*
    eliminated from a traced jaxpr).  With checks on, a concrete predicate
    raises ``jax.experimental.checkify.JaxRuntimeError`` eagerly; a traced
    predicate stages a ``checkify.debug_check`` that fires through
    :func:`checked` (and compiles to nothing under a plain ``jit``).

    Args:
        pred: Boolean scalar (Python bool, array, or tracer) or a
            zero-argument callable returning one.
        msg: Assertion message.

    Example:
        >>> guard_check(lambda: 1 / 0, "never evaluated: checks are off")
        >>> with checks():
        ...     guard_check(True, "fine")
    """
    if not checks_enabled():
        return
    if callable(pred):
        pred = pred()
    from jax.experimental import checkify
    if is_concrete(pred):
        checkify.check(bool(pred), msg)
    else:
        checkify.debug_check(pred, msg)


def checked(fn: Callable) -> Callable:
    """Functionalize ``fn`` so its staged :func:`guard_check` assertions fire.

    Wraps ``fn`` with ``checkify.checkify(errors=user_checks)`` and throws
    the collected error after the call — the harness the fault-injection
    suite (and a user debugging a numeric issue) runs guarded operators
    under.  ``user_checks`` (this layer's :func:`guard_check` assertions)
    rather than ``all_checks``: the automatic index/float instrumentation
    rewrites every scatter in the traced function and does not support the
    batched scatters the radix-sort pipeline stages.
    Compose with ``jit`` as ``jax.jit(checked(fn))`` is **not** supported by
    checkify; use ``checked(jax.jit(fn))`` or checkify first and jit the
    resulting ``(err, out)`` function.

    Args:
        fn: Any traceable callable.

    Returns:
        A callable with the same signature that raises
        ``checkify.JaxRuntimeError`` if any staged check failed.

    Example:
        >>> def f(x):
        ...     guard_check(jnp.all(x > 0), "x must be positive")
        ...     return x * 2
        >>> with checks():
        ...     out = checked(f)(jnp.asarray([1.0, 2.0]))
        >>> out.tolist()
        [2.0, 4.0]
    """
    from jax.experimental import checkify
    cfn = checkify.checkify(fn, errors=checkify.user_checks)

    @functools.wraps(fn)
    def run(*args, **kwargs):
        err, out = cfn(*args, **kwargs)
        err.throw()
        return out

    return run


# ---------------------------------------------------------------------------
# Pre-trace validation helpers (shared by every public entry point)
# ---------------------------------------------------------------------------


def validate_axis(axis: int, ndim: int, *, op: str) -> int:
    """Normalize ``axis`` against ``ndim``, rejecting out-of-range values.

    Python's ``axis % ndim`` silently wraps *any* integer (``axis=5`` on a
    2-D input lands on axis 1) — this is the numpy-style bounds check every
    scan entry point runs instead.

    Args:
        axis: Caller-supplied axis (negative allowed).
        ndim: Rank of the input.
        op: Operator name for the error message.

    Returns:
        ``axis`` normalized into ``[0, ndim)``.

    Raises:
        ValueError: If ``axis`` is outside ``[-ndim, ndim)`` or ``ndim == 0``.

    Example:
        >>> validate_axis(-1, 3, op="scan")
        2
    """
    if _BYPASS:
        return axis % max(ndim, 1)
    if ndim == 0:
        raise ValueError(f"{op}: input is 0-d; scans need at least one axis")
    if not -ndim <= axis < ndim:
        raise ValueError(f"{op}: axis {axis} is out of bounds for a "
                         f"{ndim}-d input (expected -{ndim} <= axis < {ndim})")
    return axis % ndim


def validate_bits_per_pass(bits_per_pass: int, *, op: str) -> int:
    """Reject ``bits_per_pass`` outside ``[1, 8]`` (the radix-2^k contract).

    Example:
        >>> validate_bits_per_pass(4, op="radix_sort")
        4
    """
    if not _BYPASS and not 1 <= int(bits_per_pass) <= 8:
        raise ValueError(f"{op}: bits_per_pass must be in [1, 8], got "
                         f"{bits_per_pass}")
    return int(bits_per_pass)


def validate_positive(value, *, name: str, op: str) -> int:
    """Reject a non-positive integer knob (tile sides, block counts, radices).

    Example:
        >>> validate_positive(128, name="s", op="scan_tiles")
        128
    """
    if not _BYPASS and int(value) < 1:
        raise ValueError(f"{op}: {name} must be >= 1, got {value!r}")
    return int(value)


def validate_choice(value, choices, *, name: str, op: str):
    """Reject a knob outside its closed set (e.g. an unknown kernel variant).

    Without this, an unknown ``variant=`` silently falls through a kernel's
    ``if``/``else`` chain onto whichever branch is last.

    Example:
        >>> validate_choice("scanul1", ("scanul1", "scanu"),
        ...                 name="variant", op="scan_tiles")
        'scanul1'
    """
    if not _BYPASS and value not in choices:
        raise ValueError(f"{op}: {name} must be one of {tuple(choices)}, "
                         f"got {value!r}")
    return value


def validate_broadcastable_to(b_shape, target, *, op: str,
                              name: str = "flags") -> None:
    """Reject a companion operand that does not broadcast to the payload shape.

    Example:
        >>> validate_broadcastable_to((8,), (4, 8), op="seg_scan_tiles")
    """
    if _BYPASS:
        return
    try:
        ok = jnp.broadcast_shapes(tuple(b_shape), tuple(target)) \
            == tuple(target)
    except ValueError:
        ok = False
    if not ok:
        raise ValueError(f"{op}: {name} shape {tuple(b_shape)} does not "
                         f"broadcast to the payload shape {tuple(target)}")


def validate_probability(p, *, name: str = "p", op: str) -> None:
    """Reject a concrete probability outside ``[0, 1]`` (NaN included).

    Traced values pass through (validated in-jit by :func:`guard_check` where
    an entry point stages one).

    Example:
        >>> validate_probability(0.9, op="top_p_sample")
    """
    if _BYPASS or not is_concrete(p):
        return
    v = float(p)
    if not 0.0 <= v <= 1.0:  # NaN fails every comparison -> rejected too
        raise ValueError(f"{op}: {name} must be in [0, 1], got {p!r}")


def validate_temperature(temperature, *, op: str) -> None:
    """Reject a concrete negative or NaN temperature.

    Zero is allowed — the sampler family documents ``temperature == 0`` as
    the deterministic greedy (argmax) limit.

    Example:
        >>> validate_temperature(0.0, op="top_p_sample")
    """
    if _BYPASS or not is_concrete(temperature):
        return
    v = float(temperature)
    if not v >= 0.0 or not np.isfinite(v):
        raise ValueError(f"{op}: temperature must be a finite value >= 0, "
                         f"got {temperature!r}")


def validate_offsets(offsets, n: int, *, op: str):
    """Validate CSR-style segment ``offsets`` against a length-``n`` value array.

    Static structure (rank, integer dtype, segment count) is always checked
    eagerly.  Concrete offsets are additionally checked for the full CSR
    contract — ``offsets[0] == 0``, ``offsets[-1] == n``, non-decreasing —
    with a ``ValueError`` at the call site; traced offsets stage the same
    contract as a checkified assertion (active under ``REPRO_CHECKS=1`` /
    :func:`checks`, fired by :func:`checked`).

    Args:
        offsets: ``(num_segments + 1,)`` int array.
        n: Length of the packed values array.
        op: Operator name for error messages.

    Returns:
        ``offsets`` unchanged.

    Raises:
        ValueError: Static-structure violation, or concrete offsets breaking
            the CSR contract.
        TypeError: Non-integer offsets dtype.

    Example:
        >>> o = jnp.asarray([0, 3, 5], jnp.int32)
        >>> validate_offsets(o, 5, op="segment_scan") is o
        True
    """
    if _BYPASS:
        return offsets
    offsets = jnp.asarray(offsets) if not isinstance(offsets, jax.Array) \
        and not isinstance(offsets, jax.core.Tracer) else offsets
    if offsets.ndim != 1:
        raise ValueError(f"{op}: offsets must be 1-D "
                         f"(num_segments + 1,), got shape {offsets.shape}")
    if offsets.shape[0] < 1:
        raise ValueError(f"{op}: offsets cannot be empty (need at least "
                         "[0] — one entry per segment boundary plus one)")
    if not jnp.issubdtype(offsets.dtype, jnp.integer):
        raise TypeError(f"{op}: offsets must be integer, got "
                        f"{offsets.dtype}")
    if is_concrete(offsets):
        off = np.asarray(offsets)
        if off[0] != 0:
            raise ValueError(f"{op}: offsets[0] must be 0, got {off[0]}")
        if off[-1] != n:
            raise ValueError(f"{op}: offsets[-1] ({off[-1]}) must equal the "
                             f"packed length ({n})")
        if np.any(np.diff(off) < 0):
            raise ValueError(f"{op}: offsets must be non-decreasing, got "
                             f"{off.tolist()}")
    else:
        guard_check(
            lambda: ((offsets[0] == 0) & (offsets[-1] == n)
                     & jnp.all(offsets[1:] >= offsets[:-1])),
            f"{op}: offsets violate the CSR contract (offsets[0] == 0, "
            f"offsets[-1] == n, non-decreasing)")
    return offsets


def validate_same_shape(a_shape: Tuple[int, ...], b_shape: Tuple[int, ...],
                        *, op: str, a_name: str = "x",
                        b_name: str = "flags") -> None:
    """Reject mismatched payload/flag shapes with a call-site error.

    The fused kernels reshape both operands together; a mismatch otherwise
    surfaces as a cryptic reshape/broadcast failure deep inside Pallas.

    Example:
        >>> validate_same_shape((4, 8), (4, 8), op="split")
    """
    if not _BYPASS and tuple(a_shape) != tuple(b_shape):
        raise ValueError(f"{op}: {a_name} shape {tuple(a_shape)} and "
                         f"{b_name} shape {tuple(b_shape)} must match")


# ---------------------------------------------------------------------------
# Backend capability probe (warn-once degrade for kernel/blocked dispatch)
# ---------------------------------------------------------------------------


# (backend, probe family, method) -> bool (lowering succeeded)
_PROBE_CACHE: dict = {}
_FORCED_FAILURES: List[Tuple[Optional[str], Optional[str]]] = []


def _reset_probes_for_testing() -> None:
    """Clear the probe cache (tests only)."""
    _PROBE_CACHE.clear()


@contextlib.contextmanager
def force_probe_failure(op: Optional[str] = None,
                        method: Optional[str] = None):
    """Make lowering probes fail inside the block (fault-injection hook).

    ``op``/``method`` restrict the simulated failure to one tuned family /
    one of ``("kernel", "blocked")``; ``None`` matches everything.  The probe
    cache is cleared on entry and restored on exit so the simulated failure
    neither sees nor pollutes real probe results.

    Example:
        >>> with force_probe_failure("scan", "kernel"):
        ...     probe_lowering("scan", "kernel")
        False
    """
    saved = dict(_PROBE_CACHE)
    _PROBE_CACHE.clear()
    _FORCED_FAILURES.append((op, method))
    try:
        yield
    finally:
        _FORCED_FAILURES.pop()
        _PROBE_CACHE.clear()
        _PROBE_CACHE.update(saved)


def _probe_family(op: str, method: str) -> str:
    """Collapse an entry-point op onto the kernel family its probe lowers."""
    fam = OP_ALIASES.get(op, op)
    if fam not in TUNED_OPS:
        fam = "scan"
    if method == "blocked" and fam in ("split", "sort", "top_p_sample"):
        # the blocked variants of the §5 operators are built from blocked
        # scans — they share the scan pipeline's probe
        fam = "scan"
    return fam


def _probe_lower(fam: str, method: str, s: int = 8) -> None:
    """Lower (without compiling) a tiny instance of the family's kernel."""
    from repro.kernels import ops as kops
    vec = jax.ShapeDtypeStruct((s * s,), jnp.float32)
    flg = jax.ShapeDtypeStruct((s * s,), jnp.int8)
    if fam == "linear_scan":
        if method == "kernel":
            jax.jit(lambda a, b: kops.linrec_kernel(a, b, s=s)).lower(vec, vec)
        else:
            jax.jit(lambda a, b: kops.linrec_blocked_kernel(
                a, b, s=s, block_tiles=2)).lower(vec, vec)
    elif fam == "segment_scan":
        if method == "kernel":
            jax.jit(lambda x, f: kops.seg_scan_kernel(x, f, s=s)).lower(vec, flg)
        else:
            jax.jit(lambda x, f: kops.seg_blocked_scan_kernel(
                x, f, s=s, block_tiles=2)).lower(vec, flg)
    elif fam == "split":
        jax.jit(lambda x, f: kops.split_kernel(x, f, s=s)).lower(vec, flg)
    elif fam == "sort":
        enc = jax.ShapeDtypeStruct((s * s,), jnp.int32)
        jax.jit(lambda e: kops.radix_sort_enc_kernel(
            e, bits=8, bits_per_pass=4, s=s)).lower(enc)
    elif fam == "top_p_sample":
        u = jax.ShapeDtypeStruct((1,), jnp.float32)
        jax.jit(lambda sp, uu: kops.topp_mask_sample_kernel(
            sp, uu, p=0.9, s=s)).lower(vec, u)
    else:  # scan
        if method == "kernel":
            jax.jit(lambda x: kops.scan_kernel(x, s=s)).lower(vec)
        else:
            jax.jit(lambda x: kops.blocked_scan_kernel(
                x, s=s, block_tiles=2)).lower(vec)


def probe_lowering(op: str, method: str, *,
                   backend: Optional[str] = None) -> bool:
    """Whether ``method`` for ``op`` lowers on ``backend`` (cached per family).

    The probe traces and **lowers** (never compiles) a tiny instance of the
    family's Pallas kernel under ``jax.jit(...).lower`` — lowering is where
    an unsupported backend/mode combination fails (e.g. forcing
    ``interpret=False`` on CPU), and it costs milliseconds-to-sub-second
    once per (backend, family, method) per process.

    Args:
        op: Entry-point operator name.
        method: ``"kernel"`` or ``"blocked"``.
        backend: Backend name; defaults to ``jax.default_backend()``.

    Returns:
        True when the probe lowers (or has lowered before); False on failure
        (cached, so the attempt happens once).

    Raises:
        RuntimeError: If the probe fails to lower on the ``tpu`` backend —
            there a kernel that does not lower is a fault, never a silent
            fallback.  Simulated failures (:func:`force_probe_failure`)
            still return False.

    Example:
        >>> probe_lowering("scan", "kernel", backend=jax.default_backend())
        True
    """
    if backend is None:
        backend = jax.default_backend()
    fam = _probe_family(op, method)
    for f_op, f_method in _FORCED_FAILURES:
        if (f_op is None or _probe_family(f_op, method) == fam) and \
                (f_method is None or f_method == method):
            return False
    key = (backend, fam, method)
    if key in _PROBE_CACHE:
        return _PROBE_CACHE[key]
    _PROBE_CACHE[key] = True  # recursion guard: a re-entrant probe passes
    try:
        # a TPU lowers kernels at its own lane width
        _probe_lower(fam, method, s=128 if backend == "tpu" else 8)
        ok = True
    except Exception as e:  # lowering errors are backend/version specific
        if backend == "tpu":
            # the kernels are written for this backend: a kernel it cannot
            # lower is a fault to report, not a reason to run something else
            del _PROBE_CACHE[key]
            raise RuntimeError(
                f"method={method!r} for op {op!r} does not lower on the TPU "
                f"backend: {type(e).__name__}: {e}") from e
        ok = False
    _PROBE_CACHE[key] = ok
    return ok


def _fallback_method(op: str) -> str:
    """The method a failed probe degrades to (table ``fallbacks``, else vector)."""
    fam = OP_ALIASES.get(op, op)
    table = load_table() or {}
    fb = table.get("fallbacks", {}).get(fam)
    if fb in CONCRETE_METHODS and fb not in ("kernel", "blocked"):
        return fb
    return "vector"


def ensure_available(method: str, op: str, *,
                     backend: Optional[str] = None) -> str:
    """Degrade ``kernel``/``blocked`` dispatch when the backend can't lower it.

    Called by :func:`repro.core.autotune.maybe_resolve` on every concrete
    resolution, so explicit ``method="kernel"`` calls and table-resolved
    ``"auto"`` calls degrade identically — the same script runs unmodified on
    a backend without Pallas support.  On a TPU a real lowering failure
    raises instead (:func:`probe_lowering`).  The degradation warns **once** per
    (backend, family, method) with :class:`ProbeFallbackWarning` and resolves
    through the tuning table's ``fallbacks`` entry (else ``"vector"``),
    extending the rule-8 warn-once taxonomy.

    Args:
        method: A **concrete** method (never ``"auto"``).
        op: Entry-point operator name.
        backend: Backend name; defaults to ``jax.default_backend()``.

    Returns:
        ``method``, or its fallback when the probe fails.

    Example:
        >>> ensure_available("matmul", "scan")   # XLA methods never probe
        'matmul'
        >>> ensure_available("kernel", "scan")   # lowers on every CI backend
        'kernel'
    """
    if _BYPASS or method not in ("kernel", "blocked"):
        return method
    if backend is None:
        backend = jax.default_backend()
    if probe_lowering(op, method, backend=backend):
        return method
    fb = _fallback_method(op)
    fam = _probe_family(op, method)
    _warn_once(
        f"probe:{backend}:{fam}:{method}",
        f"method={method!r} for op {op!r} does not lower on backend "
        f"{backend!r}; degrading to method={fb!r} (dispatch rule 10 — "
        "probe once, warn once, fall back through the tuning table)",
        category=ProbeFallbackWarning)
    return fb
