"""Mixed-precision MXU policy layer — named GEMM modes for every hot path.

"Large Scale Distributed Linear Algebra With TPUs" (arXiv:2112.09017)
shows fp32-grade GEMM composed from bf16 MXU passes running near bf16
peak. The MXU natively multiplies bf16 with fp32 accumulation;
``lax.Precision.HIGHEST`` spends SIX bf16 passes per product for full
fp32 fidelity. This module names the useful points on that curve and
gives every GEMM-dominated op family ONE policy chokepoint:

  ``f32``     today's HIGHEST, bit-for-bit — the default everywhere.
  ``bf16x3``  the classic 3-pass compensated split: a = hi + lo with
              both parts bf16-representable, A·B ≈ Ahi·Bhi + Ahi·Blo
              + Alo·Bhi (only the lo·lo term is dropped). Documented
              bound: max rel err ≤ 2e-4 vs f32 (measured ~1e-6 on the
              benchmark shapes; the bound is the COMMIT bar, not the
              typical error). Half of HIGHEST's passes.
  ``bf16``    plain bf16 multiply, f32 accumulate — ONE pass, for
              tolerance-insensitive serving/predict paths only.
              Documented bound: max rel err ≤ 3e-2 vs f32.

An operand that is exact in one bf16 piece (a one-hot matrix) needs
THREE passes for the full f32 product, not six: :func:`split3_bf16`
splits the other operand exactly and each piece is one pass
(``ops/kmeans.py``'s centre update).

The hi/lo parts are bf16-representable values carried in f32
containers, so single-pass dots on the parts are EXACT products on
both the MXU and CPU — the compensated result is backend-consistent,
which is what lets CPU CI pin the parity tables.

Policy resolution (:func:`resolve_policy`) layers, strongest first:
explicit ``setPrecision(...)`` on the estimator, the per-family
``TPUML_PRECISION_<FAMILY>`` knob, the global ``TPUML_PRECISION``
knob, a committed autotuner decision (knob ``precision_mode``), then
the family default — so with no knobs and ``TPUML_AUTOTUNE=off``
nothing changes, bit-for-bit.

The autotuner is the gatekeeper for automatic adoption
(:func:`tune_precision`): a candidate mode commits iff its measured
probe wall BEATS the f32 incumbent AND the parity probe holds at the
documented bound; a regression or parity miss is recorded ``rejected``
in the tune store and the incumbent stands.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops.linalg import _dot_precision

# Named policy modes (new vocabulary) and the legacy lax.Precision names
# that remain valid everywhere a mode string is accepted.
MODES = ("f32", "bf16x3", "bf16")
LEGACY = ("default", "high", "highest")

FAMILIES = ("covariance", "pca", "kmeans", "logistic", "linear", "serving")

PRECISION_ENV = "TPUML_PRECISION"
PRECISION_KNOB = "precision_mode"  # tune-store knob name

# Documented parity bounds vs the f32 reference (max |err| / max |ref|).
# These are the autotuner's COMMIT bars and the test-suite tolerances.
REL_TOL = {"bf16x3": 2e-4, "bf16": 3e-2}

# bf16 passes each mode spends per GEMM product — the roofline currency:
# a mode's achievable flops ceiling is bf16_peak / passes.
PASSES = {"f32": 6, "highest": 6, "high": 3, "bf16x3": 3, "default": 1, "bf16": 1}

# Registered-for-tests modes: name -> (dot callable, parity rel tol).
# The seeded parity-violating mode the autotuner must reject lives here.
_TEST_MODES: Dict[str, Tuple[Callable, float]] = {}

# family -> last resolved mode, consumed by the cost-ledger roofline so
# utilization prices against the ACTIVE policy's peak (ISSUE 17 sat. 1).
_ACTIVE_MODES: Dict[str, str] = {}


def register_test_mode(name: str, dot: Callable, rel_tol: float = 0.0) -> None:
    """Install a synthetic precision mode (tests only): ``dot(a, b)``
    replaces the GEMM, ``rel_tol`` is its parity bar for the tuner."""
    _TEST_MODES[name] = (dot, float(rel_tol))


def clear_test_modes() -> None:
    _TEST_MODES.clear()


def valid_modes() -> tuple:
    return MODES + LEGACY + tuple(_TEST_MODES)


def validate_mode(value: str) -> str:
    if value not in valid_modes():
        raise ValueError(
            f"precision mode must be one of {'/'.join(MODES + LEGACY)}, "
            f"got {value!r}"
        )
    return value


def split_hi_lo(a):
    """bf16 hi/lo split in f32 containers: a == hi + lo exactly, with
    ``hi`` the bf16 rounding of ``a`` (bf16-representable, so its
    DEFAULT-precision products are exact) and ``lo`` the residual
    carrying the next mantissa bits (|lo| <= 2^-9 |a|; its own bf16
    rounding inside a DEFAULT dot is the mode's error term, inside the
    documented :data:`REL_TOL` bound). NOT safe on non-finite values:
    hi(inf) = inf and lo = inf - inf = NaN — which is why sentinel
    constants on compensated paths must stay finite."""
    hi = a.astype(jnp.bfloat16).astype(a.dtype)
    return hi, a - hi


def split3_bf16(a):
    """Exact three-piece bfloat16 split of a float32 array: returns
    ``(hi, mid, lo)`` with dtype bfloat16 and ``(hi + mid) + lo == a`` bit
    for bit in float32 (8 + 8 + 8 mantissa bits, each residual an exact
    float32 subtraction). Built on ``lax.reduce_precision``, which the TPU
    compiler may not simplify away as it does the ``astype`` round trip
    of :func:`split_hi_lo` (PERF.md 7.9); the pieces ARE bfloat16, so a
    dot on them is one MXU pass whatever ``precision`` says. A product
    whose other operand is exact in one bfloat16 piece (a one-hot matrix)
    therefore needs THREE passes for the full float32 result, not
    HIGHEST's six. Finite inputs only, as :func:`split_hi_lo`; under
    |a| = 2^-103 (1e-31) the ``lo`` piece is subnormal, which a TPU
    flushes to zero."""
    hi = jax.lax.reduce_precision(a, 8, 7)
    r = a - hi
    mid = jax.lax.reduce_precision(r, 8, 7)
    lo = r - mid
    bf16 = jnp.bfloat16
    return hi.astype(bf16), mid.astype(bf16), lo.astype(bf16)


def is_highest_matmul(dot) -> bool:
    """True where ``dot`` is the plain ``jnp.matmul`` at
    ``Precision.HIGHEST`` that :func:`make_dot` returns for ``f32`` /
    ``highest`` (and :func:`as_dot` for the bare enum): what a caller
    can observe of the policy at trace time when it wants to spend fewer
    passes on an operand it knows to be exact in bfloat16."""
    return (
        isinstance(dot, partial)
        and dot.func is jnp.matmul
        and not dot.args
        and dot.keywords == {"precision": jax.lax.Precision.HIGHEST}
    )


def _dot_bf16x3(a, b):
    if jnp.result_type(a, b) == jnp.float64:
        # Compensated modes target f32 data; under x64 the reference
        # numerics ARE native f64 — keep them.
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    a_hi, a_lo = split_hi_lo(a)
    b_hi, b_lo = split_hi_lo(b)
    d = partial(
        jnp.matmul,
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )
    return d(a_hi, b_hi) + d(a_hi, b_lo) + d(a_lo, b_hi)


def _dot_bf16(a, b):
    if jnp.result_type(a, b) == jnp.float64:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(
        a.astype(jnp.bfloat16),
        b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def make_dot(precision: str) -> Callable:
    """The ONE chokepoint mapping a mode name to a matmul-like callable.

    Legacy names and ``f32`` return a plain ``jnp.matmul`` closure at the
    corresponding ``lax.Precision`` — the SAME primitive sequence as
    before this layer existed, so the default policy is bit-identical.
    ``precision`` is static at every call site (jit static argname), so
    the choice resolves at trace time."""
    if precision in _TEST_MODES:
        return _TEST_MODES[precision][0]
    if precision == "bf16x3":
        return _dot_bf16x3
    if precision == "bf16":
        return _dot_bf16
    legacy = "highest" if precision == "f32" else precision
    return partial(jnp.matmul, precision=_dot_precision(legacy))


def as_dot(dot) -> Callable:
    """Coerce any historical precision spelling to a matmul callable:
    a callable passes through, a mode name goes through
    :func:`make_dot`, and a bare ``lax.Precision`` enum (the
    pre-policy-layer currency some helpers were called with) wraps into
    a plain matmul at that precision."""
    if isinstance(dot, str):
        return make_dot(dot)
    if callable(dot):
        return dot
    return partial(jnp.matmul, precision=dot)


def pdot(a, b, precision: str = "f32"):
    """Policy-aware matmul — ``jnp.matmul`` with a mode name."""
    return make_dot(precision)(a, b)


def pallas_precision(precision: str) -> str:
    """Map a policy mode onto the pallas kernels' precision vocabulary.

    The fused/packed KMeans kernels already implement the 3-pass
    compensated split as their ``"high"`` emulation (Mosaic has no HIGH
    mapping), so ``bf16x3`` lowers to exactly that code path."""
    return {"f32": "highest", "bf16x3": "high", "bf16": "default"}.get(
        precision, precision
    )


def mode_passes(mode: str) -> Optional[int]:
    return PASSES.get(mode)


# ---------------------------------------------------------------------------
# active-mode registry — the roofline's source of truth
# ---------------------------------------------------------------------------


def note_mode(family: str, mode: str) -> None:
    """Record the mode a family resolved to — consumed by
    :func:`roofline_peak_scale` so ``fit_report()``/``tpuml_prof`` price
    utilization against the active policy's peak."""
    _ACTIVE_MODES[family] = mode


def active_modes() -> Dict[str, str]:
    """Copy of the full family -> resolved-mode registry (the cost
    ledger snapshots this into its dump for offline renderers)."""
    return dict(_ACTIVE_MODES)


# Ledger program families for forward passes (kmeans.predict,
# pca.transform, …) run under the SERVING policy, not the fit family the
# prefix would suggest.
SERVING_SUFFIXES = ("predict", "transform", "serve")


def active_mode(family: str) -> Optional[str]:
    """Last resolved mode for ``family``; ledger program families carry
    a dotted suffix (e.g. ``kmeans.lloyd``) — a serving suffix maps to
    the ``serving`` policy, anything else falls back to the bare family
    prefix."""
    mode = _ACTIVE_MODES.get(family)
    if mode is None and "." in family:
        if family.rsplit(".", 1)[1] in SERVING_SUFFIXES:
            mode = _ACTIVE_MODES.get("serving")
        if mode is None:
            mode = _ACTIVE_MODES.get(family.split(".", 1)[0])
    return mode


def roofline_peak_scale(program_family: str) -> float:
    """Factor to multiply the declared ``TPUML_PEAK_FLOPS`` by for a
    ledger program family: the declared peak is the fp32 (6-pass)
    ceiling, and a mode spending fewer bf16 passes has proportionally
    more headroom (bf16x3 → 2x, bf16 → 6x). 1.0 when no mode was ever
    recorded for the family — exactly the pre-policy behavior."""
    mode = active_mode(program_family)
    if mode is None:
        return 1.0
    passes = PASSES.get(mode)
    if not passes:
        return 1.0
    return PASSES["f32"] / passes


def reset_for_tests() -> None:
    _ACTIVE_MODES.clear()
    _TEST_MODES.clear()


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------


def family_env(family: str) -> str:
    return f"TPUML_PRECISION_{family.upper()}"


def _env_mode(name: str) -> Optional[str]:
    from spark_rapids_ml_tpu.utils.envknobs import EnvKnobError, env_str

    value = env_str(name)
    if value is None:
        return None
    if value not in valid_modes():
        raise EnvKnobError(name, value, f"one of {'|'.join(MODES + LEGACY)}")
    return value


def resolve_policy(
    family: str, requested: Optional[str] = None, default: str = "highest"
) -> str:
    """Resolve the active precision mode for an op family.

    ``requested`` is the EXPLICITLY-set estimator param value (None when
    the user never called ``setPrecision``; ``"auto"``/``"dd"`` keep
    their pre-existing resolution and are passed through). Layering:
    explicit param > per-family env knob > global env knob > committed
    autotuner decision > ``default``."""
    if family not in FAMILIES:
        raise ValueError(f"unknown precision family {family!r}")
    if requested is not None and requested != "auto":
        # Explicit setPrecision wins outright; "dd" keeps its dedicated
        # double-double resolution downstream.
        mode = requested if requested == "dd" else validate_mode(requested)
        note_mode(family, mode)
        return mode
    mode = _env_mode(family_env(family)) or _env_mode(PRECISION_ENV)
    if mode is None and requested is None:
        from spark_rapids_ml_tpu.observability import autotune as _autotune

        tuner = _autotune.active()
        if tuner is not None:
            mode = tune_precision(family, tuner=tuner)
    if mode is None:
        mode = requested if requested is not None else default
    note_mode(family, mode)
    return mode


# ---------------------------------------------------------------------------
# autotuner gate
# ---------------------------------------------------------------------------

# Per-family candidate ladder, fastest-last. Fit families trial only the
# compensated mode (fits feed downstream math); serving may also trial
# plain bf16 (tolerance-insensitive predict paths).
_CANDIDATES = {"serving": ("bf16x3", "bf16")}
_DEFAULT_CANDIDATES = ("bf16x3",)

# Probe GEMM: big enough that the mode's pass count dominates the wall,
# small enough to amortize into one fit (~1 MFLOP-scale, compiled once).
_PROBE_M, _PROBE_K, _PROBE_N = 512, 256, 256


@partial(jax.jit, static_argnames=("mode",))
def _probe_gemm(a, b, mode: str):
    return pdot(a, b, mode)


def _probe_operands():
    rng = np.random.default_rng(0)
    a = jnp.asarray(
        rng.standard_normal((_PROBE_M, _PROBE_K)), dtype=jnp.float32
    )
    b = jnp.asarray(
        rng.standard_normal((_PROBE_K, _PROBE_N)), dtype=jnp.float32
    )
    return a, b


def _time_probe(a, b, mode: str, repeats: int = 3) -> tuple:
    import time

    out = _probe_gemm(a, b, mode)  # compile excluded from timing
    out.block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = _probe_gemm(a, b, mode)
        out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return np.asarray(out), best


def candidate_rel_tol(mode: str) -> float:
    if mode in _TEST_MODES:
        return _TEST_MODES[mode][1]
    return REL_TOL.get(mode, 0.0)


def tune_precision(
    family: str, tuner=None, candidates: Optional[tuple] = None
) -> Optional[str]:
    """Trial faster precision modes for ``family`` through the autotuner
    and return the committed mode (or None when the tuner is off).

    The f32 reference runs first and seeds the incumbent; each candidate
    then commits iff its measured probe wall BEATS the incumbent AND its
    max relative error vs the f32 result stays within the documented
    bound (:data:`REL_TOL`). A slower candidate is recorded rejected
    with reason ``regression``; an out-of-bound one with reason
    ``parity`` — and the incumbent stands. Decisions persist in the tune
    store, so the probe runs once per (family, store)."""
    if tuner is None:
        from spark_rapids_ml_tpu.observability import autotune as _autotune

        tuner = _autotune.active()
        if tuner is None:
            return None
    decision = tuner.store.get(PRECISION_KNOB, family)
    if decision is not None:
        value = decision.get("value")
        return str(value) if value else None

    a, b = _probe_operands()
    shape = f"{_PROBE_M}x{_PROBE_K}x{_PROBE_N}"
    ref, wall_ref = _time_probe(a, b, "f32")
    tuner.record_trial(
        PRECISION_KNOB, family, "f32", wall_ref,
        evidence=[f"probe={shape}"], metric_name="probe_seconds",
    )
    scale = float(np.max(np.abs(ref))) or 1.0
    for mode in candidates or _CANDIDATES.get(family, _DEFAULT_CANDIDATES):
        res, wall = _time_probe(a, b, mode)
        err = float(np.max(np.abs(res - ref))) / scale
        tol = candidate_rel_tol(mode)
        tuner.record_trial(
            PRECISION_KNOB, family, mode, wall,
            evidence=[f"probe={shape}", f"max_rel_err={err:.3e}", f"tol={tol:.1e}"],
            metric_name="probe_seconds",
            ok=err <= tol,
            reason="parity",
        )
    decision = tuner.store.get(PRECISION_KNOB, family)
    if decision is None:
        return None
    value = decision.get("value")
    return str(value) if value else None
