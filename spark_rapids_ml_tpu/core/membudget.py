"""Fit-path device-memory budget: pricing, admission, degradation, recovery.

The serving tier has priced every request against a device-byte budget
since PR 5 (``serving/admission.py``), closed with the cost ledger's
measurements in PR 8 — but the FIT path still trusted the caller: an
oversized host matrix died inside ``prepare_rows``' ``device_put`` with a
raw ``XlaRuntimeError``. This module is the training twin of that
admission story, the "bound memory BEFORE launching" discipline of
"Memory Safe Computations with XLA" (arXiv 2206.14148) applied where the
paper's PCA workload actually hits the HBM wall:

  1. **Pricing** — :func:`padded_input_bytes` mirrors the
     ``prepare_rows`` placement spec (rows x features x dtype plus the
     validity mask, mesh padding included); when the family's programs
     have compiled before, :func:`ledger_measured_bytes` adds the cost
     ledger's MEASURED temp+output bytes. The measured-else-declared
     decision itself (:func:`measured_or_declared`) is shared with the
     serving admission gate.
  2. **Admission** — :func:`fit_memory_guard` prices a host input against
     :func:`fit_mem_budget` (``TPUML_FIT_MEM_BUDGET``; default = live
     free HBM from ``memory_stats()``; 0 = gate off). Over-budget inputs
     either reroute to the family's EXISTING streaming fit through a
     re-iterable block reader (``TPUML_FIT_DEGRADE=auto``) or raise the
     structured :class:`FitMemoryError` — never a raw XLA crash.
  3. **Recovery** — :func:`run_fit_with_oom_recovery` /
     :func:`run_streaming_with_recovery` classify ``RESOURCE_EXHAUSTED``
     at the fit chokepoints as a retryable degradation: reclaim the
     program/device caches, retry streaming at halved block rows, then
     give a structured error with the knobs to turn.

Everything observable: ``fit_admission`` events, ``fit.admission.*`` /
``fit.oom.*`` counters, and the shared ``degrade`` warning/event/counter
triple from ``robustness/degrade.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, TypeVar

import numpy as np

from spark_rapids_ml_tpu.observability.events import emit
from spark_rapids_ml_tpu.robustness.degrade import record_degradation
from spark_rapids_ml_tpu.robustness.retry import is_oom_error
from spark_rapids_ml_tpu.utils.envknobs import env_choice, env_int
from spark_rapids_ml_tpu.utils.tracing import StageRange, bump_counter

T = TypeVar("T")

FIT_MEM_BUDGET_ENV = "TPUML_FIT_MEM_BUDGET"
FIT_OOM_RETRIES_ENV = "TPUML_FIT_OOM_RETRIES"
FIT_DEGRADE_ENV = "TPUML_FIT_DEGRADE"

DEFAULT_FIT_OOM_RETRIES = 3

#: Halving never goes below this: a block this small that still OOMs is
#: not a blocking problem, and sub-row-group reads would thrash anyway.
MIN_BLOCK_ROWS = 256


class FitMemoryError(RuntimeError):
    """An estimator fit cannot run within the device-memory budget and no
    degradation rung was available — the structured, actionable
    replacement for a raw ``XlaRuntimeError``. Carries ``family``,
    ``needed_bytes`` and ``budget_bytes`` (0 when unknown); the message
    names the knobs and inputs that unblock the fit."""

    def __init__(
        self,
        family: str,
        why: str,
        *,
        needed_bytes: int = 0,
        budget_bytes: int = 0,
        hint: str = "",
    ):
        self.family = family
        self.needed_bytes = int(needed_bytes)
        self.budget_bytes = int(budget_bytes)
        parts = [f"{family} fit cannot run within the device-memory budget: {why}"]
        if needed_bytes:
            parts.append(
                f"priced ~{self.needed_bytes:,} device bytes against a "
                f"budget of {self.budget_bytes:,}"
            )
        parts.append(
            hint
            or (
                f"raise {FIT_MEM_BUDGET_ENV} (or set it to 0 to disable the "
                "gate), pass a streaming source (core.data.ArrowBlockReader "
                "over parquet, or a block reader / iterator factory), or "
                "shrink the input"
            )
        )
        super().__init__(" — ".join(parts))


# --- budget & knob resolution ------------------------------------------


def free_hbm_bytes() -> Optional[int]:
    """Live free HBM of the first device that reports allocator stats
    (``bytes_limit - bytes_in_use``), or None when no device does — the
    CPU backend's ``memory_stats()`` is None, which resolves the default
    budget to "gate off" exactly where there is no HBM to protect. A
    backend that fails to come up, or whose stats call raises, raises
    here: a swallowed error would read as "gate off" on a real device."""
    import jax

    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
    return None


def fit_mem_budget() -> int:
    """The resolved fit admission budget in bytes: an explicit
    ``TPUML_FIT_MEM_BUDGET`` wins (0 = gate off); unset defaults to the
    live free-HBM watermark, and 0/off wherever the backend reports no
    memory stats."""
    explicit = env_int(FIT_MEM_BUDGET_ENV, None, minimum=0)
    if explicit is not None:
        return explicit
    return free_hbm_bytes() or 0


def fit_oom_retries() -> int:
    """Streaming attempts after a device OOM (block rows halving between
    attempts) before the structured budget error."""
    return env_int(FIT_OOM_RETRIES_ENV, DEFAULT_FIT_OOM_RETRIES, minimum=1)


def degrade_to_streaming_enabled() -> bool:
    """``TPUML_FIT_DEGRADE``: auto (default) reroutes over-budget host
    fits to streaming; off raises :class:`FitMemoryError` instead."""
    return env_choice(FIT_DEGRADE_ENV, ("auto", "off"), "auto") == "auto"


# --- pricing ------------------------------------------------------------


def padded_input_bytes(n: int, d: int, dtype: Any, mesh: Any = None) -> int:
    """Device bytes ``prepare_rows`` will allocate for an (n, d) host
    input: the padded data matrix plus the row-validity mask, using the
    same padding arithmetic as the placement itself."""
    from spark_rapids_ml_tpu.core.ingest import _mask_dtype

    np_dtype = np.dtype(dtype)
    n_pad, d_pad = int(n), int(d)
    if mesh is not None:
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, model_axis_size

        dp = int(mesh.shape[DATA_AXIS])
        mp = model_axis_size(mesh)
        n_pad += (-n_pad) % dp
        d_pad += (-d_pad) % mp
    mask_itemsize = np.dtype(_mask_dtype(np_dtype)).itemsize
    return n_pad * d_pad * np_dtype.itemsize + n_pad * mask_itemsize


def ledger_measured_bytes(*family_prefixes: str) -> Optional[int]:
    """The cost ledger's measured temp+output bytes for this fit family —
    the largest measurement across entries whose family matches one of
    the prefixes — or None when nothing matching has compiled under the
    ledger yet. Best-effort by design: a measurement from a differently
    shaped run still bounds the solver's working set better than nothing."""
    from spark_rapids_ml_tpu.observability import costs

    ledger = costs.active()
    if ledger is None:
        return None
    best: Optional[int] = None
    for entry in ledger.entries():
        if not any(entry.family.startswith(p) for p in family_prefixes):
            continue
        measured = entry.measured_request_bytes()
        if measured and (best is None or measured > best):
            best = measured
    return best


def measured_or_declared(
    measured: Optional[int], declared: int, counter_prefix: str
) -> int:
    """The one measured-else-declared pricing decision, shared by the
    serving admission gate and the fit guard: a ledger MEASUREMENT (what
    XLA actually allocates) outranks the declared-spec estimate, and the
    ``<prefix>.measured`` / ``<prefix>.declared`` counters record which
    side priced each decision."""
    if measured is not None:
        bump_counter(f"{counter_prefix}.measured")
        return int(measured)
    bump_counter(f"{counter_prefix}.declared")
    return int(declared)


# --- admission ----------------------------------------------------------


@dataclass
class FitAdmission:
    """One admission decision. ``degrade=True`` means the caller must
    reroute to its streaming fit over :attr:`matrix` (densified host
    truth); ``degrade=False`` means proceed in memory."""

    degrade: bool
    matrix: Optional[np.ndarray] = None
    needed_bytes: int = 0
    budget_bytes: int = 0
    reason: str = ""


_ADMIT = FitAdmission(degrade=False)


def host_matrix(rows: Any) -> np.ndarray:
    """Densify a host fit input to the 2-D matrix the streaming reroute
    blocks over, at the dtype the in-memory path would have used."""
    from spark_rapids_ml_tpu.core.data import as_matrix, infer_input_dtype

    return as_matrix(rows, dtype=infer_input_dtype(rows))


def fit_memory_guard(
    family: str,
    rows: Any,
    *,
    can_stream: bool,
    why_cannot_stream: str = "",
    mesh: Any = None,
    dtype: Any = None,
    ledger_families: Sequence[str] = (),
    extra_bytes: int = 0,
) -> FitAdmission:
    """Price a fit's host input against the device-memory budget.

    Waves through (``degrade=False``) whenever there is nothing to
    decide: gate off, input already streaming or device-resident, mesh
    fits (sharded placement prices per-device and relaunches rather than
    degrades), or an input whose shape cannot be known without the very
    copy this gate exists to avoid. Over budget, either returns a
    ``degrade=True`` decision (recording the warning + event + counter)
    or raises :class:`FitMemoryError` when this configuration cannot
    stream or ``TPUML_FIT_DEGRADE=off``.

    ``extra_bytes`` prices sidecar device arrays sized with the input
    (labels, per-row stats); ``ledger_families`` names the cost-ledger
    program families whose measured temp+output bytes ride on top.

    The whole decision is the ``admit`` stage of the fit.
    """
    with StageRange("admit"):
        return _price_fit_input(
            family, rows, can_stream, why_cannot_stream, mesh, dtype,
            ledger_families, extra_bytes,
        )


def _price_fit_input(
    family: str,
    rows: Any,
    can_stream: bool,
    why_cannot_stream: str,
    mesh: Any,
    dtype: Any,
    ledger_families: Sequence[str],
    extra_bytes: int,
) -> FitAdmission:
    from spark_rapids_ml_tpu.core.data import host_rows_shape, is_streaming_source

    if mesh is not None or is_streaming_source(rows):
        return _ADMIT
    budget = fit_mem_budget()
    if budget <= 0:
        return _ADMIT
    shape = host_rows_shape(rows)
    if shape is None:
        return _ADMIT
    n, d = shape
    if dtype is None:
        from spark_rapids_ml_tpu.core.ingest import default_dtype

        dtype = default_dtype()
    declared = padded_input_bytes(n, d, dtype) + int(extra_bytes)
    # Decision (d) of the autotuner: when on AND the family has a fitted
    # bytes model, price the candidate through the measured model —
    # argument + temp + output bytes at this row count — instead of
    # re-deriving the padding arithmetic from the declared shape. Tuner
    # off, or no model yet: the static pricing bit-for-bit.
    from spark_rapids_ml_tpu.observability import autotune as _autotune

    tuner = _autotune.active()
    if tuner is not None:
        model_priced = tuner.price_input_bytes(family, n)
        if model_priced is not None:
            bump_counter("fit.admission.model_priced")
            declared = model_priced + int(extra_bytes)
    measured = ledger_measured_bytes(*ledger_families) if ledger_families else None
    # Input placement is unavoidable either way; the ledger measurement
    # bounds the solver's temp+output working set ON TOP of it.
    needed = declared + measured_or_declared(measured, 0, "fit.admission")
    if needed <= budget:
        bump_counter("fit.admission.admitted")
        return _ADMIT
    if can_stream and degrade_to_streaming_enabled():
        bump_counter("fit.admission.degraded")
        emit(
            "fit_admission", action="degrade", family=family, rows=n,
            features=d, needed_bytes=needed, budget_bytes=budget,
        )
        record_degradation(
            f"{family} fit",
            f"input of ~{needed:,} device bytes exceeds the fit memory "
            f"budget of {budget:,} (set {FIT_DEGRADE_ENV}=off to fail "
            "instead)",
            "streaming",
            "the streaming fit path",
        )
        return FitAdmission(
            degrade=True,
            matrix=host_matrix(rows),
            needed_bytes=needed,
            budget_bytes=budget,
            reason="over budget",
        )
    bump_counter("fit.admission.rejected")
    emit(
        "fit_admission", action="reject", family=family, rows=n,
        features=d, needed_bytes=needed, budget_bytes=budget,
        can_stream=can_stream,
    )
    why = "input exceeds the budget"
    if not can_stream:
        why += " and " + (
            why_cannot_stream or "this family has no streaming fit"
        )
    else:
        why += f" and {FIT_DEGRADE_ENV}=off disables streaming degradation"
    raise FitMemoryError(
        family, why, needed_bytes=needed, budget_bytes=budget
    )


def batch_within_budget(
    family: str, resident_bytes: int, per_item_bytes: int, items: int, item: str = "item"
) -> int:
    """How many of ``items`` equal work items (a forest's trees) may be in
    flight at once: as many as fit the fit budget beside ``resident_bytes``,
    priced from shapes by the caller. All of them with the gate off; a
    structured :class:`FitMemoryError` — before anything is compiled — when
    not even one fits."""
    budget = fit_mem_budget()
    if budget <= 0:
        return items
    fit = (budget - int(resident_bytes)) // max(1, int(per_item_bytes))
    if fit >= 1:
        bump_counter("fit.admission.admitted")
        return int(min(items, fit))
    needed = int(resident_bytes) + int(per_item_bytes)
    bump_counter("fit.admission.rejected")
    emit(
        "fit_admission", action="reject", family=family,
        needed_bytes=needed, budget_bytes=budget, can_stream=False,
    )
    raise FitMemoryError(
        family,
        f"the resident arrays and the working set of one {item} exceed the budget",
        needed_bytes=needed, budget_bytes=budget,
        hint=(
            f"raise {FIT_MEM_BUDGET_ENV} (or set it to 0 to disable the gate), "
            f"or shrink the input or what one {item} holds"
        ),
    )


# --- OOM recovery -------------------------------------------------------


def _reclaim() -> None:
    from spark_rapids_ml_tpu.core.serving import reclaim_device_memory

    reclaim_device_memory()


def run_streaming_with_recovery(
    family: str,
    fit_with_reader: Callable[[Any], T],
    matrix: np.ndarray,
    *,
    block_rows: Optional[int] = None,
) -> T:
    """Run a streaming fit over ``matrix`` through a fresh
    :class:`~spark_rapids_ml_tpu.core.data.HostArrayBlockReader`,
    retrying at HALVED block rows after each device OOM (caches reclaimed
    between attempts) up to ``TPUML_FIT_OOM_RETRIES`` attempts. The first
    attempt uses the same default block size an explicit streaming fit
    would, so an undisturbed degraded fit is bit-identical to the
    explicit one."""
    from spark_rapids_ml_tpu.core.data import HostArrayBlockReader, fit_block_rows
    from spark_rapids_ml_tpu.observability import autotune as _autotune

    tuner = _autotune.active()
    if block_rows:
        block = int(block_rows)
        tuner = None  # caller-pinned block: nothing to tune or record
    else:
        block = fit_block_rows(
            family,
            width=int(matrix.shape[1]),
            itemsize=int(np.dtype(matrix.dtype).itemsize),
        )
    attempts = fit_oom_retries()
    last: Optional[BaseException] = None
    for attempt in range(attempts):
        try:
            if tuner is not None:
                # Measure-and-commit: the fit runs under the ledger and
                # its seconds-per-row either commits this block size as
                # the family incumbent or is recorded as a rejected
                # candidate — a regression is never accepted.
                result, _, _ = tuner.measure_and_commit(
                    "fit_block_rows",
                    family,
                    block,
                    lambda: fit_with_reader(
                        HostArrayBlockReader(matrix, block_rows=block)
                    ),
                    rows=int(matrix.shape[0]),
                )
            else:
                result = fit_with_reader(
                    HostArrayBlockReader(matrix, block_rows=block)
                )
            if attempt:
                bump_counter("fit.oom.recovered")
                emit(
                    "fit_admission", action="recovered", family=family,
                    attempt=attempt, block_rows=block,
                )
            return result
        except FitMemoryError:
            raise
        except BaseException as exc:
            if not is_oom_error(exc):
                raise
            last = exc
            bump_counter("fit.oom.events")
            _reclaim()
            if tuner is not None:
                # Ledgered evidence this block OOMed: the tuner will
                # never propose a block at or above it again.
                tuner.note_oom(family, block)
            if attempt + 1 < attempts:
                block = max(MIN_BLOCK_ROWS, block // 2)
                bump_counter("fit.oom.block_halved")
                emit(
                    "fit_admission", action="halve", family=family,
                    attempt=attempt, block_rows=block,
                )
    raise FitMemoryError(
        family,
        f"streaming fit still exhausted device memory after {attempts} "
        f"attempt(s) down to {block} rows per block",
    ) from last


def run_fit_with_oom_recovery(
    family: str,
    attempt_fn: Callable[[], T],
    fallback: Optional[Callable[[], T]] = None,
) -> T:
    """Run the in-memory fit body; classify a device OOM (real
    ``RESOURCE_EXHAUSTED`` or injected ``:oom`` fault, possibly wrapped
    in a ``RetryExhaustedError``) as a retryable degradation: reclaim the
    program/device caches and run ``fallback`` (the family's streaming
    reroute). Without a fallback — or with ``TPUML_FIT_DEGRADE=off`` —
    the OOM becomes a structured :class:`FitMemoryError`; it never
    escapes raw. Every other error propagates untouched."""
    try:
        return attempt_fn()
    except FitMemoryError:
        raise
    except BaseException as exc:
        if not is_oom_error(exc):
            raise
        # The recovery's own work is admission work; the fallback fit it
        # then runs opens its own stages.
        with StageRange("admit"):
            bump_counter("fit.oom.events")
            emit(
                "fit_admission", action="oom", family=family,
                error=type(exc).__name__,
            )
            _reclaim()
            if fallback is None or not degrade_to_streaming_enabled():
                bump_counter("fit.admission.rejected")
                raise FitMemoryError(
                    family,
                    "device memory was exhausted mid-fit and this "
                    "configuration cannot degrade to streaming",
                ) from exc
            record_degradation(
                f"{family} fit",
                "device RESOURCE_EXHAUSTED mid-fit; caches reclaimed",
                "streaming",
                "the streaming fit path",
            )
        result = fallback()
        bump_counter("fit.oom.recovered")
        emit("fit_admission", action="recovered", family=family, attempt=0)
        return result


def reraise_if_oom(exc: BaseException, family: str) -> None:
    """The fit-boundary safety net (``Estimator.fit``): turn any device
    OOM that escaped the per-family recovery — streaming sources the
    runtime cannot re-block, exotic paths — into the structured
    :class:`FitMemoryError`. A no-op for every other error (including an
    already-structured FitMemoryError)."""
    if isinstance(exc, FitMemoryError) or not is_oom_error(exc):
        return
    bump_counter("fit.oom.events")
    emit(
        "fit_admission", action="oom", family=family,
        error=type(exc).__name__,
    )
    _reclaim()
    raise FitMemoryError(
        family, "device memory was exhausted during the fit"
    ) from exc
