"""Spans and counters — the one import the instrumented layers use.

An NVTX-parity RAII range (reference ``NvtxRange``, NvtxRange.java:37-58,
9 ARGB colors NvtxColor.java:20-29, JNI push/pop rapidsml_jni.cu:32-34)
backed by ``jax.profiler.TraceAnnotation``, a ring buffer of
(name, start, end) for profiler-less assertions, and the flat counter
surface over the typed registry. The registry itself, the JSONL event
log, reports and heartbeats live in ``spark_rapids_ml_tpu/observability/``;
everything a fit or serving path calls to be seen is here:

  - :class:`TraceRange` / ``NvtxRange`` — the RAII range: span id /
    parent id / depth, an ``ok`` flag and the exception type when the
    body raises, feeding the ambient run context (for
    ``model.fit_report()`` stage trees), the ring (``recent_events``,
    the ops plane's ``/tracez``) and the event log (as ``span`` records)
    when either is active. Inside a profiler session every range is in
    the trace's host plane, on the device trace's clock. The disabled
    path stays allocation-light (budget test in
    tests/test_observability.py).
  - :class:`StageRange` — a leaf range of the host fit path (``admit``,
    ``densify``, ``convert``, ``place``, ``solve``) whose exit also adds
    its duration to ``fit.stage.<stage>.ns`` and 1 to
    ``fit.stage.<stage>.calls``: the split of a fit's host time that the
    benchmark's ``host_*_ms`` metrics read.
  - ``bump_counter`` / ``counter_value`` / ``counters`` /
    ``clear_counters`` — the typed registry's counters as a flat dict.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from enum import Enum
from typing import Deque, Optional, Tuple

import jax

from spark_rapids_ml_tpu.observability.events import (
    current_run as _current_run,
    current_trace as _current_trace,
    emit as _emit,
    enabled as _log_enabled,
)
from spark_rapids_ml_tpu.observability.metrics import default_registry
from spark_rapids_ml_tpu.utils.lockcheck import make_lock


class TraceColor(Enum):
    """ARGB colors, values identical to NvtxColor.java:20-29."""

    GREEN = 0xFF76B900
    BLUE = 0xFF0071C5
    PURPLE = 0xFF8A2BE2
    CYAN = 0xFF00FFFF
    RED = 0xFFFF0000
    YELLOW = 0xFFFFFF00
    WHITE = 0xFFFFFFFF
    DARK_GREEN = 0xFF006400
    ORANGE = 0xFFFFA500


# Alias matching the reference class name for drop-in reads of calling code.
NvtxColor = TraceColor

_events_lock = make_lock("tracing.events")
_events: Deque[Tuple[str, float, float]] = deque(maxlen=4096)


# --- counters (registry-backed) ---


def bump_counter(name: str, amount: int = 1) -> None:
    """Increment a named counter (created at zero on first bump)."""
    default_registry.counter(name).inc(amount)


def counter_value(name: str) -> int:
    return default_registry.counter(name).value()


def counters(prefix: str = "") -> dict:
    """Snapshot of all counters whose name starts with ``prefix``."""
    return default_registry.counters_snapshot(prefix)


def clear_counters(prefix: str = "") -> None:
    default_registry.clear(prefix, kinds=("counter",))


def recent_events() -> list:
    with _events_lock:
        return list(_events)


def clear_events() -> None:
    with _events_lock:
        _events.clear()


#: Per-thread mirror of the open-range stacks — (span_id, name, start)
#: tuples keyed by thread ident. The thread-local stack answers "what is
#: MY innermost span"; this global answers the ops plane's ``/tracez``
#: question: "what is every thread doing RIGHT NOW".
_open_stacks: dict = {}  # guarded-by: _events_lock


def open_spans() -> dict:
    """Currently-open span stacks per live thread (outermost first):
    ``{ident: {"thread": name, "spans": [{span,name,depth,open_s}]}}``."""
    now = time.perf_counter()
    with _events_lock:
        items = {i: list(s) for i, s in _open_stacks.items() if s}
    alive = {t.ident: t.name for t in threading.enumerate()}
    return {
        ident: {
            "thread": alive[ident],
            "spans": [
                {
                    "span": sid,
                    "name": name,
                    "depth": depth,
                    "open_s": round(now - start, 6),
                }
                for depth, (sid, name, start) in enumerate(stack)
            ],
        }
        for ident, stack in items.items()
        if ident in alive
    }


# --- the RAII range ---

_span_ids = itertools.count(1)
# Globally-unique span ids: a per-process prefix (pid + random epoch, so
# a recycled pid cannot collide across a long telemetry run) + a local
# counter. Cross-process trace assembly resolves parents by these ids.
_SPAN_EPOCH = f"{os.getpid():x}-{os.urandom(2).hex()}"
_span_stack = threading.local()


def _new_span_id() -> str:
    return f"{_SPAN_EPOCH}-{next(_span_ids):x}"


def _stack() -> list:
    s = getattr(_span_stack, "s", None)
    if s is None:
        s = _span_stack.s = []
    return s


def current_span_id() -> Optional[str]:
    """This thread's innermost open span id — the parent a cross-thread
    or cross-process child should adopt (events.current_trace_context)."""
    s = getattr(_span_stack, "s", None)
    return s[-1] if s else None


class TraceRange:
    """RAII profiling range: ``with TraceRange("compute cov", TraceColor.RED): ...``

    Same call sites as the reference's instrumentation (RapidsRowMatrix.scala:
    78 "compute cov" RED, :153 "mean center" ORANGE, :183 "concat before cov"
    PURPLE, :88/:111 "SVD" BLUE); its per-partition :193 "gemm" GREEN is
    here the three stages it held (:class:`StageRange`: convert, place, solve).

    Each range carries a process-unique ``span_id``; nesting is tracked
    per thread, so ``parent_id``/``depth`` let reports rebuild the stage
    tree. On exit, ``ok`` records whether the body raised and
    ``exc_type`` the exception class name — visible in the run context's
    span records and the event log, where the old implementation
    silently discarded them.
    """

    __slots__ = (
        "name", "color", "_annotation", "_start", "_end",
        "span_id", "parent_id", "depth", "ok", "exc_type",
    )

    def __init__(self, name: str, color: Optional[TraceColor] = None):
        self.name = name
        self.color = color
        self._annotation = jax.profiler.TraceAnnotation(name)
        self._start = 0.0
        self._end = 0.0
        self.ok = True
        self.exc_type: Optional[str] = None

    def __enter__(self) -> "TraceRange":
        stack = _stack()
        if stack:
            self.parent_id = stack[-1]
        else:
            # Thread/process entry point: parent to the ambient trace's
            # hand-off span (set by trace_scope or the env carrier), so a
            # dispatcher thread's or gang member's root spans attach to
            # the submitting span in the merged trace tree.
            tc = _current_trace()
            self.parent_id = tc.span_id if tc is not None else None
        self.depth = len(stack)
        self.span_id = _new_span_id()
        stack.append(self.span_id)
        self._start = time.perf_counter()
        ident = threading.get_ident()
        with _events_lock:
            _open_stacks.setdefault(ident, []).append(
                (self.span_id, self.name, self._start)
            )
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None) -> None:
        self._annotation.__exit__(exc_type, exc, tb)
        end = self._end = time.perf_counter()
        stack = _stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        elif self.span_id in stack:  # tolerate interleaved exits
            stack.remove(self.span_id)
        self.ok = exc_type is None
        self.exc_type = getattr(exc_type, "__name__", None)
        ident = threading.get_ident()
        with _events_lock:
            _events.append((self.name, self._start, end))
            mirror = _open_stacks.get(ident)
            if mirror is not None:
                for i in range(len(mirror) - 1, -1, -1):
                    if mirror[i][0] == self.span_id:
                        del mirror[i]
                        break
                if not mirror:
                    del _open_stacks[ident]
        # Everything below is inert unless a run scope or event sink is
        # active — the production disabled path allocates one dict at most
        # when a report is actually being recorded.
        ctx = _current_run()
        if ctx is not None or _log_enabled():
            record = {
                "name": self.name,
                "start": self._start,
                "end": end,
                "dur": end - self._start,
                "ok": self.ok,
                "exc": self.exc_type,
                "depth": self.depth,
                "parent": self.parent_id,
                "span": self.span_id,
                "thread": threading.get_ident(),
            }
            if ctx is not None:
                ctx.add_span(record)
            _emit("span", **record)


# Alias matching the reference class name (NvtxRange.java:37).
NvtxRange = TraceRange


# --- stages: the leaves of the host fit path ---

#: Every stage a fit path opens. The counters ``fit.stage.<stage>.ns`` and
#: ``.calls`` (and ``.bytes`` for ``densify`` and ``place``, whose sites know
#: the size: :meth:`StageRange.count_bytes`) are what
#: ``perfbench/metrics/host_*`` read.
STAGES = ("admit", "densify", "convert", "place", "solve")
_STAGE_COUNTERS = {s: (f"fit.stage.{s}.ns", f"fit.stage.{s}.calls") for s in STAGES}


class StageRange(TraceRange):
    """A :class:`TraceRange` named for one of :data:`STAGES` whose exit also
    adds its duration to ``fit.stage.<stage>.ns`` and 1 to
    ``fit.stage.<stage>.calls``.

    Stages are leaves: one thread never opens a stage inside a stage, so
    their ``ns`` can be added up and held against a fit's wall time (the
    tests' ``stages_never_nest`` fixture asserts it on every fit they run).
    A stage synchronises nothing of its own: round an asynchronous call it
    measures what the host spent in the call, and the host blocked on the
    device shows in the stage that makes the blocking read. ``place`` is
    such a stage since the placement window (``core/ingest.py::
    PlacementWindow``): in a pass over host partitions it holds the
    placement call AND the wait for room before it (the read of the oldest
    placement in flight; ``ingest.place.wait_ns`` is that part alone).
    """

    __slots__ = ()

    def __init__(self, stage: str, color: Optional[TraceColor] = None):
        if stage not in _STAGE_COUNTERS:
            raise ValueError(f"unknown stage {stage!r}: one of {STAGES}")
        super().__init__(stage, color)

    def __exit__(self, exc_type=None, exc=None, tb=None) -> None:
        super().__exit__(exc_type, exc, tb)
        ns, calls = _STAGE_COUNTERS[self.name]
        bump_counter(ns, int((self._end - self._start) * 1e9))
        bump_counter(calls)

    def count_bytes(self, nbytes: int) -> None:
        """Add the bytes this stage wrote or handed over to
        ``fit.stage.<stage>.bytes`` (called after the ``with`` block, so
        the count is not in the stage's own time)."""
        bump_counter(f"fit.stage.{self.name}.bytes", int(nbytes))
