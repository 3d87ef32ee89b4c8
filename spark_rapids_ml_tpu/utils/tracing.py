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
  - Program load — one ``jax.monitoring`` listener, registered when this
    module is imported, that counts what jax reports of every program it
    traces, lowers, compiles or loads from the persistent cache:
    ``program.<phase>.ns`` / ``.calls`` for :data:`PROGRAM_PHASES`, the
    per-program table :func:`program_loads`, and a ``program <name>`` span
    record for each compile and each cache load (the benchmark's
    ``setup_trace_s`` / ``setup_compile_s`` / ``setup_cache_load_s`` /
    ``setup_programs`` read the counters).
"""

from __future__ import annotations

import itertools
import logging
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


def _parent_span(stack: list) -> Optional[str]:
    """The parent of a span opening now on this thread: its innermost open
    span. At a thread/process entry point, the ambient trace's hand-off span
    (set by trace_scope or the env carrier), so a dispatcher thread's or gang
    member's root spans attach to the submitting span in the merged trace
    tree."""
    if stack:
        return stack[-1]
    tc = _current_trace()
    return tc.span_id if tc is not None else None


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
        self.parent_id = _parent_span(stack)
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
        _publish_span(self.name, self._start, end, self.ok, self.exc_type,
                      self.depth, self.parent_id, self.span_id)


def _publish_span(name, start, end, ok, exc, depth, parent, span_id) -> None:
    """Hand a finished span to the ambient run context (``model.fit_report()``
    stage trees) and the event log. Inert unless a run scope or event sink is
    active — the production disabled path allocates one dict at most when a
    report is actually being recorded."""
    ctx = _current_run()
    if ctx is not None or _log_enabled():
        record = {
            "name": name,
            "start": start,
            "end": end,
            "dur": end - start,
            "ok": ok,
            "exc": exc,
            "depth": depth,
            "parent": parent,
            "span": span_id,
            "thread": threading.get_ident(),
        }
        if ctx is not None:
            ctx.add_span(record)
        _emit("span", **record)


# Alias matching the reference class name (NvtxRange.java:37).
NvtxRange = TraceRange


# --- stages: the leaves of the host fit path ---

#: Every stage the host fit path opens. The counters ``fit.stage.<stage>.ns``
#: and ``.calls`` (and ``.bytes`` for ``densify`` and ``place``, whose sites
#: know the size: :meth:`StageRange.count_bytes`) are what
#: ``perfbench/metrics/host_*`` read.
STAGES = ("admit", "densify", "convert", "place", "solve")
#: The stages of a UMAP fit after its admission and placement
#: (``models/umap.py``): the kNN graph up to the host's read of its
#: indices, the host sort of its edges by tail, the layout epochs; read by
#: ``perfbench/metrics/umap_*_ms``.
UMAP_STAGES = ("knn", "plan", "layout")
_STAGE_COUNTERS = {
    s: (f"fit.stage.{s}.ns", f"fit.stage.{s}.calls") for s in STAGES + UMAP_STAGES
}


class StageRange(TraceRange):
    """A :class:`TraceRange` named for one of :data:`STAGES` (or
    :data:`UMAP_STAGES`) whose exit also
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
    UMAP's ``layout`` ends by waiting for the embedding (``models/umap.py``:
    the fit returns a finished one), so it holds the epochs' device time.
    """

    __slots__ = ()

    def __init__(self, stage: str, color: Optional[TraceColor] = None):
        if stage not in _STAGE_COUNTERS:
            raise ValueError(f"unknown stage {stage!r}: one of {STAGES + UMAP_STAGES}")
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


# --- program load: what jax traces, lowers, compiles or loads ---

#: The phases of loading a program, each counted in ``program.<phase>.ns``
#: and ``.calls``: ``trace`` (Python to jaxpr), ``lower`` (jaxpr to
#: StableHLO), ``compile`` (an XLA compile that did not come from the
#: persistent cache, its failed lookup and its cache write included) and
#: ``cache_load`` (an executable read, deserialised and loaded from the
#: persistent cache). A cache load's backend event less its read stays in
#: ``program.compile.ns`` (the cache key's computation) without a call.
#: Each ``ns`` is self time: a trace nested in a trace or in a lowering
#: counts once, in the inner event (:func:`_union_ns`).
PROGRAM_PHASES = ("trace", "lower", "compile", "cache_load")
_PROGRAM_COUNTERS = {
    p: (f"program.{p}.ns", f"program.{p}.calls") for p in PROGRAM_PHASES
}

#: jax's own events, each naming its program (jax/_src/dispatch.py); the
#: cache's two name none (jax/_src/compiler.py::compile_or_get_cached,
#: which runs inside the backend event of the program it loads).
_SPAN_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

#: Programs :func:`program_loads` names; the rest add to ``"other"``.
MAX_PROGRAMS = 256
#: Closed intervals a thread keeps for the union rule.
_MAX_KEPT_INTERVALS = 4096

_loads_lock = make_lock("tracing.program_loads")
_program_loads: dict = {}  # guarded-by: _loads_lock
_listening = False  # guarded-by: _loads_lock
#: ``perf_counter() - time.time()`` at registration: jax stamps its events
#: with ``time.time()``, span records are on the ``perf_counter`` clock.
_wall_to_perf = 0.0


class _ThreadLoads(threading.local):
    """One thread's listener state: the intervals of its events already
    counted, and the persistent-cache read of the backend event still open
    (jax traces, lowers and compiles a program on the thread that asked for
    it)."""

    def __init__(self):
        self.closed: Deque[Tuple[float, float]] = deque(maxlen=_MAX_KEPT_INTERVALS)
        self.hit = False
        self.read_s = 0.0


_thread_loads = _ThreadLoads()


def program_loads() -> dict:
    """``{program: {trace_ns, lower_ns, compile_ns, cache_load_ns, compiles,
    cache_loads}}`` since the listener was registered: which programs a
    process paid for, and whether it compiled them or loaded them from the
    persistent cache. A program goes by the name jax gives its function
    (``jit(f)`` counts as ``f``); past :data:`MAX_PROGRAMS` names, the
    rest add to ``"other"``."""
    with _loads_lock:
        return {name: dict(row) for name, row in _program_loads.items()}


def _union_ns(closed: Deque[Tuple[float, float]], start: float, end: float) -> int:
    """The nanoseconds of ``[start, end]`` that no interval closed before it
    on this thread has counted: an event's self time. jax traces an inner
    jitted function inside its caller's trace, and traces some functions
    inside a lowering; one thread's events nest or are disjoint, and an
    inner one closes first, so those inside ``[start, end]`` are the last
    ones kept. The four phases' ``ns`` therefore add up to the union of a
    thread's events, never more."""
    inner = 0.0
    while closed and closed[-1][0] >= start:
        s, e = closed.pop()
        inner += e - s
    closed.append((start, end))  # the oldest drops off past the deque's length
    return max(0, int((end - start - inner) * 1e9))


def _count_phase(phase: str, start: float, end: float, fun_name: str) -> None:
    name = fun_name[4:-1] if fun_name.startswith("jit(") and fun_name.endswith(")") else fun_name
    state = _thread_loads
    ns = _union_ns(state.closed, start, end)
    if phase != "compile":
        bump_counter(_PROGRAM_COUNTERS[phase][0], ns)
        bump_counter(_PROGRAM_COUNTERS[phase][1])
        added = {f"{phase}_ns": ns}
    else:
        hit, read_ns = state.hit, int(state.read_s * 1e9)
        state.hit, state.read_s = False, 0.0
        compile_ns = max(0, ns - read_ns)
        bump_counter(_PROGRAM_COUNTERS["compile"][0], compile_ns)
        if hit:
            bump_counter(_PROGRAM_COUNTERS["cache_load"][0], read_ns)
            bump_counter(_PROGRAM_COUNTERS["cache_load"][1])
            added = {"compile_ns": compile_ns, "cache_load_ns": read_ns, "cache_loads": 1}
        else:
            bump_counter(_PROGRAM_COUNTERS["compile"][1])
            added = {"compile_ns": compile_ns, "compiles": 1}
        stack = _stack()
        _publish_span(f"program {name}", start + _wall_to_perf, end + _wall_to_perf,
                      True, None, len(stack), _parent_span(stack), _new_span_id())
    with _loads_lock:
        row = _program_loads.get(name)
        if row is None:
            if len(_program_loads) - ("other" in _program_loads) >= MAX_PROGRAMS:
                name = "other"
            row = _program_loads.setdefault(name, dict.fromkeys(
                ("trace_ns", "lower_ns", "compile_ns", "cache_load_ns",
                 "compiles", "cache_loads"), 0))
        for key, amount in added.items():
            row[key] += amount


def _on_time_span(event: str, start: float, end: float, **kwargs) -> None:
    phase = _SPAN_PHASES.get(event)
    if phase is None:
        return
    try:
        _count_phase(phase, start, end, str(kwargs.get("fun_name", "?")))
    except Exception:  # a listener must never fail the compile it watches
        logging.getLogger(__name__).exception("program-load listener failed on %s", event)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _thread_loads.hit = True


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _CACHE_READ:
        _thread_loads.hit = True
        _thread_loads.read_s += seconds


def register_program_listener() -> bool:
    """Listen to jax's trace, lowering, compile and persistent-cache events,
    once a process (this module calls it when imported, so CPU processes
    and tests count too). Returns whether this call registered it. The
    eight counters exist from here on, at 0 until something loads: a
    reader tells "nothing loaded" from "not counted"."""
    global _listening, _wall_to_perf
    with _loads_lock:
        if _listening:
            return False
        _listening = True
        _wall_to_perf = time.perf_counter() - time.time()
    for ns, calls in _PROGRAM_COUNTERS.values():
        bump_counter(ns, 0)
        bump_counter(calls, 0)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    return True


register_program_listener()
