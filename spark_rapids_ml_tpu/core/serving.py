"""Serving-path program cache — compile-free, copy-minimal transform/predict.

The reference's steady-state win is amortization: one native library is
loaded per executor and reused across every Spark task (SURVEY.md §3.5).
The JAX port's equivalent asset is a compiled XLA executable — but
``jax.jit`` keys its cache on the EXACT input shape, so a serving workload
whose batch sizes wander (every micro-batch from a request queue is a new
row count) re-traces and re-compiles endlessly, and a transform called
from host data re-ingests the batch synchronously before each program
runs. "Large Scale Distributed Linear Algebra With TPUs" (arxiv
2112.09017) shows TPU throughput lives or dies on keeping programs and
buffers resident; "Memory Safe Computations with XLA" (arxiv 2206.14148)
motivates bounding the executable working set explicitly rather than
letting caches grow without limit. This module is the one home for both:

  - **Shape buckets** (:func:`bucket_rows`): row counts round up to the
    next power of two (features stay exact — they are model state, not
    traffic), so arbitrary batch sizes hit a SMALL set of programs. Rows
    are padded with zeros and sliced back off after the program runs;
    every serving kernel is row-wise, so padding rows can never leak into
    real outputs.
  - **AOT executable cache** (:func:`serve_rows`): programs are built
    with ``jit(fn).lower(specs).compile()`` and held in a module-global
    LRU keyed on (kernel, static config, bucketed input spec, weight
    specs, device set, donation) — model parameters enter at RUN time, so
    two models with identical shapes share one program. The LRU is
    bounded by ``TPUML_SERVING_CACHE_SIZE`` (default 32 programs) and its
    hit/miss/evict/compile totals are published through
    ``utils.tracing`` counters (``serving.cache.*`` / ``serving.compile``)
    so tests can assert "compiles == buckets, not calls".
  - **Buffer donation**: when the padded scratch input is a buffer this
    layer created (a host ingest or a device-side pad), it is donated to
    the executable (``donate_argnums``) so XLA may reuse its bytes for
    outputs/temporaries — steady-state serving then allocates nothing new
    on device. Caller-owned arrays are NEVER donated (the caller may
    reuse them); backends that cannot honor a donation just ignore it
    (counted under ``serving.donate.unusable``).
  - **Double-buffered streaming** (:func:`serve_stream`): for
    host-resident block sources, the H2D ``device_put`` of block k+1 is
    issued while the program for block k is still running (dispatch is
    async), overlapping transfer with compute.
  - **Persistent compilation cache** (:func:`configure_compile_cache`):
    a process restart replays compiles from disk instead of paying them
    cold. ``JAX_COMPILATION_CACHE_DIR`` places it from outside (nothing
    is then set in code); otherwise, off the CPU, it goes to
    ``TPUML_COMPILE_CACHE_DIR`` or one fixed path under the checkout.
    Guarded OFF on the CPU backend by default — XLA:CPU's executable
    (de)serialization has crashed mid-suite on this jaxlib (see
    tests/conftest.py); ``TPUML_COMPILE_CACHE_FORCE=1`` overrides.

Residence contract (mirrors the model families'): host batches in, host
results out; device batches in, device results out. Multi-device (mesh-
sharded) inputs are served at their exact shape with their sharding baked
into the program key — padding a live sharded array would reshard it
under the caller — so they amortize compiles across repeated same-shape
calls but do not bucket.
"""

from __future__ import annotations

import os
import time
import warnings
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

import numpy as np

from spark_rapids_ml_tpu.observability import autotune as _autotune
from spark_rapids_ml_tpu.observability import costs as _costs
from spark_rapids_ml_tpu.observability.events import emit, run_scope
from spark_rapids_ml_tpu.observability.metrics import ROW_BUCKETS, histogram
from spark_rapids_ml_tpu.observability.metrics import gauge as _gauge
from spark_rapids_ml_tpu.utils.envknobs import env_choice, env_int, env_str
from spark_rapids_ml_tpu.utils.lockcheck import guarded, make_lock, make_rlock
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange, bump_counter


def _observe_batch(n: int) -> None:
    """Publish the serving batch-size histogram (pow-2 buckets, so the
    exposition reads directly as traffic-per-program-bucket)."""
    histogram(
        "serving.batch_rows", "rows per serving call", buckets=ROW_BUCKETS
    ).observe(n)


def _publish_cache_size() -> None:
    """``serving.cache.size`` gauge, updated at every mutation from a
    size read UNDER the cache lock — the thread-safe size truth (tests
    used to derive it from hit/miss arithmetic, which races concurrent
    servers). Every call site holds ``_LOCK`` — the interprocedural
    lock-guarded pass proves it statically, ``guarded()`` asserts it at
    runtime when the sanitizer is armed."""
    guarded(_LOCK, "core.serving._PROGRAMS")
    _gauge("serving.cache.size", "AOT program cache entries").set(len(_PROGRAMS))

#: Smallest row bucket — tiny interactive batches (a single scored row, a
#: 3-row unit test) all share one program instead of one each.
MIN_ROW_BUCKET = 8

#: Default bound on the AOT program LRU (``TPUML_SERVING_CACHE_SIZE``).
DEFAULT_CACHE_SIZE = 32

#: Row-block size for routing LARGE host batches through the
#: double-buffered :func:`serve_stream` path (``TPUML_SERVE_STREAM_BLOCK``):
#: a host batch bigger than one block pipelines H2D against compute
#: instead of paying one serialized transfer of the whole matrix.
DEFAULT_STREAM_BLOCK = 65536

STREAM_BLOCK_ENV = "TPUML_SERVE_STREAM_BLOCK"


def stream_block_rows() -> int:
    """Rows per block for host-batch streaming (``TPUML_SERVE_STREAM_BLOCK``)."""
    return env_int(STREAM_BLOCK_ENV, DEFAULT_STREAM_BLOCK, minimum=1)


def bucket_rows(n: int, min_bucket: int = MIN_ROW_BUCKET) -> int:
    """The pow-2 row bucket ``n`` pads into (features are never bucketed)."""
    if n <= 0:
        raise ValueError(f"batch must have at least one row, got {n}")
    if n <= min_bucket:
        return min_bucket
    return 1 << (n - 1).bit_length()


def ladder_bucket_rows(
    n: int, *, name: str, width: int, observe: bool = True
) -> int:
    """The bucket one serving request of ``n`` rows executes at: the
    pow-2 :func:`bucket_rows` value unless the autotuner's learned
    per-(model, width) ladder has an exact-fit rung (which may sit below
    the 8-row pow-2 minimum for proven-hot tiny batches). ``observe=True``
    also feeds the request into the ladder's traffic histogram; admission
    pricing peeks with ``observe=False`` so one request is not counted
    twice. With the tuner off this IS ``bucket_rows`` — one None check."""
    bucket = bucket_rows(n)
    tuner = _autotune.active()
    if tuner is None:
        return bucket
    if observe:
        return tuner.serving_bucket(name, width, n, bucket)
    return tuner.peek_serving_bucket(name, width, n, bucket)


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache (process-restart warm starts)
# ---------------------------------------------------------------------------

_cache_lock = make_lock("core_serving.cache_wiring")
_cache_wired: Optional[str] = None  # guarded-by: _cache_lock
_cache_checked = False  # guarded-by: _cache_lock

JAX_COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Where the cache goes off the CPU when nothing outside names a place:
#: ONE fixed path beside the package (the checkout's root; git ignores
#: it). The directory is part of jax's cache key, so a temp-, pid- or
#: time-made name would never hit.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def configure_compile_cache(path: Optional[str] = None, *, force: bool = False):
    """Place jax's persistent compilation cache — the ONE function the
    estimators' fit path, the serving path, ``perfbench.run`` and
    ``chip_smoke.py`` all call. Idempotent; returns the active directory
    or None.

    Precedence: where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was
    placed from outside — jax reads that variable itself and this
    function sets NOTHING in code. Otherwise ``path``, then the repo's
    own spelling ``TPUML_COMPILE_CACHE_DIR``, then (off the CPU only)
    :data:`DEFAULT_COMPILE_CACHE_DIR`.

    CPU guard: XLA:CPU's AOT (de)serializer has SIGABRT/SIGSEGVed on this
    jaxlib when replaying or writing cache entries (tests/conftest.py
    documents both crashes), so on the ``cpu`` backend nothing is wired
    unless forced (``force=True`` / ``TPUML_COMPILE_CACHE_FORCE=1``).
    """
    global _cache_wired, _cache_checked
    with _cache_lock:
        if _cache_checked and path is None:
            return _cache_wired
        _cache_checked = True
        outside = os.environ.get(JAX_COMPILE_CACHE_ENV)
        if outside:
            _cache_wired = outside
            return _cache_wired
        import jax

        force = force or env_choice(
            "TPUML_COMPILE_CACHE_FORCE", ("0", "1"), "0"
        ) == "1"
        if jax.default_backend() == "cpu" and not force:
            return _cache_wired
        path = path or env_str("TPUML_COMPILE_CACHE_DIR") or DEFAULT_COMPILE_CACHE_DIR
        if path == _cache_wired:
            return _cache_wired
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # Serving programs are small and compile fast — cache them all,
        # not just the slow ones jax's defaults keep.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _cache_wired = path
        return _cache_wired


def _reset_compile_cache_wiring_for_tests() -> None:
    global _cache_wired, _cache_checked
    with _cache_lock:
        _cache_wired = None
        _cache_checked = False


# ---------------------------------------------------------------------------
# AOT program cache
# ---------------------------------------------------------------------------

_LOCK = make_rlock("core_serving.programs")
_PROGRAMS: "OrderedDict[tuple, Any]" = OrderedDict()  # guarded-by: _LOCK
_STATS = {"hits": 0, "misses": 0, "evictions": 0, "compiles": 0}  # guarded-by: _LOCK
# Cost-ledger bookkeeping (populated ONLY while the ledger is enabled):
# cache key -> ledger entry key, and the keys the LRU evicted — so the
# retrace watchdog can tell an eviction refill from a genuine retrace.
_LEDGER_KEYS: Dict[tuple, str] = {}  # guarded-by: _LOCK
_EVICTED_KEYS: set = set()  # guarded-by: _LOCK
_MAX_EVICTED_KEYS = 4096


def _capacity() -> int:
    return env_int("TPUML_SERVING_CACHE_SIZE", DEFAULT_CACHE_SIZE, minimum=1)


def _donation_enabled() -> bool:
    return env_choice("TPUML_SERVING_DONATE", ("on", "off"), "on") == "on"


def program_cache_stats() -> dict:
    """Snapshot: {hits, misses, evictions, compiles, size, capacity}."""
    with _LOCK:
        out = dict(_STATS)
        out["size"] = len(_PROGRAMS)
        out["capacity"] = _capacity()
        return out


def clear_program_cache() -> None:
    """Drop every cached executable and zero the stats (tests, reconfigs).

    Also invalidates the per-model DEVICE-WEIGHT caches (``_centers_dev``,
    ``_wb_dev``, ``_coef_dev``, ``_forest_dev``, PCA's per-dtype component
    cache) of every model that ever populated one: an executable cache
    reset is a reconfiguration boundary, and a model whose weights were
    hot-swapped underneath must not keep serving the stale device copy."""
    with _LOCK:
        _PROGRAMS.clear()
        _JIT_FALLBACKS.clear()
        _LEDGER_KEYS.clear()
        _EVICTED_KEYS.clear()
        for k in _STATS:
            _STATS[k] = 0
        _publish_cache_size()
        models = list(_DEVICE_CACHED_MODELS)
    ledger = _costs.active()
    if ledger is not None:
        # A cache reset is a reconfiguration boundary: the recompiles
        # that refill it must not read as retrace storms.
        ledger.reset_families()
    for model in models:
        invalidate_device_caches(model)


def reclaim_device_memory() -> None:
    """Best-effort release of every reclaimable device allocation after a
    ``RESOURCE_EXHAUSTED`` failure: the AOT executable cache (and with it
    the per-model device-weight copies, via :func:`clear_program_cache`'s
    sweep), plus jax's own trace/lowering caches. The fit-path OOM
    recovery calls this between attempts so the retry runs against the
    device's true free watermark, not one depressed by cold caches."""
    clear_program_cache()
    try:
        import jax

        jax.clear_caches()
    except Exception:  # pragma: no cover - reclamation is best-effort
        pass
    bump_counter("fit.oom.reclaims")


#: Attributes holding a model family's device-resident weight copy
#: (single array / pytree — dropped to None) and dict-shaped caches
#: (cleared in place). One list so every family retires the same way.
_DEVICE_CACHE_ATTRS = ("_centers_dev", "_wb_dev", "_coef_dev", "_forest_dev")
_DEVICE_CACHE_DICTS = ("_pc_dev_cache",)

#: Models that populated a device-weight cache (weakly held): the set
#: :func:`clear_program_cache` sweeps so a cache reset cannot leave any
#: model serving stale device weights.
_DEVICE_CACHED_MODELS: "weakref.WeakSet" = weakref.WeakSet()  # guarded-by: _LOCK


def note_device_cache(model: Any) -> None:
    """Record that ``model`` holds a device-weight cache (called by the
    model families' lazy cache builders)."""
    with _LOCK:
        _DEVICE_CACHED_MODELS.add(model)


def invalidate_device_caches(model: Any) -> int:
    """Drop every device-weight cache ``model`` carries; returns how many
    were live. The shared retire hook: the model registry calls this when
    a version is retired or hot-swapped, and :func:`clear_program_cache`
    sweeps it over every tracked model — either way the next predict
    re-uploads from the model's host truth instead of serving stale
    device bytes."""
    dropped = 0
    for attr in _DEVICE_CACHE_ATTRS:
        if getattr(model, attr, None) is not None:
            setattr(model, attr, None)
            dropped += 1
    for attr in _DEVICE_CACHE_DICTS:
        cache = getattr(model, attr, None)
        if cache:
            cache.clear()
            dropped += 1
    if dropped:
        bump_counter("serving.device_cache.invalidate", dropped)
        emit("serving", action="invalidate",
             model=type(model).__name__, caches=dropped)
    return dropped


def _spec_key(spec) -> tuple:
    sharding = getattr(spec, "sharding", None)
    return (tuple(spec.shape), str(spec.dtype), sharding)


def _args_specs_and_key(args: tuple):
    """ShapeDtypeStruct pytree + hashable key for the weight arguments."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    specs = [jax.ShapeDtypeStruct(np.shape(a), a.dtype) for a in leaves]
    key = (treedef, tuple(_spec_key(s) for s in specs))
    return jax.tree_util.tree_unflatten(treedef, specs), key


def _get_program(
    fn: Callable,
    x_spec,
    args: tuple,
    static: dict,
    donate: bool,
    name: Optional[str] = None,
):
    """The cached AOT executable for (fn, static, specs, donation), as
    ``(exe, ledger_key)`` — ``ledger_key`` is the cost-ledger handle for
    invocation accounting, None whenever the ledger is disabled."""
    import jax

    arg_specs, args_key = _args_specs_and_key(args)
    key = (
        fn,
        tuple(sorted(static.items())),
        _spec_key(x_spec),
        args_key,
        donate,
    )
    ledger = _costs.active()
    with _LOCK:
        exe = _PROGRAMS.get(key)
        if exe is not None:
            _PROGRAMS.move_to_end(key)
            _STATS["hits"] += 1
            bump_counter("serving.cache.hit")
            emit("serving", action="hit", kernel=getattr(fn, "__name__", str(fn)))
            return exe, (_LEDGER_KEYS.get(key) if ledger is not None else None)
        _STATS["misses"] += 1
        was_evicted = ledger is not None and key in _EVICTED_KEYS
        bump_counter("serving.cache.miss")
        emit("serving", action="miss", kernel=getattr(fn, "__name__", str(fn)))

    jitted = jax.jit(
        fn,
        static_argnames=tuple(static) or None,
        donate_argnums=(0,) if donate else (),
    )
    compile_t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        # A donated scratch whose bytes no output can alias is a
        # no-op, not an error — drop jax's warning, keep a counter.
        warnings.simplefilter("always")
        exe = jitted.lower(x_spec, *arg_specs, **static).compile()
    for w in caught:
        if "donated buffers" in str(w.message):
            bump_counter("serving.donate.unusable")
        else:  # pragma: no cover - foreign warnings pass through
            warnings.warn_explicit(
                w.message, w.category, w.filename, w.lineno
            )
    lkey = None
    if ledger is not None:
        # Classify the compile (retrace watchdog) + capture XLA's cost
        # and memory analyses — the chokepoint the ledger exists for.
        lkey = _costs.record_aot(
            fn,
            name=name or getattr(fn, "__name__", str(fn)),
            static=static,
            x_spec=x_spec,
            args=args,
            compiled=exe,
            compile_seconds=time.perf_counter() - compile_t0,
            evicted=was_evicted,
        )
    with _LOCK:
        _STATS["compiles"] += 1
        bump_counter("serving.compile")
        emit("serving", action="compile", kernel=getattr(fn, "__name__", str(fn)))
        if key not in _PROGRAMS:
            _PROGRAMS[key] = exe
            if lkey is not None:
                _LEDGER_KEYS[key] = lkey
                _EVICTED_KEYS.discard(key)
            while len(_PROGRAMS) > _capacity():
                old_key, _ = _PROGRAMS.popitem(last=False)
                if ledger is not None:
                    if len(_EVICTED_KEYS) >= _MAX_EVICTED_KEYS:
                        _EVICTED_KEYS.clear()
                    _EVICTED_KEYS.add(old_key)
                    _LEDGER_KEYS.pop(old_key, None)
                _STATS["evictions"] += 1
                bump_counter("serving.cache.evict")
                emit("serving", action="evict")
            _publish_cache_size()
        return _PROGRAMS[key], (
            _LEDGER_KEYS.get(key) if ledger is not None else None
        )


# ---------------------------------------------------------------------------
# serve_rows — the bucketed single-batch entry
# ---------------------------------------------------------------------------


def _compute_dtype(host_dtype):
    """Host batches keep their floating dtype (canonicalized: f64 becomes
    f32 when x64 is off — same coercion ``jnp.asarray`` applies);
    non-float sources take the estimators' compute dtype."""
    import jax

    from spark_rapids_ml_tpu.core.ingest import default_dtype

    if np.issubdtype(host_dtype, np.floating):
        return jax.dtypes.canonicalize_dtype(host_dtype)
    return np.dtype(default_dtype())


def _slice_outputs(outs, bucket: int, n: int, to_host: bool):
    """Strip padding rows from every output that carries them. Host-bound
    results convert FIRST and slice in numpy — a device-side slice would
    compile one tiny program per distinct ``n`` and defeat the
    compiles == buckets contract for host callers."""
    import jax

    def one(leaf):
        if to_host:
            leaf = np.asarray(leaf)
        if n != bucket and np.ndim(leaf) >= 1 and np.shape(leaf)[0] == bucket:
            return leaf[:n]
        return leaf

    return jax.tree_util.tree_map(one, outs)


def _is_multi_device(x) -> bool:
    try:
        return len(x.sharding.device_set) > 1
    except AttributeError:  # pragma: no cover - non-sharded array types
        return False


def _any_multi_device(tree) -> bool:
    import jax

    return any(
        _is_multi_device(leaf)
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "sharding")
    )


def _jit_fallback(fn: Callable, static: dict):
    """A cached plain-jit twin of ``fn`` for mesh-sharded operands: jit
    adapts to live shardings (GSPMD) and moves uncommitted inputs, which
    strict AOT executables refuse; its own cache still amortizes compiles
    across repeated exact shapes. One wrapper per (fn, static) so the
    jit cache accumulates instead of being thrown away per call."""
    import jax

    key = (fn, tuple(sorted(static.items())))
    with _LOCK:
        jitted = _JIT_FALLBACKS.get(key)
        if jitted is None:
            jitted = jax.jit(fn, static_argnames=tuple(static) or None)
            _JIT_FALLBACKS[key] = jitted
        return jitted


_JIT_FALLBACKS: Dict[tuple, Any] = {}  # guarded-by: _LOCK


def serve_rows(
    fn: Callable,
    x: Any,
    args: tuple = (),
    *,
    name: str,
    static: Optional[dict] = None,
    donate: Optional[bool] = None,
    to_host: Optional[bool] = None,
):
    """Run the row-wise kernel ``fn(x, *args, **static)`` through the
    shape-bucketed AOT program cache.

    Each call runs under a ``serve`` run scope (observability/events.py):
    standalone predicts get their own ``run_id``; a call nested inside a
    fit or a caller's job scope joins the ambient one, so the serving
    cache traffic lands in the same event-log stream as the fit's spans.

    ``x`` may be a host array (padded into a fresh host scratch, placed
    once, result pulled back) or a ``jax.Array`` (padded on device when
    the bucket requires it; result stays on device). ``args`` are the
    model's weight arrays (any pytree) — pass DEVICE-RESIDENT weights so
    repeated calls don't re-upload them. ``static`` entries become
    ``static_argnames`` and part of the program key. Outputs whose
    leading axis is the bucket are sliced back to the true row count.
    """
    with run_scope("serve", name):
        return _serve_rows_impl(
            fn, x, args, name=name, static=static, donate=donate, to_host=to_host
        )


def _serve_rows_impl(
    fn: Callable,
    x: Any,
    args: tuple,
    *,
    name: str,
    static: Optional[dict],
    donate: Optional[bool],
    to_host: Optional[bool],
):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.core.data import is_device_array

    static = dict(static or {})
    configure_compile_cache()
    device_in = is_device_array(x)
    if to_host is None:
        to_host = not device_in

    if (device_in and _is_multi_device(x)) or _any_multi_device(args):
        # Mesh-sharded batch or weights: cached plain-jit path — padding
        # would reshard the operands under the caller, and strict AOT
        # executables reject live shardings they were not compiled for.
        # jax's own jit cache still amortizes compiles per exact shape.
        bump_counter("serving.fallback")
        n = int(np.shape(x)[0])
        jitted = _jit_fallback(fn, static)
        ledger = _costs.active()
        with TraceRange(f"serve {name}", TraceColor.GREEN):
            if ledger is not None:
                lkey = _costs.record_fallback(
                    fn, name=name, static=static, args=(x, *args),
                    lower=lambda: jitted.lower(x, *args, **static),
                )
                t0 = time.perf_counter()
                outs = jitted(x, *args, **static)
                ledger.note_invocation(lkey, time.perf_counter() - t0, rows=n)
            else:
                outs = jitted(x, *args, **static)
        _observe_batch(n)
        return _slice_outputs(outs, n, n, to_host)

    if device_in:
        if x.ndim == 1:
            x = x[None, :]
        n, d = int(x.shape[0]), int(x.shape[1])
        _observe_batch(n)
        bucket = ladder_bucket_rows(n, name=name, width=d)
        if bucket == n:
            x_pad, owned = x, False
        else:
            # Device-side pad: a small per-exact-shape program, amortized
            # the first time each row count appears; the bucket program —
            # the expensive one — is shared.
            x_pad, owned = jnp.pad(x, ((0, bucket - n), (0, 0))), True
        dtype = x.dtype
    else:
        x_host = np.asarray(x)
        if x_host.ndim == 1:
            x_host = x_host[None, :]
        if x_host.ndim != 2:
            raise ValueError(f"serving input must be 2-D, got {x_host.ndim}-D")
        n, d = x_host.shape
        _observe_batch(n)
        bucket = ladder_bucket_rows(n, name=name, width=d)
        dtype = _compute_dtype(x_host.dtype)
        # A FRESH padded scratch per call: jax may alias (zero-copy) a
        # numpy buffer on the CPU backend and H2D transfers may read it
        # asynchronously, so a reused scratch could be mutated under a
        # live array.
        pad_host = np.zeros((bucket, d), dtype=dtype)
        pad_host[:n] = x_host
        with TraceRange(f"serve {name} H2D", TraceColor.CYAN):
            x_pad = jax.device_put(pad_host)
        owned = True

    use_donate = (_donation_enabled() if donate is None else donate) and owned
    spec = jax.ShapeDtypeStruct((bucket, d), dtype)
    exe, lkey = _get_program(fn, spec, args, static, donate=use_donate, name=name)
    with TraceRange(f"serve {name}", TraceColor.GREEN):
        if lkey is not None:
            t0 = time.perf_counter()
            outs = exe(x_pad, *args)
            ledger = _costs.active()
            if ledger is not None:
                ledger.note_invocation(lkey, time.perf_counter() - t0, rows=n)
        else:
            outs = exe(x_pad, *args)
    return _slice_outputs(outs, bucket, n, to_host)


# ---------------------------------------------------------------------------
# serve_stream — double-buffered host->device streaming
# ---------------------------------------------------------------------------


def serve_stream(
    fn: Callable,
    blocks: Iterable[Any],
    args: tuple = (),
    *,
    name: str,
    static: Optional[dict] = None,
    dtype: Any = None,
) -> Iterator[Any]:
    """Stream host blocks through the bucketed program cache, yielding one
    HOST result per non-empty block.

    Double-buffering: block k's program is dispatched (async), block k+1
    is padded and ``device_put`` while it runs, and only THEN is block
    k's result pulled — the H2D copy of the next block overlaps the
    compute of the current one, the streaming discipline arxiv 2112.09017
    uses to keep the MXU fed from host-resident operands.

    ``dtype`` pins the compute dtype across blocks (pass the model's
    weight dtype) so a mixed-dtype source cannot fan out into one program
    per block dtype.
    """
    import jax

    static = dict(static or {})
    configure_compile_cache()
    fallback = _jit_fallback(fn, static) if _any_multi_device(args) else None
    pending: Optional[tuple] = None  # (outs, bucket, n)

    # NOTE: no run_scope here — a generator's contextvar writes leak into
    # whichever context consumes it, and an abandoned generator would
    # reset the scope token from a foreign context. Stream events carry
    # the AMBIENT run_id (the consuming fit/transform/job scope) instead.
    for blk in blocks:
        x_host = np.asarray(blk)
        if x_host.ndim == 1:
            x_host = x_host[None, :]
        if x_host.size == 0:
            continue
        n, d = x_host.shape
        _observe_batch(n)
        bucket = ladder_bucket_rows(n, name=name, width=d)
        blk_dtype = np.dtype(dtype) if dtype is not None else _compute_dtype(x_host.dtype)
        pad_host = np.zeros((bucket, d), dtype=blk_dtype)
        pad_host[:n] = x_host
        with TraceRange(f"serve {name} H2D", TraceColor.CYAN):
            x_pad = jax.device_put(pad_host)
        ledger = _costs.active()
        with TraceRange(f"serve {name}", TraceColor.GREEN):
            if fallback is not None:  # mesh-sharded weights (see serve_rows)
                bump_counter("serving.fallback")
                if ledger is not None:
                    lkey = _costs.record_fallback(
                        fn, name=name, static=static, args=(x_pad, *args),
                        lower=lambda: fallback.lower(x_pad, *args, **static),
                    )
                    t0 = time.perf_counter()
                    outs = fallback(x_pad, *args, **static)
                    ledger.note_invocation(
                        lkey, time.perf_counter() - t0, rows=n
                    )
                else:
                    outs = fallback(x_pad, *args, **static)
            else:
                exe, lkey = _get_program(
                    fn,
                    jax.ShapeDtypeStruct((bucket, d), blk_dtype),
                    args,
                    static,
                    donate=_donation_enabled(),
                    name=name,
                )
                if lkey is not None:
                    t0 = time.perf_counter()
                    outs = exe(x_pad, *args)  # async dispatch
                    if ledger is not None:
                        ledger.note_invocation(
                            lkey, time.perf_counter() - t0, rows=n
                        )
                else:
                    outs = exe(x_pad, *args)  # async dispatch
        bump_counter("serving.stream.blocks")
        if pending is not None:
            # Sync the PREVIOUS block only after this block's transfer
            # and dispatch are in flight.
            yield _slice_outputs(pending[0], pending[1], pending[2], True)
        pending = (outs, bucket, n)

    if pending is not None:
        yield _slice_outputs(pending[0], pending[1], pending[2], True)


def prefetch_blocks(
    blocks: Iterable[Any], prepare: Callable[[Any], Any]
) -> Iterator[Any]:
    """One-ahead double buffering for the TRAINING streaming loops —
    :func:`serve_stream`'s overlap pattern lifted out for the fit paths.

    ``prepare`` does the per-block host work + async H2D upload
    (densify, ``ascontiguousarray``, ``device_put``/``jnp.asarray``).
    Block k is yielded only after block k+1's ``prepare`` has run, so
    the host-side decode and the H2D transfer of the next block are in
    flight before the consumer blocks on computing the current one.
    Values are exactly ``prepare(block)`` in order — bit-identical to
    the unprefetched loop — and every overlapped hand-off bumps
    ``fit.stream.prefetched`` (the counter the parity tests assert).

    NOTE: no run_scope here for the same reason as :func:`serve_stream`
    — a generator's contextvar writes leak into the consuming context.
    """
    pending = _SENTINEL = object()
    for blk in blocks:
        current = prepare(blk)
        if pending is not _SENTINEL:
            bump_counter("fit.stream.prefetched")
            yield pending
        pending = current
    if pending is not _SENTINEL:
        yield pending


# ---------------------------------------------------------------------------
# serve_blocks — large host batches through the streaming path
# ---------------------------------------------------------------------------


def serve_blocks(
    fn: Callable,
    x_host: np.ndarray,
    args: tuple = (),
    *,
    name: str,
    static: Optional[dict] = None,
    block: Optional[int] = None,
):
    """Run one LARGE host batch through :func:`serve_stream` in row blocks
    and concatenate the host results — the double-buffered path (H2D of
    block k+1 overlaps compute of block k) that ``models/pca.py`` already
    uses, packaged so every family's big host-batch predict can take it
    instead of paying one serialized whole-matrix transfer.

    Results are bitwise what :func:`serve_rows` returns for the same
    batch: every serving kernel is row-wise, so a row's output does not
    depend on which block carried it. Tuple/pytree outputs concatenate
    leaf-wise along the leading axis.
    """
    import jax

    block = block or stream_block_rows()
    x_host = np.asarray(x_host)
    n = x_host.shape[0]
    dtype = _compute_dtype(x_host.dtype)
    blocks = (x_host[i : i + block] for i in range(0, n, block))
    outs = list(
        serve_stream(fn, blocks, args, name=name, static=static, dtype=dtype)
    )
    if len(outs) == 1:
        return outs[0]
    leaves0, treedef = jax.tree_util.tree_flatten(outs[0])
    rest = [jax.tree_util.tree_flatten(o)[0] for o in outs[1:]]
    cat = [
        np.concatenate([first] + [r[i] for r in rest], axis=0)
        for i, first in enumerate(leaves0)
    ]
    return jax.tree_util.tree_unflatten(treedef, cat)
