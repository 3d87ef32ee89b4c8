"""Estimator input funnel — ONE home for the device-resident fast path.

The reference's floor is a host copy per call: every JNI kernel receives
host ``double[]`` arrays and round-trips them through ``cudaMemcpy``
(reference rapidsml_jni.cu:112,179,200,327). TPU-native, an input that is
ALREADY a ``jax.Array`` must be consumed in place — no host pull, no
float64 coercion, the whole fit traced into XLA programs that read the
resident buffer. Round 3 proved this for PCA; this module generalizes the
funnel so every family (KMeans, the GLMs, forests, neighbors, DBSCAN,
UMAP) shares one implementation instead of forking the dispatch.

Host inputs keep their floating dtype on the way in: a float32 numpy
source is placed as float32 — the old ``as_matrix`` path materialized an
intermediate float64 copy (2x host RAM) only to cast back down. The same
holds for PCA's ``RowMatrix``, which enters through
:func:`dense_partitions` with the dtype its route reads (float64 only
for the dd and packed routes, which compute on host float64).

Contract of :func:`prepare_rows`:

  - ``jax.Array``  -> consumed in place (single device) or resharded over
    the mesh's data axis. Row/feature counts that don't divide the mesh
    are padded ON DEVICE (``jnp.pad`` + reshard) with a zero mask — all
    consumers of this funnel are mask-aware, unlike PCA's covariance
    path which normalizes by raw ``n`` and therefore raises instead
    (``parallel.mesh.device_array_rows_on_mesh``).
  - host data      -> dense partitions (dtype-preserving) placed via the
    existing padding/mask plumbing (``shard_rows_from_partitions``) or a
    single ``device_put``.

Returns ``(x, mask, n_true, d_true)``; ``mask`` is the row validity /
per-row weight vector (padding rows weigh zero), in a dtype wide enough
to count rows exactly (at least float32).
"""

from __future__ import annotations

import collections
import time
from typing import Any, NamedTuple, Optional

import numpy as np

from spark_rapids_ml_tpu.core.data import (
    as_partitions,
    is_device_array,
    partition_blocks,
)
from spark_rapids_ml_tpu.robustness.degrade import cpu_device, run_degradable
from spark_rapids_ml_tpu.robustness.faults import fault_point
from spark_rapids_ml_tpu.robustness.retry import default_policy, is_oom_error
from spark_rapids_ml_tpu.utils.tracing import (
    StageRange,
    TraceColor,
    TraceRange,
    bump_counter,
)


def _reclaim_between_attempts(attempt: int, exc: BaseException) -> None:
    """Retry hook for device placement: when the failed attempt was a
    device OOM (real ``RESOURCE_EXHAUSTED`` or an injected ``:oom``
    fault), drop every reclaimable cache so the next attempt runs against
    the device's true free watermark. Non-OOM failures reclaim nothing —
    a transient placement hiccup must not cold-start the program cache."""
    if is_oom_error(exc):
        from spark_rapids_ml_tpu.core.serving import reclaim_device_memory

        reclaim_device_memory()


def default_dtype():
    """The compute dtype the estimators use when the input doesn't pin one:
    float64 under x64, float32 otherwise (TPU-native)."""
    import jax
    import jax.numpy as jnp

    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def dense_partitions(rows: Any, dtype=None) -> list:
    """:func:`~spark_rapids_ml_tpu.core.data.as_partitions` as the fit
    paths call it: under the ``densify`` stage, with the bytes of every
    partition that had to be written (a block already dense in ``dtype``
    is handed on as it is and costs none) added to
    ``fit.stage.densify.bytes``."""
    with StageRange("densify", TraceColor.PURPLE) as stage:
        parts = as_partitions(rows, dtype=dtype)
    stage.count_bytes(
        sum(
            part.nbytes
            for part, block in zip(parts, partition_blocks(rows))
            if not (isinstance(block, np.ndarray) and np.may_share_memory(part, block))
        )
    )
    return parts


def place_block(block: Any, put):
    """``put(block)`` — the placement call of one block of rows — under
    the ``place`` stage, with the block's bytes added to
    ``fit.stage.place.bytes``. The stage times the call and waits for
    nothing: a transfer the runtime finishes on its own threads after the
    call has returned is not in it. For ONE block (``prepare_rows``,
    ``place_array``); a pass over many partitions places through a
    :class:`PlacementWindow`."""
    with StageRange("place", TraceColor.CYAN) as stage:
        out = put(block)
    stage.count_bytes(block.nbytes)
    return out


#: Bytes of host-partition placements a pass keeps in flight: a partition
#: of ``nbytes`` makes the window ``max(2, PLACEMENT_WINDOW_BYTES //
#: nbytes)`` placements (:class:`PlacementWindow`). Measured, not tuned
#: (builder's chip run, PR 35, TPU v5 lite, ``target/pr35/sweep.py``): one
#: pass over 4.8 GB of float32 rows, 3000 columns, each partition placed
#: and stepped with ``comoment_add_block``, median wall of five in ms by
#: placements in flight (MB in flight in brackets); the placements alone,
#: device idle, take 355-365::
#:
#:     in flight      160 x 30 MB     40 x 120 MB     10 x 480 MB
#:     2              623  (60)       413  (240)      418  (960)
#:     3              448  (90)       386  (360)      448 (1440)
#:     4              375 (120)       388  (480)      477 (1920)
#:     6              362 (180)       399  (720)      529 (2880)
#:     8              366 (240)       415  (960)      562 (3840)
#:     16             380 (480)       -               -
#:     32             381 (960)       -               -
#:     unbounded      381             508             553-610, some 2,500
#:
#: No COUNT is best at all three sizes (6-8, 3-4, 2); what they share is
#: BYTES: under about 120 MB in flight the link starves, from about 1 GB
#: up the runtime's host threads work on everything queued at once and
#: early partitions finish late. 512 MiB gives 17 / 4 / 2 placements:
#: within 5%, 0.5% and 0% of each size's best. The floor of 2 keeps one
#: transfer and one step overlapped where a single partition is larger
#: than the window.
PLACEMENT_WINDOW_BYTES = 512 * 2**20


class PlacementWindow:
    """Bounds how many host-partition placements are in flight: made once
    by a pass over partitions, which places each through :meth:`place`.

    A placement call returns at once and the runtime's host threads bring
    the block into the device's layout afterwards, all queued blocks at
    once; a loop that issues every placement up front makes early
    partitions finish late, so the device idles and then runs its backlog
    after the last row has arrived. Waiting for the OLDEST placement
    before handing over one more than :data:`PLACEMENT_WINDOW_BYTES` hold
    keeps the rows arriving in order, and the host at most a window ahead
    of the device. Steps, their order and their operands are unchanged.
    """

    def __init__(self):
        self._flying = collections.deque()

    def place(self, block: Any, put):
        """:func:`place_block` — ONE ``place`` stage a partition — whose
        ``put`` first makes room: while the window is full, the oldest
        placement is waited for (``block_until_ready``). That wait is in
        the stage's time; ``ingest.place.wait_ns`` holds it alone and
        ``ingest.place.waits`` counts the placements that had to wait (none
        in a pass of no more partitions than the window). The window keeps
        a reference to the placements it may still wait for, so up to a
        window of blocks outlive the steps that consumed them, until the
        pass drops the window."""

        def put_when_room(host):
            room = max(2, PLACEMENT_WINDOW_BYTES // max(1, host.nbytes))
            if len(self._flying) >= room:
                t0 = time.perf_counter_ns()
                while len(self._flying) >= room:
                    self._flying.popleft().block_until_ready()
                bump_counter("ingest.place.wait_ns", time.perf_counter_ns() - t0)
                bump_counter("ingest.place.waits")
            placed = put(host)
            self._flying.append(placed)
            return placed

        return place_block(block, put_when_room)


class PreparedRows(NamedTuple):
    x: Any  # (n_pad, d_pad) device array, row-sharded under a mesh
    mask: Any  # (n_pad,) row validity / weight vector, P(data) under a mesh
    n_true: int  # rows before padding
    d_true: int  # features before padding


def _mask_dtype(x_dtype):
    """Masks double as row counters (sum(mask) = n); bf16 would lose
    integers above 256, so widen narrow dtypes to float32."""
    import jax.numpy as jnp

    return jnp.promote_types(x_dtype, jnp.float32)


def prepare_rows(
    rows: Any,
    mesh=None,
    dtype=None,
    device_id: int = -1,
    weights: Optional[np.ndarray] = None,
) -> PreparedRows:
    """Normalize any supported input into device-resident rows + mask.

    Runs inside an ``ingest`` trace range, with the ``densify`` stage
    round the host copy and a ``place`` stage round each device placement
    nested in it, so fit reports split ingest into the two."""
    with TraceRange("ingest", TraceColor.BLUE):
        return _prepare_rows_impl(rows, mesh, dtype, device_id, weights)


def _prepare_rows_impl(
    rows: Any,
    mesh=None,
    dtype=None,
    device_id: int = -1,
    weights: Optional[np.ndarray] = None,
) -> PreparedRows:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import (
        DATA_AXIS,
        model_axis_size,
        row_sharding,
        shard_rows_from_partitions,
    )

    if mesh is not None and jax.process_count() > 1 and is_device_array(rows):
        # Gang mode hands each process its LOCAL rows; a member's device
        # array is a single-process artifact, so it rejoins the host path
        # and enters the global array through the process-local funnel
        # (the pull is one local shard, never the global dataset).
        rows = np.asarray(rows)

    if is_device_array(rows):
        if rows.ndim != 2:
            raise ValueError(f"device-array input must be 2-D, got {rows.ndim}-D")
        x = rows
        if not jnp.issubdtype(x.dtype, jnp.floating):
            # Integral sources cast on device — still no host round trip.
            x = x.astype(dtype or default_dtype())
        n, d = int(x.shape[0]), int(x.shape[1])
        m_dtype = _mask_dtype(x.dtype)
        if mesh is not None:
            dp = int(mesh.shape[DATA_AXIS])
            mp = model_axis_size(mesh)
            pad_n = (-n) % dp
            pad_d = (-d) % mp
            if pad_n or pad_d:
                x = jnp.pad(x, ((0, pad_n), (0, pad_d)))

            def _reshard(arr=x):
                # Resharding a live device array over the mesh: retryable
                # (pure placement), but never degradable — a mesh fit
                # quietly moving to one CPU device would change the
                # collective topology under the caller.
                fault_point("ingest.device_put")
                return place_block(
                    arr, lambda a: jax.device_put(a, row_sharding(mesh))
                )

            x = default_policy().run(
                _reshard, name="ingest.device_put",
                on_retry=_reclaim_between_attempts,
            )
            mask = (jnp.arange(n + pad_n) < n).astype(m_dtype)
            mask = jax.device_put(mask, NamedSharding(mesh, P(DATA_AXIS)))
        else:
            mask = jnp.ones(n, dtype=m_dtype)
        if weights is not None:
            mask = _combine_weights(mask, weights, n, np.dtype(m_dtype), mesh)
        return PreparedRows(x, mask, n, d)

    np_dtype = np.dtype(dtype or default_dtype())
    parts = dense_partitions(rows, dtype=np_dtype)
    n = sum(p.shape[0] for p in parts)
    d = parts[0].shape[1]
    m_dtype = _mask_dtype(np_dtype)
    if mesh is not None and jax.process_count() > 1:
        # Gang deploy mode: `parts` are THIS PROCESS's rows only. The
        # process-local funnel allgathers the counts, pads every member to
        # the agreed per-process block, and assembles ONE global
        # row-sharded array — n/d below become the GLOBAL true counts, so
        # downstream reductions (which XLA psums across processes) report
        # whole-dataset results on every member.
        from spark_rapids_ml_tpu.parallel.distributed import (
            shard_rows_process_local,
            shard_vector_process_local,
        )

        n_local = n
        x, mask, n, d = shard_rows_process_local(parts, mesh, dtype=np_dtype)
        if m_dtype != mask.dtype:
            mask = mask.astype(m_dtype)
        if weights is not None:
            # weightCol weights are local like the rows: length-check
            # against the LOCAL count, shard into the same layout, and
            # fold into the mask here (the single-process combine below
            # checks against the global count and must not see them).
            w_host = np.asarray(weights).ravel()
            if w_host.shape[0] != n_local:
                raise ValueError(
                    f"weight vector has {w_host.shape[0]} entries but this "
                    f"process's data has {n_local} rows"
                )
            w = shard_vector_process_local(
                w_host, mesh, int(x.shape[0]), dtype=m_dtype
            )
            mask = mask * w
            weights = None
        return PreparedRows(x, mask, n, d)
    if mesh is not None:
        x, mask, _ = shard_rows_from_partitions(parts, mesh, dtype=np_dtype)
        if m_dtype != x.dtype:
            mask = mask.astype(m_dtype)
    else:
        if len(parts) == 1:
            x_host = parts[0]
        else:
            with StageRange("densify", TraceColor.PURPLE) as stage:
                x_host = np.concatenate(parts, axis=0)
            stage.count_bytes(x_host.nbytes)
        device = jax.local_devices()[device_id] if device_id >= 0 else None

        def _place():
            fault_point("ingest.device_put")
            return place_block(
                x_host, lambda h: jax.device_put(jnp.asarray(h), device)
            )

        # Single-process placement is the degradable rung: if the
        # accelerator is unavailable (or placement exhausts its retry
        # budget) and TPUML_DEGRADE=cpu, the fit continues on the host
        # CPU device with a structured warning instead of raising.
        x = run_degradable(
            lambda: default_policy().run(
                _place, name="ingest.device_put",
                on_retry=_reclaim_between_attempts,
            ),
            lambda: jax.device_put(jnp.asarray(x_host), cpu_device()),
            what="estimator input placement",
            site="ingest.device_put",
        )
        mask = jnp.ones(n, dtype=m_dtype)
    if weights is not None:
        mask = _combine_weights(mask, weights, n, np.dtype(m_dtype), mesh)
    return PreparedRows(x, mask, n, d)


def _combine_weights(mask, weights, n_true: int, m_dtype, mesh):
    """User weightCol weights COMBINED with the padding-validity mask
    (product), never substituted for it: the mask is what keeps padding
    rows out of every reduction, so a weight vector must not be able to
    hand a padded row nonzero weight — whatever length the caller passed.
    """
    from spark_rapids_ml_tpu.parallel.mesh import weights_as_mask

    w_host = np.asarray(weights).ravel()
    if w_host.shape[0] != n_true:
        raise ValueError(
            f"weight vector has {w_host.shape[0]} entries but the data has "
            f"{n_true} rows"
        )
    w = weights_as_mask(w_host, int(mask.shape[0]), m_dtype, mesh)
    return mask * w


def place_array(arr: Any, dtype=None, device=None):
    """Guarded device placement for an n-sized SIDECAR array that rides
    alongside :func:`prepare_rows` output (per-row stats, one-hot label
    blocks): the same ``ingest.device_put`` fault point, retry policy,
    and OOM cache-reclaim hook as the main row funnel, so no fit-path
    whole-array upload bypasses the memory-safety chokepoint. Device
    inputs stay resident (cast in place when asked)."""
    import jax
    import jax.numpy as jnp

    if is_device_array(arr):
        if dtype is not None and arr.dtype != dtype:
            return arr.astype(dtype)
        return arr
    host = np.asarray(arr, dtype=np.dtype(dtype) if dtype is not None else None)

    def _place():
        fault_point("ingest.device_put")
        return place_block(
            host, lambda h: jax.device_put(jnp.asarray(h), device)
        )

    return default_policy().run(
        _place, name="ingest.device_put", on_retry=_reclaim_between_attempts
    )


def matrix_like(x: Any, dtype=None):
    """A (n, d) matrix in its natural residence: device arrays stay on
    device (cast there if asked), anything else densifies on host. The
    model-side twin of :func:`prepare_rows` for predict/transform inputs."""
    if is_device_array(x):
        if x.ndim == 1:
            x = x[None, :]
        if dtype is not None and x.dtype != dtype:
            return x.astype(dtype)
        return x
    from spark_rapids_ml_tpu.core.data import as_matrix

    out = as_matrix(x, dtype=np.dtype(dtype) if dtype is not None else None)
    return out


def prepare_labels(y: Any, n_pad: int, n_true: Optional[int] = None, mesh=None, dtype=None):
    """Place a label/target vector alongside :func:`prepare_rows` output:
    padded to the rows' padded length and P(data)-sharded under a mesh.
    Device-resident labels stay resident (padded on device).

    ``n_true`` (the rows' true count) guards against a LENGTH-MISMATCHED
    (X, y) pair: only mesh/block padding may be zero-filled — a y shorter
    than the data would otherwise silently train on phantom rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    dtype = dtype or default_dtype()
    if mesh is not None and jax.process_count() > 1:
        # Gang deploy mode: y holds THIS PROCESS's labels. Shard them into
        # the exact P(data) layout prepare_rows produced (local values
        # first in each process's block, zeros in the padding) and verify
        # the GLOBAL label count matches the rows' true count — the
        # length-mismatch guard below can only see local lengths.
        from spark_rapids_ml_tpu.parallel.distributed import (
            _allgather_counts_and_width,
            shard_vector_process_local,
        )

        y_arr = np.asarray(y).ravel()
        counts, _ = _allgather_counts_and_width(int(y_arr.shape[0]), 0)
        if n_true is not None and int(counts.sum()) != n_true:
            raise ValueError(
                f"label vectors total {int(counts.sum())} entries across "
                f"the gang but the data has {n_true} rows"
            )
        return shard_vector_process_local(y_arr, mesh, n_pad, dtype=dtype)
    if is_device_array(y):
        ys = y.ravel().astype(dtype) if y.dtype != dtype else y.ravel()
        if n_true is not None and int(ys.shape[0]) != n_true:
            raise ValueError(
                f"label vector has {int(ys.shape[0])} entries but the data "
                f"has {n_true} rows"
            )
        pad = n_pad - int(ys.shape[0])
        if pad:
            ys = jnp.pad(ys, (0, pad))
    else:
        y_arr = np.asarray(y).ravel()
        if n_true is not None and y_arr.shape[0] != n_true:
            raise ValueError(
                f"label vector has {y_arr.shape[0]} entries but the data "
                f"has {n_true} rows"
            )
        y_host = np.zeros(n_pad, dtype=np.dtype(dtype))
        y_host[: y_arr.shape[0]] = y_arr
        ys = jnp.asarray(y_host)
    if mesh is not None:
        ys = jax.device_put(ys, NamedSharding(mesh, P(DATA_AXIS)))
    return ys


def validate_int_labels(y: Any):
    """Shared classifier label check: non-negative integers. Works for host
    and device labels; on device this costs ONE scalar-vector readback (the
    class count defines array shapes, so a sync is inherent — what must NOT
    happen is an O(n) pull of the label vector, and each separate readback
    is a full host round trip, so the integrality flag, min, and max travel
    as one stacked device array — the
    models.random_forest._weight_exact_and_max pattern).

    Returns ``(y_int, n_classes)`` with ``y_int`` in the input's residence
    (int32 on device, int64 on host).
    """
    if is_device_array(y):
        import jax.numpy as jnp

        y = y.ravel()
        y_int = y.astype(jnp.int32)
        if jnp.issubdtype(y.dtype, jnp.floating):
            integral = jnp.all(y == y_int.astype(y.dtype))
        else:
            integral = jnp.asarray(True)
        stats = np.asarray(
            jnp.stack(
                [
                    integral.astype(jnp.int32),
                    jnp.min(y_int),
                    jnp.max(y_int),
                ]
            )
        )
        if not bool(stats[0]):
            raise ValueError("labels must be integers in [0, numClasses)")
        if int(stats[1]) < 0:
            raise ValueError("labels must be >= 0")
        return y_int, int(stats[2]) + 1
    y_host = np.asarray(y).ravel()
    y_int = y_host.astype(np.int64)
    if not np.array_equal(y_int, y_host):
        raise ValueError("labels must be integers in [0, numClasses)")
    if y_int.size and y_int.min() < 0:
        raise ValueError("labels must be >= 0")
    return y_int, int(y_int.max()) + 1 if y_int.size else 1


def to_host_f64(x) -> np.ndarray:
    """Materialize any array as host float64 (the reference's ``double[]``
    surface, JniRAPIDSML.java:64-69). The models call this LAZILY so a
    device-input fit pays the pull only when someone reads the result."""
    return np.asarray(x, dtype=np.float64)
