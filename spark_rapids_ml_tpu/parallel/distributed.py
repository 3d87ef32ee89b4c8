"""Multi-process distributed execution — the jax.distributed bring-up.

The reference scales across hosts through Spark: one executor per GPU, RDD
partitions as the local data, driver-side ``reduce`` as the fabric
(RapidsRowMatrix.scala:170-201; README.md:74-87 spark-submit flow). The
TPU-native equivalent is one PROCESS per chip (or per host), brought up
with ``jax.distributed.initialize`` so every process sees the GLOBAL device
set; a ``jax.sharding.Mesh`` over those devices is the fabric, and the
covariance/Gram reductions ride XLA collectives (psum over ICI/DCN) instead
of the driver network.

Deployment shape (mirrors the reference's executor model):

  - the launcher (Spark, SLURM, GKE, ...) starts N processes and hands each
    a coordinator address + its process id — here via env vars
    (``TPUML_COORDINATOR``/``TPUML_NUM_PROCESSES``/``TPUML_PROCESS_ID``) or
    explicit arguments;
  - each process pins itself to its chip (spark.resources.
    pin_process_to_chip) BEFORE jax initializes, calls :func:`initialize`,
    loads its LOCAL rows, and calls the ordinary estimator API with a
    global mesh: ``PCA(mesh=global_mesh()).fit(local_blocks)``;
  - every process gets the identical fitted model back (the reduced
    moments are replicated by the collectives).
"""

from __future__ import annotations

import functools as _functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    make_mesh,
    model_axis_size,
)
from spark_rapids_ml_tpu.robustness.faults import fault_point
from spark_rapids_ml_tpu.robustness.retry import default_policy
from spark_rapids_ml_tpu.utils.envknobs import EnvKnobError, env_int, env_str

_initialized = False
# The coordinates the active runtime was actually brought up with —
# compared against any LATER initialize() call so a conflicting request
# is named instead of silently ignored.
_init_record: Optional[dict] = None


class GangReinitWarning(UserWarning):
    """A second ``initialize`` asked for a DIFFERENT gang than the one
    this process already joined. jax.distributed cannot re-form a cohort
    in-process, so the request is ignored — but silently honoring the
    old coordinates while the caller believes it changed them is exactly
    how a relaunched gang rejoins a dead cohort. Carries the field name
    and both values."""

    def __init__(self, field: str, active, requested):
        self.field = field
        self.active = active
        self.requested = requested
        super().__init__(
            f"jax.distributed is already initialized with {field}="
            f"{active!r}; ignoring a later initialize() requesting "
            f"{field}={requested!r} — a genuinely new gang needs a fresh "
            "process (or jax.distributed.shutdown() first)"
        )


def _check_reinit_request(
    coordinator_address, num_processes, process_id
) -> None:
    """The already-initialized path: resolve what THIS call asked for
    (explicit args > env, malformed env treated as unknown rather than
    raising on a previously-silent no-op) and warn, field by field, where
    it conflicts with the active runtime."""
    import warnings

    if _init_record is None:
        return
    requested = {"coordinator_address": coordinator_address or env_str("TPUML_COORDINATOR")}
    try:
        requested["num_processes"] = (
            num_processes if num_processes is not None
            else env_int("TPUML_NUM_PROCESSES", minimum=1)
        )
        requested["process_id"] = (
            process_id if process_id is not None
            else env_int("TPUML_PROCESS_ID", minimum=0)
        )
    except EnvKnobError:
        requested.setdefault("num_processes", None)
        requested.setdefault("process_id", None)
    for field, asked in requested.items():
        active = _init_record.get(field)
        if asked is not None and active is not None and asked != active:
            warnings.warn(
                GangReinitWarning(field, active, asked), stacklevel=3
            )


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
    heartbeat_timeout_seconds: Optional[int] = None,
) -> None:
    """Bring up the jax.distributed runtime for this process (idempotent).

    Arguments fall back to the ``TPUML_COORDINATOR`` /
    ``TPUML_NUM_PROCESSES`` / ``TPUML_PROCESS_ID`` environment variables,
    and from there to JAX's own auto-detection (which covers TPU pods,
    where the runtime publishes the coordinator itself). Call BEFORE any
    other JAX API touches the backend.

    ``heartbeat_timeout_seconds`` (env ``TPUML_HEARTBEAT_TIMEOUT``) bounds
    FAILURE DETECTION: when a peer process dies mid-job, the surviving
    processes' next collective raises a distributed-runtime error within
    roughly this window instead of hanging (jax's default is 100 s). The
    recovery recipe is relaunch-and-refit — see docs/PARITY.md §5 (the
    Spark barrier-task retry analogue).
    """
    global _initialized, _init_record
    if _initialized:
        # Not silent anymore: a second call asking for a DIFFERENT
        # coordinator or process id gets a structured GangReinitWarning
        # naming both values (the silent path hid exactly the relaunch
        # bug the barrier launcher exists to prevent).
        _check_reinit_request(coordinator_address, num_processes, process_id)
        return
    # env_int (utils/envknobs.py) names the variable, the bad value, and
    # the expected form — a launcher typo used to surface as an anonymous
    # `invalid literal for int()` on every gang member at once.
    coordinator_address = coordinator_address or env_str("TPUML_COORDINATOR")
    if num_processes is None:
        num_processes = env_int("TPUML_NUM_PROCESSES", minimum=1)
    if process_id is None:
        process_id = env_int("TPUML_PROCESS_ID", minimum=0)
    if heartbeat_timeout_seconds is None:
        heartbeat_timeout_seconds = env_int("TPUML_HEARTBEAT_TIMEOUT", minimum=1)

    from spark_rapids_ml_tpu.utils.compat import distributed_initialize

    def _bring_up():
        # The coordination-service connect is the canonically flaky step
        # of a gang bring-up (members race the coordinator's bind); the
        # shared RetryPolicy owns the attempts/backoff/classification that
        # used to be delegated entirely to the launcher, and each attempt
        # is a profiler range so slow bring-ups are visible in traces.
        fault_point("distributed.initialize")
        distributed_initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
            heartbeat_timeout_seconds=heartbeat_timeout_seconds,
        )

    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

    # One named span around the whole bring-up (the retry policy nests
    # its per-attempt ranges inside), so a merged gang trace shows each
    # member's coordination-service connect on the critical path.
    with TraceRange("distributed bring-up", TraceColor.BLUE):
        default_policy().run(_bring_up, name="distributed.initialize")
    _initialized = True
    _init_record = {
        "coordinator_address": coordinator_address,
        "num_processes": num_processes,
        "process_id": process_id,
    }
    # Stamp the event-log envelope with this process's gang index and
    # record the bring-up, so every later record from this process is
    # attributable in a merged multi-process stream.
    from spark_rapids_ml_tpu.observability.events import emit, set_process_index

    try:
        set_process_index(
            process_id if process_id is not None else jax.process_index()
        )
    except RuntimeError:  # backend not queryable yet — keep env fallback
        pass
    emit(
        "distributed",
        action="initialize",
        coordinator=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def bringup_executor(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    chip_ordinal: Optional[int] = None,
    heartbeat_timeout_seconds: Optional[int] = None,
) -> None:
    """One-call executor entry for the one-process-per-chip deployment:
    resolve this process's chip (explicit ordinal > Spark task resource >
    0 — the reference's gpuId semantics, RapidsRowMatrix.scala:171-175),
    pin PJRT to it BEFORE backend init, then bring up jax.distributed.

    A Spark barrier task / SLURM step body reduces to::

        bringup_executor()                       # env-driven
        model = PCA(mesh=global_mesh()).fit(local_blocks)
    """
    from spark_rapids_ml_tpu.spark.resources import (
        pin_process_to_chip,
        resolve_device_ordinal,
    )

    ordinal = resolve_device_ordinal(
        -1 if chip_ordinal is None else chip_ordinal
    )
    pin_process_to_chip(ordinal)
    initialize(
        coordinator_address,
        num_processes,
        process_id,
        heartbeat_timeout_seconds=heartbeat_timeout_seconds,
    )


def global_mesh(shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """A (data × model) mesh over the GLOBAL device set — every process
    builds the identical mesh (jax.devices() is globally consistent after
    :func:`initialize`)."""
    return make_mesh(shape)


def member_env(
    process_id: int,
    num_processes: int,
    base: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The environment for one spawned gang member (the serving router's
    worker processes, or any launcher forking local peers): the parent's
    environment plus this member's gang coordinates and the PR 7 trace
    carrier, so the child's telemetry shard lands in the same merged
    trace with a distinct process index. Members run as INDEPENDENT
    single-process runtimes (no jax.distributed cohort), so any inherited
    coordinator address is dropped rather than having N children fight
    over one gang slot. The repo root rides PYTHONPATH so ``python -m``
    entry points resolve regardless of the parent's cwd."""
    from spark_rapids_ml_tpu.observability.events import inject_env

    env = dict(base if base is not None else os.environ)
    env["TPUML_PROCESS_ID"] = str(int(process_id))
    env["TPUML_NUM_PROCESSES"] = str(int(num_processes))
    env.pop("TPUML_COORDINATOR", None)
    inject_env(env)
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    existing = env.get("PYTHONPATH")
    if existing:
        if root not in existing.split(os.pathsep):
            env["PYTHONPATH"] = root + os.pathsep + existing
    else:
        env["PYTHONPATH"] = root
    return env


def _allgather_counts_and_width(n_local: int, d_local: int):
    """The deadlock-safe shape handshake shared by every process-local
    collective entry: the allgather comes FIRST — before anything that can
    raise on one process — so an empty/odd executor participates instead
    of stranding its peers, and width mismatches raise on ALL processes
    consistently. Returns ``(counts (n_proc,), d)``."""
    from jax.experimental import multihost_utils

    info = multihost_utils.process_allgather(
        np.asarray([n_local, d_local], dtype=np.int64)
    )
    info = np.asarray(info).reshape(-1, 2)
    widths = sorted({int(w) for w in info[:, 1] if w >= 0})
    if not widths:
        raise ValueError("no process contributed any blocks")
    if len(widths) > 1:
        raise ValueError(f"feature dim mismatch across processes: {widths}")
    return info[:, 0], widths[0]


def shard_rows_process_local(
    partitions: List[np.ndarray], mesh: Mesh, dtype=None
) -> Tuple[jax.Array, jax.Array, int, int]:
    """Assemble a GLOBAL row-sharded array from per-process LOCAL blocks.

    Each process passes only the rows it loaded (its executor-local
    partitions); no process ever sees the whole dataset. Per-process row
    counts may differ: every process pads its local rows to the globally
    agreed per-process maximum (one tiny allgather of the counts), and the
    row mask zeroes the padding inside the compiled reductions, so results
    are exact.

    Supports 2-D (data × model) meshes: features are
    zero-padded to the model-axis multiple and split across each process's
    OWN devices, so a process's addressable shards stay one contiguous row
    block × the full model axis. That requires the process's local device
    count to be a multiple of the model axis (jax.devices() orders a
    process's devices consecutively, so ``make_mesh``'s row-major reshape
    gives every process whole mesh rows exactly when model | local_devices).

    Returns ``(x_sharded, row_mask_sharded, n_true_rows_global, d_true)``
    — ``d_true`` is the unpadded feature width (padded columns are exactly
    zero; callers slice them off the results).
    """
    parts = [np.asarray(p) for p in partitions]
    if dtype is not None:
        parts = [p.astype(dtype, copy=False) for p in parts]
    n_local = sum(p.shape[0] for p in parts)
    # Zero-row placeholder blocks (e.g. the (0, 0) densification of an
    # empty partition list) carry no width information.
    d_local = next((p.shape[1] for p in parts if p.shape[0] > 0), -1)

    counts, d = _allgather_counts_and_width(n_local, d_local)
    n_true = int(counts.sum())
    np_dtype = parts[0].dtype if parts else np.dtype(dtype or np.float64)

    n_proc = jax.process_count()
    local_dev = jax.local_device_count()
    dp = mesh.shape[DATA_AXIS]
    mp = model_axis_size(mesh)
    if dp * mp != n_proc * local_dev:
        raise ValueError(
            f"mesh {dp}x{mp} != process_count*local_devices "
            f"{n_proc}*{local_dev}"
        )
    if local_dev % mp != 0:
        raise ValueError(
            f"model axis {mp} must divide the per-process device count "
            f"{local_dev}: each process's addressable shards must span "
            "whole mesh rows (consecutive-device mesh layout)"
        )
    d_tot = d + ((-d) % mp)
    # Equal per-process row count, padded so it slices evenly across this
    # process's local_dev/mp mesh rows — the even GSPMD slicing of the
    # global array must line up with what each process actually holds.
    rows_per_proc_of_mesh = local_dev // mp
    per_proc = int(counts.max())
    per_proc += (-per_proc) % rows_per_proc_of_mesh

    x_local = np.zeros((per_proc, d_tot), dtype=np_dtype)
    off = 0
    for p in parts:
        if p.shape[0] == 0:
            continue
        x_local[off : off + p.shape[0], :d] = p
        off += p.shape[0]
    mask_local = np.zeros(per_proc, dtype=np_dtype)
    mask_local[:n_local] = 1.0

    from spark_rapids_ml_tpu.parallel.mesh import row_sharding

    x_sharding = row_sharding(mesh)  # handles meshes without a model axis
    m_sharding = NamedSharding(mesh, P(DATA_AXIS))
    xs = jax.make_array_from_process_local_data(
        x_sharding, x_local, (per_proc * n_proc, d_tot)
    )
    ms = jax.make_array_from_process_local_data(
        m_sharding, mask_local, (per_proc * n_proc,)
    )
    return xs, ms, n_true, d


def shard_vector_process_local(
    v_local, mesh: Mesh, n_pad_global: int, dtype=None
) -> jax.Array:
    """Place a per-process LOCAL vector (labels, sample weights) into the
    GLOBAL ``P(data)`` layout of :func:`shard_rows_process_local`: that
    function puts each process's true rows first in its contiguous
    ``n_pad_global / process_count`` row block, so the companion vector
    pads the same way and rides the same sharding — row i of the global
    matrix and element i of the global vector always belong to the same
    original sample.

    ``n_pad_global`` is the padded global row count the matrix came back
    with (``x.shape[0]``); the local values must fit this process's block.
    """
    v = np.asarray(v_local)
    if dtype is not None:
        v = v.astype(dtype, copy=False)
    n_proc = jax.process_count()
    if n_pad_global % n_proc != 0:
        raise ValueError(
            f"padded global length {n_pad_global} must divide evenly "
            f"across {n_proc} processes"
        )
    per_proc = n_pad_global // n_proc
    if v.shape[0] > per_proc:
        raise ValueError(
            f"local vector has {v.shape[0]} values but this process's row "
            f"block holds {per_proc}; pass the rows and the vector from "
            "the same local partitions"
        )
    pad = np.zeros((per_proc,) + v.shape[1:], dtype=v.dtype)
    pad[: v.shape[0]] = v
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    return jax.make_array_from_process_local_data(
        sharding, pad, (n_pad_global,) + v.shape[1:]
    )


def allgather_host_max(value) -> int:
    """Global max of a per-process host scalar (one tiny allgather) —
    e.g. the label-derived class count, which each gang member computes
    from LOCAL labels but every member must agree on before tracing a
    shape-dependent solver."""
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray([int(value)], dtype=np.int64)
    )
    return int(np.asarray(gathered).max())


@_functools.lru_cache(maxsize=4)
def _replicate_identity_jit(mesh: Mesh):
    """One cached jitted replicated-identity per mesh (same cache
    discipline as :func:`_replicated_sum_jit`); the single P() sharding
    broadcasts across however many outputs a call passes."""
    return jax.jit(
        lambda *xs: xs, out_shardings=NamedSharding(mesh, P())
    )


def replicate_for_host(mesh: Optional[Mesh], *arrays):
    """Make fit results safe to read on the host from EVERY gang member.

    Outputs of an SPMD fit over globally-sharded inputs can come back
    row- or column-sharded; ``np.asarray`` on such an array raises (or
    worse, sees one shard) on a multi-process runtime. This reshards each
    array fully replicated — XLA lowers the move to an all-gather — so
    the per-member model construction reads identical host values
    everywhere. Identity when single-process (or mesh-less): the
    monolithic path pays nothing.

    Returns the arrays in order (a single array unwrapped).
    """
    if mesh is None or jax.process_count() <= 1 or not arrays:
        return arrays if len(arrays) > 1 else arrays[0]
    import jax.numpy as jnp

    out = _replicate_identity_jit(mesh)(*[jnp.asarray(a) for a in arrays])
    return tuple(out) if len(arrays) > 1 else out[0]


def streaming_covariance_process_local(
    blocks, center: bool = True, dtype=None, precision: str = "highest",
    mesh: Optional[Mesh] = None, merge: str = "auto",
):
    """Each process streams ITS OWN local blocks through the one-pass
    shifted accumulation (device Gram per block on its chip — or the dd
    double-float kernels for ``precision="dd"``), then the O(d²)
    per-process moments merge across processes — the reference's
    executor-local compute + cross-process reduce
    (RapidsRowMatrix.scala:170-201) at constant memory per process.

    Two merge backends:
      - ``"psum"`` (the default with a mesh, non-dd): a tiny O(d) host
        allgather agrees on a COMMON shift (the count-weighted mean of
        the per-process shifts — any common value is exact, the choice
        only conditions the algebra), each process rebases its moments
        onto it with the closed-form correction, and the (d, d) payload
        merges as ONE jitted replicated-sum whose cross-process reduce
        XLA lowers to a psum riding ICI — the O(d²) traffic never touches
        the host network.
      - ``"allgather"`` (the default without a mesh, and always for
        ``precision="dd"``): host allgather of the per-process moments +
        exact fp64 ShiftedMoments merge; dd payloads carry ~48 mantissa
        bits that a device-dtype psum would squash on no-x64 platforms,
        so dd stays here by construction.

    Per-process shifts differ (each uses its first block's means); both
    backends rebase exactly (the ShiftedMoments algebra, core/moments.py).
    Zero-block processes contribute nothing and strand nobody. Returns
    host fp64 ``(mean, cov, n_global)`` on every process.
    """
    import jax.numpy as jnp

    from jax.experimental import multihost_utils

    from spark_rapids_ml_tpu.ops.covariance import shifted_block_scan

    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

    if precision == "dd":
        from spark_rapids_ml_tpu.ops.doubledouble import centered_gram_dd

        def gram_fn(bs):
            return centered_gram_dd(bs, np.zeros(bs.shape[1]))

    else:
        from spark_rapids_ml_tpu.ops.covariance import centered_gram

        def gram_fn(bs):
            return centered_gram(
                jnp.asarray(bs, dtype=dtype),
                jnp.zeros(bs.shape[1], dtype=dtype),
                precision=precision,
            )

    if merge not in ("auto", "psum", "allgather"):
        raise ValueError(f"merge must be auto|psum|allgather, got {merge!r}")
    if merge == "auto":
        merge = "psum" if (mesh is not None and precision != "dd") else "allgather"
    if merge == "psum" and precision == "dd":
        raise ValueError(
            "merge='psum' would squash the dd moments to the device dtype; "
            "dd uses merge='allgather'"
        )

    # min_rows=0: a process with zero (or one) local rows still returns
    # its partial moments and joins the merge instead of raising.
    shift, gram, s, n_local = shifted_block_scan(blocks, center, gram_fn, min_rows=0)
    if gram is not None:
        gram = np.asarray(gram, dtype=np.float64)
    d_local = shift.shape[0] if shift is not None else -1

    counts, d = _allgather_counts_and_width(n_local, d_local)
    if shift is None:
        shift = np.zeros(d)
        gram = np.zeros((d, d))
        s = np.zeros(d)

    if merge == "psum":
        # One retry unit around the whole device merge: the rebase is
        # pure host math and the replicated sum is deterministic, so a
        # re-run after a transient collective failure is exact — and the
        # TPUML_FAULTS spec is process-identical, so every gang member
        # retries in lockstep.
        return default_policy().run(
            lambda: _psum_merge_moments(
                shift, gram, s, n_local, counts, d, center, dtype
            ),
            name="collective.psum",
        )

    # One allgather of the packed per-process moments: [shift | s | gram].
    # The wire must not squash the fp64 payload: without x64,
    # process_allgather canonicalizes float64 -> float32, so the payload
    # travels as a double-float (hi, lo) f32 pair (~48 mantissa bits —
    # the same fidelity bar the dd kernels meet).
    packed = np.concatenate([shift, s, gram.ravel()])
    if jax.config.jax_enable_x64:
        gathered = np.asarray(
            multihost_utils.process_allgather(packed), dtype=np.float64
        )
    else:
        from spark_rapids_ml_tpu.ops.doubledouble import split_f64

        hi, lo = split_f64(packed)
        g_hi = np.asarray(
            multihost_utils.process_allgather(hi), dtype=np.float64
        )
        g_lo = np.asarray(
            multihost_utils.process_allgather(lo), dtype=np.float64
        )
        gathered = g_hi + g_lo
    gathered = gathered.reshape(-1, 2 * d + d * d)

    # Merge through the ONE home of the shifted-moment rebase algebra.
    from spark_rapids_ml_tpu.core.moments import ShiftedMoments

    acc = None
    for i in range(gathered.shape[0]):
        n_i = int(counts[i])
        if n_i == 0:
            continue
        m = ShiftedMoments(d)
        m.n_rows = n_i
        m.shift = gathered[i, :d].copy()
        m.sum = gathered[i, d : 2 * d].copy()
        m.gram = gathered[i, 2 * d :].reshape(d, d).copy()
        acc = m if acc is None else acc.merge(m)
    if acc is None or acc.n_rows < 2:
        n_tot = 0 if acc is None else acc.n_rows
        raise ValueError(f"need at least 2 rows to compute a covariance, got {n_tot}")
    cov, mean = acc.finalize(center=center)
    return mean, cov, acc.n_rows


def _psum_merge_moments(shift, gram, s, n_local, counts, d, center, dtype):
    """Device-collective moment merge: rebase local moments onto a common
    shift (exact closed form, fp64 on host), then ONE jitted replicated
    sum over a flat all-devices mesh — XLA lowers the cross-process
    reduce to a psum over ICI, so the O(d²) payload never rides the host
    network. The payload travels at the device dtype: on no-x64 platforms
    that matches the f32 grams' own information content (dd, which
    carries more, is excluded by the caller)."""
    fault_point("collective.psum")
    import jax.numpy as jnp

    from jax.experimental import multihost_utils

    # Common shift: count-weighted mean of the per-process shifts. Any
    # COMMON value keeps the algebra exact — an f32-rounded wire here
    # only affects conditioning — so one tiny O(d) allgather suffices.
    gathered_shift = np.asarray(
        multihost_utils.process_allgather(shift.astype(np.float32)),
        dtype=np.float64,
    ).reshape(-1, d)
    weights = counts.astype(np.float64)
    total = max(weights.sum(), 1.0)
    common = (gathered_shift * weights[:, None]).sum(axis=0) / total

    # Exact rebase of THIS process's moments from its shift a to common c:
    # x − c = (x − a) + δ with δ = a − c.
    delta = np.asarray(shift, dtype=np.float64) - common
    s64 = np.asarray(s, dtype=np.float64)
    s_c = s64 + n_local * delta
    gram_c = (
        np.asarray(gram, dtype=np.float64)
        + np.outer(delta, s64)
        + np.outer(s64, delta)
        + n_local * np.outer(delta, delta)
    )

    # One payload slot per process ([gram | s | n] flattened on device
    # slot 0, zeros elsewhere); replicated-sum over a flat device mesh.
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    local_dev = jax.local_device_count()
    n_dev = len(jax.devices())
    width = d * d + d
    payload = np.zeros((local_dev, width), dtype=np.dtype(dtype))
    payload[0, : d * d] = gram_c.ravel()
    payload[0, d * d :] = s_c

    flat = Mesh(np.asarray(jax.devices()), ("proc",))
    arr = jax.make_array_from_process_local_data(
        NamedSharding(flat, P("proc")), payload, (n_dev, width)
    )
    out = np.asarray(_replicated_sum_jit(flat)(arr), dtype=np.float64)

    from spark_rapids_ml_tpu.core.moments import ShiftedMoments

    # The exact integer row count rides the HOST counts allgather (already
    # in hand), never the float device payload — a bf16/f32 payload would
    # round it.
    n_tot = int(counts.sum())
    if n_tot < 2:
        raise ValueError(
            f"need at least 2 rows to compute a covariance, got {n_tot}"
        )
    acc = ShiftedMoments(d)
    acc.n_rows = n_tot
    acc.shift = common
    acc.sum = out[d * d :].copy()
    acc.gram = out[: d * d].reshape(d, d).copy()
    cov, mean = acc.finalize(center=center)
    return mean, cov, acc.n_rows


@_functools.lru_cache(maxsize=4)
def _replicated_sum_jit(mesh: Mesh):
    """One cached jitted replicated-sum per flat mesh — a fresh lambda per
    call would miss the jit cache and recompile every fit."""
    return jax.jit(
        lambda a: a.sum(axis=0),
        out_shardings=NamedSharding(mesh, P()),
    )


# Elastic gang resume: a relaunched gang restores host checkpoint state
# on every process and replicates it onto the NEW mesh through this
# helper (one home, robustness/checkpoint.py) before resuming mid-solve.
from spark_rapids_ml_tpu.robustness.checkpoint import (  # noqa: E402
    replicate_state_onto_mesh,
)

__all__ = [
    "GangReinitWarning",
    "allgather_host_max",
    "initialize",
    "bringup_executor",
    "global_mesh",
    "replicate_for_host",
    "replicate_state_onto_mesh",
    "shard_rows_process_local",
    "shard_vector_process_local",
    "streaming_covariance_process_local",
]
