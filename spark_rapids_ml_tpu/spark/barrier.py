"""Barrier-stage gang deployment — the executable failure-recovery path.

The reference inherits its whole failure story from Spark: a CUDA error
throws through JNI (``rapidsml_jni.cu:101-153`` pattern), the task fails,
and Spark's scheduler retries it against the RDD lineage (SURVEY §5).
That per-task retry is WRONG for a multi-process jax.distributed fit: the
processes form a gang (one coordination service, collectives over every
member), so an individually retried task would rejoin a cohort whose
peers are dead or hung. The correct Spark deployment is a **barrier
stage** (``rdd.barrier().mapPartitions``): the scheduler launches all
tasks together and retries the WHOLE stage when any task fails — exactly
the relaunch-the-gang semantic the distributed fits need
(docs/PARITY.md "Failure detection / recovery").

This module is the small launcher that recipe describes:

  - :func:`barrier_gang_run` — run a per-partition task function as one
    barrier stage and collect its outputs; any task failure relaunches
    the gang (Spark's stage retry, up to spark.stage.maxConsecutiveAttempts).
  - :func:`gang_coordinates` — derive ``jax.distributed.initialize``
    arguments (coordinator address, process count/id) from the barrier
    task context, so each relaunched gang re-forms a FRESH cohort.

Works identically against genuine pyspark and the contract stub
(tests/pyspark_stub) — the shared suite exercises a mid-fit task kill
under both (tests/spark_contract_suite.py::TestBarrierGangRecovery).
"""

from __future__ import annotations

import os
from typing import Callable, Iterable, Iterator, Optional

from spark_rapids_ml_tpu.robustness.degrade import run_degradable
from spark_rapids_ml_tpu.robustness.retry import RetryPolicy
from spark_rapids_ml_tpu.utils.envknobs import env_int

DEFAULT_COORDINATOR_PORT = 8476  # jax.distributed's conventional port

# Driver-side STAGE resubmissions (whole-gang, on top of the scheduler's
# own spark.stage.maxConsecutiveAttempts budget). Default 1 = submit once
# and trust the scheduler, exactly the pre-policy behavior; raise it when
# the cluster's stage budget is too small for the failure domain.
BARRIER_RESUBMITS_ENV = "TPUML_BARRIER_RESUBMITS"


def barrier_gang_run(
    rdd,
    task_fn: Callable[[Optional[object], Iterator], Iterable],
    policy: Optional[RetryPolicy] = None,
    checkpoint_dir: Optional[str] = None,
) -> list:
    """Run ``task_fn(barrier_ctx, partition_iterator)`` over every
    partition as ONE barrier stage and return the collected outputs.

    ``barrier_ctx`` is the ``BarrierTaskContext`` (None only where a
    runtime lacks barrier support). The context's ``barrier()`` is called
    before ``task_fn`` so no member starts compute until the whole gang
    is scheduled — a member that fails at launch aborts the attempt
    before any collective can strand survivors. Any exception in any
    task relaunches ALL tasks (Spark barrier-stage retry); after the
    scheduler's stage-attempt limit the error reaches the driver, where
    the shared :class:`RetryPolicy` (robustness.retry) owns what happens
    next: classification (a ``ValueError`` from the task is a bug and
    re-raises untouched; a runtime failure is retryable), optional
    whole-stage resubmission (``TPUML_BARRIER_RESUBMITS``, default 1 =
    no resubmit), a profiler range per attempt, and one classified
    ``RetryExhaustedError`` when the budget is gone — never a hang.

    With ``TPUML_DEGRADE=cpu`` an exhausted budget degrades instead of
    raising: the partitions re-run on the driver as a plain (non-barrier,
    non-gang) stage with ``ctx=None`` — there is no cohort left to
    strand — under a structured :class:`DegradationWarning`.

    One-pass reductions simply refit from the same lineage on relaunch.
    ITERATIVE fits do better: pass ``checkpoint_dir`` (a path every
    executor can reach — the elastic-resume handoff) and each gang
    member exports it as ``TPUML_CHECKPOINT_DIR`` before running
    ``task_fn``, so a fit inside the task checkpoints its solver state
    (robustness/checkpoint.py) and a gang resubmitted after a dead
    worker — detected via the heartbeat timeout — resumes mid-solve
    from the last snapshot instead of iteration 0, resharding the
    restored state onto the fresh mesh
    (``parallel.distributed.replicate_state_onto_mesh``). Give the
    estimators STABLE uids: checkpoint identity is uid + param hash.
    Every driver-side resubmission bumps the ``gang.resubmit`` counter.

    Each gang member declares the ``barrier.attempt`` fault site
    (robustness.faults) right after the launch barrier, so chaos tests
    can kill attempt 0 and assert the relaunch refits bit-identically.

    The whole stage runs as ONE distributed trace: the driver opens (or
    joins) a trace under a ``barrier gang`` span, and a carrier dict —
    trace coordinates (``TPUML_TRACE_ID``/``TPUML_TRACE_PARENT``), the
    telemetry shard dir (``TPUML_TELEMETRY_DIR``) and the checkpoint dir
    — rides the task closure into every member, which exports it to its
    environment before compute. Each member's spans therefore carry the
    driver's trace id and parent to the driver's stage span, and each
    member process writes its own telemetry shard, so
    ``tools/tpuml_trace.py`` reassembles the gang fit as one tree.
    """
    from spark_rapids_ml_tpu.observability import events as _events
    from spark_rapids_ml_tpu.utils.tracing import (
        TraceColor,
        TraceRange,
        bump_counter,
    )

    with _events.run_scope("gang", "barrier_gang_run"), TraceRange(
        "barrier gang", TraceColor.CYAN
    ):
        carrier = _events.inject_env({})
        if checkpoint_dir is not None:
            from spark_rapids_ml_tpu.robustness.checkpoint import DIR_ENV

            carrier[DIR_ENV] = checkpoint_dir
        tdir = _events.telemetry_dir()
        if tdir is not None:
            carrier[_events.TELEMETRY_DIR_ENV] = tdir

        def wrapped(it):
            from pyspark import BarrierTaskContext

            from spark_rapids_ml_tpu.observability import events as _ev
            from spark_rapids_ml_tpu.observability.heartbeat import (
                heartbeat_scope,
            )
            from spark_rapids_ml_tpu.robustness.faults import fault_point

            # Export the carrier for the TASK'S lifetime only: executor
            # processes are reused across tasks (and under the stub the
            # "executor" IS the driver), so a permanent export would leak
            # this stage's trace into the next job's.
            saved = {k: os.environ.get(k) for k in carrier}
            os.environ.update(carrier)
            # A SIGTERM'd member (executor decommission, preemption)
            # flushes its shard + manifest from the handler — the
            # manifest-less-shard WARNING in the post-hoc merge is for
            # SIGKILL-class deaths only. On the driver-local stub this
            # is a no-op (not the main thread).
            undo_sigterm = _ev.install_sigterm_flush()
            try:
                if not _ev.enabled():
                    # A fresh executor process: wire its own telemetry
                    # shard (or event log) and pick up the driver's env
                    # trace. On the driver-local stub the sink is already
                    # live and the trace ambient — nothing to rewire.
                    _ev.configure()
                ctx = BarrierTaskContext.get()
                if ctx is not None:
                    ctx.barrier()
                fault_point("barrier.attempt")
                try:
                    member = int(ctx.partitionId()) if ctx is not None else 0
                except Exception:  # a stub context without partitionId
                    member = 0
                # Per-member heartbeat stream for the task's whole
                # lifetime (TPUML_GANG_HEARTBEAT_EVERY; observability/
                # heartbeat.py): a stuck member's heartbeat age grows
                # while its peers' stay near zero — visible BEFORE the
                # stage deadline fires.
                with _ev.trace_scope(
                    _ev.current_trace() or _ev.extract_env()
                ):
                    with heartbeat_scope(member, what="barrier"):
                        result = task_fn(ctx, it)
                        if hasattr(result, "__next__"):
                            # Drain generator tasks INSIDE the scopes: a
                            # lazily consumed body would otherwise run
                            # after the carrier is restored and the
                            # heartbeat stopped. Barrier tasks return
                            # per-member reductions, so materializing is
                            # cheap by construction.
                            result = list(result)
                        return result
            finally:
                undo_sigterm()
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v

        def fallback(it):
            # Degraded (driver-local) execution: no barrier, no gang,
            # ctx=None — and no barrier.attempt fault site, the gang is
            # what failed.
            return task_fn(None, it)

        if policy is None:
            # Deliberately NOT the generic TPUML_RETRY_MAX_ATTEMPTS knob:
            # the scheduler already retries the stage internally, so
            # driver-side resubmission has its own (default-off) budget.
            policy = RetryPolicy(
                max_attempts=env_int(BARRIER_RESUBMITS_ENV, 1, minimum=1)
            )

        def _on_resubmit(attempt, exc):
            bump_counter("gang.resubmit")
            _events.emit("barrier", action="resubmit", attempt=attempt,
                         error=type(exc).__name__)

        return run_degradable(
            lambda: policy.run(
                lambda: rdd.barrier().mapPartitions(wrapped).collect(),
                name="barrier.stage",
                on_retry=_on_resubmit,
            ),
            lambda: rdd.mapPartitions(fallback).collect(),
            what="barrier gang fit",
            site="barrier.attempt",
        )


def gang_coordinates(ctx, port: int = DEFAULT_COORDINATOR_PORT) -> dict:
    """``jax.distributed.initialize`` kwargs for one barrier gang member.

    The barrier task infos are the gang roster: task 0's host is the
    coordinator, the partition id is the process id. The task ATTEMPT
    number offsets the port: a failed attempt's coordinator process can
    outlive its task by up to the heartbeat timeout (default 100 s) while
    still bound to the port, so a relaunched gang reusing the same
    address would collide with — or worse, silently join — the dead
    cohort's coordination service. Each attempt binding a fresh port
    guarantees the relaunch forms a genuinely new service (the heartbeat
    fail-fast in parallel/distributed.py detects the death; this
    launcher provides the rebirth).
    """
    infos = ctx.getTaskInfos()
    host = infos[0].address.split(":")[0]
    attempt = int(getattr(ctx, "attemptNumber", lambda: 0)())
    return {
        "coordinator_address": f"{host}:{port + attempt}",
        "num_processes": len(infos),
        "process_id": int(ctx.partitionId()),
    }


def _as_feature_row(value):
    """One partition element as a dense numpy feature row (pyspark Vectors
    expose ``toArray``; anything else must already be array-like)."""
    import numpy as np

    return np.asarray(
        value.toArray() if hasattr(value, "toArray") else value,
        dtype=np.float64,
    )


def _gang_extract(it, labeled: bool):
    """Materialize one member's partition as its LOCAL fit dataset:
    a (rows, d) matrix, or an ``(x, y)`` pair when ``labeled`` (elements
    are (features, label) sequences — the ``select(features, label).rdd``
    row shape)."""
    import numpy as np

    xs, ys = [], []
    for r in it:
        if labeled:
            xs.append(_as_feature_row(r[0]))
            ys.append(float(r[1]))
        else:
            xs.append(_as_feature_row(r[0] if isinstance(r, (tuple, list)) else r))
    x = np.stack(xs) if xs else np.zeros((0, 0))
    return (x, np.asarray(ys)) if labeled else x


def gang_fit(
    estimator,
    rdd,
    labeled: bool = False,
    extract: Optional[Callable[[Iterator], object]] = None,
    port: Optional[int] = None,
    policy: Optional[RetryPolicy] = None,
    checkpoint_dir: Optional[str] = None,
) -> list:
    """Fit ``estimator`` gang-parallel: one barrier stage, one gang member
    per partition, each calling the PUBLIC ``fit()`` on its local rows.

    This is the chip-per-executor deployment of the core estimators
    (ROADMAP item 4) as a driver-side one-liner::

        models = gang_fit(PCA().setK(2), df.rdd.map(lambda r: r[0]))

    Per member: the partition materializes as that member's LOCAL dataset
    (``labeled`` switches to (x, y) extraction; ``extract`` overrides the
    whole mapping), :func:`gang_coordinates` derives the member's
    jax.distributed coordinates from the barrier roster, and — for gangs
    of more than one member — they export as the ``TPUML_COORDINATOR`` /
    ``TPUML_NUM_PROCESSES`` / ``TPUML_PROCESS_ID`` knobs for the fit's
    lifetime. The member then copies the estimator, sets
    ``deployMode='gang'``, and calls ``fit`` — ``Estimator._join_gang``
    brings up the cohort, the ingest funnel assembles the globally
    sharded array, and the solver's reductions psum across members, so
    every member returns the identical whole-dataset model (the driver
    conventionally keeps ``models[0]``).

    All of :func:`barrier_gang_run`'s machinery rides along unchanged:
    whole-stage relaunch with fresh coordinator ports per attempt, the
    trace/telemetry carrier (each member writes its own shard; the merged
    trace shows one gang fit), per-member heartbeats, and the
    ``checkpoint_dir`` elastic-resume handoff. ``port`` defaults to the
    ``TPUML_GANG_PORT`` knob. NOTE: the contract stub runs barrier tasks
    sequentially on the driver, so only single-member gangs (one
    partition) are testable under the stub — a multi-member stub gang
    would deadlock in the bring-up; real clusters schedule members
    concurrently (tests/multiproc_gang_fit_worker.py is the real
    2-process proof).
    """
    if port is None:
        port = env_int("TPUML_GANG_PORT", DEFAULT_COORDINATOR_PORT, minimum=1)
    do_extract = extract if extract is not None else (
        lambda it: _gang_extract(it, labeled)
    )

    def task(ctx, it):
        local = do_extract(it)
        gang_env = {}
        if ctx is not None and hasattr(ctx, "getTaskInfos"):
            coords = gang_coordinates(ctx, port)
            if int(coords["num_processes"]) > 1:
                gang_env = {
                    "TPUML_COORDINATOR": coords["coordinator_address"],
                    "TPUML_NUM_PROCESSES": str(coords["num_processes"]),
                    "TPUML_PROCESS_ID": str(coords["process_id"]),
                }
        saved = {k: os.environ.get(k) for k in gang_env}
        os.environ.update(gang_env)
        try:
            member = estimator.copy().setDeployMode("gang")
            return [member.fit(local)]
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    return barrier_gang_run(
        rdd, task, policy=policy, checkpoint_dir=checkpoint_dir
    )


def serving_gang_run(
    rdd,
    rendezvous: str,
    policy: Optional[RetryPolicy] = None,
) -> list:
    """Run serving-tier members as ONE barrier stage: each partition's
    task body is :func:`serving.worker.serve_member` — publish a contact
    card into ``rendezvous``, accept the router connection, serve until
    shutdown. Partition elements are member ids (ints); an empty
    partition falls back to its partition id, so the common
    ``parallelize(range(n), n)`` roster works with either convention.

    Blocks until the whole gang drains (the router's ``close``), so the
    router runs it on a background thread. All of
    :func:`barrier_gang_run`'s machinery — launch barrier, whole-stage
    relaunch, per-member heartbeats, the trace/telemetry carrier —
    applies unchanged; the PR 7 carrier is what merges every member's
    serving events into the router's trace. NOTE: the contract stub runs
    barrier tasks sequentially on the driver, so only a single-member
    gang is testable under the stub — a real cluster schedules members
    concurrently.
    """
    from spark_rapids_ml_tpu.serving.worker import serve_member

    def task(ctx, it):
        members = sorted(int(i) for i in it)
        if not members:
            try:
                members = [int(ctx.partitionId())] if ctx is not None else [0]
            except Exception:
                members = [0]
        return [serve_member(m, rendezvous) for m in members]

    return barrier_gang_run(rdd, task, policy=policy)
