"""Estimator / Model base classes mirroring Spark ML's abstractions.

The reference's L2 layer (RapidsPCA.scala) extends Spark's
``Estimator[Model]`` with a ``Params`` trait; ``fit`` validates the schema
then delegates to the distributed linalg layer. Here the same shape exists
without a JVM: ``Estimator.fit(dataset)`` -> ``Model`` (a ``Transformer``).
"""

from __future__ import annotations

from typing import Any, Optional

from spark_rapids_ml_tpu.core.params import Param, Params, toString
from spark_rapids_ml_tpu.core.persistence import MLReadable


class HasInputCol(Params):
    inputCol = Param("_", "inputCol", "input column name", toString)

    def getInputCol(self) -> Optional[str]:
        return self.getOrDefault(self.inputCol) if self.isDefined(self.inputCol) else None

    def setInputCol(self, value: str):
        return self.set(self.inputCol, value)


class HasOutputCol(Params):
    outputCol = Param("_", "outputCol", "output column name", toString)

    def getOutputCol(self) -> str:
        if self.isDefined(self.outputCol):
            return self.getOrDefault(self.outputCol)
        return f"{self.uid}__output"

    def setOutputCol(self, value: str):
        return self.set(self.outputCol, value)


class Transformer(Params):
    def transform(self, dataset: Any) -> Any:
        raise NotImplementedError


class Estimator(Params):
    #: Fit deployment mode: ``"single"`` (default) fits on this process's
    #: devices alone; ``"gang"`` makes this process one MEMBER of a
    #: multi-process gang — every member calls the same public ``fit``
    #: with its LOCAL rows, the ingest funnel assembles one globally
    #: sharded array, and XLA collectives merge the reductions, so every
    #: member returns the identical whole-dataset model. The env twin is
    #: ``TPUML_GANG_FIT=1`` (a barrier launcher flips it without touching
    #: estimator code).
    deployMode = Param(
        "_", "deployMode",
        "fit deployment mode: 'single' or 'gang'", toString,
    )

    def getDeployMode(self) -> str:
        if self.isDefined(self.deployMode):
            return self.getOrDefault(self.deployMode)
        from spark_rapids_ml_tpu.utils.envknobs import env_str

        return "gang" if env_str("TPUML_GANG_FIT", "0") == "1" else "single"

    def setDeployMode(self, value: str):
        if value not in ("single", "gang"):
            raise ValueError(
                f"deployMode must be 'single' or 'gang', got {value!r}"
            )
        return self.set(self.deployMode, value)

    def _join_gang(self) -> None:
        """Gang-member bring-up, run once at the top of a gang-mode fit:
        join the jax.distributed cohort (idempotent — a member that
        already initialized, e.g. fitting a second estimator in the same
        task, just revalidates its coordinates) and default this
        estimator's mesh to the GLOBAL device set. A gang of one (the
        stub Spark runner executes barrier tasks sequentially in one
        process, so locally-launched gangs are single-member —
        ``serving_gang_run`` documents the same limit) skips the runtime
        bring-up entirely: jax.distributed can only form a cohort once
        per process, and a 1-process cohort would wedge any later real
        gang this process joins."""
        import jax

        from spark_rapids_ml_tpu.parallel import distributed as dist
        from spark_rapids_ml_tpu.utils.envknobs import env_int, env_str

        num = env_int("TPUML_NUM_PROCESSES", minimum=1)
        if (num is not None and num > 1) or env_str("TPUML_COORDINATOR"):
            dist.initialize()
        if hasattr(self, "mesh") and getattr(self, "mesh") is None:
            self.mesh = dist.global_mesh()
        from spark_rapids_ml_tpu.observability.events import emit

        emit(
            "gang_fit",
            action="join",
            estimator=type(self).__name__,
            num_processes=jax.process_count(),
            process_id=jax.process_index(),
        )

    def fit(self, dataset: Any):
        """Fit, instrumented: the whole call runs under a ``fit`` run
        scope (observability/) — a fresh ``run_id`` standalone, the
        ambient one when a caller's job scope is open — optionally inside
        a ``TPUML_PROFILE_DIR`` profiler session, and the finished
        :class:`~spark_rapids_ml_tpu.observability.report.RunReport`
        (stage-timing tree, counter deltas, compile counts, checkpoint
        activity, device memory) hangs off the model as
        ``model.fit_report()``.

        Families implement :meth:`_fit`; estimators that override
        ``fit`` directly opt out of the instrumentation.

        This boundary is also the fit path's OOM safety net: a device
        ``RESOURCE_EXHAUSTED`` that escaped the per-family recovery
        (streaming sources the runtime cannot re-block, exotic paths)
        re-raises as the structured
        :class:`~spark_rapids_ml_tpu.core.membudget.FitMemoryError` —
        a raw ``XlaRuntimeError`` never escapes a fit.

        It also places jax's persistent compilation cache
        (:func:`~spark_rapids_ml_tpu.core.serving.configure_compile_cache`),
        so a second process fitting the same shapes replays the compiles
        from disk."""
        from spark_rapids_ml_tpu.core.serving import configure_compile_cache
        from spark_rapids_ml_tpu.observability.report import RunRecorder

        with RunRecorder("fit", type(self).__name__) as rec:
            try:
                if self.getDeployMode() == "gang":
                    self._join_gang()
                # After the gang bring-up: resolving the backend before
                # jax.distributed.initialize would wedge it.
                configure_compile_cache()
                model = self._fit(dataset)
            except RuntimeError as exc:
                from spark_rapids_ml_tpu.core.membudget import reraise_if_oom

                reraise_if_oom(exc, type(self).__name__)
                raise
        rec.attach(model)
        return model

    def _fit(self, dataset: Any):
        raise NotImplementedError

    def partial_fit(self, dataset: Any, *, model=None):
        """Incremental refit: fit over ``dataset`` (the NEW rows only),
        seeding the segmented solver from ``model``'s solution — the
        continuous-training entry (lifecycle/partial_fit.py). With
        ``model=None`` this is the zero state: bit-identical to a
        from-scratch fit of ``dataset``. Supported for KMeans (center
        seed), LogisticRegression (L-BFGS seed), LinearRegression
        (FISTA seed), and PCA (exact streaming-moment merge, where
        ``dataset`` ACCUMULATES rather than replaces)."""
        from spark_rapids_ml_tpu.lifecycle.partial_fit import partial_fit

        return partial_fit(self, dataset, model=model)

    def _fit_checkpointer(self, solver: str, data=()):
        """Checkpoint/restore handle for this fit (preemption tolerance,
        robustness/checkpoint.py), or None when the ``TPUML_CHECKPOINT_*``
        knobs leave checkpointing disabled — the default, in which case
        this touches no device state and the fit keeps the monolithic
        single-program solver path exactly.

        Identity is (estimator uid, param hash, data fingerprint): the
        checkpointer discovers the latest valid snapshot under
        ``TPUML_CHECKPOINT_DIR`` at fit time, the segmented solver
        resumes mid-solve bit-identically, and a completed fit retires
        its own snapshots. Resuming across processes (a relaunched gang,
        a resubmitted job) needs a stable uid — pass one to the
        estimator constructor."""
        from spark_rapids_ml_tpu.robustness.checkpoint import (
            EphemeralSegmenter,
            FitCheckpointer,
        )

        ckpt = FitCheckpointer.for_fit(self, solver=solver, data=data)
        if ckpt is None and getattr(self, "_force_segment_every", 0):
            # partial_fit forces the segmented driver (disk-free) so
            # warm-seed convergence is counter-observable; a real
            # TPUML_CHECKPOINT_* checkpointer outranks it.
            return EphemeralSegmenter(self._force_segment_every)
        return ckpt


class Model(Transformer, MLReadable):
    """A fitted transformer; carries a parent uid via copyValues like Spark."""

    _fit_report = None

    def fit_report(self):
        """The :class:`~spark_rapids_ml_tpu.observability.report.RunReport`
        of the fit that produced this model (stage-timing tree, counter
        deltas, compile counts, checkpoint activity, device memory), or
        None for models built outside an instrumented fit (loaded from
        disk, unpickled, hand-constructed)."""
        return self._fit_report
