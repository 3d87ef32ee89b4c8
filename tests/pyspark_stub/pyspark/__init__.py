"""Test-only pyspark API stub (contract-testing shim).

The CI image has no pyspark, so the adapter layer
(``spark_rapids_ml_tpu.spark.adapter``) could never execute. This package implements the EXACT surface
the adapter consumes — local, single-process, but with real partition
semantics (mapPartitions / treeReduce run the same callables Spark would
ship to executors, including a pickle round-trip to catch closure bugs) —
so the adapter's code paths run for real under pytest.

It deliberately mirrors pyspark's public API shapes (keyword_only,
Params._dummy(), TypeConverters, Estimator._fit / Model._transform,
pandas_udf columns) rather than inventing friendlier ones: drift against
these shapes is exactly what the tests exist to catch.
"""

from __future__ import annotations

import functools
import pickle
from typing import Optional


def keyword_only(func):
    """pyspark.keyword_only: capture the kwargs of a method call into
    ``self._input_kwargs`` (positional args are disallowed)."""

    @functools.wraps(func)
    def wrapper(self, *args, **kwargs):
        if args:
            raise TypeError(
                f"Method {func.__name__} forces keyword arguments."
            )
        self._input_kwargs = kwargs
        return func(self, **kwargs)

    return wrapper


class TaskContext:
    """Driver-side stand-in: no task context outside executor code."""

    @staticmethod
    def get() -> Optional["TaskContext"]:
        return None


def _pickle_roundtrip(obj):
    """Simulate the executor serialization boundary: every function and
    accumulator the adapter hands to an RDD op must survive serialization,
    as it would on a real cluster. Spark serializes closures with
    cloudpickle, so the stub does too (falling back to stdlib pickle)."""
    try:
        import cloudpickle as _cp

        return _cp.loads(_cp.dumps(obj))
    except ImportError:  # pragma: no cover
        return pickle.loads(pickle.dumps(obj))


# Torrent-broadcast analogue: values serialize ONCE at broadcast() time
# (counted, for the one-serialization contract tests); the Broadcast
# handle that rides task closures pickles as a registry id only —
# exactly the cost model of Spark's TorrentBroadcast.
import itertools as _itertools

_BROADCAST_REGISTRY = {}
_BROADCAST_IDS = _itertools.count()  # monotonic: destroy() must not free ids
BROADCAST_VALUE_PICKLES = {"count": 0}


def _broadcast_from_id(bid: int) -> "Broadcast":
    b = Broadcast.__new__(Broadcast)
    b._bid = bid
    return b


class Broadcast:
    """pyspark.broadcast.Broadcast: read-only shared variable, one
    serialization per broadcast, ``.value`` on executors."""

    def __init__(self, value):
        bid = next(_BROADCAST_IDS)
        BROADCAST_VALUE_PICKLES["count"] += 1
        _BROADCAST_REGISTRY[bid] = _pickle_roundtrip(value)
        self._bid = bid

    @property
    def value(self):
        return _BROADCAST_REGISTRY[self._bid]

    def __reduce__(self):
        # Task closures ship the HANDLE, never the value.
        return (_broadcast_from_id, (self._bid,))

    def unpersist(self, blocking: bool = False) -> None:
        pass

    def destroy(self, blocking: bool = False) -> None:
        _BROADCAST_REGISTRY.pop(self._bid, None)


class SparkContext:
    """Driver-side context stub: the adapter touches only broadcast()."""

    def broadcast(self, value) -> Broadcast:
        return Broadcast(value)


_SC = SparkContext()


class BarrierTaskInfo:
    """pyspark.taskcontext.BarrierTaskInfo: the per-task descriptor
    ``BarrierTaskContext.getTaskInfos()`` returns (``address`` attr)."""

    def __init__(self, address: str):
        self.address = address


class BarrierTaskContext(TaskContext):
    """pyspark.BarrierTaskContext: the task context inside a barrier
    stage. ``get()`` is only valid in a task launched by
    ``RDDBarrier.mapPartitions`` (returns None elsewhere, like the plain
    TaskContext stub); ``barrier()`` is the global sync point (a no-op in
    the stub's sequential gang execution — ordering IS the sync);
    ``getTaskInfos()`` lists all gang members, the handle a launcher uses
    to derive jax.distributed coordinates."""

    _current: Optional["BarrierTaskContext"] = None

    def __init__(self, partition_id: int, num_tasks: int, attempt: int):
        self._pid = partition_id
        self._num = num_tasks
        self._attempt = attempt

    @classmethod
    def get(cls) -> Optional["BarrierTaskContext"]:
        return cls._current

    def barrier(self) -> None:
        pass

    def partitionId(self) -> int:
        return self._pid

    def attemptNumber(self) -> int:
        return self._attempt

    def getTaskInfos(self):
        return [BarrierTaskInfo("localhost:0") for _ in range(self._num)]


__all__ = [
    "keyword_only",
    "TaskContext",
    "BarrierTaskContext",
    "BarrierTaskInfo",
    "Broadcast",
    "SparkContext",
    "BROADCAST_VALUE_PICKLES",
]
