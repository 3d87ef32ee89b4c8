"""The JAX names the kernels share — ONE import home.

The library runs on one installation (JAX 0.9): ``jax.shard_map`` with
``check_vma=``, ``jax.lax.axis_size``, and a ``jax.distributed.initialize``
that takes ``heartbeat_timeout_seconds``. Kernels import these names from
here so that the next rename lands in one file.
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.lax import axis_size


def distributed_initialize(
    coordinator_address=None,
    num_processes=None,
    process_id=None,
    local_device_ids=None,
    heartbeat_timeout_seconds=None,
):
    """``jax.distributed.initialize`` with the failure-detection knob
    passed only when set: it bounds how long survivors wait before a dead
    peer's absence raises, and ``None`` keeps jax's own 100 s default."""
    kwargs = dict(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    if heartbeat_timeout_seconds is not None:
        kwargs["heartbeat_timeout_seconds"] = heartbeat_timeout_seconds
    jax.distributed.initialize(**kwargs)


__all__ = ["axis_size", "distributed_initialize", "shard_map"]
