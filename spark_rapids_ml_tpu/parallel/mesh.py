"""Device-mesh construction and sharding helpers.

The reference delegates all parallelism to Spark: RDD partitions are the data-
parallel unit and driver-side reduce/broadcast the communication backend
(SURVEY.md §2 checklist). TPU-native, the equivalent fabric is a
``jax.sharding.Mesh`` over the slice's chips: the ``data`` axis replaces RDD
row-partitioning, the ``model`` axis shards the feature dimension (the
reference's scaling axis, SURVEY.md §5 "long-context"), and XLA collectives
over ICI (psum / reduce_scatter / all_gather) replace Spark's
``reduce``/``treeAggregate``/``broadcast`` (RapidsRowMatrix.scala:162-234).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    shape: Optional[Tuple[int, int]] = None,
    axis_names: Tuple[str, str] = (DATA_AXIS, MODEL_AXIS),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 2-D (data × model) mesh over the available devices.

    Default: all devices on the data axis (pure DP — the reference's only
    parallelism), model axis 1. Pass ``shape=(dp, mp)`` to also shard the
    feature dimension.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shape is None:
        shape = (n, 1)
    if shape[0] * shape[1] != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, axis_names)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.local_devices()[0]
    return Mesh(np.asarray([device]).reshape(1, 1), (DATA_AXIS, MODEL_AXIS))


def model_axis_size(mesh: Mesh) -> int:
    """Size of the model axis, treating a mesh WITHOUT one (a pure-DP
    1-axis mesh) as model=1 — every consumer that indexes
    ``mesh.shape[MODEL_AXIS]`` directly KeyErrors on such meshes."""
    return int(mesh.shape.get(MODEL_AXIS, 1))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows over the data axis, features over the model axis (features
    unsharded when the mesh has no model axis)."""
    if MODEL_AXIS in mesh.shape:
        return NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS))
    return NamedSharding(mesh, P(DATA_AXIS, None))


def device_array_rows_on_mesh(x, mesh: Mesh, shard_features: bool = False):
    """Reshard a DEVICE-RESIDENT (n, d) array row-wise over the mesh's
    data axis (an explicit mesh must never be silently dropped). Unlike
    host partitions — which pad with masking — a live device array is
    not copied into padded form, so rows must divide the data axis (and,
    with ``shard_features``, features the model axis)."""
    dp = int(mesh.shape[DATA_AXIS])
    if x.shape[0] % dp != 0:
        raise ValueError(
            f"device-array input with a mesh needs rows divisible by "
            f"the data axis ({dp}), got {x.shape[0]}; pad/trim the "
            f"array or pass host partitions (which pad with masking)"
        )
    if shard_features and MODEL_AXIS in mesh.shape:
        mp = int(mesh.shape[MODEL_AXIS])
        if x.shape[1] % mp != 0:
            raise ValueError(
                f"device-array input with shard_features needs features "
                f"divisible by the model axis ({mp}), got {x.shape[1]}"
            )
        return jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS)))
    return jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS, None)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_rows(x, mesh: Mesh):
    """Place a host (n, d) array onto the mesh row-sharded, padding n up to
    a multiple of the data axis (and d up to the model axis) with zeros.

    Returns ``(x_sharded, row_mask_sharded, n_true_rows)``; the mask weights
    padded rows to zero inside the compiled computations. Thin wrapper over
    :func:`shard_rows_from_partitions` — ONE home for the padding/mask/
    placement semantics.
    """
    return shard_rows_from_partitions([np.asarray(x)], mesh)


def shard_rows_from_partitions(partitions, mesh: Mesh, dtype=None):
    """Place a LIST of host (rows_i, d) blocks onto the mesh row-sharded
    WITHOUT ever materializing the concatenated dataset on the host.

    The host-side peak is one device shard (n_padded/dp rows): for each
    addressable device, the rows belonging to its slice are assembled from
    the partitions (slicing across partition boundaries), placed with a
    plain ``device_put``, and stitched into the global array via
    ``jax.make_array_from_single_device_arrays``. Semantically identical to
    ``shard_rows(np.concatenate(partitions), mesh)`` — the shape every
    device sees, the padding, and the mask are the same — but the extra
    full-dataset host copy is gone (at the north-star 100M x 1024 scale
    that copy is 400 GB).

    Returns ``(x_sharded, row_mask_sharded, n_true_rows)``.
    """
    partitions = [np.asarray(p) for p in partitions]
    if dtype is not None:
        partitions = [p.astype(dtype, copy=False) for p in partitions]
    n = sum(p.shape[0] for p in partitions)
    d = partitions[0].shape[1]
    dp = mesh.shape[DATA_AXIS]
    mp = model_axis_size(mesh)
    n_tot = n + ((-n) % dp)
    d_tot = d + ((-d) % mp)
    rows_per = n_tot // dp
    cols_per = d_tot // mp
    np_dtype = partitions[0].dtype

    def rows_slice(start: int, stop: int) -> np.ndarray:
        """Assemble global rows [start, stop) from the partition list,
        zero-padding rows beyond n (the mask kills them downstream)."""
        pieces = []
        off = 0
        for p in partitions:
            lo, hi = max(start, off), min(stop, off + p.shape[0])
            if lo < hi:
                pieces.append(p[lo - off : hi - off])
            off += p.shape[0]
        got = sum(pc.shape[0] for pc in pieces)
        want = stop - start
        if got < want:
            pieces.append(np.zeros((want - got, d), dtype=np_dtype))
        block = pieces[0] if len(pieces) == 1 else np.concatenate(pieces, axis=0)
        if d_tot > d:
            block = np.pad(block, ((0, 0), (0, d_tot - d)))
        return np.ascontiguousarray(block)

    x_sharding = row_sharding(mesh)
    m_sharding = NamedSharding(mesh, P(DATA_AXIS))
    mesh_devs = np.asarray(mesh.devices).reshape(dp, mp)

    def _place_shards():
        # Pure host->device placement: safe to re-run wholesale, so the
        # whole loop is one retry unit (robustness.retry) with one named
        # injection site (robustness.faults).
        from spark_rapids_ml_tpu.robustness.faults import fault_point

        fault_point("ingest.device_put")
        x_shards, m_shards = [], []
        for di in range(dp):
            block = rows_slice(di * rows_per, (di + 1) * rows_per)
            mask_blk = np.zeros(rows_per, dtype=np_dtype)
            n_valid = min(max(n - di * rows_per, 0), rows_per)
            mask_blk[:n_valid] = 1.0
            for mi in range(mp):
                dev = mesh_devs[di, mi]
                x_shards.append(
                    jax.device_put(block[:, mi * cols_per : (mi + 1) * cols_per], dev)
                )
                m_shards.append(jax.device_put(mask_blk, dev))
        xs = jax.make_array_from_single_device_arrays(
            (n_tot, d_tot), x_sharding, x_shards
        )
        ms = jax.make_array_from_single_device_arrays(
            (n_tot,), m_sharding, m_shards
        )
        return xs, ms

    from spark_rapids_ml_tpu.robustness.retry import default_policy

    xs, ms = default_policy().run(_place_shards, name="ingest.device_put")
    return xs, ms, n


def weights_as_mask(w_host, n_rows: int, dtype, mesh: Optional[Mesh] = None):
    """Per-row weightCol weights as the row mask: padded to ``n_rows`` with
    zeros (padding must contribute nothing) and, under a mesh, placed with
    the same P(data) sharding the row mask uses."""
    w_pad = np.zeros(n_rows, dtype=dtype)
    w_host = np.asarray(w_host)
    w_pad[: len(w_host)] = w_host
    if mesh is not None:
        return jax.device_put(w_pad, NamedSharding(mesh, P(DATA_AXIS)))
    import jax.numpy as jnp

    return jnp.asarray(w_pad)
