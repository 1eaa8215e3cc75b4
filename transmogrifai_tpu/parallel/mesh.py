"""Device-mesh construction and sharding helpers.

TPU-native replacement for the reference's distributed substrate (Spark
driver/executor RPC + shuffle, SURVEY §5.8; the fold x grid task-parallel
``Future`` loop of core/src/main/scala/com/salesforce/op/tuning/
OpValidator.scala:270-310 and XGBoost's Rabit allreduce,
core/build.gradle:27). Here the unit of parallelism is a
``jax.sharding.Mesh`` over TPU chips:

- axis ``"folds"`` — cross-validation folds (each shard fits candidates on
  its own fold; metrics are averaged with ``psum``/``pmean`` over ICI),
- axis ``"data"``  — row (data) parallelism inside one candidate fit
  (gradient/histogram reductions via ``psum``).

On a single host the same code runs against a virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``); on a pod slice,
against real chips over ICI — no code change, XLA inserts the collectives.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["make_mesh", "cv_mesh", "n_devices", "replicate", "shard_rows",
           "to_host", "pad_candidates", "PartitionSpec", "Mesh",
           "NamedSharding"]


def n_devices() -> int:
    return len(jax.devices())


def make_mesh(axis_sizes: Dict[str, int],
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh with the given axis sizes from the available
    devices (row-major assignment). The product of sizes must divide the
    device count; leftover devices are unused."""
    devices = list(devices if devices is not None else jax.devices())
    total = math.prod(axis_sizes.values())
    if total > len(devices):
        raise ValueError(
            f"mesh {axis_sizes} needs {total} devices, have {len(devices)}")
    arr = np.asarray(devices[:total]).reshape(*axis_sizes.values())
    return Mesh(arr, tuple(axis_sizes.keys()))


def cv_mesh(n_folds: int, devices: Optional[Sequence] = None) -> Mesh:
    """Mesh for fold-parallel cross-validation: ``folds`` x ``data``.

    Uses all devices: ``folds`` gets min(n_folds, n_devices) shards and the
    remaining device factor becomes row parallelism. Maps the reference's
    per-fold ``Future`` parallelism (OpCrossValidation.scala:100-117) onto
    chips instead of driver threads.
    """
    devices = list(devices if devices is not None else jax.devices())
    nd = len(devices)
    fold_shards = math.gcd(n_folds, nd)
    data_shards = nd // fold_shards
    return make_mesh({"folds": fold_shards, "data": data_shards}, devices)


def replicate(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding over the mesh."""
    return NamedSharding(mesh, PartitionSpec())


def shard_rows(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard a (rows, ...) array's leading dim over one mesh axis."""
    return NamedSharding(mesh, PartitionSpec(axis))


def to_host(array) -> "np.ndarray":
    """Materialize a (possibly multi-process global) jax.Array on the
    host. Single-process arrays convert directly; arrays spanning other
    processes' devices gather their remote shards first
    (multihost_utils.process_allgather) — the DCN hop of SURVEY §5.8.
    """
    if getattr(array, "is_fully_addressable", True):
        return np.asarray(array)
    sharding = getattr(array, "sharding", None)
    if sharding is not None and getattr(sharding, "is_fully_replicated",
                                        False):
        # every process already holds the complete value (e.g. a
        # row-sharded fit's out_specs=P() trees): take the local copy
        # directly instead of paying process_allgather's redundant
        # cross-process collective (which handles this case correctly,
        # just not for free)
        return np.asarray(array.addressable_data(0))
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(
        array, tiled=True))


def pad_candidates(mesh, arrays, n_rows):
    """Pad the flattened candidate axis of a fold-grid program's per-lane
    ``arrays`` to a multiple of the mesh's ``models`` shard count (padded
    lanes fit on all-ones masks and are discarded; a 2-D array is a lane's
    (n_rows,) mask, a 1-D one a lane's scalar). Returns (padded arrays,
    original count)."""
    count = arrays[0].shape[0]
    if mesh is None:
        return arrays, count
    shards = mesh.shape["models"]
    pad = (-count) % shards
    if not pad:
        return arrays, count
    out = []
    for a in arrays:
        fill = np.ones((pad, n_rows)) if a.ndim == 2 else np.ones(pad)
        out.append(np.concatenate([a, fill.astype(a.dtype)], axis=0))
    return out, count
