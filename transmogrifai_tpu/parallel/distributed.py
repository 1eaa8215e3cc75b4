"""Multi-host distribution + wide-feature-matrix sharding.

TPU-native replacement for the reference's distributed substrate
(SURVEY §5.7-5.8): the reference scales out via Spark's driver/executor
RPC + shuffle and caps feature width with the hashing trick
(Transmogrifier.scala:56 MaxNumOfFeatures=16384). Here:

**Multi-host (DCN)** — :func:`initialize_distributed` wraps
``jax.distributed.initialize``: every host runs the same program
(single-controller SPMD), ``jax.devices()`` then spans all hosts'
chips, and any mesh built from it carries collectives over ICI within a
slice and DCN across slices — no Netty RPC, no Kryo, no shuffle. The
CV kernels in parallel/cv.py work unchanged on such a mesh: candidates
shard over all chips, data-axis psums ride the fastest available link
(XLA picks ICI-first reduction topologies).

**Wide vectors (HBM)** — when a transmogrified matrix outgrows one
chip's HBM (wide one-hot/hashed blocks), :func:`wide_matrix_sharding`
shards the FEATURE axis over the mesh: layout (rows replicated or
data-sharded, features split), so per-chip memory is d/n_chips columns.
Linear-model matvecs against a feature-sharded matrix contract the
sharded axis — XLA inserts the psum automatically under jit. Histogram
trees shard cleanly too: each chip histograms its own feature block and
split-gain argmaxes reduce with one small psum (the packed-bin layout
in models/trees/design.py keeps blocks contiguous).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["initialize_distributed", "wide_matrix_sharding",
           "shard_wide_matrix"]


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> int:
    """Join the multi-host JAX runtime (single-controller SPMD over DCN;
    reference analogue: Spark driver/executor bring-up, OpApp.scala:93).

    On a single host (or when already initialized) this is a no-op.
    Returns the global device count visible after initialization.
    """
    try:
        if coordinator_address is not None:
            try:
                # XLA:CPU refuses cross-process programs unless a CPU
                # collectives backend is selected BEFORE bring-up; on
                # TPU/GPU this knob is inert, so set it unconditionally
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
            except AttributeError:
                pass
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        else:
            jax.distributed.initialize()
    except (RuntimeError, ValueError):
        # already initialized, or single-process with no coordinator —
        # the local device set is the cluster
        pass
    return len(jax.devices())


def wide_matrix_sharding(mesh: Mesh, features_axis: str = "data",
                         rows_axis: Optional[str] = None) -> NamedSharding:
    """Sharding for an (n, d) feature matrix whose WIDTH is the memory
    problem (SURVEY §5.7): features split over ``features_axis``; rows
    optionally split over ``rows_axis`` (else replicated)."""
    return NamedSharding(mesh, P(rows_axis, features_axis))


def shard_wide_matrix(X: np.ndarray, mesh: Mesh,
                      features_axis: str = "data",
                      rows_axis: Optional[str] = None):
    """Place a host matrix on the mesh feature-sharded, padding the
    feature axis up to a multiple of the shard count (zero columns — a
    no-op for every downstream linear/tree kernel)."""
    import jax.numpy as jnp
    shards = mesh.shape[features_axis]
    n, d = X.shape
    pad = (-d) % shards
    if pad:
        X = np.concatenate([X, np.zeros((n, pad), X.dtype)], axis=1)
    return jax.device_put(
        jnp.asarray(X), wide_matrix_sharding(mesh, features_axis,
                                             rows_axis))
