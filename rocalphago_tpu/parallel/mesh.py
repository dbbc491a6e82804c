"""Device topology + named shardings — the rebuild's entire "comm backend".

The reference has no distributed layer at all (single process, single
Theano device; SURVEY.md §2c). The TPU rebuild's communication backend
is exactly this module: construct one `jax.sharding.Mesh` over the
slice, name the axes, and hand out `NamedSharding`s. XLA inserts the
collectives (gradient `psum` over ICI for data-parallel training,
DCN across hosts once `jax.distributed` is initialized) — there is no
hand-written NCCL/MPI analogue to port.

Axis convention:
  * ``data``  — batch / self-play game axis (the only axis the AlphaGo
    workload needs; SURVEY.md §2b).
  * ``model`` — reserved tensor-parallel axis, size 1 by default. The
    nets are small enough that TP is never profitable, but keeping the
    axis in the mesh means evaluator/trainer code is already written
    against a 2-D mesh if someone shards a bigger trunk later.
"""

from __future__ import annotations

import functools
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def distributed_init(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> None:
    """Multi-host bring-up (DCN). No-op for single-process runs.

    On Cloud TPU pods the arguments are auto-detected from the
    environment; pass them explicitly elsewhere. Multi-process CPU
    runs (the localhost DCN test, CPU-only actor fleets) ride jax's
    default CPU collectives, gloo over TCP.
    """
    multiproc = (num_processes is not None and num_processes > 1
                 or coordinator is not None
                 or int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1)
    if not multiproc:
        return
    if num_processes is not None and num_processes > 1 or (
            coordinator is not None):
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id)
    else:
        jax.distributed.initialize()


def is_coordinator() -> bool:
    """True on the process that owns artifact writes — metadata.json,
    metrics.jsonl, weight exports, the persisted shuffle split. Orbax
    checkpoint saves are NOT guarded by this: every process must
    participate in a multi-host save (each holds addressable shards).
    Single-process runs are always the coordinator."""
    return jax.process_index() == 0


def make_mesh(num_devices: int | None = None,
              model_parallel: int = 1) -> Mesh:
    """A ``(data, model)`` mesh over the first ``num_devices`` devices.

    ``model_parallel`` must divide the device count; data-parallel width
    is whatever remains. With the virtual-CPU trick
    (``--xla_force_host_platform_device_count=N``) the same call builds
    an N-way test mesh on one host (SURVEY.md §4 multi-node testing).
    """
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    n = len(devices)
    if n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide {n} devices")
    grid = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


@functools.lru_cache(maxsize=None)
def _cached_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def data_sharding(mesh: Mesh, rank: int = 1) -> NamedSharding:
    """Shard the leading (batch) axis over ``data``; trailing axes
    replicated."""
    return _cached_sharding(
        mesh, P(DATA_AXIS, *(None,) * (rank - 1)))


def replicated(mesh: Mesh) -> NamedSharding:
    return _cached_sharding(mesh, P())


def axis_sharding(mesh: Mesh, axis: int) -> NamedSharding:
    """Shard dimension ``axis`` over ``data``, all other dimensions
    replicated — e.g. ``axis=1`` for time-major ``[T, B, ...]`` game
    histories (the zero replay layout, docs/SCALE.md). The spec is a
    valid pytree-prefix/partial spec: trailing dimensions beyond
    ``axis`` are implicitly replicated."""
    return _cached_sharding(mesh, P(*(None,) * axis, DATA_AXIS))


def shard_batch(mesh: Mesh, batch):
    """Place a host pytree of arrays with leading batch axes onto the
    mesh, batch axis split over ``data``."""
    return jax.tree.map(
        lambda x: jax.device_put(
            x, data_sharding(mesh, np.ndim(x) or 1)), batch)


def replicate(mesh: Mesh, tree):
    """Replicate a pytree (params, opt state) across every device."""
    return jax.device_put(tree, replicated(mesh))


def global_batch_size(mesh: Mesh, per_device: int) -> int:
    return per_device * mesh.shape[DATA_AXIS]
