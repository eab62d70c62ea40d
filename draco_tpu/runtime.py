"""Mesh / distributed runtime bootstrap.

Replaces the reference's MPI world wiring (reference: src/distributed_nn.py:79-133,
rank 0 = parameter server process, ranks 1..P = workers). Here there are no
roles: one SPMD program over a ``Mesh`` with a worker axis ``w``. A logical
worker is a shard of the worker axis; the "PS" is the replicated post-gather
phase of the same jitted step.

Multi-host: call :func:`init_distributed` once per host before any jax call;
the mesh then spans all hosts' devices and the gradient gather rides ICI
within a slice and DCN across slices — the same program, no code changes
(replaces the reference's NCCL/MPI-over-TCP transport, README.md:16).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WORKER_AXIS = "w"

_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on XLA's persistent compilation cache and return its directory.

    The directory is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
    is set, jax reads it itself and this function sets no directory at all —
    the path is part of the cache key's world, so an entry written there by
    one command is found by the next only if nobody moves it. Where the
    variable is not set, the cache lives at the fixed
    ``<checkout>/.jax_cache`` (gitignored). Every entry point — the CLI, the
    tools' bootstrap (cli.maybe_force_cpu_mesh), benchmark/, chip_smoke.py —
    goes through here, on every backend: a coded ResNet-18 step costs about
    a minute to compile cold and seconds warm. Safe to call repeatedly.
    """
    # an entry's key covers its labels too (the ``draco_*`` named scopes live
    # in the instructions' metadata, which the default key leaves out): an
    # executable compiled from another version of the source would come back
    # with that version's labels, and a device trace is attributed through
    # them (obs/device_attr.scope_map_from_hlo)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # the default 1 s floor would skip mid-size kernels; cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialise multi-host JAX if requested via args or env.

    No-op on a single host. Mirrors the role of the reference's mpirun
    bootstrap (src/README.md:10) without assigning roles to ranks.
    """
    addr = coordinator_address or os.environ.get("DRACO_COORDINATOR")
    if addr is None:
        return
    if num_processes is None:
        num_processes = int(os.environ["DRACO_NUM_PROCESSES"])
    if process_id is None:
        process_id = int(os.environ["DRACO_PROCESS_ID"])
    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=num_processes,
        process_id=process_id,
    )


def worker_axis_size(num_workers: int, n_devices: int) -> int:
    """Devices the worker axis takes: the largest divisor of ``num_workers``
    that fits ``n_devices`` — the workers then fold onto them in equal
    blocks. THE mesh rule's arithmetic, shared by :func:`make_mesh` and the
    2-D meshes of parallel/mesh.py."""
    w = max(1, min(num_workers, n_devices))
    while num_workers % w:
        w -= 1
    return w


def make_mesh(num_workers: int, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a 1-D mesh with axis ``w``.

    ``num_workers`` logical workers are laid out over the available devices;
    each device holds an equal contiguous block of the worker axis. When
    num_workers does not divide the device count, the mesh shrinks to the
    largest divisor-count of devices and the rest idle — loudly.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not devices:
        raise ValueError("make_mesh: no devices available")
    n_dev = worker_axis_size(num_workers, len(devices))
    if n_dev < len(devices):
        print(
            f"make_mesh: using {n_dev}/{len(devices)} devices for "
            f"{num_workers} workers (pick num_workers as a multiple of the "
            f"device count to use the whole slice)",
            flush=True,
        )
    return Mesh(np.asarray(devices[:n_dev]), (WORKER_AXIS,))


def put_global(arr: np.ndarray, sharding: NamedSharding):
    """Host array -> (possibly multi-host) global device array.

    Single-process: a plain sharded device_put. Multi-process: every process
    holds the full host array (batch indices are deterministic, so all hosts
    agree) and contributes only the shards its addressable devices own —
    the multi-host feeding discipline that replaces the reference's per-rank
    MPI sends (baseline_worker.py:258-273); the cross-host gradient gather
    then rides DCN inside the jitted step.
    """
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def worker_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for arrays with a leading logical-worker axis."""
    return NamedSharding(mesh, P(WORKER_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
