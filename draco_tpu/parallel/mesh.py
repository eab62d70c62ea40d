"""2-D device meshes: coded worker axis × one model-parallel axis."""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from draco_tpu.runtime import WORKER_AXIS, worker_axis_size

SEQ_AXIS = "sp"
TP_AXIS = "tp"
EP_AXIS = "ep"
PP_AXIS = "pp"


def _make_mesh_w2(axis2: str, num_workers: int, shards: int,
                  devices: Optional[Sequence[jax.Device]]) -> Mesh:
    """(w, axis2) mesh for ``num_workers`` LOGICAL workers × ``shards``
    model-parallel shards, on whatever devices there are.

    One rule for every route (the runtime.make_mesh discipline): the
    model-parallel axis takes exactly ``shards`` devices — it shards the
    model, so it cannot shrink — and the worker axis takes the largest
    divisor of ``num_workers`` that fits the rest, ``len(devices) //
    shards``. Fewer w devices than workers FOLDS the workers onto them in
    equal lane blocks (every step builder vmaps its lanes), said loudly;
    so n=8 runs on one chip (w=1, 8 lanes), on four (w=4, 2 lanes; or
    w=2 × 2 shards) and on eight alike. The model-parallel axis is
    innermost, riding the fastest ICI links (its collectives fire several
    times per step; the worker-axis gather once)."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < shards:
        raise ValueError(
            f"(w, {axis2}={shards}) mesh needs at least {shards} devices, "
            f"have {len(devices)}"
        )
    w = worker_axis_size(num_workers, len(devices) // shards)
    if w < num_workers or w * shards < len(devices):
        print(
            f"mesh (w={w}, {axis2}={shards}): {num_workers} logical workers "
            f"fold {num_workers // w} to a device on {w * shards}/"
            f"{len(devices)} devices",
            flush=True,
        )
    grid = np.asarray(devices[:w * shards]).reshape(w, shards)
    return Mesh(grid, (WORKER_AXIS, axis2))


def make_mesh_2d(
    num_workers: int,
    seq_shards: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(w, sp) mesh (_make_mesh_w2): the sequence axis innermost so its ring
    rides neighbouring ICI links; the worker-axis gather crosses the slower
    dimension once per step."""
    return _make_mesh_w2(SEQ_AXIS, num_workers, seq_shards, devices)


def make_folded_wtp_mesh(num_workers: int) -> Mesh:
    """(w, tp=1) mesh: the trivial tp axis makes the GSPMD LM builder
    (tp_step.build_tp_train_setup) applicable on any device count — the
    single-chip n-lane vmapped regime the perf/convergence tools run in."""
    return make_mesh_wtp(num_workers, 1)


def make_mesh_wtp(
    num_workers: int,
    tensor_shards: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(w, tp) mesh (_make_mesh_w2)."""
    return _make_mesh_w2(TP_AXIS, num_workers, tensor_shards, devices)


def make_mesh_wep(
    num_workers: int,
    expert_shards: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(w, ep) mesh (_make_mesh_w2)."""
    return _make_mesh_w2(EP_AXIS, num_workers, expert_shards, devices)


def make_mesh_wpp(
    num_workers: int,
    pipeline_shards: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(w, pp) mesh (_make_mesh_w2)."""
    return _make_mesh_w2(PP_AXIS, num_workers, pipeline_shards, devices)
