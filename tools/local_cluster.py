#!/usr/bin/env python
"""Local multi-process cluster launcher — the ``mpirun -n P`` equivalent.

The reference trains multi-node by launching one MPI rank per host
(reference: src/README.md:10, tools/local_script.sh). The TPU-native
equivalent is one *JAX process* per host sharing a global device mesh via
``jax.distributed``; this script simulates that cluster on one machine:
it spawns N processes, each pinned to K virtual CPU devices, wired to a
shared coordinator — the same code path (gloo collectives over the
process boundary) a real multi-host TPU pod uses over DCN.

Usage:
  python tools/local_cluster.py -n 2 -d 4 -- \
      python -m draco_tpu.cli --approach cyclic --network LeNet \
        --dataset synthetic-mnist --num-workers 8 --worker-fail 1 \
        --max-steps 20 --cpu-mesh 4

Each child gets DRACO_COORDINATOR / DRACO_NUM_PROCESSES / DRACO_PROCESS_ID
(read by draco_tpu.runtime.init_distributed) and an XLA host-device count of
``-d``. Exit code is the first non-zero child exit code.

CPU only: a chip belongs to one process at a time, and this launcher starts
several — so every child runs with ``JAX_PLATFORMS=cpu``, and an environment
that asks for anything else is refused rather than overridden.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import tempfile


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def launch(num_processes: int, devices_per_process: int, cmd: list[str],
           env: dict | None = None, prefix_output: bool = True) -> int:
    port = _free_port()
    base = dict(os.environ, **(env or {}))
    base["DRACO_COORDINATOR"] = f"localhost:{port}"
    base["DRACO_NUM_PROCESSES"] = str(num_processes)
    base["XLA_FLAGS"] = (
        base.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_process}"
    ).strip()
    asked = base.get("JAX_PLATFORMS", "cpu").strip().lower()
    if asked != "cpu":
        raise ValueError(
            f"local_cluster simulates a cluster on virtual CPU devices and "
            f"starts {num_processes} processes; a chip belongs to one "
            f"process at a time. Refusing JAX_PLATFORMS={asked!r}: unset it "
            f"or set it to 'cpu'.")
    base["JAX_PLATFORMS"] = "cpu"

    # Each child writes to its own temp file, never a pipe: collectives keep
    # all children in lock-step, so a child blocked on a full pipe buffer
    # stalls the whole cluster while the launcher drains children in pid
    # order — the classic launcher deadlock. Files have no backpressure.
    procs, logs = [], []
    for pid in range(num_processes):
        child_env = dict(base, DRACO_PROCESS_ID=str(pid))
        log = tempfile.TemporaryFile(mode="w+b", prefix=f"draco_proc{pid}_") if prefix_output else None
        logs.append(log)
        procs.append(
            subprocess.Popen(
                cmd, env=child_env,
                stdout=log if prefix_output else None,
                stderr=subprocess.STDOUT if prefix_output else None,
            )
        )
    rc = 0
    for pid, p in enumerate(procs):
        p.wait()
        if prefix_output:
            logs[pid].seek(0)
            # children can emit non-UTF-8 bytes (native/libtpu log garbage);
            # never let a decode error eat the other children's logs
            text = logs[pid].read().decode("utf-8", errors="replace")
            for line in text.splitlines():
                print(f"[proc {pid}] {line}", flush=True)
            logs[pid].close()
        if p.returncode != 0 and rc == 0:
            rc = p.returncode
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", "--num-processes", type=int, default=2)
    ap.add_argument("-d", "--devices-per-process", type=int, default=4)
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run in every process (prefix with --)")
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given")
    return launch(args.num_processes, args.devices_per_process, cmd)


if __name__ == "__main__":
    raise SystemExit(main())
