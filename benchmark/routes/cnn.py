"""The image route: ``draco_tpu.training.trainer.Trainer`` and its eager or
chunked loop, exactly as ``draco_tpu.cli`` drives it."""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

# The job's own seed: batch order, augmentation, adversary schedule. Fixed,
# because the step program carries it as a constant and must be found in the
# compile cache whatever ``--seed`` a run is given; ``--seed`` makes the data
# and the weights.
JOB_SEED = 428
# Rows of the adversary schedule made at set-up; a window never outruns it.
MAX_STEPS = 8192


class _Records:
    """Stands where the trainer's metric writer stands and keeps every
    step's record in memory."""

    def __init__(self):
        self.rows: list = []

    def write(self, record: dict) -> None:
        self.rows.append(dict(record))

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class Route:
    def __init__(self, fields: dict, data, devices, trace_dir: str = ""):
        from draco_tpu.config import TrainConfig
        from draco_tpu.data.datasets import Dataset
        from draco_tpu.runtime import make_mesh
        from draco_tpu.training.trainer import Trainer

        fields = dict(fields, seed=JOB_SEED, max_steps=MAX_STEPS,
                      eval_freq=0, log_every=1, train_dir="",
                      trace_dir=trace_dir, checkpoint_step=0)
        self.cfg = TrainConfig(**fields).validate()
        train_x, train_y = data
        self._sample_shape = tuple(train_x.shape[1:])
        ds = Dataset(name=self.cfg.dataset, train_x=train_x, train_y=train_y,
                     test_x=train_x[:1], test_y=train_y[:1], synthetic=True)
        self.mesh = make_mesh(self.cfg.num_workers, devices)
        self.trainer = Trainer(self.cfg, mesh=self.mesh, dataset=ds,
                               quiet=True)
        self.records = _Records()
        self.trainer.writer = self.records
        self._tracer_t0 = None
        if trace_dir:
            # the tracer's clock starts at its creation; read the offset to
            # perf_counter once so its spans can be laid beside the profile
            self._tracer_t0 = (time.perf_counter()
                               - self.trainer.tracer.now_us() * 1e-6)
        self._trace_path = (os.path.join(trace_dir, "trace.json")
                            if trace_dir else "")

    # ---- what the harness reads --------------------------------------
    @property
    def examples_per_step(self) -> int:
        return self.cfg.num_workers * self.cfg.batch_size

    @property
    def adversaries_per_step(self) -> int:
        """Live adversaries every step has to locate; 0 where the job has
        no decoder that locates."""
        return (self.cfg.num_adversaries if self.cfg.approach == "cyclic"
                else 0)

    def job(self) -> dict:
        c = self.cfg
        return {"policy": "baseline" if c.approach == "baseline" else "cyclic",
                "n": c.num_workers, "batch": c.batch_size, "seed": c.seed,
                "lr": c.lr, "momentum": c.momentum,
                "augment": "cifar" in c.dataset.lower(),
                "wire": c.wire_dtype, "dim": int(self.trainer.setup.dim)}

    def param_shapes(self):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            self.trainer.state.params)

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def install_weights(self, params) -> None:
        """Put seeded weights in the trainer's state (copies: the step
        donates its state)."""
        fresh = jax.jit(lambda t: jax.tree.map(jnp.copy, t),
                        out_shardings=self.replicated())(params)
        self.trainer.state = self.trainer.state._replace(params=fresh)

    def params(self):
        return self.trainer.state.params

    def first_gradient(self):
        """The gradient the optimizer was handed at the first step, from its
        state after that step: torch-style momentum keeps it as its buffer.
        The buffer is the first run of optimizer-state leaves shaped like
        the parameters."""
        want = [x.shape for x in jax.tree.leaves(self.trainer.state.params)]
        leaves = jax.tree.leaves(self.trainer.state.opt_state)
        for i in range(len(leaves) - len(want) + 1):
            if [x.shape for x in leaves[i:i + len(want)]] == want:
                return leaves[i:i + len(want)]
        raise RuntimeError("no momentum buffer in the optimizer state")

    def run_to(self, step: int):
        """Drive the production loop until ``step`` has run; the records of
        the steps it ran, and the host clock before and after the call (the
        loop ends each step in ``block_until_ready``)."""
        first = len(self.records.rows)
        t0 = time.perf_counter()
        self.trainer.run(max_steps=step)
        jax.block_until_ready(self.trainer.state.params)
        t1 = time.perf_counter()
        return self.records.rows[first:], t0, t1

    def step_hlo(self) -> str:
        """The compiled step program's text, for the trace's scope map: the
        same lowering the loop dispatched, so it comes from the compile
        cache. '' where a step is not one ``train_step`` program."""
        import numpy as np
        from draco_tpu.runtime import put_global, worker_sharding

        c = self.cfg
        if c.steps_per_call != 1:
            return ""
        shard = worker_sharding(self.mesh)
        rows = (c.num_workers, c.batch_size)
        x = put_global(np.zeros(rows + self._sample_shape, np.float32),
                       shard)
        y = put_global(np.zeros(rows, np.int32), shard)
        mask = np.zeros(c.num_workers, bool)
        return self.trainer.setup.train_step.lower(
            self.trainer.state, x, y, mask).compile().as_text()

    def compiles(self) -> int:
        return int(self.trainer.compile_watch.snapshot()["compiles"])

    def host_spans(self) -> list:
        """Complete host spans as (name, start_s, end_s) on perf_counter."""
        if not self._trace_path:
            return []
        self.trainer.tracer.flush()
        with open(self._trace_path) as fh:
            events = json.load(fh).get("traceEvents", [])
        t0 = self._tracer_t0
        return [(e["name"], t0 + e["ts"] * 1e-6,
                 t0 + (e["ts"] + e["dur"]) * 1e-6)
                for e in events if e.get("ph") == "X"]

    def close(self) -> None:
        self.trainer.close()
