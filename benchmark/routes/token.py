"""The token route: ``draco_tpu.parallel.token_loop.run_token_loop`` over the
step builder ``draco_tpu.cli`` picks for the job (``build_sp_train_setup`` at
the traffic's ``seq_shards``), exactly as the CLI drives it, fed the run's
seeded sequences instead of the synthetic stream.

What the comparison keeps beside the job — the seeded weights, the first
gradient — is kept in HOST memory (``replicated`` places the weights on the
CPU backend; ``params`` and ``first_gradient`` hand back host copies): a
model that fills the chip as a deployment would leaves no room for two more
copies of itself, and the chip then holds the job alone."""

from __future__ import annotations

import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.xplane import _HLO_LINE_RE, _META_RE, SCOPE_RE
# the job's own seed and schedule length, and the in-memory metric writer:
# the image route's
from benchmark.routes.cnn import JOB_SEED, MAX_STEPS, _Records

# a call of this many steps or more (a window, not the check's or the warm
# steps) prints the medians of the loop's own host ledger, so that an
# untraced run already says whether its step time is the device's or the
# host's
LEDGER_ROWS = 8


def inner_scopes(hlo_text: str) -> dict:
    """{instruction: innermost ``draco_*`` segment of its op_name} for every
    instruction of a compiled module's text that has one (harness/xplane's
    map keeps the FIRST segment; the nested scopes of the token model are
    read from this one)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE_RE.match(line)
        meta = _META_RE.search(line) if m else None
        scopes = SCOPE_RE.findall(meta.group(1)) if meta else []
        if scopes:
            out[m.group(1)] = scopes[-1]
    return out


class Route:
    def __init__(self, fields: dict, data, devices, trace_dir: str = ""):
        from draco_tpu.config import TrainConfig
        from draco_tpu.obs import compile_watch, make_tracer
        from draco_tpu.parallel import make_mesh_2d
        from draco_tpu.parallel.sp_step import build_sp_train_setup

        fields = dict(fields, seed=JOB_SEED, max_steps=MAX_STEPS,
                      eval_freq=0, log_every=1, train_dir="",
                      trace_dir=trace_dir, checkpoint_step=0)
        self.cfg = TrainConfig(**fields).validate()
        c = self.cfg
        if (c.tensor_shards > 1 or c.expert_shards > 1
                or c.pipeline_shards > 1 or c.pp_microbatches > 0):
            raise NotImplementedError(
                "benchmark/routes/token.py builds the (w, sp) route only")
        if c.steps_per_call != 1:
            raise NotImplementedError(
                "benchmark/routes/token.py drives the eager K=1 loop only")
        self.data = np.asarray(data, np.int32)
        self.mesh = make_mesh_2d(c.num_workers, c.seq_shards, devices)
        self.setup = build_sp_train_setup(c, self.mesh)
        self.records = _Records()
        self.done = 0
        self._inner: dict = {}
        self._host = jax.devices("cpu")[0]
        compile_watch.install()
        self._builds = compile_watch.global_stats
        self._builds0 = self._builds()["builds"]
        self.tracer = make_tracer(trace_dir) if trace_dir else None
        self._tracer_t0 = None
        if trace_dir:
            # the tracer's clock starts at its creation; read the offset to
            # perf_counter once so its spans can be laid beside the profile
            self._tracer_t0 = (time.perf_counter()
                               - self.tracer.now_us() * 1e-6)
        self._trace_path = (os.path.join(trace_dir, "trace.json")
                            if trace_dir else "")

    # ---- the run's rows ------------------------------------------------
    def _tokens(self, step: int, rows: int) -> np.ndarray:
        """(rows, B, T) of 1-based ``step``: the stream in order,
        ``rows * B`` sequences a step, wrapping."""
        b = self.cfg.batch_size
        first = (step - 1) * rows * b
        idx = (first + np.arange(rows * b)) % len(self.data)
        return self.data[idx].reshape(rows, b, -1)

    # ---- what the harness reads ----------------------------------------
    @property
    def _groups(self) -> int:
        from draco_tpu.parallel.sp_step import token_rows

        return token_rows(self.cfg)[0]

    @property
    def examples_per_step(self) -> int:
        """Distinct sequences a step consumes."""
        return self._groups * self.cfg.batch_size

    @property
    def adversaries_per_step(self) -> int:
        return (self.cfg.num_adversaries
                if self.cfg.approach in ("cyclic", "maj_vote") else 0)

    def job(self) -> dict:
        c = self.cfg
        return {"groups": self._groups, "batch": c.batch_size,
                "n": c.num_workers, "lr": c.lr, "momentum": c.momentum,
                "seq_len": c.seq_len, "wire": c.wire_dtype,
                "dim": int(self.setup.dim),
                "model_spec": c.model_spec,
                # of the program a traced run read (step_hlo); else empty
                "inner_scopes": self._inner}

    def param_shapes(self):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            self.setup.state.params)

    def replicated(self):
        """Where the seeded weights are made: host memory (module
        docstring); ``install_weights`` uploads the state's own copy."""
        from jax.sharding import SingleDeviceSharding

        return SingleDeviceSharding(self._host)

    def _on_mesh(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def install_weights(self, params) -> None:
        state = self.setup.state
        # a copy of its own: the step donates its state
        state = state._replace(params=jax.device_put(
            params, self._on_mesh(), may_alias=False))
        self.setup = self.setup._replace(state=state)

    def params(self):
        return jax.device_put(self.setup.state.params, self._host)

    def first_gradient(self):
        """The gradient the optimizer was handed at the first step, from its
        state after that step (torch-style momentum keeps it as its
        buffer: the first run of optimizer-state leaves shaped like the
        parameters), copied to host memory."""
        state = self.setup.state
        want = [x.shape for x in jax.tree.leaves(state.params)]
        leaves = jax.tree.leaves(state.opt_state)
        for i in range(len(leaves) - len(want) + 1):
            if [x.shape for x in leaves[i:i + len(want)]] == want:
                return jax.device_put(leaves[i:i + len(want)], self._host)
        raise RuntimeError("no momentum buffer in the optimizer state")

    def run_to(self, step: int):
        """Drive the production loop until ``step`` has run; the records of
        the steps it ran, and the host clock before and after the call."""
        from draco_tpu.parallel.token_loop import run_token_loop

        first = len(self.records.rows)
        t0 = time.perf_counter()
        state, _ = run_token_loop(
            self.setup, self.cfg, steps=step - self.done, quiet=True,
            tag="bench", tokens=self._tokens, start_step=self.done + 1,
            writer=self.records, tracer=self.tracer)
        jax.block_until_ready(state.params)
        t1 = time.perf_counter()
        self.setup = self.setup._replace(state=state)
        self.done = step
        rows = self.records.rows[first:]
        if len(rows) >= LEDGER_ROWS:
            def mid(k):
                return statistics.median(r[k] for r in rows)

            print("ledger: " + " ".join(
                [f"{k}={1e3 * mid(k):.3f}ms" for k in (
                    "t_fetch", "t_dispatch", "t_wait", "t_drain", "t_book")]
                # the model's own counters: work that follows the data
                + [f"{k}={mid(k):.6g}" for k in getattr(
                    self.setup.model, "stat_names", ())])
                + f" steps={len(rows)}", flush=True)
        return rows, t0, t1

    def step_hlo(self) -> str:
        """The compiled step program's text, for the scope maps: the same
        lowering the loop dispatched, so it comes from the compile cache."""
        c = self.cfg
        toks = jnp.zeros((c.num_workers, c.batch_size, c.seq_len), jnp.int32)
        mask = jnp.zeros((c.num_workers,), bool)
        text = self.setup.train_step.lower(
            self.setup.state, toks, mask).compile().as_text()
        self._inner = inner_scopes(text)
        return text

    def compiles(self) -> int:
        return int(self._builds()["builds"] - self._builds0)

    def host_spans(self) -> list:
        """Complete host spans as (name, start_s, end_s) on perf_counter."""
        if not self._trace_path:
            return []
        self.tracer.flush()
        with open(self._trace_path) as fh:
            events = json.load(fh).get("traceEvents", [])
        t0 = self._tracer_t0
        return [(e["name"], t0 + e["ts"] * 1e-6,
                 t0 + (e["ts"] + e["dur"]) * 1e-6)
                for e in events if e.get("ph") == "X"]

    def close(self) -> None:
        if self.tracer is not None:
            self.tracer.close()
        self.setup = None
