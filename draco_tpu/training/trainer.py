"""Training loop — host-side orchestration around the jitted SPMD step.

Replaces the reference's per-role hot loops (SyncReplicasMaster_NN.start /
DistributedWorker.train and their coded variants, SURVEY.md §3) with one loop:
build batches (deterministic, approach-specific), device_put them sharded over
the worker axis, call the jitted step, emit metrics with the reference's
segment names, checkpoint every eval_freq steps.

Two execution regimes, selected by ``cfg.steps_per_call``:

* K=1 (default): the eager per-step loop, one step ahead — one dispatch,
  one wait, one metrics fetch per step, and the dispatch of step k+1 comes
  BEFORE the wait for step k (``_run_eager``): JAX dispatch is asynchronous,
  so the host's fetch, dispatch, drain and bookkeeping of one step all run
  while the device runs the next, and the device goes from one program
  straight into the other. Every step is still waited for, drained and
  booked, in order, one record a step. The loop syncs — retires a step with
  nothing queued behind it — where something reads the state AT that step:
  a call's last step, an ``eval_freq`` boundary (evaluate, checkpoint), an
  edge of the profiler's window, a step the fault plan names, a pending
  stop. Honest on CPU (PERF_HISTORY.md §4: XLA:CPU serializes conv thunks
  inside scan bodies) and the bitwise reference for the chunked path.
* K>1: the scan-chunked loop — ``train_many`` fuses K full coded steps into
  one device program (training/step.py); the host runs a two-deep pipeline
  (assemble + device_put chunk i+1 while chunk i executes), metrics are
  deferred (K, m) device blocks materialized only at log/eval/checkpoint
  boundaries, and there is NO host sync in steady state. Eval/checkpoint
  cadence snaps to chunk boundaries via explicit remainder chunks, so
  ``max_steps`` need not divide by K. This is what hides the ~70 ms/dispatch
  RTT of remote backends (PERF_HISTORY.md §0) behind useful device work.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from draco_tpu import rng as drng
from draco_tpu.config import TrainConfig
from draco_tpu.data import batching
from draco_tpu.data.datasets import Dataset, load_dataset
from draco_tpu.data.prefetch import BatchPrefetcher, ChunkPrefetcher
from draco_tpu.obs import (
    RunHeartbeat,
    make_compile_watch,
    make_tracer,
    profiler_window,
)
from draco_tpu.obs.forensics import record_value
from draco_tpu.obs.tracer import setup_span
from draco_tpu.resilience import faults as faults_mod
from draco_tpu.resilience.supervisor import (
    GracefulStop,
    ImmediateStopError,
    SupervisedPrefetcher,
    restore_with_walkback,
)
from draco_tpu.runtime import WORKER_AXIS, make_mesh, put_global
from draco_tpu.training.step import build_train_setup
from draco_tpu.utils import checkpoint as ckpt
from draco_tpu.utils.metrics import MetricWriter, Segments


class _InFlight(NamedTuple):
    """A dispatched step the eager loop has not waited for yet."""

    metrics: dict  # the step's metric columns, still on the device
    present: Optional[np.ndarray]  # its straggler row (None: no schedule)
    ahead: float  # 1.0: dispatched before its predecessor was waited for


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None, dataset: Optional[Dataset] = None,
                 quiet: bool = False):
        self.cfg = cfg.validate()
        self.mesh = mesh if mesh is not None else make_mesh(cfg.num_workers)
        self.ds = dataset if dataset is not None else load_dataset(cfg.dataset, cfg.data_dir)
        self.setup = build_train_setup(cfg, self.mesh, dataset_name=self.ds.name)
        self.state = self.setup.state
        # on multi-host, only process 0 emits metrics (checkpoint saves stay
        # collective — every process contributes its addressable shards)
        self._is_main = jax.process_index() == 0
        self.writer = MetricWriter(cfg.train_dir if self._is_main else None,
                                   quiet=quiet or not self._is_main)
        # telemetry (draco_tpu/obs): host span trace when cfg.trace_dir is
        # set, status.json heartbeat whenever there is a train_dir — both
        # no-ops off the metrics-emitting process, and the tracer is the
        # allocation-free NULL_TRACER when disabled
        self.tracer = make_tracer(cfg.trace_dir, self._is_main)
        # num_workers keys the heartbeat's per-worker accusation ledger
        # (obs/forensics.AccusationLedger) — it folds the packed forensics
        # mask columns at the same observer hook, zero extra fetches.
        # The incident engine (obs/incidents.py, ISSUE 13) rides the same
        # hook + the beat when cfg.incident_watch is on: host-side only,
        # bitwise-transparent to training
        from draco_tpu.obs import incidents as incidents_mod

        self.heartbeat = RunHeartbeat(
            cfg.train_dir or None, enabled=self._is_main,
            num_workers=cfg.num_workers,
            incidents=incidents_mod.make_engine(cfg, self._is_main),
            job_name=getattr(cfg, "job_name", "") or None)
        # static logical wire-bytes ledger (obs/numerics.wire_ledger,
        # ISSUE 10): the ``wire`` status block — derived from the program's
        # registered shapes, stamped once per run
        from draco_tpu.obs import numerics as numerics_mod

        self.heartbeat.set_wire(numerics_mod.wire_ledger(cfg,
                                                         self.setup.dim))
        # compile/retrace sentinel (obs/compile_watch.py): every XLA
        # executable build lands in compiles.jsonl + the trace's compile
        # lane, and a steady-state recompile of a labelled program trips
        # the guard (cfg.compile_guard) — the compile-once contract the
        # chunked regime's economics rest on
        self.compile_watch = make_compile_watch(cfg, self.tracer,
                                                self._is_main)
        self._shard_w = NamedSharding(self.mesh, P(WORKER_AXIS))
        # resilience wiring (draco_tpu/resilience): the parsed fault plan
        # (None without cfg.fault_spec), its one-shot host-event injector,
        # and the graceful-stop holder the active run() installs
        self._fault_plan = faults_mod.plan_from_cfg(cfg)
        self._injector = faults_mod.HostFaultInjector(self._fault_plan)
        self._stop: Optional[GracefulStop] = None
        self._stopped_step: Optional[int] = None
        self._autopilot = None  # cached control/autopilot.Autopilot
        self._build_schedules(cfg.max_steps)
        self._engine = None  # live ChunkedEngine while _run_chunked runs
        # (label, key, callable, argument shapes) of the newest dispatch
        self._dispatched = None
        self._eager_step = None  # the step self.state is at (eager loop)
        self._group_seeds = drng.group_seeds(cfg.seed, max(cfg.num_groups, 1))
        # both prefetchers are lazy: the chunked path never touches the
        # per-step one (and vice versa), so neither thread pool should
        # exist until its loop actually runs (each may be wrapped in a
        # SupervisedPrefetcher — same get/depth/close surface)
        self._prefetch = None  # BatchPrefetcher | SupervisedPrefetcher
        self._chunk_prefetch = None  # ChunkPrefetcher | SupervisedPrefetcher
        self._start_step = 1
        if cfg.checkpoint_step:
            self.restore(cfg.checkpoint_step)

    # ---- data ------------------------------------------------------------
    def _batch_indices(self, step: int) -> np.ndarray:
        """Flat (n·B,) sample indices for 1-based training ``step``."""
        cfg = self.cfg
        n = len(self.ds)
        if cfg.approach == "baseline":
            return batching.indices_baseline(n, step - 1, cfg.num_workers,
                                             cfg.batch_size, cfg.seed)
        if cfg.approach == "maj_vote":
            return batching.indices_grouped(n, step - 1, cfg.num_workers,
                                            cfg.group_size, cfg.batch_size,
                                            self._group_seeds)
        return batching.indices_cyclic(n, step - 1, cfg.num_workers,
                                       cfg.batch_size, cfg.seed)

    def _supervised(self, factory):
        """Prefetcher restart supervision (resilience/supervisor.py):
        transient worker faults are retried with backoff up to
        cfg.prefetch_restarts times; 0 disables the wrapper entirely."""
        if self.cfg.prefetch_restarts <= 0:
            return factory()
        return SupervisedPrefetcher(factory,
                                    restarts=self.cfg.prefetch_restarts,
                                    tracer=self.tracer)

    def _host_batch(self, step: int):
        if self._prefetch is None:
            indices_fn = self._injector.wrap_step_fn(self._batch_indices)
            self._prefetch = self._supervised(lambda: BatchPrefetcher(
                self.ds, indices_fn, self.cfg.num_workers,
                self.cfg.batch_size, tracer=self.tracer
            ))
        return self._prefetch.get(step)

    def _device_batch(self, step: int):
        x, y = self._host_batch(step)
        return (
            put_global(np.asarray(x), self._shard_w),
            put_global(np.asarray(y), self._shard_w),
        )

    # ---- schedules -------------------------------------------------------
    def _ensure_schedules(self, n_steps: int) -> None:
        """Keep the adversary/straggler tables live past cfg.max_steps.

        ``run(max_steps=N)`` with N > cfg.max_steps used to replay the last
        precomputed row forever via ``min(step, cfg.max_steps)`` — block-wise
        callers like tools/time_to_acc.py silently trained against a frozen
        adversary set past the table end. Regeneration at the larger length
        is prefix-stable (each row consumes a fixed amount of the numpy
        stream), so already-trained steps keep their exact schedule."""
        if n_steps > self._sched_steps:
            self._build_schedules(n_steps)

    def _build_schedules(self, n_steps: int) -> None:
        """The adversary and straggler tables for steps 1..``n_steps``,
        wherever they are made (``setup.schedules`` in the set-up ledger,
        obs/tracer.py)."""
        cfg = self.cfg
        with setup_span("setup.schedules"):
            # fault-plan overlays: over_budget pushes rows past the s
            # budget, adversary events mark declarative within-budget
            # attack episodes (time-varying adversaries,
            # faults.apply_adversary)
            self._adv_schedule = faults_mod.apply_adversary(
                faults_mod.apply_over_budget(
                    drng.adversary_schedule(cfg.seed, n_steps,
                                            cfg.num_workers,
                                            cfg.num_adversaries),
                    self._fault_plan, cfg.worker_fail,
                ), self._fault_plan)
            # the fault plan's straggle events (sustained per-worker drops)
            # overlay the seeded straggler schedule — or materialize one
            # when the config ran with none (faults.apply_straggle)
            self._straggle_schedule = faults_mod.apply_straggle(
                drng.straggler_schedule(
                    cfg.seed, n_steps, cfg.num_workers, cfg.straggle_count)
                if cfg.straggle_mode == "drop" and cfg.straggle_count > 0
                else None,
                self._fault_plan, cfg.num_workers, n_steps,
            )
            if getattr(cfg, "autopilot", "off") == "on" \
                    and self._straggle_schedule is None:
                # the autopilot's quarantine actuates through the
                # present-mask schedule: materialize an all-present table
                # up front so exclusion is a host array write, never a
                # program-signature change (presents None→array would
                # retrace the chunk program under compile_guard="raise")
                self._straggle_schedule = np.zeros(
                    (n_steps + 1, cfg.num_workers), dtype=bool)
            if self._autopilot is not None:
                # a regenerated table must not silently re-admit workers
                # the policy still holds excluded (block-wise run() calls
                # past the precomputed length)
                self._autopilot.reapply_quarantines(self._straggle_schedule)
        self._sched_steps = n_steps  # rows precomputed in the schedules

    # ---- chunking --------------------------------------------------------
    def _chunk_ranges(self, start: int, n_steps: int) -> list:
        """[(start, k), ...] covering steps [start, n_steps] — the shared
        boundary-snapping rule (batching.chunk_ranges, one implementation
        for this loop and the LM token loop)."""
        return batching.chunk_ranges(start, n_steps, self.cfg.steps_per_call,
                                     self.cfg.eval_freq)

    def _chunk_indices(self, start: int, k: int) -> np.ndarray:
        """(k, n·B) flat sample indices for 1-based steps [start, start+k) —
        row i bitwise equals _batch_indices(start + i)."""
        cfg = self.cfg
        n = len(self.ds)
        if cfg.approach == "baseline":
            return batching.indices_baseline_range(
                n, start - 1, k, cfg.num_workers, cfg.batch_size, cfg.seed)
        if cfg.approach == "maj_vote":
            return batching.indices_grouped_range(
                n, start - 1, k, cfg.num_workers, cfg.group_size,
                cfg.batch_size, self._group_seeds)
        return batching.indices_cyclic_range(
            n, start - 1, k, cfg.num_workers, cfg.batch_size, cfg.seed)

    def _device_chunk(self, rng: tuple, next_range: Optional[tuple]):
        """Assemble + upload one stacked chunk; submits next_range's host
        gather to the native pool before returning (double buffering)."""
        start, k = rng
        with self.tracer.span("gather", chunk_start=start, k=k):
            x, y = self._chunk_prefetch.get(rng, next_range)
        with self.tracer.span("upload", chunk_start=start, k=k):
            shard = NamedSharding(self.mesh, P(None, WORKER_AXIS))
            xs = put_global(np.asarray(x), shard)
            ys = put_global(np.asarray(y), shard)
            # numpy (uncommitted) so multi-host jit treats them as replicated
            masks = np.asarray(self._adv_schedule[start : start + k])
            presents = (
                np.asarray(~self._straggle_schedule[start : start + k])
                if self._straggle_schedule is not None
                else None
            )
        return xs, ys, masks, presents

    # ---- train -----------------------------------------------------------
    def run(self, max_steps: Optional[int] = None,
            profile_dir: Optional[str] = None,
            profile_steps: tuple = (3, 8)) -> dict:
        """Train. ``profile_dir`` captures a jax.profiler trace of steps
        [profile_steps) — the structured replacement for the reference's
        printed per-phase timers (SURVEY.md §5.1); the t_fetch/t_comp segment
        metrics keep the reference's names either way. With
        cfg.steps_per_call > 1 the scan-chunked loop runs instead of the
        eager per-step loop (module docstring); trace capture then snaps to
        the chunks containing profile_steps.

        The call's two edges are spans of their own (``loop.prologue``: entry
        to the first step's fetch; ``loop.epilogue``: the end of the last
        step's ``t_comp`` to the return), so that prologue + the records'
        ``t_book + t_fetch + t_comp`` + epilogue is the call's wall time."""
        with contextlib.ExitStack() as edge:
            edge.enter_context(self.tracer.span("loop.prologue"))
            return self._run(edge, max_steps, profile_dir, profile_steps)

    def _run(self, edge, max_steps, profile_dir, profile_steps) -> dict:
        """:meth:`run` inside its edges: the loop closes ``edge`` (the
        prologue) where its first step begins and enters the epilogue in it
        where its last ends."""
        n_steps = max_steps if max_steps is not None else self.cfg.max_steps
        self._ensure_schedules(n_steps)
        # resilience envelope (ISSUE 6): SIGTERM/SIGINT become a
        # cooperative stop honored at step/chunk boundaries (boundary
        # checkpoint + "preempted" terminal state), and any unhandled
        # exception stamps a "crashed" terminal status.json with a one-line
        # cause before re-raising — operators and tools/trace_report.py can
        # distinguish crash / preempted / done without parsing stdout
        self._stopped_step = None
        try:
            with GracefulStop() as stop:
                self._stop = stop
                if self.cfg.steps_per_call > 1:
                    last = self._run_chunked(n_steps, profile_dir,
                                             profile_steps, edge)
                else:
                    last = self._run_eager(n_steps, profile_dir,
                                           profile_steps, edge)
        except ImmediateStopError as e:
            # second SIGTERM during a chunk (resilience/supervisor.py):
            # checkpoint the newest dispatched state NOW and end with the
            # terminal "preempted" status instead of finishing the grid
            self._stop = None
            return self._escalated_stop(e)
        except BaseException as e:
            self.heartbeat.terminal("crashed",
                                    cause=f"{type(e).__name__}: {e}")
            raise
        finally:
            self._stop = None
        if self._stopped_step is not None:
            self.heartbeat.terminal(
                "preempted",
                cause=f"graceful stop on {stop.signame}",
                resumable_step=(self._stopped_step
                                if self.cfg.train_dir else None),
            )
        else:
            self.heartbeat.terminal("done")
        # advance the cursor so a subsequent run(max_steps=...) continues
        # instead of retraining from step 1 (block-wise callers:
        # tools/time_to_acc.py); a preempted run's cursor stays at its
        # stop point (set by _snap_stop)
        if self._stopped_step is None:
            self._start_step = max(self._start_step, n_steps + 1)
        return last

    def _escalated_stop(self, e: ImmediateStopError) -> dict:
        """The second-signal escalation path: save a resumable checkpoint
        of the NEWEST dispatched state right now — blocking on the
        in-flight chunk if one is executing — and stamp the terminal
        ``preempted`` status. Un-flushed deferred metric records are lost
        (the operator asked for immediate teardown); the checkpoint and
        status.json are not."""
        eng = self._engine
        if eng is not None and eng.state is not None:
            self.state, step = eng.state, eng.last_end
        else:
            step = self._eager_step
        if self.cfg.train_dir and step is not None:
            with self.tracer.span("ckpt", at_step=step):
                ckpt.save(self.cfg.train_dir, step, self.state,
                          compress=self.cfg.compress_ckpt,
                          keep=self.cfg.keep_checkpoints)
        if step is not None:
            self._start_step = step + 1
        self.heartbeat.terminal(
            "preempted", cause=str(e),
            resumable_step=(step if self.cfg.train_dir and step is not None
                            else None))
        return {}

    def _check_stop(self, step: int) -> bool:
        """True when the run should stop after ``step``: a SIGTERM/SIGINT
        arrived (or the fault plan injects one here — delivered through
        the real handler path, supervisor.stop_requested)."""
        from draco_tpu.resilience.supervisor import stop_requested

        return stop_requested(self._stop, self._injector, step)

    def _snap_stop(self, step: int, already_saved: bool = False) -> None:
        """Honor a graceful stop at a step/chunk boundary: snap a resumable
        checkpoint there (the preemption/elasticity mechanism — resume with
        checkpoint_step=step or -1) and record where we stopped for the
        terminal heartbeat. ``already_saved``: the boundary path just
        checkpointed this exact step — don't pay the device_get + write
        twice."""
        if self.cfg.train_dir and not already_saved:
            with self.tracer.span("ckpt", at_step=step):
                ckpt.save(self.cfg.train_dir, step, self.state,
                          compress=self.cfg.compress_ckpt,
                          keep=self.cfg.keep_checkpoints)
        self._stopped_step = step
        if self._stop is not None:
            self._stop.stopped_step = step
        self._start_step = step + 1

    def _run_eager(self, n_steps: int, profile_dir, profile_steps,
                   edge) -> dict:
        """The per-step loop, one step in flight. An iteration retires
        ``step``; in steady state ``step`` is already on the device, and the
        iteration

        1. fetches the batch of ``step + 1`` and dispatches its
           ``train_step`` on the state ``step`` returns (a future, donated
           as ever) without touching ``step``'s outputs first;
        2. waits for ``step``'s metrics (its state went into ``step + 1``
           and may not be read), drains them and books ``step`` — while
           the device runs ``step + 1``.

        It does NOT send ``step + 1`` ahead where :meth:`_may_run_ahead`
        says something reads the state at ``step``; the next iteration then
        finds nothing in flight and sends its own step first. One loop, its
        depth 0 or 1 by the step's position.

        ``self.state`` and ``self._eager_step`` move together, at dispatch:
        a checkpoint names the step its state is at. A stop requested while
        ``step + 1`` is in flight is therefore honoured one iteration later,
        at ``step + 1``, the newest dispatched step.

        The record of ``step`` holds the parts of the iteration that retired
        it: ``t_fetch`` and ``t_dispatch`` of the step sent in it, ``t_wait``
        and ``t_drain`` of ``step``, ``t_book`` since the previous
        iteration's last clock read; ``t_dispatch + t_wait + t_drain ==
        t_comp``, and the records tile the loop as before. The odd halves:
        an iteration that finds nothing in flight (a call's first) sends two
        steps and books both fetches and both dispatches; one that sends
        nothing (a call's last) has ``t_fetch`` and ``t_dispatch`` 0.
        ``ahead`` is 1.0 on a step that was dispatched before its
        predecessor was waited for."""
        cfg = self.cfg
        last = {}
        # the shared capture window (obs/profiling.py): start/stop/drain +
        # the merged-timeline anchor, one implementation for all four loop
        # sites (previously copy-pasted per site, ISSUE 9); on stop the
        # capture folds into the heartbeat's ``device`` status block
        win = profiler_window(profile_dir, profile_steps, self._is_main,
                              self.tracer,
                              on_stop=self.heartbeat.observe_device)
        comp_end = None  # clock read that closed the previous step's t_comp
        flight = None  # the step sent ahead by the previous iteration
        win.maybe_start(self._start_step)
        edge.close()  # loop.prologue ends where the first step begins
        for step in range(self._start_step, n_steps + 1):
            seg = Segments()
            # t_book: everything since the previous step's t_comp closed
            # (the ``book`` span below + the loop's own turn-around); with
            # it the records tile the loop: sum(t_book + t_fetch + t_comp)
            # is the wall time from the first fetch to the last sync
            seg.begin("fetch", since=comp_end, gap="book")
            if flight is None:
                flight = self._send(step, 0.0, seg, win)
                seg.begin("fetch", lap="dispatch")
            mine, flight = flight, None
            if self._may_run_ahead(step, n_steps, win):
                flight = self._send(step + 1, 1.0, seg, win)
            else:
                seg.begin("comp")
            seg.lap("dispatch")  # where nothing was sent the part stays, at 0

            # t_comp's other parts: t_wait (the host waiting for the device
            # to finish ``step`` — with a successor queued, what is left of
            # it after this iteration's fetch and dispatch), t_drain (the
            # metric columns, one device->host fetch each)
            with self.tracer.span("sync", step=step):
                # wait FIRST, on the step's outputs, so the wait is not
                # hidden inside the first column's fetch; the state only
                # where it is this step's (else it was donated onward)
                with self.tracer.span("device_wait", step=step):
                    jax.block_until_ready(
                        mine.metrics if flight is not None
                        else (mine.metrics, self.state.params))
                seg.lap("wait")
                with self.tracer.span("drain", step=step,
                                      columns=len(mine.metrics)):
                    # record_value: forensics bitmask columns materialize
                    # as exact integer words, everything else as float
                    metrics = {k: record_value(k, v)
                               for k, v in mine.metrics.items()}
                    if mine.present is not None:
                        metrics["present"] = float(mine.present.sum())
            comp_end = seg.end(lap="drain")

            with self.tracer.span("book", step=step):
                win.maybe_stop(step, self.state.params)
                record = {"step": step, **metrics, "ahead": mine.ahead,
                          **seg.as_dict()}
                last = record
                self.heartbeat.observe(record)
                if step % cfg.log_every == 0 or step == 1:
                    self.writer.write(record)
                boundary = self._boundary(step)
                if boundary or step == n_steps:
                    with self.tracer.span("flush", at_step=step):
                        self.writer.flush()
                        self.heartbeat.beat(step, n_steps,
                                            extra={**self._prefetch_depth(),
                                                   **self.compile_watch
                                                   .snapshot()})
                        self.tracer.flush()
                if boundary:
                    self.evaluate(step)
                    if cfg.train_dir:
                        with self.tracer.span("ckpt", at_step=step):
                            ckpt.save(cfg.train_dir, step, self.state,
                                      compress=cfg.compress_ckpt,
                                      keep=cfg.keep_checkpoints)
                # a stop seen with ``step + 1`` in flight waits for the
                # next iteration, which sends nothing further, retires
                # ``step + 1`` and lands here with the state at that step
                if self._check_stop(step) and flight is None:
                    with self.tracer.span("flush", at_step=step):
                        self.writer.flush()
                    self._snap_stop(step, already_saved=boundary)
                    break
                if step < n_steps:
                    win.maybe_start(step + 1)
        # loop.epilogue began on the read that closed the last t_comp
        edge.enter_context(self.tracer.span_since("loop.epilogue", comp_end))
        win.stop(self.state.params)  # loop ended inside the window
        return last

    def _boundary(self, step: int) -> bool:
        """An ``eval_freq`` boundary: evaluate and checkpoint at ``step``."""
        return bool(self.cfg.eval_freq) and step % self.cfg.eval_freq == 0

    def _may_run_ahead(self, step: int, n_steps: int, win) -> bool:
        """May ``step + 1`` be dispatched before ``step`` is waited for?
        Not where something reads the state AT ``step`` or has to see whole
        steps: the call's last step, an ``eval_freq`` boundary (evaluate,
        checkpoint), an edge of the profiler's window, a step the fault plan
        names, a stop already requested. Read from what the loop can
        observe, step by step — there is no option for it."""
        return not (
            step >= n_steps
            or self._boundary(step)
            or win.holds(step)
            or self._injector.holds(step)
            or (self._stop is not None and self._stop.requested))

    def _send(self, step: int, ahead: float, seg: Segments,
              win) -> _InFlight:
        """Fetch ``step``'s batch (the open ``t_fetch``) and dispatch its
        ``train_step`` on the newest state (inside the ``t_comp`` this
        leaves open: the caller's next clock read closes ``t_dispatch``).
        The step's outputs are not waited for here."""
        with self.tracer.span("gather+upload", step=step):
            x, y = self._device_batch(step)
            # numpy (uncommitted) so multi-host jit treats it as
            # replicated
            mask = np.asarray(self._adv_schedule[step])
            present = (
                np.asarray(~self._straggle_schedule[step])
                if self._straggle_schedule is not None
                else None
            )
        # fwd+bwd+encode+gather+decode+update, one program
        seg.begin("comp")
        args = (self.state, x, y, mask) if present is None \
            else (self.state, x, y, mask, present)
        self._note_dispatch("train_step", self.setup.train_step, args)
        win.note_program("train_step", self.setup.train_step, args)
        with self.tracer.span("dispatch", step=step, ahead=ahead), \
                self.compile_watch.expect("train_step"):
            # the state and the step it is at move together
            self.state, metrics = self.setup.train_step(*args)
            self._eager_step = step
        del args  # the donated state must not outlive its call
        return _InFlight(metrics, present, ahead)

    def _note_dispatch(self, label: str, fn, args, key=None) -> None:
        """Remember what is about to be dispatched — the callable and, as
        shapes, every argument after the state (which the call donates and
        ``self.state`` stands for) — for :meth:`dispatched_hlo`."""
        if self._dispatched is None or self._dispatched[:2] != (label, key):
            from draco_tpu.obs.profiling import abstract_args

            self._dispatched = (label, key, fn, abstract_args(args[1:]))

    def dispatched_hlo(self) -> str:
        """Optimized-HLO text of the step program the loop dispatched last
        (``train_step``, or ``train_many`` at its last chunk length), lowered
        from that call's own argument types — the program a device trace of
        this run names its events after (obs/device_attr.scope_map_from_hlo).
        '' before the first step."""
        if self._dispatched is None:
            return ""
        from draco_tpu.obs.profiling import abstract_args, program_text

        _, _, fn, rest = self._dispatched
        return program_text(fn, abstract_args((self.state,)) + rest)

    def _run_chunked(self, n_steps: int, profile_dir, profile_steps,
                     edge) -> dict:
        """The scan-fused loop, driven by the shared ``ChunkedEngine``
        (control/engine.py — one implementation with the LM token loop):
        dispatch train_many per chunk, upload the next chunk while the
        device runs the current one, defer metrics to flush boundaries."""
        cfg = self.cfg
        ranges = self._chunk_ranges(self._start_step, n_steps)
        if not ranges:
            return {}
        if self._chunk_prefetch is None:
            range_fn = self._injector.wrap_range_fn(self._chunk_indices)
            self._chunk_prefetch = self._supervised(lambda: ChunkPrefetcher(
                self.ds, range_fn, cfg.num_workers, cfg.batch_size,
                tracer=self.tracer
            ))
        from draco_tpu.control.clients import TrainerChunkClient
        from draco_tpu.control.engine import ChunkedEngine

        self._engine = ChunkedEngine(
            TrainerChunkClient(self), eval_freq=cfg.eval_freq,
            total_end=n_steps, tracer=self.tracer, heartbeat=self.heartbeat,
            compile_watch=self.compile_watch, writer=self.writer,
            autopilot=self._make_autopilot(), timed=True,
            profile_dir=profile_dir, profile_steps=profile_steps,
            is_main=self._is_main)
        edge.close()  # loop.prologue: up to the engine's first chunk
        self.state, last = self._engine.run(self.state, ranges)
        edge.enter_context(self.tracer.span("loop.epilogue"))
        return last

    def _make_autopilot(self):
        """The adaptive coding autopilot (control/autopilot.py) when
        ``cfg.autopilot == "on"`` — None otherwise (the engine then runs
        the historical loop bit-for-bit). Built once and cached: regime
        and quarantine state outlive individual run() calls (block-wise
        callers), re-attached to each run's fresh client by the engine."""
        if getattr(self.cfg, "autopilot", "off") != "on":
            return None
        if self._autopilot is None:
            from draco_tpu.control.autopilot import make_autopilot

            self._autopilot = make_autopilot(self.cfg, self.heartbeat,
                                             dim=self.setup.dim)
        return self._autopilot

    def _prefetch_depth(self) -> dict:
        """Heartbeat extra: in-flight prefetch requests of whichever
        prefetcher the active regime runs, plus the supervision restart
        counter when wrapped (resilience/supervisor.py — the incident
        engine's starvation signal, ISSUE 13)."""
        p = self._chunk_prefetch if self._chunk_prefetch is not None \
            else self._prefetch
        if p is None:
            # no prefetcher, no depth claim: a constant 0 would read as
            # starvation to the incident engine (same rule as token_loop)
            return {}
        out = {"prefetch_depth": p.depth}
        if hasattr(p, "stats"):
            out.update(p.stats())
        return out

    # ---- eval ------------------------------------------------------------
    def evaluate(self, step: int, batch_size: Optional[int] = None) -> dict:
        """Full-split accuracy: the ragged final batch (n % bs != 0) is padded
        up to the compiled batch shape and masked out of the counts, so every
        test sample is scored exactly once (shared pad/mask loop:
        evaluator.masked_full_split_eval)."""
        from draco_tpu.training.evaluator import masked_full_split_eval

        with self.tracer.span("eval", at_step=step):
            p1, p5 = masked_full_split_eval(
                lambda x, y, valid: self.setup.eval_step(self.state, x, y,
                                                         valid),
                self.ds.test_x, self.ds.test_y,
                batch_size or self.cfg.test_batch_size,
            )
        rec = {"step": step, "prec1_test": p1, "prec5_test": p5}
        self.writer.write(rec)
        # eval cadence is rare and follows the loops' boundary flush, so
        # drain immediately — callers that never close() (perf tools) still
        # get a complete metrics.jsonl
        self.writer.flush()
        return rec

    def close(self):
        if self._prefetch is not None:
            self._prefetch.close()
        if self._chunk_prefetch is not None:
            self._chunk_prefetch.close()
        self.writer.close()
        self.compile_watch.stop()
        self.tracer.close()

    # ---- checkpoint ------------------------------------------------------
    def restore(self, step: int):
        """Resume from ``step`` (or the newest checkpoint when ``step ==
        -1``), walking back past corrupt checkpoints
        (resilience/supervisor.restore_with_walkback) — a torn newest
        checkpoint costs the steps since the previous good one, never the
        run."""
        # abstract tree must carry each leaf's sharding: on multi-host, save()
        # writes global jax.Arrays collectively, and a sharding-less restore
        # would fail (or come back host-local) exactly there
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            self.state,
        )
        try:
            self.state, loaded, _skipped = restore_with_walkback(
                self.cfg.train_dir, step, abstract
            )
        except FileNotFoundError:
            if step != -1:
                raise
            # -1 is the restart-controller flag ("resume from whatever is
            # there"): an empty train_dir means a fresh start, not a crash
            # loop for jobs that died before their first checkpoint
            print(f"checkpoint_step=-1: no checkpoints in "
                  f"{self.cfg.train_dir!r}; starting fresh", flush=True)
            return
        self._start_step = loaded + 1
