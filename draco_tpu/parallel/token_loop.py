"""The shared LM training loop — one host driver for all five token routes
(single-shard, sp, tp, pp, ep; anything exposing ``.state``, ``.train_step``,
``.eval_step``, ``.train_token_many``).

Two execution regimes, selected by ``cfg.steps_per_call`` — the same contract
as the CNN ``Trainer`` (training/trainer.py):

* K=1 (default): the eager per-step loop — one ``synthetic_text`` host
  generation, one fresh upload, one dispatch per step. The bitwise reference
  for the chunked path, and honest on local CPU.
* K>1: the scan-chunked loop — ``train_token_many`` (parallel/common.py)
  fuses K full LM coded steps (token-batch slice → vmapped lane fwd/bwd →
  encode → aggregate/decode → update) into ONE jitted ``lax.scan`` with the
  state carry donated and the adversary/straggler schedules sliced on device
  from (K, n) blocks. Per-step losses accumulate into a (K, m) device block
  fetched once per flush window (``DeferredMetricWriter``); the next chunk's
  (K, n·B, T) token block is assembled on a background thread while the
  device runs the current one (``TokenChunkPrefetcher``). Per K steps the
  host pays ONE dispatch instead of K × (host token gen + device_put +
  dispatch) — this is what hides the ~70 ms/dispatch RTT of remote backends
  (PERF_HISTORY.md §0/§4b) on the LM routes, where it was ~70 % of the flagship
  step (PERF_HISTORY.md §1b).

``cfg.token_gen == "device"`` removes the host token path entirely: the
scanned program regenerates each step's batch in-graph from the scalar
(seed, step) (``sp_step.synthetic_text_in_graph``, the same discipline as
``rng.random_projection_factors_in_graph``), so a chunk's upload is K int32
scalars. The device stream is a distinct PRNG draw from the host stream, so
the flag selects WHICH deterministic stream trains — both regimes of a given
stream stay bitwise-equivalent (K=1 runs the scanned driver too in this
mode).

Eval/checkpoint cadence snaps to chunk boundaries via explicit remainder
chunks (``batching.chunk_ranges`` — the one snapping rule, shared with
``Trainer._run_chunked``), so ``max_steps`` need not divide by K and a
resumed run re-enters the exact chunk grid. Held-out eval needs only
``eval_freq`` (the metric writer prints when there is no ``train_dir``);
checkpoints need only ``train_dir`` — a run with ``eval_freq=0`` still saves
its final state (previously both hid behind one ``eval_freq and train_dir``
guard and checkpointing without eval was impossible).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from draco_tpu import rng as drng
from draco_tpu.config import TrainConfig
from draco_tpu.data.batching import chunk_ranges
from draco_tpu.obs import (
    NULL_TRACER,
    CompileWatch,
    RunHeartbeat,
    profiler_window,
)
from draco_tpu.obs.forensics import record_value
from draco_tpu.resilience import faults as faults_mod
from draco_tpu.resilience.supervisor import (
    GracefulStop,
    ImmediateStopError,
    SupervisedPrefetcher,
    restore_with_walkback,
)


class _LoopTelemetry(NamedTuple):
    """Telemetry + resilience context threaded through both regimes'
    drivers (defaults = everything disabled, so direct driver calls need no
    setup)."""

    tracer: Any = NULL_TRACER
    heartbeat: RunHeartbeat = RunHeartbeat(None)
    total_end: int = 0  # last step of the run (heartbeat ETA denominator)
    profile_dir: Optional[str] = None
    profile_steps: tuple = (3, 8)
    # compile/retrace sentinel; the default is an unstarted (inert) watch
    compile_watch: CompileWatch = CompileWatch(guard="off")
    # deterministic host-fault injector (inert without cfg.fault_spec) and
    # the graceful-stop holder run_token_loop installs (ISSUE 6)
    injector: Any = faults_mod.NULL_INJECTOR
    stop: Optional[GracefulStop] = None
    # mutable {"state", "step"} holder the eager loop refreshes per step —
    # the escalated-stop (ImmediateStopError) checkpoint source there
    latest: Any = None


def _stop_requested(obs: _LoopTelemetry, step: int) -> bool:
    """True when the loop should stop after ``step`` — a SIGTERM/SIGINT
    arrived, or the fault plan injects one here (delivered through the
    real handler path; the shared poll lives in supervisor.stop_requested,
    one implementation for both production loops)."""
    from draco_tpu.resilience.supervisor import stop_requested

    return stop_requested(obs.stop, obs.injector, step)


def _snap_stop(cfg, state, step: int, obs: _LoopTelemetry,
               already_saved: bool = False) -> None:
    """Honor a graceful stop: snap a resumable boundary checkpoint and
    record where (the terminal "preempted" heartbeat reports it).
    ``already_saved``: the boundary path just checkpointed this exact step
    — don't pay the device_get + write twice."""
    from draco_tpu.utils import checkpoint as ckpt_mod

    if cfg.train_dir and not already_saved:
        with obs.tracer.span("ckpt", at_step=step):
            ckpt_mod.save(cfg.train_dir, step, state,
                          compress=cfg.compress_ckpt,
                          keep=cfg.keep_checkpoints)
    if obs.stop is not None:
        obs.stop.stopped_step = step


def step_tokens(cfg: TrainConfig, step: int, tokens=None) -> np.ndarray:
    """The (n, B, T) int32 rows of training ``step``, on the host: one row a
    worker — or, under ``maj_vote``, one row a repetition group, held by
    all its members (the vote's soundness condition; sp_step.token_rows).
    ``tokens``: the run's own source ``(step, rows) -> (rows, B, T)`` in
    place of the synthetic stream (run_token_loop)."""
    from draco_tpu.parallel.sp_step import synthetic_text, token_rows

    rows, repeat = token_rows(cfg)
    if tokens is None:
        toks = synthetic_text(cfg.seed, step, rows, cfg.batch_size,
                              cfg.seq_len, cfg.vocab)
    else:
        toks = np.asarray(tokens(step, rows), np.int32)
    return np.repeat(toks, repeat, axis=0) if repeat > 1 else toks


def run_token_loop(setup, cfg: TrainConfig, steps: Optional[int] = None,
                   quiet: bool = False, tag: str = "mp",
                   profile_dir: Optional[str] = None,
                   profile_steps: tuple = (3, 8), rebuild=None, *,
                   tokens=None, start_step: Optional[int] = None,
                   writer=None, tracer=None):
    """Train ``steps or cfg.max_steps`` steps on the synthetic token stream.

    A caller that drives the loop in pieces over its own data (the
    benchmark's token route, as block-wise callers drive ``Trainer.run``)
    passes ``tokens`` (:func:`step_tokens`' source, in place of the
    synthetic stream), ``start_step`` (the first step's number; the
    schedules and the rows follow it), and may stand its own ``writer``
    (``write`` / ``flush`` / ``close``) and ``tracer`` where the loop's
    would be; a tracer handed in is flushed, not closed.

    Same operational contract as the CNN Trainer: step-indexed Orbax
    checkpoints + held-out eval every ``eval_freq`` steps (reference:
    baseline_master.py:142-144), resume via ``cfg.checkpoint_step``.
    ``tag`` labels the route in error messages only; metric records carry
    the step number. Returns (state, last metrics).

    Telemetry (draco_tpu/obs, same contract as Trainer.run): ``profile_dir``
    captures a jax.profiler device trace of steps [profile_steps) — under
    the chunked regime capture snaps to the chunks containing those steps,
    exactly like ``Trainer._run_chunked``; ``cfg.trace_dir`` writes the
    host-span ``trace.json``; ``cfg.train_dir`` gets the ``status.json``
    heartbeat at every flush boundary.
    """
    from draco_tpu.obs import make_compile_watch, make_tracer
    from draco_tpu.parallel.sp_step import synthetic_text
    from draco_tpu.utils import checkpoint as ckpt_mod
    from draco_tpu.utils.metrics import MetricWriter

    state = setup.state
    start = 1 if start_step is None else start_step
    if cfg.checkpoint_step > 0 or cfg.checkpoint_step == -1:
        # walk-back restore (resilience/supervisor.py): a corrupt
        # checkpoint is skipped, not fatal; -1 means "newest loadable" —
        # and, for restart controllers, an EMPTY train_dir means a fresh
        # start rather than a crash loop
        try:
            state, loaded, _skipped = restore_with_walkback(
                cfg.train_dir, cfg.checkpoint_step,
                jax.tree.map(lambda x: x, state))
            start = loaded + 1
        except FileNotFoundError:
            if cfg.checkpoint_step != -1:
                raise
            print(f"checkpoint_step=-1: no checkpoints in "
                  f"{cfg.train_dir!r}; starting fresh", flush=True)
    total = steps or cfg.max_steps
    last_step = start + total - 1
    # live adversaries may be fewer than the code parameter s when decode
    # budget is reserved for stragglers (config.adversary_count); the
    # fault plan's over_budget events (cfg.fault_spec) push their steps'
    # rows past the s budget — deterministically, like everything else
    fault_plan = faults_mod.plan_from_cfg(cfg)
    adv = faults_mod.apply_adversary(
        faults_mod.apply_over_budget(
            drng.adversary_schedule(cfg.seed, start + total + 1,
                                    cfg.num_workers, cfg.num_adversaries),
            fault_plan, cfg.worker_fail,
        ), fault_plan)
    # straggle events (sustained per-worker drops, faults.apply_straggle)
    # overlay the seeded schedule — or materialize one from scratch
    straggle = faults_mod.apply_straggle(
        drng.straggler_schedule(cfg.seed, start + total + 1, cfg.num_workers,
                                cfg.straggle_count)
        if cfg.straggle_mode == "drop" and cfg.straggle_count > 0
        else None,
        fault_plan, cfg.num_workers, start + total + 1,
    )
    if getattr(cfg, "autopilot", "off") == "on" and straggle is None:
        # autopilot quarantine actuates through the present-mask schedule:
        # materialize an all-present table so exclusion is a host array
        # write, never a program-signature change (same rule as Trainer)
        straggle = np.zeros((start + total + 1, cfg.num_workers), dtype=bool)
    is_main = jax.process_index() == 0
    if writer is None:
        writer = MetricWriter(cfg.train_dir or None, quiet=quiet)
    own_tracer = tracer is None
    if own_tracer:
        tracer = make_tracer(cfg.trace_dir, is_main)
    # num_workers keys the heartbeat's per-worker accusation ledger
    # (obs/forensics.AccusationLedger), fed by the same observer hook; the
    # incident engine (obs/incidents.py, ISSUE 13) rides the same hook +
    # the beat when cfg.incident_watch is on — host-side only, bitwise-
    # transparent to training
    from draco_tpu.obs import incidents as incidents_mod

    heartbeat = RunHeartbeat(cfg.train_dir or None, enabled=is_main,
                             num_workers=cfg.num_workers,
                             incidents=incidents_mod.make_engine(cfg,
                                                                 is_main),
                             job_name=getattr(cfg, "job_name", "") or None)
    # static logical wire-bytes ledger (obs/numerics.wire_ledger, ISSUE
    # 10): the ``wire`` status block, from the route's flat-grad dimension
    from draco_tpu.obs import numerics as numerics_mod

    heartbeat.set_wire(numerics_mod.wire_ledger(cfg, setup.dim))
    compile_watch = make_compile_watch(cfg, tracer, is_main)
    eval_toks = None
    if cfg.eval_freq:
        # held-out stream: step 0 is never trained on
        eval_toks = jnp.asarray(
            synthetic_text(cfg.seed + 1, 0, cfg.num_workers, cfg.batch_size,
                           cfg.seq_len, cfg.vocab)
        )

    def boundary_eval_ckpt(step, st):
        if eval_toks is not None:
            with tracer.span("eval"):
                eval_loss = float(setup.eval_step(st.params, eval_toks))
            writer.write({"step": step, "split": "eval", "loss": eval_loss})
            writer.flush()
        if cfg.train_dir:
            with tracer.span("ckpt"):
                ckpt_mod.save(cfg.train_dir, step, st,
                              compress=cfg.compress_ckpt,
                              keep=cfg.keep_checkpoints)

    # resilience envelope (ISSUE 6), mirroring Trainer.run: SIGTERM/SIGINT
    # become a cooperative stop honored at step/chunk boundaries (boundary
    # checkpoint + "preempted" terminal heartbeat state); an unhandled
    # exception stamps a "crashed" terminal status.json before re-raising.
    # ``engine_ref``/``latest`` track the newest dispatched state + step so
    # a second signal (ImmediateStopError) can checkpoint immediately
    engine_ref: list = []
    latest = {"state": state, "step": None}
    try:
        with GracefulStop() as stop:
            obs = _LoopTelemetry(tracer=tracer, heartbeat=heartbeat,
                                 total_end=last_step,
                                 profile_dir=(profile_dir if is_main
                                              else None),
                                 profile_steps=profile_steps,
                                 compile_watch=compile_watch,
                                 injector=faults_mod.HostFaultInjector(
                                     fault_plan),
                                 stop=stop, latest=latest)
            K = max(cfg.steps_per_call, 1)
            if K > 1 or cfg.token_gen == "device":
                # the device-generated stream exists only inside the
                # scanned program, so that mode runs the chunked driver
                # even at K=1
                state, metrics = _run_chunked(setup, cfg, state, start,
                                              last_step, adv, straggle,
                                              writer, boundary_eval_ckpt,
                                              tag, obs, rebuild=rebuild,
                                              engine_ref=engine_ref,
                                              tokens=tokens)
            else:
                state, metrics = _run_eager(setup, cfg, state, start,
                                            last_step, adv, straggle,
                                            writer, boundary_eval_ckpt, obs,
                                            tokens=tokens)
            if (cfg.train_dir and not cfg.eval_freq
                    and stop.stopped_step is None):
                # checkpointing without eval: no cadence boundaries exist,
                # so save the final state (with eval_freq set the boundary
                # saves stand alone, preserving the historical
                # on-boundary-only layout); a preempted run already snapped
                # its resumable checkpoint at the stop point
                with tracer.span("ckpt"):
                    ckpt_mod.save(cfg.train_dir, last_step, state,
                                  compress=cfg.compress_ckpt,
                                  keep=cfg.keep_checkpoints)
        if stop.stopped_step is not None:
            heartbeat.terminal(
                "preempted", cause=f"graceful stop on {stop.signame}",
                resumable_step=(stop.stopped_step if cfg.train_dir
                                else None))
        else:
            heartbeat.terminal("done")
    except ImmediateStopError as e:
        # second SIGTERM during a chunk (resilience/supervisor.py):
        # checkpoint the newest dispatched state NOW — blocking on the
        # in-flight chunk if one is executing — and end with the terminal
        # "preempted" status instead of finishing the chunk grid
        eng = engine_ref[0] if engine_ref else None
        if eng is not None and eng.state is not None:
            state, step_now = eng.state, eng.last_end
        else:
            state, step_now = latest["state"], latest["step"]
        if cfg.train_dir and step_now is not None:
            with tracer.span("ckpt", at_step=step_now):
                ckpt_mod.save(cfg.train_dir, step_now, state,
                              compress=cfg.compress_ckpt,
                              keep=cfg.keep_checkpoints)
        heartbeat.terminal(
            "preempted", cause=str(e),
            resumable_step=(step_now if cfg.train_dir
                            and step_now is not None else None))
        metrics = {}
    except BaseException as e:
        heartbeat.terminal("crashed", cause=f"{type(e).__name__}: {e}")
        raise
    finally:
        writer.close()
        compile_watch.stop()
        if own_tracer:
            tracer.close()
        else:
            tracer.flush()
    return state, metrics


def _run_eager(setup, cfg, state, start, last_step, adv, straggle, writer,
               boundary_eval_ckpt, obs=_LoopTelemetry(), tokens=None):
    """One dispatch per step — the K=1 bitwise reference. A logged step's
    record carries the Trainer's ledger in seconds (utils/metrics.Segments):
    ``t_fetch`` (the rows, gathered and uploaded), ``t_comp`` =
    ``t_dispatch + t_wait + t_drain``, ``t_book`` (everything since the
    previous step's ``t_comp`` closed)."""
    from draco_tpu.utils.metrics import Segments

    tracer, heartbeat, watch = obs.tracer, obs.heartbeat, obs.compile_watch
    total_end = obs.total_end
    # shared capture window (obs/profiling.py): start/stop + the
    # drain-before-stop fix + the merged-timeline anchor, one
    # implementation for all four loop sites (ISSUE 9); on stop the capture
    # folds into the heartbeat's ``device`` status block
    win = profiler_window(obs.profile_dir, obs.profile_steps, tracer=tracer,
                          on_stop=heartbeat.observe_device)
    metrics = {}
    comp_end = None  # clock read that closed the previous step's t_comp
    for step in range(start, last_step + 1):
        win.maybe_start(step)
        seg = Segments()
        seg.begin("fetch", since=comp_end, gap="book")
        with tracer.span("gather"):
            toks = jnp.asarray(step_tokens(cfg, step, tokens))
        args = (state, toks, jnp.asarray(adv[step]))
        if straggle is not None:
            args += (jnp.asarray(~straggle[step]),)
        seg.end()
        seg.begin("comp")
        win.note_program("train_step", setup.train_step, args)
        with tracer.span("dispatch"), watch.expect("train_step"):
            state, metrics = setup.train_step(*args)
        del args  # the donated state must not outlive its call
        seg.lap("dispatch")
        win.maybe_stop(step, state.params)
        if obs.latest is not None:  # escalated-stop checkpoint cursor
            obs.latest["state"], obs.latest["step"] = state, step
        # materialize metrics at log boundaries only — the eager loop's
        # historical device-sync cadence; fetching every step for the
        # heartbeat would re-serialize the async-dispatch pipeline. The
        # heartbeat therefore aggregates the LOGGED steps in this regime
        # (the chunked driver observes every step for free at its flush)
        if step % cfg.log_every == 0:
            with tracer.span("sync"):
                with tracer.span("device_wait"):
                    jax.block_until_ready(metrics)
                seg.lap("wait")
                with tracer.span("drain", columns=len(metrics)):
                    # the columns in ONE device-to-host fetch (a blocking
                    # fetch a column is 0.7 ms each on the chip, 12 ms for
                    # the LM vote's 17: host time the device idles through,
                    # and the step's run-to-run noise). record_value:
                    # forensics bitmask columns materialize as exact
                    # integer words (obs/forensics docstring)
                    record = {"step": step}
                    record.update({k: record_value(k, v) for k, v
                                   in jax.device_get(metrics).items()})
            comp_end = seg.end(lap="drain")
            record.update(seg.as_dict())
            heartbeat.observe(record)
            writer.write(record)
        else:
            comp_end = seg.end()  # not synced: the dispatch alone
        boundary = cfg.eval_freq and step % cfg.eval_freq == 0
        if boundary or step == last_step:
            with tracer.span("flush"):
                writer.flush()
                heartbeat.beat(step, total_end, extra=watch.snapshot())
                tracer.flush()
        if boundary:
            boundary_eval_ckpt(step, state)
        if _stop_requested(obs, step):
            with tracer.span("flush"):
                writer.flush()
            _snap_stop(cfg, state, step, obs, already_saved=bool(boundary))
            break
    win.stop(state.params)  # loop ended inside the window
    return state, metrics


def _run_chunked(setup, cfg, state, start, last_step, adv, straggle, writer,
                 boundary_eval_ckpt, tag="mp", obs=_LoopTelemetry(),
                 rebuild=None, engine_ref=None, tokens=None):
    """One dispatch per chunk of up to K steps, driven by the shared
    ``ChunkedEngine`` (control/engine.py — one implementation with the CNN
    Trainer loop): metrics deferred to flush boundaries, next chunk
    assembled while the device runs the current one."""
    from draco_tpu.control.clients import TokenChunkClient
    from draco_tpu.control.engine import ChunkedEngine
    from draco_tpu.data.prefetch import TokenChunkPrefetcher

    if setup.train_token_many is None:
        raise ValueError(
            f"{tag} route setup lacks train_token_many — rebuild it with "
            "the current route builders (parallel/{sp,tp,ep,pp}_step.py)"
        )
    ranges = chunk_ranges(start, last_step, cfg.steps_per_call, cfg.eval_freq)
    if not ranges:
        return state, {}
    prefetch = None
    if cfg.token_gen != "device":
        # generation fn wrapped by the fault injector (inert by default),
        # prefetcher wrapped by restart supervision with a bounded queue
        # wait — a dead/hung worker thread is retried with backoff, then
        # surfaces as the named PrefetchStallError, never a silent hang
        gen_fn = obs.injector.wrap_step_fn(
            lambda step: step_tokens(cfg, step, tokens))
        factory = lambda: TokenChunkPrefetcher(  # noqa: E731
            gen_fn, tracer=obs.tracer, timeout_s=cfg.prefetch_timeout_s)
        prefetch = (SupervisedPrefetcher(factory,
                                         restarts=cfg.prefetch_restarts,
                                         tracer=obs.tracer)
                    if cfg.prefetch_restarts > 0 else factory())
    client = TokenChunkClient(setup, cfg, adv, straggle, prefetch, obs,
                              boundary_eval_ckpt, rebuild=rebuild)
    autopilot = None
    if getattr(cfg, "autopilot", "off") == "on":
        from draco_tpu.control.autopilot import make_autopilot

        autopilot = make_autopilot(cfg, obs.heartbeat, dim=setup.dim)
    engine = ChunkedEngine(
        client, eval_freq=cfg.eval_freq, total_end=obs.total_end,
        tracer=obs.tracer, heartbeat=obs.heartbeat,
        compile_watch=obs.compile_watch, writer=writer,
        autopilot=autopilot, profile_dir=obs.profile_dir,
        profile_steps=obs.profile_steps)
    if engine_ref is not None:
        engine_ref.append(engine)  # the escalated-stop checkpoint source
    state, last = engine.run(state, ranges)
    return state, ({"loss": last["loss"]} if "loss" in last else {})
