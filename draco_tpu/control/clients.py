"""ChunkedEngine clients — the loop-specific halves of the unified
chunked host loop (control/engine.py), one per production loop.

Each client owns exactly what its loop is ABOUT: what a chunk payload is
(stacked image batches + masks for the coded-DP Trainer, token blocks or
a (K,) step vector for the LM routes), how to dispatch it, and what an
eval/checkpoint boundary does. Everything both loops must do identically
(flush cadence, deferred metrics, stop/snap discipline, profiler
windows, heartbeat beats, the autopilot hook) lives in the engine.

The clients are also the autopilot's actuation surface
(control/autopilot.py): ``switch_regime`` swaps the dispatched setup —
warm, because the autopilot caches built setups per regime, so a return
swap reuses the jitted executable — and ``quarantine``/``readmit``
mutate the present-mask schedule the next assembled chunk reads (an
erasure at a known position; no program change at all).
"""

from __future__ import annotations

import numpy as np


class TrainerChunkClient:
    """Client for the coded-DP CNN Trainer (training/trainer.py): a chunk
    payload is the stacked (xs, ys, masks, presents) upload."""

    BASE_LABEL = "train_many"

    def __init__(self, tr):
        self.tr = tr
        self.label = self.BASE_LABEL
        self.setup = tr.setup
        # current regime's wire segmentation (ISSUE 16) — re-stamped by the
        # autopilot on segments_up/segments_down swaps so the engine's
        # dispatch spans carry the live S
        self.wire_segments = int(getattr(tr.cfg, "wire_segments", 1) or 1)
        self._pre_quarantine = {}  # worker -> schedule column before it

    @property
    def metric_names(self):
        return self.setup.metric_names

    def assemble(self, i, ranges):
        return self.tr._device_chunk(
            ranges[i], ranges[i + 1] if i + 1 < len(ranges) else None)

    def program(self, state, chunk):
        xs, ys, masks, presents = chunk
        fn, args = self.setup.train_many, (state, xs, ys, masks, presents)
        self.tr._note_dispatch(self.label, fn, args, key=len(masks))
        return fn, args

    def defer_extras(self, chunk, fetch_s, k):
        extras = {"t_fetch": round(fetch_s / k, 6)}
        presents = chunk[3]
        if presents is not None:
            extras["present"] = presents.sum(axis=1)
        return extras

    def should_log(self, step):
        return step % self.tr.cfg.log_every == 0 or step == 1

    def beat_extras(self):
        return self.tr._prefetch_depth()

    def boundary(self, end, state):
        from draco_tpu.utils import checkpoint as ckpt

        tr = self.tr
        tr.state = state
        tr.evaluate(end)
        if tr.cfg.train_dir:
            with tr.tracer.span("ckpt", at_step=end):
                ckpt.save(tr.cfg.train_dir, end, state,
                          compress=tr.cfg.compress_ckpt,
                          keep=tr.cfg.keep_checkpoints)

    def stop_requested(self, end):
        return self.tr._check_stop(end)

    def snap_stop(self, end, state, already_saved):
        self.tr.state = state
        self.tr._snap_stop(end, already_saved=already_saved)

    def cleanup(self):
        pass  # prefetchers close with the Trainer (close())

    # ---- autopilot actuation (control/autopilot.py) ----------------------
    def build_setup(self, cfg):
        """Build a regime's TrainSetup — the warm-swap cache's
        construction hook (called once per NEW regime)."""
        from draco_tpu.training.step import build_train_setup

        return build_train_setup(cfg, self.tr.mesh,
                                 dataset_name=self.tr.ds.name)

    def switch_regime(self, setup, label):
        self.setup = setup
        self.label = label

    def quarantine(self, worker, from_step):
        """Present-mask exclusion: the worker's rows stop arriving from
        ``from_step`` on — an erasure at a known position, decoded around
        exactly like a scheduled straggler."""
        sched = self.tr._straggle_schedule
        self._pre_quarantine[worker] = sched[:, worker].copy()
        sched[from_step:, worker] = True

    def readmit(self, worker, from_step):
        """Restore the worker's pre-quarantine schedule column from
        ``from_step`` on (seeded drops it would have had anyway stay)."""
        saved = self._pre_quarantine.pop(worker, None)
        sched = self.tr._straggle_schedule
        if saved is None:
            sched[from_step:, worker] = False
        else:
            sched[from_step:, worker] = saved[from_step:len(sched)]


class TokenChunkClient:
    """Client for the LM token routes (parallel/token_loop.py): a chunk
    payload is (tokens | (K,) step vector, masks, presents). Family swaps
    rebuild the route setup via ``rebuild`` when the route provided one
    (sp does); without it the autopilot still quarantines/readmits."""

    BASE_LABEL = "train_token_many"

    def __init__(self, setup, cfg, adv, straggle, prefetch, obs,
                 boundary_eval_ckpt, rebuild=None):
        self.setup = setup
        self.cfg = cfg
        self.adv = adv
        self.straggle = straggle
        self.prefetch = prefetch
        self.obs = obs
        self._boundary = boundary_eval_ckpt
        self._rebuild = rebuild
        self.label = self.BASE_LABEL
        # current regime's wire segmentation (ISSUE 16) — see
        # TrainerChunkClient.wire_segments
        self.wire_segments = int(getattr(cfg, "wire_segments", 1) or 1)
        self._device_gen = cfg.token_gen == "device"
        self._pre_quarantine = {}  # worker -> schedule column before it

    @property
    def metric_names(self):
        return self.setup.metric_names

    def assemble(self, i, ranges):
        s0, k = ranges[i]
        with self.obs.tracer.span("gather", chunk_start=s0, k=k):
            if self._device_gen:
                # the program regenerates the batches in-graph: upload K
                # scalars
                toks = np.arange(s0, s0 + k, dtype=np.int32)
            else:
                toks = self.prefetch.get(
                    ranges[i],
                    ranges[i + 1] if i + 1 < len(ranges) else None)
            # numpy (uncommitted) so jit treats the schedules as replicated
            masks = np.asarray(self.adv[s0 : s0 + k])
            presents = (
                np.asarray(~self.straggle[s0 : s0 + k])
                if self.straggle is not None
                else None
            )
        return toks, masks, presents

    def program(self, state, chunk):
        toks, masks, presents = chunk
        return self.setup.train_token_many, (state, toks, masks, presents)

    def defer_extras(self, chunk, fetch_s, k):
        return None

    def should_log(self, step):
        return step % self.cfg.log_every == 0

    def beat_extras(self):
        # prefetch extras only when a prefetcher EXISTS: the device
        # token-gen mode has no host prefetch path, and reporting a
        # constant depth 0 there would read as starvation to the incident
        # engine (ISSUE 13); stats() is the supervision restart counter
        pf_extra = {}
        if self.prefetch is not None:
            pf_extra["prefetch_depth"] = self.prefetch.depth
            if hasattr(self.prefetch, "stats"):
                pf_extra.update(self.prefetch.stats())
        return pf_extra

    def boundary(self, end, state):
        self._boundary(end, state)

    def stop_requested(self, end):
        from draco_tpu.parallel.token_loop import _stop_requested

        return _stop_requested(self.obs, end)

    def snap_stop(self, end, state, already_saved):
        from draco_tpu.parallel.token_loop import _snap_stop

        _snap_stop(self.cfg, state, end, self.obs,
                   already_saved=already_saved)

    def cleanup(self):
        if self.prefetch is not None:
            self.prefetch.close()

    # ---- autopilot actuation (control/autopilot.py) ----------------------
    @property
    def can_swap(self):
        return self._rebuild is not None

    def build_setup(self, cfg):
        if self._rebuild is None:
            raise RuntimeError(
                "token route launched without a setup rebuild hook — "
                "autopilot family swaps unavailable on this route")
        return self._rebuild(cfg)

    def switch_regime(self, setup, label):
        self.setup = setup
        self.label = label

    def quarantine(self, worker, from_step):
        self._pre_quarantine[worker] = self.straggle[:, worker].copy()
        self.straggle[from_step:, worker] = True

    def readmit(self, worker, from_step):
        saved = self._pre_quarantine.pop(worker, None)
        if saved is None:
            self.straggle[from_step:, worker] = False
        else:
            self.straggle[from_step:, worker] = \
                saved[from_step:len(self.straggle)]
