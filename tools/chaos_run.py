#!/usr/bin/env python
"""Chaos harness: drive the deterministic fault × loop matrix and commit
``baselines_out/chaos_matrix.json``.

Every fault class the resilience layer (draco_tpu/resilience, ISSUE 6)
claims to handle is injected into real production-loop runs — the coded-DP
CNN Trainer and two TransformerLM routes (single-shard fold + GSPMD tp),
eager (K=1) and scan-chunked (K=4) — and the outcome is CLASSIFIED, not
eyeballed:

  masked              final params bitwise-equal to the fault-free run of
                      the same loop (supervision/vote absorbed the fault)
  guarded             run completed with guard_trips > 0 and finite final
                      params (the in-graph guard skipped the poisoned
                      update; bounded degradation, training continued)
  preempted_resumed   SIGTERM produced the "preempted" terminal heartbeat
                      state + a resumable boundary checkpoint, and resuming
                      from it reproduced the fault-free final params
                      bitwise (the elasticity round trip)
  recovered_walkback  a corrupt/truncated newest checkpoint raised the
                      named CheckpointCorruptError on direct load, and the
                      checkpoint_step=-1 walk-back resume retrained from
                      the previous good one to the bitwise fault-free state
  degraded_bounded    approx-family straggle cells (ISSUE 8): the run
                      completed finite with zero guard trips, the victim
                      really stayed absent (and was never accused —
                      absence is an erasure, not evidence), and every
                      step's measured decode_residual sat under its
                      analytic decode_residual_bound — the bounded,
                      measurable degradation the family trades exactness
                      for
  degraded_error      a NAMED error propagated and the terminal heartbeat
                      says "crashed" with a cause (graceful: diagnosable,
                      no hang, no raw traceback class)
  FAILED              anything else — an unnamed error, a wrong terminal
                      state, a divergent resume, or (worker-targeted
                      faults) an unattributed survival. ``all_ok`` goes
                      false.

Worker-targeted faults (``nan_grad:w<k>``, ``over_budget``) additionally
must ATTRIBUTE: the per-worker forensics columns (obs/forensics.py, ISSUE
7) at the fault step have to accuse every injected worker — the cell
records ``injected`` / ``accused`` / ``attributed`` and an unattributed
survival is a FAILED cell, because "the guard saved the run but nobody
knows whose fault it was" is exactly the observability gap this layer
closes.

Every cell also runs the incident engine (``incident_watch="on"``,
obs/incidents.py, ISSUE 13) and carries an ``incident`` verdict: the
injected fault class must raise EXACTLY the expected incident type(s) —
nan_grad the attributed ``nonfinite`` incident, over_budget the attributed
``guard`` incident, prefetch faults ``starvation`` where the supervision
restart is observable — and fault classes the resilience layer absorbs
with clean telemetry (straggle inside budget, sigterm, checkpoint
corruption) must raise NONE. An unraised, mis-typed, mis-attributed, or
spurious incident is a FAILED cell.

``tools/perf_watch.py`` folds the committed matrix, so a fault class
silently flipping from masked/guarded to FAILED — or an ``attributed`` /
``incident.ok`` flag flipping false — gates nonzero.

Usage (CPU, ~10 min):
  python tools/chaos_run.py --cpu-mesh 8
  python tools/chaos_run.py --cpu-mesh 8 --loops cnn_k4 --faults nan_grad
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from draco_tpu.cli import maybe_force_cpu_mesh  # noqa: E402

FAULTS = ("nan_grad", "over_budget", "prefetch_crash", "prefetch_hang",
          "sigterm", "ckpt_corrupt", "ckpt_truncate", "straggle",
          "adversary", "drift_grad", "subtree_straggle")
# the autopilot REAL-wire cell (ISSUE 15): an int8-wire run under the
# declarative drift_grad window must raise the numerics_drift incident AND
# the autopilot must actuate — a `wire_widen` remediation moving the wire
# dtype one f32-ward step as a warm program swap, recorded + attributed in
# incidents.jsonl. Only the dedicated ap_wire loop runs it.
WIRE_FAULTS = ("drift_grad",)
# drift window end: covers the second chunk so the widened regime actually
# dispatches (boundaries at 4/8/12; the episode opens ~step 7, the widen
# fires at boundary 8, chunk 9-12 runs on the widened wire)
WIRE_MAX_STEPS = 12
# the declarative within-budget adversary episode (faults.apply_adversary)
# runs on the dedicated random-attack loops: cfg.err_mode="random" (the
# seeded random-gradient attack, ISSUE 14 satellite — a reference TODO
# until now), base adversary_count=0 so the event's worker is the ONLY
# live adversary. Expected outcome: the cyclic decode detects, attributes
# AND excises the attack (detection P/R 1.0 at the fault step, named
# worker accused, zero guard trips) — `attributed_excised`.
RAND_FAULTS = ("adversary",)
# eager loops have no chunk prefetcher thread and ckpt rows ride the
# chunked regime; the in-graph + signal faults cover both regimes
EAGER_FAULTS = ("nan_grad", "over_budget", "sigterm")
# the approx code family's cells (ISSUE 8): straggle is ITS fault model
# (a sustained drop is a scheduled erasure the decode absorbs boundedly —
# the expected outcome is degraded_bounded, not masked/guarded); nan_grad
# must still be guarded + attributed, sigterm must still round-trip. The
# exact-code loops skip straggle — their budget arithmetic already has
# dedicated cells (the over_budget class) and a sustained drop on top of
# the live adversary would just re-test the same locator failure.
APPROX_FAULTS = ("straggle", "nan_grad", "sigterm")
STRAGGLE_WORKER = 3  # the named straggle victim (absent ≠ accused target)
# the segmented-wire loops (ISSUE 16): the same production loops with the
# wire split into S=2 segments and the live-adversary budget released
# (adversary_count=0), so the cell's fault is the only one in play.
# `sigterm` lands between chunk dispatches of the SEGMENTED regime and
# must round-trip through the existing preemption/resume machinery
# bitwise against the loop's own S=2 clean run (`preempted_resumed`).
# `straggle` runs on the vote-family segmented loop (mv_seg2), where a
# mid-stream drop is bitwise-MASKED — the vote picks among bitwise-equal
# replicas, so segmenting the wire must leave the clean-run equality
# intact. The cyclic segmented loops skip straggle here: per-segment
# recombination legitimately rounds differently from S=1 once the honest
# support shifts, so their straggle/adversary equivalence is the
# tolerance-based pin in tests/test_segments.py, not a bitwise chaos cell.
SEG_FAULTS = ("straggle", "sigterm")
# the tree-topology loops (ISSUE 17): sigterm lands BETWEEN chunk
# dispatches of the hierarchical regime and must round-trip through the
# existing preemption/resume machinery bitwise against the loop's own tree
# clean run (`preempted_resumed` — the level structure lives inside the
# jitted program, so a boundary checkpoint is level-consistent by
# construction). `subtree_straggle` drops an ENTIRE leaf group (the
# worst-case-one-group shape the per-group budget is sized for) on the
# approx tree loop: the group's partial recovers nothing, the root
# residual must still sit under the Cauchy-Schwarz-folded bound every
# step, and NO member of the victim group is ever accused — absence is an
# erasure, not evidence, even when a whole subtree goes dark.
TREE_FAULTS = ("sigterm", "subtree_straggle")
SUBTREE_WORKERS = (4, 5, 6, 7)  # the whole second leaf group at g=4, n=8

FAULT_STEP = 5  # mid-run, between the two eval/ckpt boundaries (4 and 8)
# sigterm lands ON the first chunk boundary so the K=4 loops stop with
# half the run still ahead (a step strictly inside (4, 8) would only be
# honored at the final chunk's end — a degenerate "preemption" at step 8)
SIGTERM_STEP = 4
MAX_STEPS = 8
EVAL_FREQ = 4
NUM_WORKERS = 8
# worker-targeted in-graph faults name their victim explicitly so the cell
# can assert the forensics columns (obs/forensics.py) attribute the fault
# to exactly this worker; faults that attribute are checked against the
# run's own metrics.jsonl at the fault step (ISSUE 7)
NAN_WORKER = 3
ATTRIBUTED_FAULTS = ("nan_grad", "over_budget", "adversary")


def _base_cfg_kw():
    return dict(
        approach="cyclic", worker_fail=1, redundancy="shared",
        batch_size=4, num_workers=NUM_WORKERS, max_steps=MAX_STEPS,
        eval_freq=EVAL_FREQ, log_every=1, lr=0.05, compress_ckpt=True,
        step_guard="on", prefetch_timeout_s=2.0, prefetch_restarts=2,
        # numerics observatory on in EVERY cell (obs/numerics.py, ISSUE
        # 10): the columns must stay finite-sentineled under each fault
        # class — the nan_grad cells assert it (_numerics_verdict)
        numerics_watch="on",
        # incident engine on in EVERY cell (obs/incidents.py, ISSUE 13):
        # each fault class must raise exactly its expected incident type
        # with the right worker attribution (_incident_verdict)
        incident_watch="on",
    )


def _loops():
    """loop name -> (make_cfg(**kw), run(cfg, steps=None) -> params_vec)."""
    import functools

    import jax
    import numpy as np

    from draco_tpu.config import TrainConfig
    from draco_tpu.data.datasets import load_dataset

    # a cell is up to three fresh Trainers and a loop a dozen: the (seeded,
    # read-only) dataset is made once a loop, not once a Trainer
    dataset = functools.lru_cache(maxsize=None)(load_dataset)

    def pv(state):
        return np.concatenate([
            np.ravel(x) for x in jax.tree.leaves(jax.device_get(state.params))
        ])

    def cnn_cfg(**kw):
        base = dict(_base_cfg_kw(), network="FC", dataset="synthetic-mnist")
        base.update(kw)
        return TrainConfig(**base)

    def cnn_run(cfg, steps=None):
        # Trainer.run's max_steps is ABSOLUTE; the matrix passes a step
        # COUNT (the LM routes' convention), so resume runs translate via
        # the restored cursor
        from draco_tpu.training.trainer import Trainer

        t = Trainer(cfg, quiet=True,
                    dataset=dataset(cfg.dataset, cfg.data_dir))
        try:
            t.run(max_steps=None if steps is None
                  else t._start_step - 1 + steps)
        finally:
            t.close()
        return pv(t.state)

    def lm_cfg(**kw):
        base = dict(_base_cfg_kw(), network="TransformerLM",
                    dataset="synthetic-text", seq_len=16, vocab=32,
                    model_dim=32, model_heads=2, model_layers=1)
        base.update(kw)
        return TrainConfig(**base)

    def lm_fold_run(cfg, steps=None):
        from draco_tpu.parallel import make_mesh_2d
        from draco_tpu.parallel.sp_step import train_sp

        state, _ = train_sp(cfg, make_mesh_2d(cfg.num_workers, 1),
                            steps=steps, quiet=True)
        return pv(state)

    def lm_tp_run(cfg, steps=None):
        from draco_tpu.parallel.mesh import make_mesh_wtp
        from draco_tpu.parallel.tp_step import train_tp

        state, _ = train_tp(cfg, make_mesh_wtp(4, 2), steps=steps,
                            quiet=True)
        return pv(state)

    def with_k(cfg_fn, k, **fixed):
        return lambda **kw: cfg_fn(steps_per_call=k, **fixed, **kw)

    # the segmented-wire loops (ISSUE 16): wire_segments rides as a
    # DEFAULT so the straggle cell can rebuild the same loop at S=1 for
    # its bitwise segment-invariance reference
    def with_seg(cfg_fn, k, **fixed):
        def make(**kw):
            kw.setdefault("wire_segments", 2)
            return cfg_fn(steps_per_call=k, **fixed, **kw)
        return make

    # the tree-topology loops (ISSUE 17): topology/fanout ride as DEFAULTS
    # so resume runs rebuild the identical hierarchical program
    def with_tree(cfg_fn, k, **fixed):
        def make(**kw):
            kw.setdefault("topology", "tree")
            kw.setdefault("tree_fanout", 4)
            return cfg_fn(steps_per_call=k, **fixed, **kw)
        return make

    # the approx family rejects live adversaries (config.validate: no
    # Byzantine certificate), so its cells run worker_fail=0 with the
    # ISSUE 8 design point r=1.5 / α=0.25 on the same FC loop
    approx_kw = dict(approach="approx", worker_fail=0,
                     redundancy="shared", code_redundancy=1.5,
                     straggler_alpha=0.25)

    # the random-attack loops (ISSUE 14 satellite): err_mode="random" with
    # the code budget reserved (adversary_count=0), so the `adversary`
    # fault event's worker is the only live adversary and the clean run
    # trains attack-free
    rand_kw = dict(err_mode="random", adversary_count=0)

    # the autopilot wire-dial loop (ISSUE 15): a REAL int8 wire with the
    # policy engine live — drift_grad must widen it (WIRE_FAULTS).
    # adversary_count=0 isolates the drift: the cell's surface is the
    # numerics_drift → wire_widen chain, not the (separately-celled)
    # Byzantine detection path — a live adversary would legitimately
    # collapse trust and blur the incident contract
    ap_wire_kw = dict(wire_dtype="int8", autopilot="on",
                      adversary_count=0, max_steps=WIRE_MAX_STEPS)

    return {
        "cnn_k1": (with_k(cnn_cfg, 1), cnn_run),
        "cnn_k4": (with_k(cnn_cfg, 4), cnn_run),
        "lm_k1": (with_k(lm_cfg, 1), lm_fold_run),
        "lm_k4": (with_k(lm_cfg, 4), lm_fold_run),
        "lm_tp_k4": (with_k(lm_cfg, 4, tensor_shards=2), lm_tp_run),
        "approx_k1": (with_k(cnn_cfg, 1, **approx_kw), cnn_run),
        "approx_k4": (with_k(cnn_cfg, 4, **approx_kw), cnn_run),
        "cnn_rand_k1": (with_k(cnn_cfg, 1, **rand_kw), cnn_run),
        "cnn_rand_k4": (with_k(cnn_cfg, 4, **rand_kw), cnn_run),
        "ap_wire_k4": (with_k(cnn_cfg, 4, **ap_wire_kw), cnn_run),
        # the segmented-wire loops (ISSUE 16): adversary_count=0 releases
        # the code budget so the cell's injected fault is the only one in
        # play; mv_seg2 is the vote family (group replication), where the
        # straggle drop must stay bitwise-masked under the segmented wire
        "cnn_seg2_k4": (with_seg(cnn_cfg, 4, adversary_count=0), cnn_run),
        "lm_seg2_k4": (with_seg(lm_cfg, 4, adversary_count=0), lm_fold_run),
        "mv_seg2_k4": (with_seg(cnn_cfg, 4, approach="maj_vote",
                                group_size=4, adversary_count=0), cnn_run),
        # the tree-topology loops (ISSUE 17): adversary_count=0 (the g=4
        # per-group budget s_g = min(1, 0) carries no live adversary — the
        # detection-parity pin lives in tests/test_tree.py at g=8);
        # approx_tree runs the whole-leaf-group drop at the α=0.5 design
        # point that covers it
        "cnn_tree_k4": (with_tree(cnn_cfg, 4, adversary_count=0), cnn_run),
        "approx_tree_k4": (with_tree(cnn_cfg, 4, approach="approx",
                                     worker_fail=0, redundancy="shared",
                                     code_redundancy=2.0,
                                     assignment_scheme="pairwise",
                                     straggler_alpha=0.5), cnn_run),
    }


def _status(train_dir):
    try:
        with open(os.path.join(train_dir, "status.json")) as fh:
            status = json.load(fh)
    except Exception:
        return {}
    # versioned payloads must satisfy the central schema contract table
    # (obs/heartbeat.check_status_schema); pre-versioning files carry no
    # field (tolerated). An unknown schema means the harness and the loops
    # disagree on the payload shape, and folding it silently would
    # misclassify every cell
    from draco_tpu.obs.heartbeat import check_status_schema

    return check_status_schema(status, f"{train_dir}/status.json",
                               "tools/chaos_run.py")


def _accusation(train_dir, fault, step):
    """(injected, accused, attributed) at the fault step, from the run's
    own metrics.jsonl forensics columns (obs/forensics.py; log_every=1, so
    every step's record is on disk). ``injected``: the worker(s) the fault
    plan targeted — the named :w victim for nan_grad, the over-budget
    step's live adversary row (packed in-graph as the seeded ground truth)
    for over_budget. ``attributed``: every injected worker is in the
    step's accused set."""
    from draco_tpu.obs import replay
    from draco_tpu.obs.forensics import record_masks

    rec = replay.record_at_step(os.path.join(train_dir, "metrics.jsonl"),
                                step)
    masks = record_masks(rec, NUM_WORKERS) if rec else None
    if masks is None:
        return None, None, False
    accused = sorted(i for i, b in enumerate(masks["accused"]) if b)
    if fault == "nan_grad":
        injected = [NAN_WORKER]
    else:  # over_budget: the mutated schedule row IS the injected set
        injected = sorted(i for i, b in enumerate(masks["adv"]) if b)
    attributed = bool(injected) and set(injected) <= set(accused)
    return injected, accused, attributed


def _straggle_verdict(train_dir, workers, step):
    """The approx straggle cell's bounded-degradation evidence, from the
    run's own metrics.jsonl (log_every=1): ``dropped`` — every victim's
    present bit is off on every record from the fault step on (the
    sustained drop really landed); ``bounded`` — every train record's
    measured decode_residual sits under its analytic
    decode_residual_bound (the ISSUE 8 certificate; under topology="tree"
    the bound is the Cauchy-Schwarz fold across groups and must hold even
    with a whole leaf group dark); ``never_accused`` — no scheduled
    straggler's accused bit ever fires (absence is an erasure, not
    evidence; obs/forensics). ``workers``: the victim set — one worker for
    the classic cell, a whole leaf group for subtree_straggle."""
    from draco_tpu.obs import replay
    from draco_tpu.obs.forensics import record_masks

    workers = list(workers)
    recs = replay.train_records(os.path.join(train_dir, "metrics.jsonl"))
    if not recs:
        return {"dropped": False, "bounded": False, "never_accused": False}
    dropped = bounded = never_accused = True
    for r in recs:
        masks = record_masks(r, NUM_WORKERS)
        if masks is None:
            dropped = bounded = never_accused = False
            break
        if r.get("step", 0) >= step \
                and any(masks["present"][w] for w in workers):
            dropped = False
        if any(masks["accused"][w] for w in workers):
            never_accused = False
        if not (r.get("decode_residual", float("nan"))
                <= r.get("decode_residual_bound", float("-inf")) + 1e-5):
            bounded = False
    return {"dropped": dropped, "bounded": bounded,
            "never_accused": never_accused}


def _numerics_verdict(train_dir, step):
    """ISSUE 10 NaN-safety at the fault step: the numerics columns carry
    FINITE sentinel values (stats are computed over the finite elements
    only — the fault's signature is the nonfinite fraction going loud,
    never a NaN column), and no scalar column of the record is NaN/Inf —
    i.e. an injected non-finite gradient does not poison the metric
    block. Returns {numerics_finite, fault_visible}."""
    import math

    from draco_tpu.obs import replay

    rec = replay.record_at_step(os.path.join(train_dir, "metrics.jsonl"),
                                step)
    if rec is None or "nx_grad_nonfinite" not in rec:
        return {"numerics_finite": False, "fault_visible": False}
    # the observatory columns + the training metrics must be finite; the
    # decode-health residual is deliberately NOT in this set — a NaN
    # decode_residual at the fault step IS the guard's loud signal
    # (resilience/guards.py), not poisoning
    finite = all(
        math.isfinite(float(v)) for k, v in rec.items()
        if isinstance(v, (int, float))
        and (k.startswith("nx_") or k.startswith("shadow_")
             or k in ("loss", "prec1")))
    return {"numerics_finite": bool(finite),
            "fault_visible": bool(rec["nx_grad_nonfinite"] > 0.0)}


def _expected_incidents(loop, fault):
    """The cell's incident contract (obs/incidents.py, ISSUE 13):
    ``required`` = [(type, attribution)] that MUST be raised — attribution
    is a worker list, "injected" (the cell's injected set must be a subset
    of the incident's workers), or None (no attribution expected);
    ``allowed`` = extra types tolerated alongside. Any raised type outside
    required ∪ allowed is a spurious incident and FAILS the cell."""
    if fault == "nan_grad":
        # the non-finite ingest incident, attributed to the named victim;
        # the guard trip + loud residual + a trust dip ride along
        return ([("nonfinite", [NAN_WORKER])],
                {"guard", "decode_residual", "trust"})
    if fault == "over_budget":
        # the guard skips the poisoned update: the incident must name (at
        # least) every injected adversary; the loud residual rides along
        return ([("guard", "injected")],
                {"decode_residual", "nonfinite", "trust"})
    if fault == "prefetch_crash":
        # supervised restart (resilience/supervisor.py) surfaces at the
        # next beat as the starvation incident — no worker to name
        return [("starvation", None)], set()
    if fault == "prefetch_hang":
        # the LM token prefetcher stalls (PrefetchStallError → restart);
        # the CNN chunk gather pays the sleep inline on the main thread,
        # so there is no restart and nothing to detect
        if loop.startswith("lm"):
            return [("starvation", None)], set()
        return [], {"starvation", "throughput"}
    if fault == "straggle":
        # a SUSTAINED drop (the spot-instance shape): the straggle
        # detector (ISSUE 14 — the autopilot's dial-down evidence) must
        # fire once the victim's absence streak crosses its threshold,
        # attributed to the named victim; the decode itself stays clean
        return [("straggle", [STRAGGLE_WORKER])], set()
    if fault == "subtree_straggle":
        # an ENTIRE leaf group drops (ISSUE 17): the detector must fire
        # naming every member of the dark subtree — and nobody else
        return [("straggle", list(SUBTREE_WORKERS))], set()
    if fault == "adversary":
        # a single within-budget attack step: detected, attributed and
        # excised by the decode — one accusation cannot collapse EW trust
        # (the hysteresis), so NO incident may open
        return [], set()
    if fault == "drift_grad":
        # the declarative drift window must raise numerics_drift (no
        # worker to name — the whole wire drifts); the regime swap's
        # compile pause may dent a beat (throughput tolerated)
        return [("numerics_drift", None)], {"throughput"}
    # sigterm (graceful preemption), ckpt_* (offline recovery): the
    # resilience layer absorbs these with clean telemetry, and a spurious
    # incident is exactly the flapping the hysteresis exists to prevent
    return [], set()


def _incident_verdict(train_dir, loop, fault, injected=None):
    """Diff the cell's incidents.jsonl onsets against the contract. The
    ledger is torn/empty/missing tolerated (obs/replay) — an expected
    incident that never made it to disk is exactly a FAILED verdict."""
    from draco_tpu.obs import replay

    onsets = [e for e in replay.iter_jsonl(
        os.path.join(train_dir, "incidents.jsonl"))
        if e.get("event") == "onset" and e.get("type")]
    raised = sorted({e["type"] for e in onsets})
    required, allowed = _expected_incidents(loop, fault)
    ok, details = True, []
    for typ, attr in required:
        ons = [e for e in onsets if e["type"] == typ]
        if not ons:
            ok = False
            details.append(f"expected incident {typ!r} not raised")
            continue
        if attr is not None:
            want = set(injected or []) if attr == "injected" else set(attr)
            got = set()
            for e in ons:
                got |= set(e.get("workers") or [])
            if not want or not want <= got:
                ok = False
                details.append(f"{typ} attributed {sorted(got)}, expected "
                               f"superset of {sorted(want)}")
    unexpected = set(raised) - {t for t, _ in required} - allowed
    if unexpected:
        ok = False
        details.append(f"spurious incident(s): {sorted(unexpected)}")
    verdict = {"ok": ok, "raised": raised,
               "required": [t for t, _ in required]}
    if details:
        verdict["detail"] = "; ".join(details)
    return verdict


def _attempt(run, cfg, steps=None):
    """(params_vec | None, error | None) — a run either finishes or raises."""
    try:
        return run(cfg, steps), None
    except Exception as e:  # noqa: BLE001 — classification IS the point
        return None, e


NAMED_ERRORS = ("InjectedFaultError", "PrefetchStallError",
                "CheckpointCorruptError")


def run_case(loop: str, fault: str, make_cfg, run, clean_vec, workdir):
    """Execute one (loop, fault) cell and classify the outcome."""
    import numpy as np

    from draco_tpu.utils import checkpoint as ckpt

    d = os.path.join(workdir, f"{loop}_{fault}")
    row = {"loop": loop, "fault": fault, "ok": False, "outcome": "FAILED"}
    # a REUSED --workdir must not let a previous invocation's onsets
    # satisfy (or violate) this run's incident contract — the verdict
    # folds every onset in the cell's incidents.jsonl
    try:
        os.remove(os.path.join(d, "incidents.jsonl"))
    except OSError:
        pass

    if fault in ("ckpt_corrupt", "ckpt_truncate"):
        # victim run (no injection during training), then corrupt the
        # NEWEST checkpoint and resume with walk-back
        vec, err = _attempt(run, make_cfg(train_dir=d))
        if err is not None:
            row["detail"] = f"victim run failed: {type(err).__name__}: {err}"
            return row
        newest = ckpt.available_steps(d)[-1]
        path = os.path.join(d, f"model_step_{newest}.dcg")
        with open(path, "rb") as fh:
            raw = bytearray(fh.read())
        if fault == "ckpt_corrupt":
            raw[len(raw) // 2] ^= 0xFF
            with open(path, "wb") as fh:
                fh.write(bytes(raw))
        else:
            with open(path, "wb") as fh:
                fh.write(bytes(raw[: len(raw) // 2]))
        # the corrupt bytes must surface as the NAMED error, not
        # struct/zlib guts (resume itself auto-walks-back, so probe the
        # integrity check directly)
        try:
            ckpt.verify(d, newest)
            row["detail"] = "corrupt checkpoint verified clean"
            return row
        except ckpt.CheckpointCorruptError as e:
            row["named_error"] = f"{type(e).__name__}"
            row["error_detail"] = str(e)[:200]
        except Exception as e:
            row["detail"] = (f"corrupt load raised unnamed "
                             f"{type(e).__name__}: {e}")
            return row
        # walk-back resume: -1 skips the corrupt newest, reloads the
        # previous good one, retrains to the end — must be bitwise clean
        prev_good = ckpt.available_steps(d)[-2]
        vec2, err2 = _attempt(run, make_cfg(train_dir=d, checkpoint_step=-1),
                              steps=MAX_STEPS - prev_good)
        if err2 is not None:
            row["detail"] = f"walk-back resume failed: {err2}"
            return row
        row["walked_back_to"] = prev_good
        row["resume_bitwise_equal"] = bool(np.array_equal(clean_vec, vec2))
        if row["resume_bitwise_equal"]:
            row.update(ok=True, outcome="recovered_walkback")
        return row

    # injected-fault run. prefetch_hang duration: on the LM token loop the
    # sleep lands on the prefetch WORKER thread, so it must outlast the
    # queue-wait timeout (2 s) plus the device's chunk — 20 s forces the
    # stall + supervised-restart path; the CNN chunk gather computes its
    # indices on the main thread, where the sleep is an inline delay the
    # loop simply rides out (4 s keeps the matrix quick)
    step = SIGTERM_STEP if fault == "sigterm" else FAULT_STEP
    spec = f"{fault}@{step}"
    if fault == "subtree_straggle":
        # one sustained straggle event per member of the victim leaf group
        # (the fault grammar attributes per-event :w victims) — the whole
        # subtree goes dark at once
        spec = ",".join(f"straggle@{step}:w{w}" for w in SUBTREE_WORKERS)
    if fault == "drift_grad":
        # declarative window covering the rest of the run, so the widened
        # regime's chunk dispatches while the drift is still live
        spec = f"drift_grad@{step}-{WIRE_MAX_STEPS}"
    if fault == "nan_grad":
        spec += f":w{NAN_WORKER}"  # named victim — the attribution target
    if fault == "adversary":
        spec += f":w{NAN_WORKER}"  # named attacker — attribution target
    if fault == "straggle":
        # named victim, no :d — sustained to the end of the run (the
        # spot-instance shape the approx family exists for)
        spec += f":w{STRAGGLE_WORKER}"
    if fault == "prefetch_hang":
        spec += ":d20" if loop.startswith("lm") else ":d4"
    vec, err = _attempt(run, make_cfg(train_dir=d, fault_spec=spec))
    status = _status(d)
    row["terminal_state"] = status.get("state")
    guard = status.get("guard") or {}
    row["guard_trips"] = guard.get("trips", 0.0)
    if fault in ATTRIBUTED_FAULTS:
        # per-worker forensics must point at the injected worker(s) —
        # degrading boundedly is not enough, the ledger has to NAME them
        injected, accused, attributed = _accusation(d, fault, step)
        row["injected"] = injected
        row["accused"] = accused
        row["attributed"] = attributed
    if fault == "nan_grad":
        # ISSUE 10 NaN-safety pin: the numerics columns at the fault step
        # are finite sentinels and the injected non-finite gradient is
        # VISIBLE in the nonfinite-fraction column
        row.update(_numerics_verdict(d, step))

    if err is not None:
        name = type(err).__name__
        row["named_error"] = name
        row["error_detail"] = str(err)[:200]
        if name in NAMED_ERRORS and status.get("state") == "crashed":
            row.update(ok=True, outcome="degraded_error")
        else:
            row["detail"] = f"unnamed error {name} or wrong terminal state"
        return row

    if status.get("state") == "preempted":
        resumable = status.get("resumable_step")
        row["resumable_step"] = resumable
        if resumable is None:
            row["detail"] = "preempted without a resumable checkpoint"
            return row
        vec2, err2 = _attempt(run,
                              make_cfg(train_dir=d, checkpoint_step=resumable),
                              steps=MAX_STEPS - resumable)
        if err2 is not None:
            row["detail"] = f"resume failed: {err2}"
            return row
        row["resume_bitwise_equal"] = bool(np.array_equal(clean_vec, vec2))
        if row["resume_bitwise_equal"]:
            row.update(ok=True, outcome="preempted_resumed")
        return row

    # completed: masked (bitwise clean), guarded (skipped, finite), or —
    # straggle on the approx family — degraded_bounded (the decode
    # diverges from the fault-free run BY DESIGN, but every step's
    # measured residual sat under its analytic bound, the victim really
    # stayed absent, and absence was never accused)
    row["bitwise_equal_clean"] = bool(np.array_equal(clean_vec, vec))
    row["final_finite"] = bool(np.all(np.isfinite(vec)))
    if fault == "straggle" and "_seg" in loop:
        # the segmented-wire straggle cell (ISSUE 16, vote family): the
        # mid-stream drop must stay bitwise-MASKED with the wire split
        # into segments — the vote picks among bitwise-equal replicas, so
        # the S=2 run's final params land on the fault-free clean run of
        # the same loop; plus the victim really stayed absent and absence
        # was never accused (erasure, not evidence)
        from draco_tpu.obs import replay
        from draco_tpu.obs.forensics import record_masks

        recs = replay.train_records(os.path.join(d, "metrics.jsonl"))
        dropped = never_accused = bool(recs)
        for r in recs:
            masks = record_masks(r, NUM_WORKERS)
            if masks is None:
                dropped = never_accused = False
                break
            if (r.get("step", 0) >= step
                    and masks["present"][STRAGGLE_WORKER]):
                dropped = False
            if masks["accused"][STRAGGLE_WORKER]:
                never_accused = False
        row["dropped"] = dropped
        row["never_accused"] = never_accused
        if (row["final_finite"] and status.get("state") == "done"
                and row["guard_trips"] == 0 and dropped and never_accused
                and row["bitwise_equal_clean"]):
            row.update(ok=True, outcome="masked")
        else:
            row["detail"] = (f"segmented straggle not masked: "
                             f"bitwise={row['bitwise_equal_clean']} "
                             f"dropped={dropped} "
                             f"never_accused={never_accused} "
                             f"guard_trips={row['guard_trips']}")
        return row
    if fault in ("straggle", "subtree_straggle"):
        victims = (SUBTREE_WORKERS if fault == "subtree_straggle"
                   else [STRAGGLE_WORKER])
        verdict = _straggle_verdict(d, victims, step)
        row.update(verdict)
        if (row["final_finite"] and status.get("state") == "done"
                and row["guard_trips"] == 0 and all(verdict.values())):
            row.update(ok=True, outcome="degraded_bounded")
        else:
            row["detail"] = (f"{fault} cell not bounded-degraded: "
                             f"{verdict}")
        return row
    if fault == "drift_grad":
        # the autopilot wire-dial cell (ISSUE 15): the injected numerics
        # drift must be SEEN (numerics_drift incident — checked by the
        # incident contract) and ACTED ON — a `wire_widen` remediation in
        # incidents.jsonl moving the regime's wire dtype one f32-ward
        # step, attributed to the drift episode. The drift itself is
        # finite by construction, so the run must finish clean (no guard
        # trips — a guarded drift cell would mean the injection broke the
        # decode instead of the numerics).
        from draco_tpu.obs import replay

        rems = [e for e in replay.iter_jsonl(
            os.path.join(d, "incidents.jsonl"))
            if e.get("event") == "remediation"]
        widens = [r for r in rems if r.get("action") == "wire_widen"]
        row["remediations"] = [r.get("action") for r in rems]
        row["widened"] = bool(widens)
        row["widen_attributed"] = bool(widens) and all(
            (r.get("trigger") or {}).get("type")
            in ("numerics_drift", "decode_residual") for r in widens)
        row["wire_dtype_after"] = (
            ((widens[-1].get("regime") or {}).get("wire_dtype"))
            if widens else None)
        if (row["final_finite"] and status.get("state") == "done"
                and row["guard_trips"] == 0 and row["widened"]
                and row["widen_attributed"]):
            row.update(ok=True, outcome="wire_widened")
        else:
            row["detail"] = (f"drift cell not widened cleanly: "
                             f"widened={row['widened']} attributed="
                             f"{row['widen_attributed']} "
                             f"guard_trips={row['guard_trips']}")
        return row
    if fault == "adversary":
        # the random-attack cell (ISSUE 14 satellite): the seeded random
        # gradient must be DETECTED (in-graph detection columns score
        # P/R 1.0 at the fault step), ATTRIBUTED (checked above) and
        # EXCISED (decode exact → no guard trip, run finishes clean).
        # Bitwise equality with the clean run is NOT expected: locating
        # an error changes which honest rows the recombination solves
        # from (different f32 rounding), not the algebraic value.
        from draco_tpu.obs import replay

        rec = replay.record_at_step(os.path.join(d, "metrics.jsonl"),
                                    step)
        detected = bool(rec
                        and rec.get("det_adv") == 1
                        and rec.get("det_tp") == 1
                        and rec.get("located_errors") == 1)
        row["detected"] = detected
        if (row["final_finite"] and status.get("state") == "done"
                and row["guard_trips"] == 0 and detected
                and row["attributed"]):
            row.update(ok=True, outcome="attributed_excised")
        else:
            row["detail"] = (f"random attack not excised cleanly: "
                             f"detected={detected} "
                             f"attributed={row.get('attributed')} "
                             f"guard_trips={row['guard_trips']}")
        return row
    if row["bitwise_equal_clean"] and status.get("state") == "done":
        row.update(ok=True, outcome="masked")
    elif (row["guard_trips"] > 0 and row["final_finite"]
          and status.get("state") == "done"):
        row.update(ok=True, outcome="guarded")
    else:
        row["detail"] = ("completed but neither masked nor guarded "
                         "(silent divergence)")
    if row["ok"] and fault in ATTRIBUTED_FAULTS and not row["attributed"]:
        # survived the fault but could not NAME the culprit — that is a
        # forensics regression, not an ok cell
        row.update(ok=False, outcome="FAILED",
                   detail=f"fault survived but unattributed: injected "
                          f"{row['injected']} vs accused {row['accused']}")
    if row["ok"] and fault == "nan_grad" and not (
            row["numerics_finite"] and row["fault_visible"]):
        # survived the fault but the observatory either went NaN (block
        # poisoned) or failed to show the non-finite ingest — the ISSUE
        # 10 NaN-safety contract, not an ok cell
        row.update(ok=False, outcome="FAILED",
                   detail=f"numerics columns under nan_grad: finite="
                          f"{row['numerics_finite']} visible="
                          f"{row['fault_visible']}")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=str,
                    default=os.path.join("baselines_out",
                                         "chaos_matrix.json"))
    ap.add_argument("--loops", type=str, default="",
                    help="comma-separated loop subset (default: all)")
    ap.add_argument("--faults", type=str, default="",
                    help="comma-separated fault subset (default: all)")
    ap.add_argument("--workdir", type=str, default="",
                    help="train dirs land here (default: a temp dir)")
    ap.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                    help="force an N-device virtual CPU mesh")
    args = ap.parse_args(argv)
    # the shared bootstrap (compile cache + cpu mesh). The matrix classifies
    # outcomes by BITWISE final-state comparison; the donated-carry
    # corruption that once kept cached XLA:CPU executables out of it
    # (jax 0.4.x) does not reproduce on jax 0.9.0 — cold- and warm-cache
    # runs of the sigterm / ckpt_corrupt / nan_grad / prefetch_crash cells
    # classify identically (PERF.md, chip bring-up)
    if args.cpu_mesh:
        maybe_force_cpu_mesh(args)

    loops = _loops()
    pick_loops = [s for s in args.loops.split(",") if s] or list(loops)
    pick_faults = [s for s in args.faults.split(",") if s] or list(FAULTS)
    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_run_")

    rows = []
    for loop in pick_loops:
        make_cfg, run = loops[loop]
        eager = loop.endswith("_k1")
        if "_tree" in loop:
            # the tree-topology loops (ISSUE 17): sigterm round-trips on
            # both; the whole-leaf-group drop is the approx tree's cell
            # (its bounded certificate is what absorbs a dark subtree) —
            # checked FIRST so approx_tree does not fall into the flat
            # approx family's fault triple
            faults = [f for f in pick_faults if f in TREE_FAULTS
                      and (f != "subtree_straggle"
                           or loop.startswith("approx"))]
        elif loop.startswith("approx"):
            # both regimes run the family's own fault triple (ISSUE 8)
            faults = [f for f in pick_faults if f in APPROX_FAULTS]
        elif loop.startswith("cnn_rand"):
            # the random-attack loops run exactly the adversary episode
            faults = [f for f in pick_faults if f in RAND_FAULTS]
        elif loop.startswith("ap_wire"):
            # the autopilot wire-dial loop runs exactly the drift episode
            faults = [f for f in pick_faults if f in WIRE_FAULTS]
        elif "_seg" in loop:
            # the segmented-wire loops run the ISSUE 16 pair; straggle is
            # the vote loop's cell (bitwise-masked there — see SEG_FAULTS)
            faults = [f for f in pick_faults if f in SEG_FAULTS
                      and (f != "straggle" or loop.startswith("mv_"))]
        else:
            faults = [f for f in pick_faults
                      if f not in ("straggle", "subtree_straggle")
                      + RAND_FAULTS + WIRE_FAULTS
                      and not (eager and f not in EAGER_FAULTS)]
        if not faults:
            continue
        clean_dir = os.path.join(workdir, f"{loop}_clean")
        clean_vec, err = _attempt(run, make_cfg(train_dir=clean_dir))
        if err is not None:
            raise SystemExit(f"chaos_run: clean {loop} run failed: {err}")
        for fault in faults:
            row = run_case(loop, fault, make_cfg, run, clean_vec, workdir)
            # incident contract (ISSUE 13): exactly the expected incident
            # type(s), correctly attributed, nothing spurious — checked on
            # the cell's own incidents.jsonl (resume runs append to it)
            verdict = _incident_verdict(
                os.path.join(workdir, f"{loop}_{fault}"), loop, fault,
                row.get("injected"))
            row["incident"] = verdict
            if row["ok"] and not verdict["ok"]:
                row.update(ok=False, outcome="FAILED",
                           detail=f"incident verdict: "
                                  f"{verdict.get('detail', '?')}")
            rows.append(row)
            inc = "+".join(verdict["raised"]) or "-"
            print(f"chaos_run: {loop:9s} {fault:15s} -> "
                  f"{row['outcome']:18s} incidents: {inc}"
                  f"{'' if row['ok'] else '  ** FAILED'}",
                  flush=True)

    by_fault = {}
    for row in rows:
        by_fault.setdefault(row["fault"], []).append(row["ok"])
    summary = {f: {"cells": len(oks), "ok": all(oks)}
               for f, oks in sorted(by_fault.items())}
    payload = {
        "schema": 1,
        "tool": "tools/chaos_run.py",
        "fault_step": FAULT_STEP,
        "max_steps": MAX_STEPS,
        "rows": rows,
        "fault_classes": summary,
        "all_ok": all(r["ok"] for r in rows) and bool(rows),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print(f"chaos_run: {sum(r['ok'] for r in rows)}/{len(rows)} cells ok "
          f"-> {args.out}")
    if not args.workdir:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0 if payload["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
