"""Run heartbeat: ``train_dir/status.json`` rewritten at flush boundaries.

Long chip jobs run for hours with the host dark between flushes; the only
way to watch one today is to tail stdout or poll metrics.jsonl (which the
buffered MetricWriter now also only touches at flush boundaries). The
heartbeat is the external-monitoring contract instead: a single small JSON
file, atomically replaced (tmp + rename) at every flush boundary, holding
everything a dashboard or a watchdog needs —

  step / total_steps / steps_per_s / eta_s   progress and rate
  loss (+ prec1 when the route emits it)     last materialized train record
  decode_health                              cumulative detection
                                             precision/recall vs the seeded
                                             adversary schedule, last decode
                                             residual / vote agreement
  prefetch_depth                             in-flight prefetch requests
  ahead_share                                share of the run's steps that
                                             the eager loop dispatched
                                             before their predecessor was
                                             waited for (the records'
                                             ``ahead`` column)
  setup                                      what came before the first
                                             step, as of the first beat:
                                             the process's set-up ledger
                                             (obs/tracer.py) summed by span
                                             name, in seconds, and the
                                             persistent compile cache's
                                             hits and misses
  updated_at                                 wall-clock of the last beat

The decode-health precision/recall is computed HERE, on the host, from the
per-step in-graph columns (det_tp / det_adv / located_errors /
det_flagged) that ride the (K, m) metric block — the device never runs a
callback and the host never does an extra fetch: :meth:`observe` is wired
as the DeferredMetricWriter observer, so it sees exactly the records the
flush materializes anyway.

A stale ``updated_at`` is itself the signal: a watchdog that sees no beat
for a few flush periods knows the run is wedged without attaching to it.

Terminal states (ISSUE 6): every beat carries ``state: "running"``; the
loops end the file's life with :meth:`terminal` — ``"done"`` on normal
completion, ``"preempted"`` (plus ``resumable_step``) when a
SIGTERM/SIGINT graceful stop snapped a boundary checkpoint, ``"crashed"``
(plus a one-line ``cause``) when an unhandled exception escapes — so
``tools/trace_report.py`` and operators can distinguish the three without
parsing a traceback.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

from draco_tpu.obs.compile_watch import global_stats
from draco_tpu.obs.forensics import AccusationLedger
from draco_tpu.obs.tracer import setup_totals

# status.json payload schema version. The payload grew organically across
# PRs 4-6 with no versioning; consumers (tools/trace_report.py,
# tools/chaos_run.py) tolerate files with no ``schema`` field (pre-version
# runs) and assert it when present. Bump when a field changes meaning or
# moves — additive fields do not need a bump.
#   2: first versioned schema (adds ``schema`` itself, the ``forensics``
#      block, and ``num_workers``). The ``device`` block (ISSUE 9 — last
#      profiled window's phase fractions / decode share) is ADDITIVE under
#      schema 2: consumers tolerate it missing, assert it when present.
#   3: the numerics observatory (ISSUE 10): a static ``wire`` block (the
#      logical worker→aggregator bytes ledger, obs/numerics.wire_ledger,
#      set once per run via :meth:`RunHeartbeat.set_wire` — BOTH
#      production loops stamp it on every run, watch or not, since the
#      ledger is derived from shapes alone) and a folded ``numerics``
#      block (last dynamic-range values + worst-case underflow/overflow
#      fractions + shadow-wire error/agreement extremes +
#      ``shadow_sentinel_steps``, the count of fault-poisoned shadow
#      comparisons), which appears only on watch-enabled runs. Consumers
#      tolerate either block missing, assert shape when present.
#   4: the incident engine (ISSUE 13): an ``incidents`` block (open
#      episodes, per-type totals, last onset — obs/incidents.py) on
#      watch-enabled runs (``cfg.incident_watch="on"``), carried by the
#      terminal crash/preempted write too.
#   5: run identity (ISSUE 19): a ``run_id`` (stable per train_dir —
#      re-read from the dir's existing status.json on construction so a
#      resumed run keeps the id its first attempt minted) and an optional
#      operator-facing ``job_name`` (cfg.job_name). Consumers tolerate
#      both missing (pre-fleet runs); the fleet registry
#      (obs/fleet.RunRegistry) uses run_id to fold a resumed run's
#      attempts as ONE run.
STATUS_SCHEMA = 5

# The ONE schema contract table (ISSUE 13 satellite): optional status.json
# block name -> the schema version that introduced it. Every jax-free
# consumer (tools/trace_report.py, tools/incident_report.py,
# tools/forensics_report.py, tools/chaos_run.py, tools/check_artifacts.py)
# validates against THIS table via :func:`check_status_schema` instead of
# carrying its own accepted-set literal, so a schema bump cannot silently
# strand a tool.
STATUS_BLOCKS = {
    "decode_health": 2, "guard": 2, "forensics": 2, "device": 2,
    "wire": 3, "numerics": 3,
    "incidents": 4,
    # the autopilot's ``control`` block (control/autopilot.py — current
    # regime, swaps, quarantined workers, last remediation) is ADDITIVE
    # under schema 4: consumers tolerate it missing, assert when present
    "control": 4,
    # run identity (ISSUE 19): both optional-on-read — every consumer
    # tolerates their absence (pre-fleet files), asserts placement via
    # this table when present
    "run_id": 5, "job_name": 5,
    # the set-up ledger as of the run's first beat (``setup_block``):
    # ADDITIVE under schema 5
    "setup": 5,
    # the eager loop's run-ahead share (the records' ``ahead`` column):
    # ADDITIVE under schema 5
    "ahead_share": 5,
}
KNOWN_STATUS_SCHEMAS = tuple(range(2, STATUS_SCHEMA + 1))


def check_status_schema(status: dict, path: str = "status.json",
                        tool: str = "this tool") -> dict:
    """Validate a loaded status.json payload against the central contract:
    a ``schema`` field, when present, must be a version this tree knows
    (pre-versioning files carry none and are accepted), and no optional
    block may appear under a schema older than the one that introduced it.
    Raises SystemExit naming the mismatch — silently folding an unknown
    payload shape would misreport the run. Returns ``status`` unchanged."""
    if not isinstance(status, dict):
        return status
    schema = status.get("schema")
    if schema is not None and schema not in KNOWN_STATUS_SCHEMAS:
        raise SystemExit(
            f"{path}: status.json schema {schema!r} not in known "
            f"{KNOWN_STATUS_SCHEMAS} — update {tool} alongside "
            f"obs/heartbeat.STATUS_SCHEMA")
    if schema is not None:
        for block, introduced in STATUS_BLOCKS.items():
            if block in status and schema < introduced:
                raise SystemExit(
                    f"{path}: block {block!r} requires status schema >= "
                    f"{introduced}, payload claims {schema} — a writer and "
                    f"obs/heartbeat.STATUS_BLOCKS disagree")
    return status

# per-step detection-count columns (in-graph, coding/cyclic.py +
# coding/repetition.py): tp = flagged ∧ adversarial ∧ present,
# adv = adversarial ∧ present, flagged = located_errors | det_flagged
_TP_KEY = "det_tp"
_ADV_KEY = "det_adv"
_FLAGGED_KEYS = ("located_errors", "det_flagged")
# last-value health fields copied verbatim from the newest record (the
# approx family's residual-vs-bound certificate rides the last three:
# parallel/common.APPROX_HEALTH_NAMES)
_LAST_KEYS = ("decode_residual", "vote_agree", "flagged_groups",
              "honest_located", "decode_residual_bound",
              "recovered_fraction")

# numerics-observatory fold (obs/numerics.py, ISSUE 10): last-value range
# stats, running maxima of the danger fractions and shadow errors, running
# minimum of the shadow flag agreement — the ``numerics`` status block
_NX_LAST = ("nx_grad_absmax", "nx_grad_rms", "nx_wire_absmax",
            "nx_wire_rms", "nx_agg_absmax", "nx_agg_rms")
_NX_MAX = ("nx_wire_uf_bf16", "nx_wire_uf_int8", "nx_wire_of_bf16",
           "nx_grad_nonfinite", "nx_wire_nonfinite", "shadow_err",
           "shadow_residual")
_NX_MIN = ("shadow_flag_agree",)


def setup_block() -> dict:
    """{set-up span name: seconds} — ``setup.model_init``, ``setup.state``,
    ``setup.schedules``, ``setup.step_build``, and ``compile`` /
    ``cache_load`` over every executable build so far — plus the persistent
    compile cache's ``cache_hits`` / ``cache_misses``: why the first step
    came when it did, for a run with no trace directory."""
    block = {name: round(s, 3) for name, s in setup_totals().items()}
    stats = global_stats()
    block["cache_hits"] = stats["cache_hits"]
    block["cache_misses"] = stats["cache_misses"]
    return block


class RunHeartbeat:
    """Accumulates per-step records (:meth:`observe`) and rewrites
    ``status.json`` on :meth:`beat`. Disabled (``train_dir`` falsy or not
    the metrics-emitting process) it is a cheap no-op — both methods
    return immediately."""

    def __init__(self, train_dir: Optional[str], enabled: bool = True,
                 num_workers: Optional[int] = None, incidents=None,
                 job_name: Optional[str] = None):
        self.path = (os.path.join(train_dir, "status.json")
                     if (train_dir and enabled) else None)
        if self.path:
            os.makedirs(train_dir, exist_ok=True)
        # run identity (ISSUE 19): stable per train_dir — a resume into
        # the same dir re-reads the id the first attempt minted (torn or
        # pre-fleet status files mint a fresh one); the fleet registry
        # folds attempts sharing an id as ONE run
        self.run_id = self._load_or_mint_run_id() if self.path else None
        self.job_name = str(job_name) if job_name else None
        self._t0 = time.perf_counter()
        self._first_step: Optional[int] = None
        self._tp = 0.0
        self._adv = 0.0
        self._flagged = 0.0
        self._guard_trips = 0.0
        self._skipped_steps = 0.0
        self._guard_seen = False  # any record carried guard columns
        self._ahead = self._ahead_n = 0.0  # the ``ahead`` column, summed
        self._last: dict = {}
        # numerics-observatory fold (ISSUE 10): the ``numerics`` status
        # block accumulated from the nx_*/shadow_* columns, plus the
        # static ``wire`` ledger the loops stamp once (set_wire)
        self._nx: dict = {}
        self._wire: Optional[dict] = None
        # last profiled window's device block (obs/device_attr.py, ISSUE 9)
        # — set by observe_device, wired as the profiler window's on_stop
        # hook; rides every subsequent beat
        self._device: Optional[dict] = None
        # autopilot ``control`` block (control/autopilot.py, set_control)
        self._control: Optional[dict] = None
        # newest record that actually carried detection columns — kept
        # separately from _last so a mixed-route train_dir (a trailing
        # record WITHOUT the optional health family, e.g. a baseline run
        # sharing the dir) cannot hide the cumulative health block
        self._last_health_rec: dict = {}
        self._last_payload: dict = {}
        self.beats = 0
        self._setup: Optional[dict] = None  # setup_block() at the first beat
        # per-worker accusation ledger (obs/forensics.py), fed by the same
        # observer hook; needs the worker count to unpack the bitmask
        # columns — loops pass cfg.num_workers, bare constructions skip
        # forensics entirely
        self.ledger = (AccusationLedger(num_workers)
                       if (self.path and num_workers) else None)
        # incident engine (obs/incidents.py, ISSUE 13): rides the same
        # observer hook + the beat — zero extra fetches; None = watch off
        self.incidents = incidents if self.path else None

    def _load_or_mint_run_id(self) -> str:
        """Re-read the dir's existing run_id (resume keeps identity), else
        mint a fresh one. Tolerates every partial state a killed run
        leaves behind — identity must never take a run down."""
        try:
            with open(self.path) as fh:
                prior = json.load(fh)
            rid = prior.get("run_id") if isinstance(prior, dict) else None
            if isinstance(rid, str) and rid:
                return rid
        except (OSError, ValueError):
            pass
        import uuid

        return uuid.uuid4().hex[:12]

    # ---- accumulation ----------------------------------------------------
    def observe(self, record: dict) -> None:
        """One materialized train record (every step, logged or not) —
        wired as the DeferredMetricWriter observer in the chunked loops,
        called inline per step by the eager loops. Every column family is
        optional (baseline routes emit no health/guard/forensics columns;
        eval records carry none): a record only advances the accumulators
        for the families it carries."""
        if self.path is None:
            return
        step = record.get("step")
        if step is not None and self._first_step is None:
            self._first_step = int(step)
        if _TP_KEY in record:
            self._tp += float(record[_TP_KEY])
            self._adv += float(record.get(_ADV_KEY, 0.0))
            for k in _FLAGGED_KEYS:
                if k in record:
                    self._flagged += float(record[k])
                    break
            self._last_health_rec = record
        elif "decode_residual_bound" in record:
            # approx family (ISSUE 8): no detection columns — the health
            # block carries the last residual/bound/coverage instead, and
            # the empty detection denominators read as the healthy 1.0
            self._last_health_rec = record
        if "ahead" in record:
            self._ahead += float(record["ahead"])
            self._ahead_n += 1
        if "guard_trips" in record:
            self._guard_trips += float(record["guard_trips"])
            self._skipped_steps += float(record.get("skipped_steps", 0.0))
            self._guard_seen = True
        # numerics observatory (ISSUE 10): fold whatever nx_/shadow_
        # columns the record carries — last values for the range stats,
        # running max for the danger fractions / shadow errors, running
        # min for the flag agreement
        for k in _NX_LAST:
            if k in record:
                self._nx[k] = float(record[k])
        # a shadow column at the -1.0 NaN sentinel (numerics.
        # SHADOW_SENTINEL) marks a fault-poisoned comparison: it must stay
        # VISIBLE at the roll-up, not vanish under max() — count the step
        # once and exclude sentinel values from the extreme folds
        if any(k in record and float(record[k]) < 0.0
               for k in _NX_MAX + _NX_MIN if k.startswith("shadow_")):
            self._nx["shadow_sentinel_steps"] = \
                self._nx.get("shadow_sentinel_steps", 0) + 1
        for k in _NX_MAX:
            if k in record:
                v = float(record[k])
                if k.startswith("shadow_") and v < 0.0:
                    continue
                key = f"{k}_max"
                self._nx[key] = max(self._nx.get(key, float("-inf")), v)
        for k in _NX_MIN:
            if k in record:
                v = float(record[k])
                if v < 0.0:
                    continue
                key = f"{k}_min"
                self._nx[key] = min(self._nx.get(key, float("inf")), v)
        # engine first: it unpacks the record's forensics masks once into
        # its cache, which the heartbeat's own ledger fold then reuses —
        # one bit-unpack per record on the watch-enabled observer path
        if self.incidents is not None:
            self.incidents.observe(record)
        if self.ledger is not None:
            # reuse only when the engine unpacked for the SAME worker
            # count (the loops wire both from cfg.num_workers; a bare
            # mismatched construction falls back to its own unpack)
            masks = (self.incidents.current_masks
                     if self.incidents is not None
                     and self.incidents.num_workers == self.ledger.n
                     else None)
            self.ledger.observe(record, masks=masks)
        self._last = record

    def set_wire(self, ledger: Optional[dict]) -> None:
        """Stamp the run's static logical wire-bytes ledger
        (obs/numerics.wire_ledger) — the ``wire`` status block. Called once
        by both production loops right after setup, when the program's
        flat-gradient dimension is known. None (or a disabled heartbeat)
        is a no-op."""
        if self.path is None or ledger is None:
            return
        self._wire = dict(ledger)

    def set_control(self, block: Optional[dict]) -> None:
        """Stamp the autopilot's ``control`` status block (current regime,
        swaps, quarantined workers, last remediation — control/autopilot
        status_block). Refreshed at every autopilot decision pass; rides
        every subsequent beat AND the terminal write, so the run's last
        word records the regime it ended in."""
        if self.path is None or block is None:
            return
        self._control = dict(block)

    def observe_device(self, profile_dir: str) -> None:
        """Fold the just-stopped profiler capture into the ``device`` status
        block (phase fractions, decode share, attribution coverage — ISSUE
        9). Wired as ``obs.profiling.profiler_window``'s ``on_stop`` hook by
        both production loops, so the block lands on the first beat after
        the capture window closes. A fold that fails must never take the
        run down — but it is not dropped either: the block then holds the
        one-line cause (``error``), so a missing ledger explains itself."""
        if self.path is None:
            return
        try:
            from draco_tpu.obs import device_attr

            fold = device_attr.fold_capture(profile_dir, strict=True)
            block = device_attr.device_status_block(fold) if fold else None
            if block is None:
                block = {"error": "no capture under the profile dir"
                         if fold is None else "the capture holds no op of "
                         "a mapped program"}
            elif fold.get("scope_map_errors"):
                block["error"] = "; ".join(fold["scope_map_errors"])[:300]
        except Exception as e:
            block = {"error": f"{type(e).__name__}: {e}"[:300]}
        block["profile_dir"] = profile_dir
        self._device = block

    def decode_health(self) -> Optional[dict]:
        """Cumulative detection precision/recall (1.0 denominators-empty:
        nothing flagged / no live adversary is a healthy state) + the
        newest per-step health values."""
        if not self._last_health_rec:
            return None
        health = {
            "precision": (self._tp / self._flagged) if self._flagged else 1.0,
            "recall": (self._tp / self._adv) if self._adv else 1.0,
            "flagged_total": self._flagged,
            "adv_total": self._adv,
        }
        for k in _LAST_KEYS:
            if k in self._last_health_rec:
                health[k] = float(self._last_health_rec[k])
        return health

    # ---- emission --------------------------------------------------------
    def beat(self, step: int, total_steps: Optional[int] = None,
             extra: Optional[dict] = None) -> Optional[dict]:
        """Rewrite status.json (atomic). ``extra`` merges verbatim (e.g.
        ``{"prefetch_depth": 1}``). Returns the written payload (None when
        disabled) so tests and callers can assert on it."""
        if self.path is None:
            return None
        now = time.perf_counter()
        done = step - (self._first_step or step) + 1
        dt = max(now - self._t0, 1e-9)
        rate = done / dt
        payload = {
            "schema": STATUS_SCHEMA,
            "state": "running",
            "run_id": self.run_id,
            "step": int(step),
            "total_steps": int(total_steps) if total_steps else None,
            "steps_per_s": round(rate, 4),
            "eta_s": (round(max(total_steps - step, 0) / rate, 1)
                      if (total_steps and rate > 0) else None),
            "updated_at": time.time(),
        }
        if self.job_name:
            payload["job_name"] = self.job_name
        for k in ("loss", "prec1"):
            if k in self._last:
                payload[k] = float(self._last[k])
        health = self.decode_health()
        if health is not None:
            payload["decode_health"] = health
        # keyed off "ever seen", not the newest record: a mixed-route
        # train_dir whose trailing record carries no guard columns must not
        # hide the cumulative totals
        if self._guard_seen:
            payload["guard"] = {"trips": self._guard_trips,
                                "skipped_steps": self._skipped_steps}
        if self._ahead_n:
            payload["ahead_share"] = round(self._ahead / self._ahead_n, 4)
        if self.ledger is not None and self.ledger.active:
            # per-worker forensics (obs/forensics.AccusationLedger):
            # top suspects, trust vector, episode counts
            payload["forensics"] = self.ledger.summary()
        if self._wire is not None:
            # static logical wire-bytes ledger (ISSUE 10, set_wire)
            payload["wire"] = self._wire
        if self._nx:
            # folded numerics-observatory block (ISSUE 10)
            payload["numerics"] = dict(self._nx)
        if self._device is not None:
            # last profiled window's device-time attribution (ISSUE 9);
            # consumers tolerate the key missing, assert it when present
            payload["device"] = self._device
        if self._control is not None:
            # the autopilot's runtime-control state (control/autopilot.py)
            payload["control"] = self._control
        if self._setup is None:
            # read once, at the run's first beat: what preceded its first
            # step; later beats carry the same block
            self._setup = setup_block()
        payload["setup"] = self._setup
        if self.incidents is not None:
            # the beat IS the engine's beat-source observation (throughput
            # wall-rate, compile counters, prefetch depth/restarts all
            # arrive in ``extra``), then the folded block rides the payload
            self.incidents.observe_beat(step, extra)
            payload["incidents"] = self.incidents.status_block()
        if extra:
            payload.update(extra)
        self._write(payload)
        self.beats += 1
        return payload

    def terminal(self, state: str, cause: Optional[str] = None,
                 resumable_step: Optional[int] = None) -> Optional[dict]:
        """Write the run's FINAL status.json state: ``done`` | ``preempted``
        | ``crashed`` (module docstring). Builds on the last beat's payload
        so a monitor keeps step/rate/health context, then overrides
        ``state`` (+ one-line ``cause``, + ``resumable_step`` when a
        graceful stop snapped a boundary checkpoint to resume from)."""
        if self.path is None:
            return None
        # seed from the last payload for step/rate/health context, but
        # strip terminal-only keys: a terminal seeded from a PREVIOUS
        # terminal (block-wise callers re-run between beats) must not leak
        # a stale cause or resumable_step into a different final state
        payload = {k: v for k, v in self._last_payload.items()
                   if k not in ("state", "cause", "resumable_step")}
        payload["schema"] = STATUS_SCHEMA  # present even with no prior beat
        payload["state"] = state
        payload["run_id"] = self.run_id  # identity even with no prior beat
        if self.job_name:
            payload["job_name"] = self.job_name
        payload["updated_at"] = time.time()
        if self._device is not None:
            # a capture window that stops on the run's LAST work unit has
            # no later beat — the terminal write is the block's only ride
            payload["device"] = self._device
        if self._control is not None:
            # the regime the run ENDED in (a post-last-beat remediation
            # must survive into the run's last word — same rule as the
            # incidents block below)
            payload["control"] = self._control
        if self.incidents is not None:
            # the FINAL incidents state must ride the terminal write: an
            # incident that opened after the last beat (a crash step, a
            # SIGTERM-boundary guard trip) would otherwise vanish from the
            # run's last word — the same bug PR 9 fixed for ``device``
            # (ISSUE 13 satellite, pinned by the SIGTERM-path test)
            payload["incidents"] = self.incidents.status_block()
            self.incidents.finalize()
        if cause is not None:
            payload["cause"] = str(cause)[:500]
        if resumable_step is not None:
            payload["resumable_step"] = int(resumable_step)
        self._write(payload)
        return payload

    def _write(self, payload: dict) -> None:
        self._last_payload = payload
        tmp = self.path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, self.path)
