"""Fleet observatory: multi-run registry + SLO engine + roll-up (ISSUE 19).

ROADMAP item 4 ("fleet-as-a-service") needs per-job artifacts rolled up
into a fleet-level dashboard + SLO report before the autopilot can be
promoted from run babysitter to fleet scheduler. This module is that
observation half, and it follows the obs/ discipline end to end:

  * importable WITHOUT jax (like incidents.py / replay.py) — every
    consumer runs on a laptop against artifacts scp'd from a chip job;
  * zero device cost — it only folds files the runs already write
    (status.json, metrics.jsonl, incidents.jsonl); no extra fetches,
    no graph changes;
  * torn / empty / missing inputs degrade with a visible note on the
    RunSummary, never a traceback (obs/replay tolerance rules).

Three layers:

**RunRegistry** — discovers run directories and folds each one's
status.json (validated through the central ``check_status_schema``
contract), incidents.jsonl, and metrics.jsonl tail into a
:class:`RunSummary`. A resumed run (same ``run_id`` across attempts, or
an incident-stream seq reset inside one dir) folds as ONE run. A
crashed run (``state: "crashed"``) folds as an SLO violation, not a
parse error.

**SLO engine** — declaratively registered, mirroring the PR 13
``register_detector``/``detector_table()`` pattern: ``@register_slo``
classes land in the enumerable ``SLOS`` registry, thresholds are
overridable via ``parse_slo_thresholds("<slo>.<key>=<float>")``. Each
SLO evaluates one RunSummary into an error budget (``budget`` /
``burned`` / ``burn_frac``), burn-rate windows (max burn inside
trailing fast/slow step windows), and a typed verdict
(``ok | violated | not_evaluated``). Six SLOs ship: step-availability,
detection-quality (the Draco P/R certificate as an SLO — never
evaluated on the baseline approach, which emits no columns),
decode-health, throughput (vs the run's own warm baseline), incident
MTTR/MTTD (onset→remediation latency joined from autopilot
``remediation`` events in the same stream), and the wire-byte budget.

**Fleet roll-up** — cross-run per-worker trust fold (a worker accused
in 3 of 4 runs outranks a one-run spike), fleet compute-to-target, and
per-run SLO compliance; emitted by ``tools/fleet_report.py`` and
proven by ``tools/fleet_study.py`` → ``baselines_out/fleet_slo.json``.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
from typing import Any, Dict, List, Optional, Tuple

from draco_tpu.obs import replay
from draco_tpu.obs.forensics import AccusationLedger, record_masks
from draco_tpu.obs.heartbeat import STATUS_SCHEMA, check_status_schema

# fleet.json / fleet_slo.json payload schema (bump on shape changes)
FLEET_SCHEMA = 1

# typed SLO verdicts — the only three states a fleet report may print
VERDICTS = ("ok", "violated", "not_evaluated")

# SLOs whose burn is a pure function of the committed artifacts (no
# wall-clock in the *burn* accounting) — their per-run burn sum is the
# ``budget_burned`` scalar perf_watch pins at 0 on clean cells
DETERMINISTIC_SLOS = ("step_availability", "detection_quality",
                      "decode_health", "wire_bytes")

# metrics.jsonl tail cap per run: the registry folds at most this many
# train records (newest kept). Long-run cumulative truth (detection
# P/R, guard totals) comes from status.json; the tail feeds the
# step-resolved folds (residuals, rates, burn windows).
DEFAULT_TAIL = 4096

# steps each offline throughput sample spans: records materialize in
# per-chunk flush BURSTS (a chunk's K records share one wall-clock
# neighborhood), so record-to-record deltas measure flush cadence, not
# training rate — every rate sample divides >= RATE_SPAN steps by the
# wall clock they actually took, which averages across flush bursts
RATE_SPAN = 8

_FLAGGED_KEYS = ("located_errors", "det_flagged")


# --------------------------------------------------------------------------
# RunSummary + fold
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RunSummary:
    """One run directory folded into plain data. Every field is optional
    in spirit: a torn or partial run leaves Nones/empties plus a note —
    the SLO layer decides what is evaluable, the fold never raises."""

    run_dir: str
    run_id: Optional[str] = None
    job_name: Optional[str] = None
    schema: Optional[int] = None
    state: Optional[str] = None
    status: Dict[str, Any] = dataclasses.field(default_factory=dict)
    step: Optional[int] = None
    total_steps: Optional[int] = None
    steps_per_s: Optional[float] = None
    loss: Optional[float] = None
    updated_at: Optional[float] = None
    # metrics tail fold
    records: int = 0
    first_step: Optional[int] = None
    last_step: Optional[int] = None
    losses: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    skipped_events: List[Tuple[int, float]] = \
        dataclasses.field(default_factory=list)
    guard_trips: float = 0.0
    skipped_steps: float = 0.0
    guard_seen: bool = False
    detection: Optional[Dict[str, float]] = None
    residuals: List[Tuple[int, float, Optional[float]]] = \
        dataclasses.field(default_factory=list)
    rates: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    record_times: Dict[int, float] = dataclasses.field(default_factory=dict)
    num_workers: Optional[int] = None
    worker_rows: Optional[List[dict]] = None  # replayed forensics fold
    forensics: Optional[dict] = None          # status.json summary block
    wire: Optional[dict] = None
    control: Optional[dict] = None
    # incidents stream
    events: List[dict] = dataclasses.field(default_factory=list)
    remediations: List[dict] = dataclasses.field(default_factory=list)
    resumed: bool = False
    attempts: int = 1
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def steps_observed(self) -> int:
        """Steps the fold has evidence for: the record span when the
        tail carries steps, else the status step counter."""
        if self.first_step is not None and self.last_step is not None:
            return self.last_step - self.first_step + 1
        return int(self.step or 0)

    def label(self) -> str:
        base = os.path.basename(os.path.normpath(self.run_dir)) or \
            self.run_dir
        return self.job_name or base


def _fold_status(out: RunSummary, status_path: str, tool: str) -> None:
    try:
        with open(status_path) as fh:
            status = json.load(fh)
    except OSError:
        out.notes.append("status.json missing")
        return
    except ValueError:
        out.notes.append("status.json torn/unreadable")
        return
    if not isinstance(status, dict):
        out.notes.append("status.json not an object")
        return
    try:
        check_status_schema(status, status_path, tool)
    except SystemExit as e:
        # an unknown (newer) schema must not take the whole fleet
        # report down — the run degrades to metrics-only with a note
        out.notes.append(f"status.json rejected: {e}")
        return
    out.status = status
    out.schema = status.get("schema")
    out.state = status.get("state")
    out.run_id = status.get("run_id")
    out.job_name = status.get("job_name")
    out.step = status.get("step")
    out.total_steps = status.get("total_steps")
    out.steps_per_s = status.get("steps_per_s")
    out.loss = status.get("loss")
    out.updated_at = status.get("updated_at")
    out.forensics = status.get("forensics")
    out.wire = status.get("wire")
    out.control = status.get("control")
    if out.schema is not None and out.schema < 5 and out.run_id is None:
        out.notes.append(f"pre-run_id status (schema {out.schema})")
    guard = status.get("guard")
    if isinstance(guard, dict):
        out.guard_seen = True
        out.guard_trips = float(guard.get("trips", 0.0))
        out.skipped_steps = float(guard.get("skipped_steps", 0.0))
    health = status.get("decode_health")
    if isinstance(health, dict):
        out.detection = {
            "precision": float(health.get("precision", 1.0)),
            "recall": float(health.get("recall", 1.0)),
            "flagged_total": float(health.get("flagged_total", 0.0)),
            "adv_total": float(health.get("adv_total", 0.0)),
        }


def _fold_records(out: RunSummary, files: replay.RunFiles,
                  tail: int) -> None:
    recs: "collections.deque[dict]" = collections.deque(maxlen=tail)
    total = 0
    for rec in replay.train_records(files.metrics):
        recs.append(rec)
        total += 1
    if not total:
        out.notes.append("metrics.jsonl missing or empty")
        return
    if total > len(recs):
        out.notes.append(
            f"metrics tail: folded newest {len(recs)}/{total} records")
    out.records = len(recs)
    det_tp = det_adv = det_flagged = 0.0
    det_seen = False
    prev_step: Optional[int] = None
    prev_time: Optional[float] = None
    any_masks = False
    for rec in recs:
        step = rec.get("step")
        step = int(step) if step is not None else None
        if step is not None:
            if out.first_step is None:
                out.first_step = step
            out.last_step = step
        if "loss" in rec and step is not None:
            out.losses.append((step, float(rec["loss"])))
        if "guard_trips" in rec:
            if not out.guard_seen:
                # recompute only when status carried no cumulative
                # guard block (torn run) — the tail may undercount
                out.guard_trips += float(rec["guard_trips"])
                out.skipped_steps += float(rec.get("skipped_steps", 0.0))
            skipped = float(rec.get("skipped_steps", 0.0))
            if step is not None:
                out.skipped_events.append((step, skipped))
        if "det_tp" in rec:
            det_seen = True
            det_tp += float(rec["det_tp"])
            det_adv += float(rec.get("det_adv", 0.0))
            for k in _FLAGGED_KEYS:
                if k in rec:
                    det_flagged += float(rec[k])
                    break
        if "decode_residual" in rec and step is not None:
            bound = rec.get("decode_residual_bound")
            out.residuals.append(
                (step, float(rec["decode_residual"]),
                 float(bound) if bound is not None else None))
        t = rec.get("time")
        if t is not None and step is not None:
            if prev_step is None or step > prev_step:
                out.record_times[step] = float(t)
                prev_step, prev_time = step, float(t)
        if "wmask_accused0" in rec:
            any_masks = True
    del prev_time
    pts = sorted(out.record_times.items())
    base = 0
    for i, (step, t) in enumerate(pts):
        # newest base point at least RATE_SPAN steps back
        while base + 1 < i and pts[base + 1][0] <= step - RATE_SPAN:
            base += 1
        bstep, bt = pts[base]
        if bstep <= step - RATE_SPAN and t > bt:
            out.rates.append((step, (step - bstep) / (t - bt)))
    if det_seen and out.detection is None:
        out.detection = {
            "precision": (det_tp / det_flagged) if det_flagged else 1.0,
            "recall": (det_tp / det_adv) if det_adv else 1.0,
            "flagged_total": det_flagged,
            "adv_total": det_adv,
        }
    if any_masks:
        n = replay.infer_num_workers(list(recs), files.status,
                                     tool="obs/fleet.py")
        out.num_workers = n
        ledger = AccusationLedger(n)
        for rec in recs:
            ledger.observe(rec, masks=record_masks(rec, n))
        out.worker_rows = ledger.worker_rows()
    elif out.forensics:
        out.num_workers = out.forensics.get("num_workers")


def _fold_incidents(out: RunSummary, incidents_path: str) -> None:
    prev_seq: Optional[int] = None
    resets = 0
    for ev in replay.iter_jsonl(incidents_path):
        if "event" not in ev:
            continue
        seq = ev.get("seq")
        if isinstance(seq, int):
            if prev_seq is not None and seq <= prev_seq:
                resets += 1
            prev_seq = seq
        out.events.append(ev)
        if ev.get("event") == "remediation":
            out.remediations.append(ev)
    if resets:
        out.resumed = True
        out.attempts = resets + 1
        out.notes.append(
            f"incident seq reset x{resets}: folded as one resumed run "
            f"({resets + 1} attempts)")


def fold_run(path: str, tail: int = DEFAULT_TAIL,
             tool: str = "obs/fleet.py") -> RunSummary:
    """Fold one run directory (or metrics.jsonl path) into a RunSummary.
    Never raises on torn/empty/missing inputs — degradations land in
    ``notes``."""
    files = replay.find_run_files(path)
    out = RunSummary(run_dir=files.root)
    _fold_status(out, files.status, tool)
    _fold_records(out, files, tail)
    _fold_incidents(out, files.incidents)
    return out


class RunRegistry:
    """Discovers run directories and folds them into RunSummaries,
    merging attempts that share a ``run_id`` so a resumed run counts as
    ONE run in every roll-up."""

    def __init__(self, run_dirs: List[str], tail: int = DEFAULT_TAIL,
                 tool: str = "obs/fleet.py"):
        self.summaries = _merge_attempts(
            [fold_run(d, tail=tail, tool=tool) for d in run_dirs])

    @staticmethod
    def discover(root: str) -> List[str]:
        """Run directories under ``root``: every directory holding a
        status.json or metrics.jsonl (sorted, stable)."""
        found = []
        for dirpath, _dirnames, filenames in os.walk(root):
            if "status.json" in filenames or "metrics.jsonl" in filenames:
                found.append(dirpath)
        return sorted(found)


def _merge_attempts(summaries: List[RunSummary]) -> List[RunSummary]:
    by_id: Dict[str, List[RunSummary]] = {}
    order: List[Tuple[str, RunSummary]] = []
    for i, s in enumerate(summaries):
        key = s.run_id or f"__anon_{i}__"
        if key not in by_id:
            order.append((key, s))
        by_id.setdefault(key, []).append(s)
    out = []
    for key, _first in order:
        group = by_id[key]
        if len(group) == 1:
            out.append(group[0])
            continue
        primary = max(group, key=lambda s: ((s.updated_at or 0.0),
                                            s.records))
        primary.resumed = True
        primary.attempts += sum(g.attempts for g in group
                                if g is not primary)
        primary.notes.append(
            f"run_id {key} seen in {len(group)} dirs: folded as one "
            f"resumed run (kept {primary.run_dir})")
        out.append(primary)
    return out


# --------------------------------------------------------------------------
# SLO registry (mirrors obs/incidents.register_detector)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SLOSpec:
    """One registered SLO: identity + declarative threshold defaults
    (every key overridable via ``parse_slo_thresholds`` strings)."""

    name: str
    thresholds: Dict[str, float]
    doc: str
    factory: Any


SLOS: Dict[str, SLOSpec] = {}


def register_slo(name: str, thresholds: Dict[str, float]):
    """Class decorator declaring an SLO into the enumerable registry.
    The class must expose ``evaluate(run: RunSummary) -> dict`` built on
    :func:`slo_result` so every verdict is typed the same way."""

    def deco(cls):
        SLOS[name] = SLOSpec(
            name=name, thresholds=dict(thresholds),
            doc=(cls.__doc__ or "").strip().splitlines()[0], factory=cls)
        return cls

    return deco


def slo_table() -> List[dict]:
    """The enumerable SLO set (PERF_HISTORY.md §21's table source)."""
    return [{"name": s.name, "thresholds": dict(s.thresholds),
             "doc": s.doc} for s in SLOS.values()]


def parse_slo_thresholds(spec: str) -> Dict[str, float]:
    """``"throughput.floor_frac=0.25,mttr.mttr_max_s=60"`` -> override
    dict. Unknown SLO or threshold keys are config-time errors (the
    registry is the contract), values must parse as floats."""
    out: Dict[str, float] = {}
    for item in (spec or "").split(","):
        item = item.strip()
        if not item:
            continue
        try:
            key, val = item.split("=", 1)
            slo, th = key.strip().split(".", 1)
            fval = float(val)
        except ValueError:
            raise ValueError(
                f"SLO threshold {item!r} is not '<slo>.<key>=<float>'")
        if slo not in SLOS:
            raise ValueError(
                f"unknown SLO {slo!r} (registered: "
                f"{', '.join(sorted(SLOS))})")
        if th not in SLOS[slo].thresholds:
            raise ValueError(
                f"SLO {slo!r} has no threshold {th!r} (declared: "
                f"{', '.join(sorted(SLOS[slo].thresholds))})")
        out[f"{slo}.{th}"] = fval
    return out


def make_slos(overrides: Any = "") -> Dict[str, Any]:
    """Instantiate every registered SLO with defaults + overrides
    (string spec or an already-parsed dict)."""
    if isinstance(overrides, str):
        overrides = parse_slo_thresholds(overrides)
    out = {}
    for name, spec in SLOS.items():
        th = dict(spec.thresholds)
        for key, val in (overrides or {}).items():
            slo, tkey = key.split(".", 1)
            if slo == name:
                th[tkey] = val
        out[name] = spec.factory(th)
    return out


def slo_result(name: str, evaluated: bool, ok: Optional[bool] = None,
               budget: float = 0.0, burned: float = 0.0,
               windows: Optional[dict] = None, detail: str = "",
               **extra) -> dict:
    """The one typed-verdict shape every SLO returns. ``burn_frac`` is
    None when a zero budget burned anyway (an infinite burn rate — kept
    JSON-clean instead of Infinity)."""
    if evaluated:
        verdict = "ok" if ok else "violated"
        if budget > 0:
            burn_frac: Optional[float] = burned / budget
        else:
            burn_frac = 0.0 if burned <= 0 else None
    else:
        verdict, ok, burn_frac = "not_evaluated", None, None
    return dict({
        "slo": name,
        "evaluated": bool(evaluated),
        "ok": ok if ok is None else bool(ok),
        "verdict": verdict,
        "budget": float(budget),
        "burned": float(burned),
        "burn_frac": burn_frac,
        "windows": windows or {},
        "detail": detail,
    }, **extra)


def burn_windows(events: List[Tuple[int, float]],
                 windows: Dict[str, float]) -> dict:
    """Max burn inside any trailing window of W steps, per named window
    — the burn-RATE half of the error budget: a slow leak and a spike
    can burn the same total, only the window fold tells them apart."""
    out = {}
    evs = sorted((int(s), float(b)) for s, b in events)
    for label, w in windows.items():
        w = max(int(w), 1)
        best, best_at, lo, acc = 0.0, None, 0, 0.0
        for hi, (step, b) in enumerate(evs):
            acc += b
            while evs[lo][0] <= step - w:
                acc -= evs[lo][1]
                lo += 1
            if acc > best:
                best, best_at = acc, step
        out[label] = {"steps": w, "max_burn": best, "at_step": best_at}
    return out


class _SLO:
    def __init__(self, thresholds: Dict[str, float]):
        self.th = dict(thresholds)


@register_slo("step_availability",
              thresholds={"budget_frac": 0.02, "window_fast": 8.0,
                          "window_slow": 32.0})
class StepAvailabilitySLO(_SLO):
    """Step availability: guard-skipped steps vs an availability budget
    (budget_frac of observed steps); a crashed terminal state is an
    availability violation by definition, never a parse error."""

    def evaluate(self, run: RunSummary) -> dict:
        crashed = run.state == "crashed"
        if not run.records and run.step is None and not crashed:
            return slo_result("step_availability", False,
                              detail="no step evidence "
                                     "(no records, no status)")
        steps = max(run.steps_observed, 1)
        burned = float(run.skipped_steps)
        budget = self.th["budget_frac"] * steps
        wins = burn_windows(run.skipped_events,
                            {"fast": self.th["window_fast"],
                             "slow": self.th["window_slow"]})
        ok = burned <= budget and not crashed
        if crashed:
            cause = run.status.get("cause")
            detail = "terminal state 'crashed'" + \
                (f": {cause}" if cause else "")
        else:
            detail = (f"{burned:g} skipped of {steps} steps "
                      f"(budget {budget:g})")
        return slo_result("step_availability", True, ok=ok,
                          budget=budget, burned=burned, windows=wins,
                          detail=detail, crashed=crashed,
                          guard_trips=run.guard_trips)


@register_slo("detection_quality",
              thresholds={"precision_floor": 1.0, "recall_floor": 1.0,
                          "window_fast": 8.0, "window_slow": 32.0})
class DetectionQualitySLO(_SLO):
    """Detection quality: the Draco P/R-1.0 certificate as an SLO —
    burned = false accusations + missed adversaries; never evaluated on
    the baseline approach, which emits no detection columns."""

    def evaluate(self, run: RunSummary) -> dict:
        det = run.detection
        if det is None:
            return slo_result("detection_quality", False,
                              detail="no detection columns "
                                     "(baseline route or no records)")
        p, r = det["precision"], det["recall"]
        flagged, adv = det["flagged_total"], det["adv_total"]
        tp = min(p * flagged, r * adv) if (flagged and adv) else \
            (p * flagged if flagged else r * adv)
        burned = max(flagged - tp, 0.0) + max(adv - tp, 0.0)
        budget = ((1.0 - self.th["precision_floor"]) * flagged
                  + (1.0 - self.th["recall_floor"]) * adv)
        ok = (p >= self.th["precision_floor"] - 1e-12
              and r >= self.th["recall_floor"] - 1e-12)
        return slo_result(
            "detection_quality", True, ok=ok, budget=budget,
            burned=burned,
            detail=f"precision {p:g} recall {r:g} "
                   f"(floors {self.th['precision_floor']:g}/"
                   f"{self.th['recall_floor']:g})",
            precision=p, recall=r, flagged_total=flagged, adv_total=adv)


@register_slo("decode_health",
              thresholds={"cyclic_tol": 1e-3, "bound_frac": 0.95,
                          "ew_alpha": 0.25, "crossing_budget": 0.0,
                          "window_fast": 8.0, "window_slow": 32.0})
class DecodeHealthSLO(_SLO):
    """Decode health: cyclic residual tolerance crossings (exact decode
    must sit at numerical noise) and approx EW residual/bound drift
    toward the certificate edge."""

    def evaluate(self, run: RunSummary) -> dict:
        if not run.residuals:
            return slo_result("decode_health", False,
                              detail="no residual columns in tail")
        tol = self.th["cyclic_tol"]
        alpha = self.th["ew_alpha"]
        events = []
        burned = 0.0
        ew: Optional[float] = None
        hard = 0
        for step, res, bound in run.residuals:
            if bound is None:
                bad = (not math.isfinite(res)) or res > tol
            else:
                ratio = (res / bound) if bound > 0 else \
                    (0.0 if res == 0 else float("inf"))
                if math.isfinite(ratio):
                    ew = ratio if ew is None else \
                        (1 - alpha) * ew + alpha * ratio
                bad = (not math.isfinite(res)) or \
                    (math.isfinite(bound) and res > bound)
            if bad:
                hard += 1
                burned += 1.0
                events.append((step, 1.0))
        drift = ew is not None and ew > self.th["bound_frac"]
        budget = self.th["crossing_budget"]
        ok = burned <= budget and not drift
        wins = burn_windows(events, {"fast": self.th["window_fast"],
                                     "slow": self.th["window_slow"]})
        detail = (f"{hard} residual crossings / {len(run.residuals)} "
                  f"rows" + (f"; EW residual/bound {ew:.3g} over "
                             f"{self.th['bound_frac']:g}" if drift
                             else ""))
        return slo_result("decode_health", True, ok=ok, budget=budget,
                          burned=burned, windows=wins, detail=detail,
                          ew_residual_over_bound=ew)


@register_slo("throughput",
              thresholds={"warmup": 3.0, "ew_alpha": 0.3,
                          "floor_frac": 0.3, "budget_frac": 0.1,
                          "window_fast": 8.0, "window_slow": 32.0})
class ThroughputSLO(_SLO):
    """Throughput: EW steps/s from the records' wall-clock stream vs
    the run's own warm baseline — burn = post-warmup samples below
    floor_frac of the warm median."""

    def evaluate(self, run: RunSummary) -> dict:
        warmup = int(self.th["warmup"])
        rates = run.rates
        if len(rates) <= warmup + 1:
            return slo_result("throughput", False,
                              detail=f"{len(rates)} rate samples "
                                     f"(need > {warmup + 1})")
        warm = sorted(r for _s, r in rates[warmup:warmup + 5])
        baseline = warm[len(warm) // 2]
        alpha = self.th["ew_alpha"]
        floor = self.th["floor_frac"] * baseline
        ew = baseline
        events = []
        burned = 0.0
        for step, r in rates[warmup:]:
            ew = (1 - alpha) * ew + alpha * r
            if r < floor:
                burned += 1.0
                events.append((step, 1.0))
        samples = len(rates) - warmup
        budget = self.th["budget_frac"] * samples
        ok = burned <= budget
        wins = burn_windows(events, {"fast": self.th["window_fast"],
                                     "slow": self.th["window_slow"]})
        return slo_result(
            "throughput", True, ok=ok, budget=budget, burned=burned,
            windows=wins,
            detail=f"{burned:g}/{samples} samples under "
                   f"{floor:.3g} steps/s (warm baseline "
                   f"{baseline:.3g})",
            warm_baseline=baseline, ew_steps_per_s=ew)


@register_slo("incident_mttr",
              thresholds={"mttr_max_s": 300.0, "mttd_max_s": 300.0})
class IncidentMttrSLO(_SLO):
    """Incident MTTR/MTTD: onset→remediation wall-clock latency joined
    from autopilot ``remediation`` events in the same incident stream
    (MTTR), and onset-step record time → onset event time (MTTD);
    unattributed remediations burn the (zero) budget."""

    def evaluate(self, run: RunSummary) -> dict:
        onsets = {}
        detect_lags = []
        for ev in run.events:
            if ev.get("event") != "onset":
                continue
            key = (ev.get("type"), ev.get("onset_step"))
            onsets.setdefault(key, ev)
            ts = ev.get("ts")
            step_t = run.record_times.get(ev.get("onset_step"))
            if ts is not None and step_t is not None:
                detect_lags.append(max(float(ts) - step_t, 0.0))
        if not run.remediations:
            return slo_result(
                "incident_mttr", False,
                detail=f"no remediation events "
                       f"({len(onsets)} onsets)",
                mttd_s=(sum(detect_lags) / len(detect_lags)
                        if detect_lags else None))
        latencies = []
        unattributed = 0
        for rem in run.remediations:
            trig = rem.get("trigger") or {}
            key = (trig.get("type"), trig.get("onset_step"))
            onset = onsets.get(key)
            ts, onset_ts = rem.get("ts"), \
                (onset or {}).get("ts")
            if onset is None or ts is None or onset_ts is None:
                unattributed += 1
                continue
            lat = float(ts) - float(onset_ts)
            if not math.isfinite(lat) or lat < 0:
                unattributed += 1
                continue
            latencies.append(lat)
        mttr = (sum(latencies) / len(latencies)) if latencies else None
        mttd = (sum(detect_lags) / len(detect_lags)) if detect_lags \
            else None
        slow = sum(1 for x in latencies if x > self.th["mttr_max_s"])
        slow += sum(1 for x in detect_lags
                    if x > self.th["mttd_max_s"])
        burned = float(unattributed + slow)
        ok = burned == 0 and mttr is not None
        return slo_result(
            "incident_mttr", True, ok=ok, budget=0.0, burned=burned,
            detail=f"{len(latencies)}/{len(run.remediations)} "
                   f"remediations attributed; MTTR "
                   f"{'%.3gs' % mttr if mttr is not None else 'n/a'}",
            mttr_s=mttr, mttd_s=mttd,
            remediations=len(run.remediations),
            attributed=len(latencies), unattributed=unattributed)


@register_slo("wire_bytes", thresholds={"tol_frac": 0.0})
class WireBytesSLO(_SLO):
    """Wire-byte budget: the status ``wire`` block must stay internally
    consistent with its own ledger — the materialized dtype's physical
    bytes equal the logical candidate row, per-step = per-worker × n,
    and the segment bytes sum to the whole."""

    def evaluate(self, run: RunSummary) -> dict:
        wire = run.wire
        if not isinstance(wire, dict):
            return slo_result("wire_bytes", False,
                              detail="no wire block in status.json")
        tol = self.th["tol_frac"]
        problems = []

        def close(a, b):
            a, b = float(a), float(b)
            return abs(a - b) <= tol * max(abs(a), abs(b), 1.0)

        dtype = wire.get("wire_dtype")
        cand = (wire.get("bytes_per_worker") or {}).get(dtype)
        phys_w = wire.get("physical_bytes_per_worker")
        phys_s = wire.get("physical_bytes_per_step")
        n = wire.get("num_workers")
        if cand is None or phys_w is None:
            problems.append(f"ledger missing dtype row {dtype!r}")
        elif not close(cand, phys_w):
            problems.append(
                f"physical_bytes_per_worker {phys_w} != ledger "
                f"{dtype} row {cand}")
        if None not in (phys_w, phys_s, n) and \
                not close(phys_s, float(phys_w) * float(n)):
            problems.append(
                f"physical_bytes_per_step {phys_s} != per_worker x "
                f"{n}")
        segs = wire.get("segments")
        if isinstance(segs, dict) and phys_w is not None:
            seg_sum = sum(segs.get("physical_bytes_per_worker") or [])
            if not close(seg_sum, phys_w):
                problems.append(
                    f"segment bytes sum {seg_sum} != per_worker "
                    f"{phys_w}")
        burned = float(len(problems))
        return slo_result(
            "wire_bytes", True, ok=not problems, budget=0.0,
            burned=burned,
            detail="; ".join(problems) if problems else
                   f"{dtype} wire consistent "
                   f"({phys_w} B/worker/step)",
            wire_dtype=dtype,
            physical_bytes_per_step=phys_s)


def evaluate_run(run: RunSummary,
                 slos: Optional[Dict[str, Any]] = None) -> Dict[str, dict]:
    """Every registered SLO evaluated on one RunSummary (registry
    order)."""
    slos = slos if slos is not None else make_slos()
    return {name: slo.evaluate(run) for name, slo in slos.items()}


def budget_burned(results: Dict[str, dict]) -> float:
    """The run's deterministic error-budget burn — the scalar the
    committed fleet study pins at 0 on clean cells (throughput and
    MTTR burn wall-clock-dependent amounts and are gated separately)."""
    return sum(results[name]["burned"] for name in DETERMINISTIC_SLOS
               if name in results and results[name]["evaluated"])


# --------------------------------------------------------------------------
# fleet roll-up
# --------------------------------------------------------------------------


def worker_rollup(summaries: List[RunSummary], top: int = 8) -> List[dict]:
    """Cross-run per-worker trust fold: rank by the number of RUNS that
    accused the worker first (a worker accused in 3 of 4 runs outranks
    a one-run spike), then by total accusations, then by minimum
    trust."""
    stats: Dict[int, dict] = {}
    for s in summaries:
        rows = s.worker_rows
        if rows is None and s.forensics:
            # degraded path: no records to replay — use the status
            # block's trust vector + top suspects
            trust = s.forensics.get("trust") or []
            suspects = {d.get("worker"): d.get("accused", 0)
                        for d in (s.forensics.get("top_suspects") or [])}
            rows = [{"worker": w, "accused": suspects.get(w, 0),
                     "trust": t} for w, t in enumerate(trust)]
        if not rows:
            continue
        for row in rows:
            w = int(row["worker"])
            st = stats.setdefault(
                w, {"worker": w, "runs_seen": 0, "runs_accusing": 0,
                    "accused_total": 0, "min_trust": 1.0,
                    "trust_sum": 0.0})
            st["runs_seen"] += 1
            st["accused_total"] += int(row.get("accused", 0))
            if row.get("accused", 0):
                st["runs_accusing"] += 1
            t = float(row.get("trust", 1.0))
            st["min_trust"] = min(st["min_trust"], t)
            st["trust_sum"] += t
    out = []
    for st in stats.values():
        st["mean_trust"] = round(st.pop("trust_sum") / st["runs_seen"], 4)
        out.append(st)
    out.sort(key=lambda r: (-r["runs_accusing"], -r["accused_total"],
                            r["min_trust"], r["worker"]))
    return out[:top]


def compute_rollup(summaries: List[RunSummary],
                   target_loss: Optional[float] = None) -> dict:
    """Fleet compute-to-target: per-run worker-steps spent, and (when a
    target loss is given) the worker-steps each run needed to first
    reach it — the autopilot_study objective lifted to the fleet."""
    by_run = []
    total_ws = 0.0
    for s in summaries:
        n = s.num_workers or 0
        steps = s.steps_observed
        ws = float(steps * n)
        total_ws += ws
        to_target = None
        if target_loss is not None:
            first = s.first_step
            for step, loss in s.losses:
                if loss <= target_loss:
                    base = first if first is not None else step
                    to_target = float((step - base + 1) * n)
                    break
        by_run.append({"run": s.label(), "run_id": s.run_id,
                       "steps": steps, "workers": n,
                       "worker_steps": ws, "final_loss": s.loss,
                       "worker_steps_to_target": to_target})
    reached = [r["worker_steps_to_target"] for r in by_run
               if r["worker_steps_to_target"] is not None]
    return {
        "target_loss": target_loss,
        "total_worker_steps": total_ws,
        "runs_reaching_target": len(reached) if target_loss is not None
        else None,
        "worker_steps_to_target_total": (sum(reached) if reached
                                         else None),
        "by_run": by_run,
    }


def fleet_fold(summaries: List[RunSummary], overrides: Any = "",
               target_loss: Optional[float] = None) -> dict:
    """The whole fleet folded: per-run SLO results + compliance counts,
    the cross-run worker table, and compute-to-target — the fleet.json
    / fleet_slo.json body."""
    slos = make_slos(overrides)
    runs = []
    compliance = {name: {"ok": 0, "violated": 0, "not_evaluated": 0}
                  for name in SLOS}
    all_ok = True
    for s in summaries:
        results = evaluate_run(s, slos)
        for name, res in results.items():
            compliance[name][res["verdict"]] += 1
            if res["verdict"] == "violated":
                all_ok = False
        runs.append({
            "run": s.label(), "run_dir": s.run_dir, "run_id": s.run_id,
            "job_name": s.job_name, "state": s.state,
            "schema": s.schema, "steps": s.steps_observed,
            "records": s.records, "loss": s.loss,
            "resumed": s.resumed, "attempts": s.attempts,
            "notes": list(s.notes),
            "budget_burned": budget_burned(results),
            "slo": results,
        })
    return {
        "fleet_schema": FLEET_SCHEMA,
        "status_schema": STATUS_SCHEMA,
        "runs": runs,
        "slo_table": slo_table(),
        "slo_compliance": compliance,
        "workers": worker_rollup(summaries),
        "compute": compute_rollup(summaries, target_loss),
        "all_ok": all_ok,
    }
