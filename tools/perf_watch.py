#!/usr/bin/env python
"""Artifact-regression watch: fold the committed study artifacts into one
flat metric set and diff it against the committed baseline snapshot.

The accumulation point for what a CPU can COUNT and what is EXACT — jax-free
(pure artifact folding), so it gates without touching a backend:

  python tools/perf_watch.py --snapshot        # (re)write the baseline
                                               #  baselines_out/perf_watch.json
  python tools/perf_watch.py                   # diff current artifacts vs
                                               #   baseline; exit 1 on any
                                               #   out-of-tolerance regression
  python tools/perf_watch.py --json report.json

It gates NO time: a millisecond, a compile time, a phase's share of a CPU
trace, or a boolean derived from one (a "win", a crossover) follows the
host's load, and every number a PR is judged by comes from the chip through
benchmark/run.py and the driver's ledger (PERF.md §2). What is folded (all
optional — a missing artifact folds nothing):

  baselines_out/program_lint.json
                                per-program module bytes (constant_bloat
                                rule), the memory/cost ledger columns
                                (memory_budget rule: peak_bytes, flops)
                                and the per-axis collective wire ledger
                                (collective_axes rule: ops/bytes per mesh
                                axis, pinned at tolerance 0)
  baselines_out/chaos_matrix.json
                                the resilience fault × loop matrix
                                (tools/chaos_run.py): per-cell ok flags —
                                a fault class silently flipping from
                                masked/guarded to FAILED gates nonzero
                                (kind "ok", tolerance 0)
  baselines_out/straggler_study.json
                                the exact-vs-approx crossover sweep
                                (tools/straggler_study.py, ISSUE 8):
                                per-cell reached_target /
                                residual_within_bound / full-recovery
                                bools at tolerance 0 (a residual
                                exceeding its analytic bound is never
                                noise), feasibility flags pinned in BOTH
                                directions (kind "pinned" — a budget-
                                infeasible cell silently becoming
                                feasible is a semantic change, not an
                                improvement)
  baselines_out/autopilot_study.json
                                the adaptive-autopilot-vs-fixed scenario
                                study (tools/autopilot_study.py, ISSUE
                                14): beats-fixed (a count of worker-
                                steps) / remediation-attribution /
                                quarantine-clean certificates at
                                tolerance 0, cell feasibility pinned both
                                directions
  baselines_out/fleet_slo.json  the fleet observatory's SLO verdicts
                                (tools/fleet_study.py, ISSUE 19) at
                                tolerance 0, the error-budget burn and
                                the detection P/R pinned
  baselines_out/wire_study.json
                                the shadow-quantized wire matrix
                                (tools/wire_study.py, ISSUE 10): shadow
                                residual / flag agreement pinned at
                                tolerance 0 (deterministic decode of a
                                deterministically quantized wire), shadow
                                detection P/R + det_preserved as
                                0-tolerance ok flags, logical wire bytes
                                at the bytes tolerance
  baselines_out/segment_study.json
                                the streaming segmented wire
                                (tools/segment_study.py, ISSUE 16):
                                segment counts + per-segment physical
                                bytes pinned tolerance-0 in both
                                directions
  baselines_out/tree_study.json
                                the hierarchical tree aggregation
                                (tools/tree_study.py, ISSUE 17): the
                                per-cell bytes_ok / detection-parity
                                bools at tolerance 0, per-LEVEL ingest
                                bytes pinned tolerance-0 both ways (the
                                leaf level must keep summing exactly to
                                the flat per-step bytes)
  baselines_out/device_profile.json
                                the device-trace attribution ledger
                                (tools/device_profile.py, ISSUE 9):
                                explicit-collective instruction/byte
                                counts pinned at tolerance 0 both ways,
                                manifest cross-check + seeded mismatch
                                control as 0-tolerance ok flags

Tolerances are per metric KIND (relative change vs baseline): bytes 10%,
flops 2% (analytic flops should not drift at all without an algorithm
change), booleans and pinned values 0. Improvements and new metrics are
reported, never fatal; metrics that disappear are reported as missing
(fatal only under --strict-missing, so artifact sets can evolve).

Exit codes: 0 clean / snapshot written; 1 regression(s); 2 no baseline
(run --snapshot first and commit it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SNAPSHOT_REL = os.path.join("baselines_out", "perf_watch.json")

# metric kinds: comparison direction + default relative tolerance
KINDS = {
    "bytes": {"dir": "lower_better", "tol": 0.10},
    "flops": {"dir": "lower_better", "tol": 0.02},
    "ok": {"dir": "higher_better", "tol": 0.0},
    # semantic flags with no good direction: ANY flip is a regression
    # (e.g. a budget-infeasible straggler cell silently becoming feasible)
    "pinned": {"dir": "equal", "tol": 0.0},
}


def _read_json(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except Exception:
        return None


def fold_program_lint(root: str, metrics: dict) -> None:
    path = os.path.join(root, "baselines_out", "program_lint.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/program_lint.json"
    if "all_ok" in data:
        metrics["lint.all_ok"] = {"value": float(bool(data["all_ok"])),
                                  "kind": "ok", "source": src}
    for row in data.get("rows", []):
        if row.get("control"):
            continue
        name = row.get("name")
        rules = row.get("rules") or {}
        module_bytes = (rules.get("constant_bloat") or {}).get("module_bytes")
        if isinstance(module_bytes, (int, float)):
            metrics[f"lint.{name}.module_bytes"] = {
                "value": float(module_bytes), "kind": "bytes", "source": src}
        mem = (rules.get("memory_budget") or {}).get("memory") or {}
        if isinstance(mem.get("peak_bytes"), (int, float)):
            metrics[f"lint.{name}.peak_bytes"] = {
                "value": float(mem["peak_bytes"]), "kind": "bytes",
                "source": src}
        flops = (rules.get("memory_budget") or {}).get("flops")
        if isinstance(flops, (int, float)):
            metrics[f"lint.{name}.flops"] = {
                "value": float(flops), "kind": "flops", "source": src}
        # the per-axis wire ledger (sharding auditor, rule 8): ops and
        # bytes per mesh axis are structural — ANY drift is a topology
        # change, so they ride pinned (tol 0) in both directions
        ledger = (rules.get("collective_axes") or {}).get("axis_ledger")
        for axis, led in sorted((ledger or {}).items()):
            for col in ("ops", "bytes"):
                if isinstance(led.get(col), (int, float)):
                    metrics[f"lint.{name}.coll.{axis}.{col}"] = {
                        "value": float(led[col]), "kind": "pinned",
                        "source": src}


def fold_chaos(root: str, metrics: dict) -> None:
    """Resilience chaos matrix: one ok-flag per (loop, fault) cell plus the
    roll-up — masked→crashed is a 1→0 flip on a 0-tolerance "ok" metric.
    Worker-targeted cells additionally carry a forensics ``attributed``
    flag (the accused set named every injected worker, tools/chaos_run.py):
    an attribution silently flipping false gates at tolerance 0 too.
    Every cell now also carries an ``incident`` verdict (obs/incidents.py,
    ISSUE 13 — the expected incident type raised with the right worker
    attribution, nothing spurious): the per-cell ``incident.ok`` folds at
    tolerance 0, so a detector silently going blind (or flapping) on a
    committed fault class gates nonzero — the flipped-row control test in
    tests/test_cli_tools.py proves that gate live."""
    path = os.path.join(root, "baselines_out", "chaos_matrix.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/chaos_matrix.json"
    if "all_ok" in data:
        metrics["chaos.all_ok"] = {"value": float(bool(data["all_ok"])),
                                   "kind": "ok", "source": src}
    for row in data.get("rows", []):
        loop, fault = row.get("loop"), row.get("fault")
        if not loop or not fault:
            continue
        metrics[f"chaos.{loop}.{fault}.ok"] = {
            "value": float(bool(row.get("ok"))), "kind": "ok",
            "source": src}
        if "attributed" in row:
            metrics[f"chaos.{loop}.{fault}.attributed"] = {
                "value": float(bool(row["attributed"])), "kind": "ok",
                "source": src}
        # ISSUE 10 NaN-safety flags on the nan_grad cells: the numerics
        # columns staying finite-sentineled (and the fault staying
        # visible in the nonfinite fraction) gate at tolerance 0 too
        for flag in ("numerics_finite", "fault_visible"):
            if flag in row:
                metrics[f"chaos.{loop}.{fault}.{flag}"] = {
                    "value": float(bool(row[flag])), "kind": "ok",
                    "source": src}
        # ISSUE 13 incident verdict: the cell's expected incident type
        # raised + attributed, nothing spurious — 0-tolerance gate
        if isinstance(row.get("incident"), dict):
            metrics[f"chaos.{loop}.{fault}.incident_ok"] = {
                "value": float(bool(row["incident"].get("ok"))),
                "kind": "ok", "source": src}


def fold_straggler(root: str, metrics: dict) -> None:
    """Straggler-study crossover artifact (tools/straggler_study.py): the
    certificate bools gate at tolerance 0 — a cell whose measured residual
    creeps past its analytic bound, stops reaching the target loss, or
    loses full batch recovery is a correctness regression, never noise.
    Infeasible cells
    (exact-code budget exceeded) fold only their feasibility flag — a
    budget-exceeded scenario silently becoming "feasible" (or vice versa)
    is a semantic change worth tripping on too."""
    path = os.path.join(root, "baselines_out", "straggler_study.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/straggler_study.json"
    if "all_ok" in data:
        metrics["straggler.all_ok"] = {
            "value": float(bool(data["all_ok"])), "kind": "ok",
            "source": src}
    for row in data.get("rows", []):
        family, drops = row.get("family"), row.get("drop_count")
        if family is None or drops is None:
            continue
        key = f"straggler.{family}.e{drops}"
        metrics[f"{key}.feasible"] = {
            "value": float(bool(row.get("feasible"))), "kind": "pinned",
            "source": src}
        if not row.get("feasible"):
            continue
        for flag in ("reached_target", "residual_within_bound"):
            metrics[f"{key}.{flag}"] = {
                "value": float(bool(row.get(flag))), "kind": "ok",
                "source": src}
        if isinstance(row.get("recovered_fraction_min"), (int, float)):
            # ok-kind at its raw value: any coverage LOSS gates at 0
            # tolerance, recoveries never do (higher_better)
            metrics[f"{key}.recovered_fraction_min"] = {
                "value": float(row["recovered_fraction_min"]),
                "kind": "ok", "source": src}


def fold_autopilot(root: str, metrics: dict) -> None:
    """Autopilot-study artifact (tools/autopilot_study.py, ISSUE 14): the
    adaptive-control certificates gate at tolerance 0 — the autopilot
    beating every fixed configuration on compute-to-target
    (``beats_fixed``), every remediation naming its triggering incident
    (``remediations_attributed``), the dial actually moving both
    directions, and the quarantined worker never corrupting the aggregate
    (``quarantine_clean``). Cell feasibility is pinned BOTH directions:
    the fixed-approx row silently becoming feasible under the adversary
    scenario would mean the family's Byzantine-certificate validation
    regressed."""
    path = os.path.join(root, "baselines_out", "autopilot_study.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/autopilot_study.json"
    for flag in ("all_ok", "autopilot_beats_fixed"):
        if flag in data:
            metrics[f"autopilot.{flag}"] = {
                "value": float(bool(data[flag])), "kind": "ok",
                "source": src}
    for row in data.get("rows", []):
        cell = row.get("cell")
        if not cell:
            continue
        key = f"autopilot.{cell}"
        metrics[f"{key}.feasible"] = {
            "value": float(bool(row.get("feasible"))), "kind": "pinned",
            "source": src}
        if not row.get("feasible"):
            continue
        metrics[f"{key}.reached_target"] = {
            "value": float(bool(row.get("reached_target"))), "kind": "ok",
            "source": src}
        for flag in ("remediations_attributed", "dialed_down", "dialed_up",
                     "quarantine_clean"):
            if flag in row:
                metrics[f"{key}.{flag}"] = {
                    "value": float(bool(row[flag])), "kind": "ok",
                    "source": src}


def fold_wire_study(root: str, metrics: dict) -> None:
    """Wire-study artifact (tools/wire_study.py, ISSUES 10 + 15): the
    shadow residual and flag-agreement columns are PINNED at tolerance 0
    in both directions — a deterministic seeded decode of a
    deterministically quantized wire moving AT ALL is a semantic change
    (the flipped-row control in tests/test_cli_tools.py proves the gate
    live). The detection-preserved bool and shadow detection P/R gate as
    0-tolerance ok-kind; wire bytes ride at the bytes tolerance so a
    ledger drift (dim change) shows up without gating honest model edits.
    The ISSUE 15 REAL-wire rows add narrow-wire detection P/R (ok-kind),
    the pinned end-to-end error, and PHYSICAL bytes/worker; the locator
    cells pin the n=32 s=3 blocker certificate in both directions."""
    path = os.path.join(root, "baselines_out", "wire_study.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/wire_study.json"
    if "all_ok" in data:
        metrics["wire.all_ok"] = {"value": float(bool(data["all_ok"])),
                                  "kind": "ok", "source": src}
    for row in data.get("rows", []):
        mode = row.get("mode", "shadow")
        if mode == "locator":
            # ISSUE 15 locator cells: the blocker certificate is PINNED in
            # both directions — the λ=0 row silently becoming usable means
            # the exact path changed; the regularized row losing usability
            # means the blocker is back. Margins pin too (deterministic
            # seeded trials).
            n, s, dtype = row.get("n"), row.get("s"), row.get("dtype")
            if n is None or dtype is None:
                continue
            reg = "reg" if row.get("regularized") else "unreg"
            key = f"wire.locator.n{n}s{s}.{dtype}.{reg}"
            metrics[f"{key}.usable"] = {
                "value": float(bool(row.get("usable"))), "kind": "pinned",
                "source": src}
            for col in ("honest_dev_max_noadv", "adv_dev_min"):
                if isinstance(row.get(col), (int, float)):
                    metrics[f"{key}.{col}"] = {
                        "value": float(row[col]), "kind": "pinned",
                        "source": src}
            continue
        fam, dtype, k = row.get("family"), row.get("dtype"), row.get("k")
        if fam is None or dtype is None or k is None:
            continue
        if mode == "real":
            # ISSUE 15 real-wire rows: detection P/R on the narrow wire's
            # own flags + the end-to-end error pinned at tolerance 0
            # (deterministic seeded runs of a deterministic quantizer);
            # PHYSICAL bytes at the bytes tolerance
            key = f"wire.real.{fam}.{dtype}.k{k}"
            for col in ("det_precision", "det_recall"):
                if isinstance(row.get(col), (int, float)):
                    metrics[f"{key}.{col}"] = {
                        "value": float(row[col]), "kind": "ok",
                        "source": src}
            if isinstance(row.get("end_to_end_err"), (int, float)):
                metrics[f"{key}.end_to_end_err"] = {
                    "value": float(row["end_to_end_err"]),
                    "kind": "pinned", "source": src}
            metrics[f"{key}.det_preserved"] = {
                "value": float(bool(row.get("det_preserved"))),
                "kind": "ok", "source": src}
            w = row.get("wire") or {}
            if isinstance(w.get("physical_bytes_per_worker"),
                          (int, float)):
                metrics[f"{key}.physical_bytes_per_worker"] = {
                    "value": float(w["physical_bytes_per_worker"]),
                    "kind": "bytes", "source": src}
            continue
        key = f"wire.{fam}.{dtype}.k{k}"
        for col in ("shadow_err_max", "shadow_residual_max",
                    "shadow_flag_agree_min"):
            if isinstance(row.get(col), (int, float)):
                metrics[f"{key}.{col}"] = {
                    "value": float(row[col]), "kind": "pinned",
                    "source": src}
        metrics[f"{key}.det_preserved"] = {
            "value": float(bool(row.get("det_preserved"))), "kind": "ok",
            "source": src}
        for col in ("det_precision_shadow", "det_recall_shadow"):
            if isinstance(row.get(col), (int, float)):
                metrics[f"{key}.{col}"] = {
                    "value": float(row[col]), "kind": "ok", "source": src}
        per = (row.get("wire") or {}).get("bytes_per_worker") or {}
        if isinstance(per.get(dtype), (int, float)):
            metrics[f"{key}.bytes_per_worker"] = {
                "value": float(per[dtype]), "kind": "bytes", "source": src}


def fold_segment_study(root: str, metrics: dict) -> None:
    """Segment-study artifact (tools/segment_study.py, ISSUE 16): the
    per-cell segment COUNTS and per-segment physical bytes, PINNED at
    tolerance 0 in BOTH directions — a segment silently appearing,
    vanishing, or changing size is a wire-format change, never noise (the
    flipped-row control in tests/test_segments.py proves the gate live).
    The study's overlap fractions and ms/step win are wall-clock measures
    of a CPU run and are not folded."""
    path = os.path.join(root, "baselines_out", "segment_study.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/segment_study.json"
    for row in data.get("rows", []):
        dtype, s = row.get("dtype"), row.get("segments")
        if dtype is None or s is None:
            continue
        key = f"segment.{dtype}.s{s}"
        seg = (row.get("wire") or {}).get("segments") or {}
        if isinstance(seg.get("count"), (int, float)):
            metrics[f"{key}.segments_count"] = {
                "value": float(seg["count"]), "kind": "pinned",
                "source": src}
        for i, b in enumerate(seg.get("physical_bytes_per_worker") or []):
            if isinstance(b, (int, float)):
                metrics[f"{key}.seg{i}_bytes_per_worker"] = {
                    "value": float(b), "kind": "pinned", "source": src}


def fold_tree_study(root: str, metrics: dict) -> None:
    """Tree-study artifact (tools/tree_study.py, ISSUE 17): the
    hierarchical CodedReduce evidence a CPU can state exactly. The per-cell
    bools gate at tolerance 0 — bytes_ok (leaf-level ingest sums exactly to
    the flat per-step bytes) and the detection-parity pin on every
    s_g >= 1 cell (tree flags == flat flags under the same live adversary;
    the flipped-row control in tests/test_tree.py proves the gate live).
    The per-LEVEL byte columns are PINNED in both directions — a level's
    bytes moving at all is a topology/wire-format change, never noise. The
    study's decode and critical-path ms, the win they decide and the
    crossover n are wall-clock measures of a CPU run and are not folded."""
    path = os.path.join(root, "baselines_out", "tree_study.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/tree_study.json"
    for row in data.get("rows", []):
        n, g = row.get("n"), row.get("fanout")
        if row.get("kind") == "flat" or n is None or g is None:
            continue
        key = f"tree.n{n}.g{g}"
        metrics[f"{key}.bytes_ok"] = {
            "value": float(bool(row.get("bytes_ok"))), "kind": "ok",
            "source": src}
        det = row.get("detection") or {}
        if det.get("checked"):
            metrics[f"{key}.detection_ok"] = {
                "value": float(bool(det.get("ok"))), "kind": "ok",
                "source": src}
            for col in ("precision_tree", "recall_tree"):
                if isinstance(det.get(col), (int, float)):
                    metrics[f"{key}.{col}"] = {
                        "value": float(det[col]), "kind": "ok",
                        "source": src}
        tb = (row.get("ledger") or {}).get("tree") or {}
        for i, b in enumerate(tb.get("level_bytes_per_step") or []):
            if isinstance(b, (int, float)):
                metrics[f"{key}.level{i}_bytes_per_step"] = {
                    "value": float(b), "kind": "pinned", "source": src}


def fold_device_profile(root: str, metrics: dict) -> None:
    """Device-trace attribution artifact (tools/device_profile.py, ISSUE
    9): the explicit-collective instruction/byte ledger pinned at tolerance
    0 in BOTH directions (the runtime trace and the static Manifest must
    agree; a collective appearing OR vanishing is a semantic change, never
    noise). Cross-check flags and the seeded mismatch control gate as
    0-tolerance ok-kind. The phases' shares of a CPU trace's time are not
    folded: the chip's are the benchmark's per-layer metrics."""
    path = os.path.join(root, "baselines_out", "device_profile.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/device_profile.json"
    if "all_ok" in data:
        metrics["device.all_ok"] = {"value": float(bool(data["all_ok"])),
                                    "kind": "ok", "source": src}
    for row in data.get("cells", []):
        cell = row.get("cell")
        if not cell:
            continue
        if row.get("control"):
            metrics[f"device.{cell}.tripped"] = {
                "value": float(bool(row.get("ok"))), "kind": "ok",
                "source": src}
            continue
        programs = row.get("programs", [])
        for pi, prog in enumerate(programs):
            # today every cell profiles ONE program and the key is the bare
            # cell; a multi-program cell suffixes the module so a second
            # program can never silently overwrite the first's gate rows
            base = f"device.{cell}" if len(programs) == 1 else \
                f"device.{cell}.{prog.get('module') or pi}"
            check = prog.get("cross_check") or {}
            metrics[f"{base}.cross_check_ok"] = {
                "value": float(bool(check.get("ok"))), "kind": "ok",
                "source": src}
            expl = (prog.get("collectives") or {}).get("explicit") or {}
            for kind, led in sorted(expl.items()):
                if not led.get("instructions") and not (
                        check.get("expected") or {}).get(kind):
                    continue
                metrics[f"{base}.coll.{kind}.instructions"] = {
                    "value": float(led.get("instructions", 0)),
                    "kind": "pinned", "source": src}
                metrics[f"{base}.coll.{kind}.bytes"] = {
                    "value": float(led.get("bytes", 0)),
                    "kind": "pinned", "source": src}


def fold_fleet(root: str, metrics: dict) -> None:
    """Fleet-SLO artifact (tools/fleet_study.py, ISSUE 19): the fleet
    observatory's certificates gate at tolerance 0 — every cell's SLO
    verdict bool, the deterministic error-budget burn PINNED at zero
    (a clean or in-budget cell starting to burn is a regression; a
    burning cell silently going quiet is a contract change that must
    re-baseline consciously), and the detection SLO's P/R pinned at
    the certificate 1.0 on the adversary cells. The remediated cells'
    MTTR is a wall-clock measure and is not folded; that every repair
    names its incident (``mttr_attributed``) is."""
    path = os.path.join(root, "baselines_out", "fleet_slo.json")
    data = _read_json(path)
    if not isinstance(data, dict):
        return
    src = "baselines_out/fleet_slo.json"
    if "all_ok" in data:
        metrics["fleet_slo.all_ok"] = {
            "value": float(bool(data["all_ok"])), "kind": "ok",
            "source": src}
    rows = data.get("rows", [])
    metrics["fleet_slo.cells"] = {
        "value": float(len(rows)), "kind": "pinned", "source": src}
    for row in rows:
        cell = row.get("cell")
        if not cell:
            continue
        key = f"fleet_slo.{cell}"
        metrics[f"{key}.ok"] = {
            "value": float(bool(row.get("ok"))), "kind": "ok",
            "source": src}
        metrics[f"{key}.state_done"] = {
            "value": float(row.get("state") == "done"), "kind": "ok",
            "source": src}
        metrics[f"{key}.run_id_present"] = {
            "value": float(bool(row.get("run_id"))), "kind": "ok",
            "source": src}
        if "budget_burned" in row:
            metrics[f"{key}.budget_burned"] = {
                "value": float(row["budget_burned"]), "kind": "pinned",
                "source": src}
        slo = row.get("slo") or {}
        for name, res in sorted(slo.items()):
            if not isinstance(res, dict) or not res.get("evaluated"):
                continue
            metrics[f"{key}.{name}.ok"] = {
                "value": float(bool(res.get("ok"))), "kind": "ok",
                "source": src}
        det = slo.get("detection_quality") or {}
        if det.get("evaluated"):
            for col in ("precision", "recall"):
                if det.get(col) is not None:
                    metrics[f"{key}.detection.{col}"] = {
                        "value": float(det[col]), "kind": "pinned",
                        "source": src}
        mttr = slo.get("incident_mttr") or {}
        if mttr.get("mttr_s") is not None:
            metrics[f"{key}.mttr_attributed"] = {
                "value": float(mttr.get("unattributed", 0) == 0
                               and bool(mttr.get("attributed"))),
                "kind": "ok", "source": src}


def fold_all(root: str) -> dict:
    metrics: dict = {}
    fold_program_lint(root, metrics)
    fold_chaos(root, metrics)
    fold_straggler(root, metrics)
    fold_autopilot(root, metrics)
    fold_fleet(root, metrics)
    fold_wire_study(root, metrics)
    fold_segment_study(root, metrics)
    fold_tree_study(root, metrics)
    fold_device_profile(root, metrics)
    return metrics


def compare(baseline: dict, current: dict, tols: dict) -> dict:
    """Per-metric verdicts. A metric regresses when its relative change in
    the kind's bad direction exceeds the kind's tolerance."""
    regressions, improvements, unchanged, missing, new = [], [], [], [], []
    for name in sorted(set(baseline) | set(current)):
        if name not in baseline:
            new.append({"metric": name, **current[name]})
            continue
        if name not in current:
            missing.append({"metric": name, **baseline[name]})
            continue
        base, cur = baseline[name], current[name]
        kind = cur["kind"]
        spec = KINDS[kind]
        tol = tols.get(kind, spec["tol"])
        b, c = float(base["value"]), float(cur["value"])
        if b == 0.0:
            rel = 0.0 if c == 0.0 else float("inf") * (1 if c > 0 else -1)
        else:
            rel = (c - b) / abs(b)
        if spec["dir"] == "equal":
            bad, good = abs(rel) > tol, False
        else:
            bad = rel > tol if spec["dir"] == "lower_better" else rel < -tol
            good = rel < -tol if spec["dir"] == "lower_better" else rel > tol
        row = {"metric": name, "kind": kind, "baseline": b, "current": c,
               "rel_change": (round(rel, 4) if rel == rel
                              and abs(rel) != float("inf") else None),
               "tolerance": tol}
        (regressions if bad else improvements if good else unchanged
         ).append(row)
    return {"regressions": regressions, "improvements": improvements,
            "unchanged": unchanged, "missing": missing, "new": new,
            "ok": not regressions}


def _print_report(cmp_report: dict, out=None) -> None:
    out = out if out is not None else sys.stdout  # resolve at call time

    def show(rows, tag):
        for r in rows:
            rel = r["rel_change"]
            # rel is None when the baseline was 0 (a count going 0 -> 1): an
            # infinite relative change, not a no-op
            pct = ("inf%" if rel is None
                   else f"{'+' if rel >= 0 else ''}{rel * 100:.1f}%")
            print(f"  [{tag}] {r['metric']} ({r['kind']}): "
                  f"{r['baseline']:g} -> {r['current']:g} "
                  f"({pct} vs tol {r['tolerance'] * 100:.0f}%)", file=out)

    print(f"perf_watch: {len(cmp_report['regressions'])} regression(s), "
          f"{len(cmp_report['improvements'])} improvement(s), "
          f"{len(cmp_report['unchanged'])} unchanged, "
          f"{len(cmp_report['missing'])} missing, "
          f"{len(cmp_report['new'])} new", file=out)
    show(cmp_report["regressions"], "REGRESSION")
    show(cmp_report["improvements"], "improved")
    for r in cmp_report["missing"]:
        print(f"  [missing] {r['metric']} (was {r['value']:g}, "
              f"{r['source']})", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", type=str, default=".",
                    help="repo root holding baselines_out/")
    ap.add_argument("--baseline", type=str, default="",
                    help=f"baseline snapshot (default <root>/{SNAPSHOT_REL})")
    ap.add_argument("--snapshot", action="store_true",
                    help="write the current fold as the new baseline "
                         "snapshot instead of comparing")
    ap.add_argument("--json", type=str, default="",
                    help="also write the comparison report as JSON here")
    ap.add_argument("--tol-bytes", type=float, default=KINDS["bytes"]["tol"])
    ap.add_argument("--tol-flops", type=float, default=KINDS["flops"]["tol"])
    ap.add_argument("--strict-missing", action="store_true",
                    help="treat metrics that disappeared from the artifacts "
                         "as regressions")
    args = ap.parse_args(argv)

    baseline_path = args.baseline or os.path.join(args.root, SNAPSHOT_REL)
    current = fold_all(args.root)

    if args.snapshot:
        payload = {
            "schema": 1,
            "tool": "tools/perf_watch.py --snapshot",
            "metrics": current,
        }
        os.makedirs(os.path.dirname(baseline_path) or ".", exist_ok=True)
        with open(baseline_path, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        print(f"perf_watch: snapshot of {len(current)} metrics -> "
              f"{baseline_path}")
        return 0

    snap = _read_json(baseline_path)
    if not isinstance(snap, dict) or "metrics" not in snap:
        print(f"perf_watch: no baseline snapshot at {baseline_path} — run "
              f"`python tools/perf_watch.py --snapshot` and commit it",
              file=sys.stderr)
        return 2

    tols = {"bytes": args.tol_bytes, "flops": args.tol_flops}
    report = compare(snap["metrics"], current, tols)
    if args.strict_missing and report["missing"]:
        report["ok"] = False
    _print_report(report)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
