#!/usr/bin/env python
"""Re-verify every committed ``baselines_out/`` artifact in one jax-free
command — ISSUE 10's "is the evidence still true?" button.

The repo's committed artifacts are load-bearing: perf_watch gates rounds
against them, tests assert they cover the registry, and PERF.md quotes
their numbers. Each artifact already has its own verifier; this tool runs
ALL of them (plus schema smokes of the jax-free report tools against
synthesized inputs, so a report-tool regression surfaces here too) and
exits nonzero NAMING THE FIRST FAILURE:

  perf_watch          diff current artifacts vs the committed snapshot
  device_profile      --check: sums/cross-check/control of the committed
                      device-time ledger
  wire_study          --check: ledger arithmetic + bf16 detection pins of
                      the committed shadow-wire matrix, plus (ISSUE 15)
                      the real-wire rows' P/R + physical-bytes pins and
                      the n=32 s=3 regularized-locator certificate
  segment_study       --check: per-segment bytes sums + bounds algebra and
                      the overlap/ms-per-step-win acceptance pins of the
                      committed streaming-wire evidence (ISSUE 16)
  tree_study          --check: plan algebra + per-level byte sums +
                      detection-parity pins + crossover honesty of the
                      committed tree-aggregation evidence (ISSUE 17)
  decode_study        --check: no stale error rows, tree crossover
                      columns self-consistent (ISSUE 17)
  program_lint        committed all_ok roll-up
  sharding audit      every non-control lint row carries ok verdicts for
                      sharding_contract / collective_axes /
                      replication_leaks and the auditor's five live
                      controls are present and tripped (ISSUE 18)
  lint config         ruff.toml / pyproject.toml exists and pins the
                      repo's line-length (declarative; no ruff binary in
                      the image)
  chaos_matrix        committed all_ok roll-up
  straggler_study     committed all_ok roll-up
  chaos incident      every committed chaos cell carries an ``incident``
      coverage        verdict with ok true (expected type raised +
                      attributed, nothing spurious — ISSUE 13)
  trace_report smoke  folds a synthesized trace.json + metrics.jsonl +
                      schema-current status.json (incl. the ``incidents``
                      block) without error
  forensics_report    folds a synthesized packed-mask metrics.jsonl and
      smoke           reproduces the expected per-worker fold
  incident_report     live engine over a synthesized trust collapse →
      smoke           incidents.jsonl; the jax-free replay must reproduce
                      the ledger exactly, torn tail tolerated

Pure artifact folding — runs on a laptop against an scp'd checkout, no
accelerator stack. Wired into tests/test_cli_tools.py.

Usage:
  python tools/check_artifacts.py [--root .]
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _flag_check(relpath, flag="all_ok"):
    def check(root):
        path = os.path.join(root, relpath)
        try:
            with open(path) as fh:
                data = json.load(fh)
        except (OSError, ValueError) as e:
            return f"cannot read {relpath}: {e}"
        if not data.get(flag):
            return f"{relpath}: {flag} is false"
        return None
    return check


def _check_perf_watch(root):
    from tools import perf_watch

    rc = perf_watch.main(["--root", root])
    return None if rc == 0 else f"perf_watch exited {rc}"


def _check_device_profile(root):
    from tools import device_profile

    artifact = os.path.join(root, "baselines_out", "device_profile.json")
    rc = device_profile.main(["--check", "--artifact", artifact])
    return None if rc == 0 else f"device_profile --check exited {rc}"


def _check_wire_study(root):
    from tools import wire_study

    artifact = os.path.join(root, "baselines_out", "wire_study.json")
    rc = wire_study.main(["--check", "--artifact", artifact])
    return None if rc == 0 else f"wire_study --check exited {rc}"


def _check_segment_study(root):
    from tools import segment_study

    artifact = os.path.join(root, "baselines_out", "segment_study.json")
    rc = segment_study.main(["--check", "--artifact", artifact])
    return None if rc == 0 else f"segment_study --check exited {rc}"


def _check_trace_report(root):
    """Schema smoke: the jax-free report must fold a minimal-but-current
    run dir (trace + metrics + a STATUS_SCHEMA-versioned status.json) —
    a schema bump that forgot trace_report trips here, jax-free."""
    from draco_tpu.obs.heartbeat import STATUS_SCHEMA
    from tools import trace_report

    with tempfile.TemporaryDirectory(prefix="check_trace_") as d:
        events = [
            {"name": "dispatch", "ph": "X", "ts": 0.0, "dur": 5000.0,
             "pid": 1, "tid": 1},
            {"name": "flush", "ph": "X", "ts": 5000.0, "dur": 1000.0,
             "pid": 1, "tid": 1},
        ]
        with open(os.path.join(d, "trace.json"), "w") as fh:
            json.dump({"traceEvents": events}, fh)
        with open(os.path.join(d, "metrics.jsonl"), "w") as fh:
            fh.write(json.dumps({"step": 1, "loss": 1.0, "t_comp": 0.01})
                     + "\n")
        status = {"schema": STATUS_SCHEMA, "state": "done", "step": 1,
                  "updated_at": 0.0,
                  "wire": {"family": "cyclic", "dim": 10,
                           "bytes_per_worker": {"f32": 80, "bf16": 40,
                                                "int8": 14}},
                  "numerics": {"nx_wire_absmax": 1.0,
                               "shadow_err_max": 0.001,
                               "shadow_flag_agree_min": 1.0},
                  "incidents": {"total": 1, "open": [],
                                "by_type": {"guard": 1},
                                "last": {"type": "guard", "severity":
                                         "critical", "onset_step": 1,
                                         "workers": [2], "open": False}}}
        with open(os.path.join(d, "status.json"), "w") as fh:
            json.dump(status, fh)
        rc = trace_report.main([d])
        return None if rc == 0 else f"trace_report smoke exited {rc}"


def _check_forensics_report(root):
    from tools import forensics_report

    with tempfile.TemporaryDirectory(prefix="check_fx_") as d:
        rec = {"step": 1, "loss": 1.0, "wmask_accused0": 0b0100,
               "wmask_present0": 0b1111, "wmask_adv0": 0b0100}
        with open(os.path.join(d, "metrics.jsonl"), "w") as fh:
            fh.write(json.dumps(rec) + "\n")
        rc = forensics_report.main([d, "--num-workers", "4"])
        if rc != 0:
            return f"forensics_report smoke exited {rc}"
        rep = json.load(open(os.path.join(d, "forensics.json")))
        if rep["workers"][2]["accused"] != 1 \
                or rep["workers"][2]["tp"] != 1:
            return "forensics_report smoke: fold did not attribute w2"
        return None


def _check_chaos_incidents(root):
    """ISSUE 13: every committed chaos cell must carry an ``incident``
    verdict with ok true (the expected incident type raised, attributed,
    nothing spurious) — a matrix regenerated without the incident watch,
    or with a blind detector, trips here jax-free."""
    path = os.path.join(root, "baselines_out", "chaos_matrix.json")
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        return f"cannot read chaos_matrix.json: {e}"
    rows = data.get("rows") or []
    if not rows:
        return "chaos_matrix.json has no rows"
    for row in rows:
        verdict = row.get("incident")
        if not isinstance(verdict, dict):
            return (f"cell ({row.get('loop')}, {row.get('fault')}) carries "
                    f"no incident verdict — regenerate the matrix with "
                    f"tools/chaos_run.py (incident_watch is on in every "
                    f"cell)")
        if not verdict.get("ok"):
            return (f"cell ({row.get('loop')}, {row.get('fault')}) incident "
                    f"verdict failed: {verdict.get('detail', verdict)}")
    return None


def _check_incident_report(root):
    """Schema smoke: the live engine writes incidents.jsonl over a
    synthesized trust-collapse stream, and the jax-free replay
    (tools/incident_report.py) must reproduce the ledger EXACTLY — then a
    torn tail line must be tolerated. One engine implementation for the
    live fold and the replay, so a divergence here is a real defect."""
    from draco_tpu.obs import incidents as incidents_mod
    from tools import incident_report

    with tempfile.TemporaryDirectory(prefix="check_inc_") as d:
        recs = []
        for step in range(1, 11):
            accused = 0b0100 if step <= 6 else 0
            recs.append({"step": step, "loss": 1.0,
                         "wmask_accused0": accused,
                         "wmask_present0": 0b1111,
                         "wmask_adv0": accused})
        with open(os.path.join(d, "metrics.jsonl"), "w") as fh:
            fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
        engine = incidents_mod.IncidentEngine(
            num_workers=4, out_path=os.path.join(d, "incidents.jsonl"))
        for r in recs:
            engine.observe(r)
        engine.finalize()
        if engine.total_onsets != 1:
            return (f"synthesized trust collapse raised "
                    f"{engine.total_onsets} incidents, expected 1")
        rc = incident_report.main([d, "--num-workers", "4"])
        if rc != 0:
            return f"incident_report replay diverged (exit {rc})"
        rep = json.load(open(os.path.join(d, "incidents_report.json")))
        if not rep["diff"]["match"]:
            return f"incident_report diff mismatch: {rep['diff']}"
        if rep["replayed"][0]["type"] != "trust" \
                or rep["replayed"][0]["workers"] != [2]:
            return f"replay mis-attributed: {rep['replayed'][0]}"
        # torn tail: killed mid-write must not take the report down
        with open(os.path.join(d, "incidents.jsonl"), "a") as fh:
            fh.write('{"v": 1, "event": "ons')
        rc = incident_report.main([d, "--num-workers", "4"])
        return None if rc == 0 else f"torn-tail replay exited {rc}"


def _check_autopilot_study(root):
    """ISSUE 14: the committed scenario artifact must certify the
    autopilot beating every fixed configuration on compute-to-target
    (with at least one fixed row recorded infeasible — the scenario must
    actually close a family out), every remediation attributed to its
    triggering incident, and the quarantine never corrupting the
    aggregate."""
    path = os.path.join(root, "baselines_out", "autopilot_study.json")
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        return f"cannot read autopilot_study.json: {e}"
    if not data.get("autopilot_beats_fixed"):
        return ("autopilot_beats_fixed is false — the adaptive dial lost "
                "to a fixed configuration")
    if not data.get("infeasible_fixed"):
        return ("no fixed configuration was infeasible — the scenario no "
                "longer exercises the certificate boundary")
    rows = {r.get("cell"): r for r in data.get("rows") or []}
    ap_row = rows.get("autopilot")
    if not isinstance(ap_row, dict):
        return "no autopilot row in the artifact"
    for flag in ("remediations_attributed", "dialed_down",
                 "quarantine_clean", "ok"):
        if not ap_row.get(flag):
            return f"autopilot row: {flag} is false"
    for rem in ap_row.get("remediations") or []:
        if not rem.get("trigger") or rem.get("trigger_onset") is None:
            return f"unattributed remediation in artifact: {rem}"
    if not data.get("all_ok"):
        return "autopilot_study.json: all_ok is false"
    return None


def _check_sharding_audit(root):
    """The static sharding audit (rules 7-9) must actually be IN the
    committed lint artifact: every non-control program row carries
    sharding_contract / collective_axes / replication_leaks verdicts with
    ok true, and the auditor's live negative controls are present and
    tripped. An artifact regenerated from a stale checkout (six-rule
    linter) or with blunted controls fails here, jax-free."""
    path = os.path.join(root, "baselines_out", "program_lint.json")
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        return f"cannot read program_lint.json: {e}"
    new_rules = ("sharding_contract", "collective_axes",
                 "replication_leaks")
    missing = [r for r in new_rules if r not in (data.get("rules") or [])]
    if missing:
        return (f"artifact rule list lacks {missing} — regenerate with "
                f"tools/program_lint.py")
    controls = {}
    for row in data.get("rows") or []:
        name = row.get("name")
        if row.get("control"):
            controls[name] = row
            continue
        rules = row.get("rules") or {}
        for rn in new_rules:
            verdict = rules.get(rn)
            if not isinstance(verdict, dict):
                return (f"program row {name!r} carries no {rn} verdict — "
                        f"stale artifact, regenerate")
            if not verdict.get("ok"):
                return (f"program row {name!r} fails {rn}: "
                        f"{verdict.get('error', verdict)}")
    expected_controls = {
        "control_resharded_carry": "sharding_contract",
        "control_unnormalized_spec": "sharding_contract",
        "control_unmatched_param": "sharding_contract",
        "control_wrong_axis_psum": "collective_axes",
        "control_replicated_wire": "replication_leaks",
    }
    for cname, rule in expected_controls.items():
        row = controls.get(cname)
        if row is None:
            return (f"sharding-audit control {cname!r} missing from the "
                    f"artifact")
        if row.get("expected_fail") != rule or not row.get("ok"):
            return (f"control {cname!r} must trip exactly [{rule}] "
                    f"(expected_fail={row.get('expected_fail')}, "
                    f"ok={row.get('ok')})")
    return None


def _check_lint_config(root):
    """Satellite of the static-auditor PR: the repo-wide lint config must
    exist and pin the 79-column limit the codebase is written to (a text
    presence check — the image has no ruff binary and py3.10 has no
    tomllib, so this is deliberately declarative)."""
    for rel in ("ruff.toml", "pyproject.toml"):
        path = os.path.join(root, rel)
        if os.path.exists(path):
            try:
                with open(path) as fh:
                    text = fh.read()
            except OSError as e:
                return f"cannot read {rel}: {e}"
            if "line-length" not in text:
                return f"{rel} exists but pins no line-length"
            return None
    return "no ruff.toml / pyproject.toml lint config at the repo root"


def _check_tree_study(root):
    from tools import tree_study

    artifact = os.path.join(root, "baselines_out", "tree_study.json")
    rc = tree_study.check_artifact(artifact)
    return None if rc == 0 else f"tree_study --check exited {rc}"


def _check_decode_study(root):
    from tools import decode_study

    artifact = os.path.join(root, "baselines_out", "decode_study.json")
    rc = decode_study.check_artifact(artifact)
    return None if rc == 0 else f"decode_study --check exited {rc}"


def _check_fleet_slo(root):
    """ISSUE 19: re-verify the committed fleet SLO matrix — every cell's
    acceptance bools recomputed from the cell's own SLO results (clean
    cells burned zero deterministic budget, adversary cells held P/R 1.0
    on live adversaries, remediated cells carry a finite attributed
    MTTR), both production loops covered, and a stale status schema
    REFUSED rather than silently re-blessed."""
    from tools import fleet_study

    path = os.path.join(root, "baselines_out", "fleet_slo.json")
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, ValueError) as e:
        return f"cannot read baselines_out/fleet_slo.json: {e}"
    problems = fleet_study.verify_payload(payload)
    return problems[0] if problems else None


CHECKS = (
    ("perf_watch", _check_perf_watch),
    ("device_profile --check", _check_device_profile),
    ("wire_study --check", _check_wire_study),
    ("segment_study --check", _check_segment_study),
    ("tree_study --check", _check_tree_study),
    ("decode_study --check", _check_decode_study),
    ("program_lint all_ok",
     _flag_check(os.path.join("baselines_out", "program_lint.json"))),
    ("sharding audit coverage", _check_sharding_audit),
    ("lint config present", _check_lint_config),
    ("chaos_matrix all_ok",
     _flag_check(os.path.join("baselines_out", "chaos_matrix.json"))),
    ("chaos incident coverage", _check_chaos_incidents),
    ("straggler_study all_ok",
     _flag_check(os.path.join("baselines_out", "straggler_study.json"))),
    ("autopilot_study certificates", _check_autopilot_study),
    ("fleet_slo certificates", _check_fleet_slo),
    ("trace_report smoke", _check_trace_report),
    ("forensics_report smoke", _check_forensics_report),
    ("incident_report smoke", _check_incident_report),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", type=str, default=".",
                    help="repo root holding baselines_out/")
    ap.add_argument("--verbose", action="store_true",
                    help="show the sub-verifiers' own output")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    for name, check in CHECKS:
        buf = io.StringIO()
        try:
            if args.verbose:
                err = check(root)
            else:
                with contextlib.redirect_stdout(buf), \
                        contextlib.redirect_stderr(buf):
                    err = check(root)
        except Exception as e:  # noqa: BLE001 — naming failures IS the job
            err = f"{type(e).__name__}: {e}"
        if err is not None:
            sub = buf.getvalue().strip()
            if sub:
                print(sub)
            print(f"check_artifacts: FAILED at {name!r}: {err}")
            return 1
        print(f"check_artifacts: ok  {name}")
    print(f"check_artifacts: all {len(CHECKS)} artifact checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
