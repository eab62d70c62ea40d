#!/usr/bin/env python
"""Replay a run's incident ledger offline and diff it against the live one.

The incident engine (draco_tpu/obs/incidents.py, PERF_HISTORY.md §15) folds the
per-step metric column families into typed, attributed incident episodes
live, streaming onset/offset events to ``train_dir/incidents.jsonl``. This
tool is its offline twin — the same discipline as forensics_report.py:
rebuild the ledger from ``metrics.jsonl`` with the SAME engine (one
implementation, so live and offline cannot drift), diff the two, print the
timeline, and write ``incidents_report.json`` next to the metrics file:

  python tools/incident_report.py train_out/           # a train dir
  python tools/incident_report.py train_out/ --thresholds trust.floor=0.4

Only the RECORD-sourced detectors (decode residual, trust, guard,
nonfinite, numerics drift) are recomputable — they see nothing but metric
columns, so the replay is bit-identical to the live fold whenever every
step was logged (log_every=1, the chaos/report discipline). BEAT-sourced
detectors (throughput, compile storm, prefetch starvation) depend on host
wall-clock and counters that are not columns; their episodes are carried
through from incidents.jsonl verbatim and labelled ``beat`` in the table.
A replay/ledger mismatch on the record-sourced set exits 1 naming the
divergence — that is the report's whole point. The strict diff applies
only when the JSONL covers every step (log_every=1): a subsampled stream
replays fewer firing observations by construction, so the diff degrades
to a labelled carry-through (exit 0) with a rerun hint instead of a false
DIVERGED.

No jax import. Tolerates every partial-artifact state a killed run leaves
behind (obs/replay.py): missing/empty/torn metrics.jsonl or
incidents.jsonl fold to the empty side of the diff, never a crash. The
status.json schema, when present, is validated against the central
contract table (obs/heartbeat.STATUS_BLOCKS).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# draco_tpu.obs is importable without jax — ONE engine implementation for
# the live heartbeat hook and this offline fold, so the two cannot drift
from draco_tpu.obs import incidents as incidents_mod  # noqa: E402
from draco_tpu.obs import replay  # noqa: E402


def infer_num_workers(records: list, status_path: str) -> int:
    """--num-workers fallback chain — the ONE shared implementation
    (obs/replay.infer_num_workers, same rule as forensics_report.py)."""
    return replay.infer_num_workers(records, status_path,
                                    "tools/incident_report.py")


def _episode_key(ep: dict) -> tuple:
    """The diffable identity of an episode: type, onset, offset (None =
    still open), implicated workers. A STILL-OPEN episode's worker set is
    excluded: the ledger's onset line carries the onset-time set while the
    set may have grown since (only the offset event records the final
    union), so comparing it would fail a correct ledger."""
    offset = ep.get("offset_step")
    workers = tuple(ep.get("workers") or ()) if offset is not None else ()
    return (ep.get("type"), ep.get("onset_step"), offset, workers)


def load_ledger_episodes(path: str) -> "tuple[list, bool]":
    """(episodes, multi_run) from incidents.jsonl: offset events are
    closed episodes; onset events with no matching offset are the open
    tails. ``multi_run``: the per-engine ``seq`` counter reset mid-file —
    a resumed run appended a SECOND engine instance's events (detectable
    even when the metrics step range is gap-free), so the strict
    single-engine replay diff does not apply. Torn/empty/missing
    tolerated (obs/replay.iter_jsonl)."""
    opens: dict = {}
    episodes = []
    last_seq = None
    multi_run = False
    for ev in replay.iter_jsonl(path):
        seq = ev.get("seq")
        if isinstance(seq, int):
            if last_seq is not None and seq <= last_seq:
                multi_run = True
            last_seq = seq
        kind, typ = ev.get("event"), ev.get("type")
        if typ is None:
            continue
        # the wall-clock ``ts`` stamp (ISSUE 19) is carried through
        # verbatim but is NOT part of _episode_key — replayed ledgers
        # (which have no wall clock) still diff clean against it
        body = {k: ev.get(k) for k in
                ("type", "severity", "source", "onset_step", "last_step",
                 "steps", "workers", "evidence", "ts")}
        if kind == "onset":
            opens[(typ, ev.get("onset_step"))] = body
        elif kind == "offset":
            body["offset_step"] = ev.get("offset_step")
            opens.pop((typ, ev.get("onset_step")), None)
            episodes.append(dict(body, open=False))
    episodes.extend(dict(b, offset_step=None, open=True)
                    for b in opens.values())
    return episodes, multi_run


def make_report(metrics_path: str, incidents_path: str,
                num_workers: int = 0, thresholds: str = "") -> dict:
    records = replay.train_records(metrics_path, require_loss=True)
    status_path = replay.find_run_files(metrics_path).status
    n = num_workers or infer_num_workers(records, status_path)
    # the run's own effective threshold overrides (the live engine stamps
    # its non-defaults into the status block — incl. make_engine's
    # cyclic_tol <- guard_residual_tol), then any explicit --thresholds on
    # top: the replay must fold with the thresholds the run USED, or a
    # non-default run would falsely diverge
    overrides = {}
    try:
        with open(status_path) as fh:
            status = json.load(fh)
        if isinstance(status, dict):
            overrides.update(
                ((status.get("incidents") or {}).get("thresholds")) or {})
    except (OSError, ValueError):
        pass
    overrides.update(incidents_mod.parse_thresholds(thresholds))
    engine = incidents_mod.IncidentEngine(num_workers=n,
                                          thresholds=overrides)
    for rec in records:
        engine.observe(rec)
    replayed = [dict(ep, offset_step=ep.get("offset_step"))
                for ep in engine.all_episodes()]
    for ep in replayed:
        ep.setdefault("offset_step", None)
    ledger, multi_run = load_ledger_episodes(incidents_path)
    have_ledger = os.path.exists(incidents_path)

    # the strict diff is only meaningful when the JSONL carries EVERY step
    # the live engine observed, exactly once, in order (log_every=1 on a
    # single uninterrupted run — the chaos/report discipline): a
    # subsampled stream (default log cadence), a missing metrics.jsonl,
    # or a RESUMED run re-appending overlapping steps (two live engine
    # instances with reset hysteresis/EW state, which one continuous
    # replay engine cannot reproduce) all degrade to a labelled
    # carry-through instead of a false DIVERGED verdict
    # ... and so does an AUTOPILOT run (control/autopilot.py): its
    # remediation events mark runtime-control state — quarantines mutate
    # the present-mask schedules and the straggle detector's exclusion
    # set, regime swaps change which columns exist — that a pure column
    # replay cannot reproduce, so the ledger is carried through
    controlled = any(e.get("event") == "remediation"
                     for e in replay.iter_jsonl(incidents_path))
    ordered = [r["step"] for r in records
               if isinstance(r.get("step"), int)]
    steps = sorted(set(ordered))
    full_coverage = bool(steps) \
        and len(steps) >= steps[-1] - steps[0] + 1 \
        and all(b > a for a, b in zip(ordered, ordered[1:])) \
        and not multi_run and not controlled

    # diff the RECORD-sourced halves; beat-sourced episodes are carried
    # through (not recomputable offline — module docstring)
    def rec_side(eps):
        return sorted((_episode_key(ep) for ep in eps
                       if incidents_mod.DETECTORS.get(ep.get("type"))
                       and incidents_mod.DETECTORS[ep["type"]].source
                       == "record"))

    replay_keys, ledger_keys = rec_side(replayed), rec_side(ledger)
    only_replay = [k for k in replay_keys if k not in ledger_keys]
    only_ledger = [k for k in ledger_keys if k not in replay_keys]
    match = have_ledger and not only_replay and not only_ledger
    return {
        "tool": "tools/incident_report.py",
        "schema": incidents_mod.INCIDENT_SCHEMA,
        "metrics": metrics_path,
        "incidents": incidents_path,
        "num_workers": n,
        "records_seen": len(records),
        "replayed": replayed,
        "ledger": ledger,
        "diff": {
            "have_ledger": have_ledger,
            "full_coverage": full_coverage,
            "multi_run_ledger": multi_run,
            "controlled_run": controlled,
            "match": match,
            "only_replay": [list(k) for k in only_replay],
            "only_ledger": [list(k) for k in only_ledger],
        },
        "detectors": incidents_mod.detector_table(),
    }


def print_table(report: dict, out=None) -> None:
    out = out if out is not None else sys.stdout  # resolve at call time
    diff = report["diff"]
    print(f"incidents: {report['incidents']}   replayed "
          f"{len(report['replayed'])} episode(s) over "
          f"{report['records_seen']} records   workers: "
          f"{report['num_workers']}", file=out)
    rows = report["ledger"] if diff["have_ledger"] else report["replayed"]
    if not rows:
        print("no incidents (clean run)", file=out)
    else:
        hdr = (f"{'type':<16}{'sev':<10}{'src':<8}{'onset':>7}{'offset':>8}"
               f"{'steps':>7}  workers")
        print(hdr, file=out)
        print("-" * len(hdr), file=out)
        for ep in sorted(rows, key=lambda e: (e.get("onset_step") or 0)):
            off = ep.get("offset_step")
            workers = ",".join(map(str, ep.get("workers") or ())) or "-"
            print(f"{ep['type']:<16}{ep.get('severity', '?'):<10}"
                  f"{ep.get('source', '?'):<8}"
                  f"{ep.get('onset_step', '?'):>7}"
                  f"{off if off is not None else 'open':>8}"
                  f"{ep.get('steps', '?'):>7}  {workers}", file=out)
    if not diff["have_ledger"]:
        print("no incidents.jsonl (pre-incident run or clean run with no "
              "events) — replay-only report", file=out)
    elif not diff["full_coverage"]:
        if diff.get("controlled_run"):
            print("autopilot-controlled run (remediation events in the "
                  "ledger): quarantines and regime swaps are runtime-"
                  "control state a pure column replay cannot reproduce — "
                  "ledger carried through unverified", file=out)
        else:
            print("metrics.jsonl is subsampled (log_every > 1), missing, "
                  "or a resumed run's appended stream — the live fold saw "
                  "observations the replay cannot reproduce, so the "
                  "ledger is carried through unverified (a single "
                  "log_every=1 run gets the strict diff)", file=out)
    elif diff["match"]:
        print("replay == ledger on every record-sourced episode", file=out)
    else:
        for k in diff["only_replay"]:
            print(f"DIVERGED: replay raised {k} but the ledger did not",
                  file=out)
        for k in diff["only_ledger"]:
            print(f"DIVERGED: ledger carries {k} but the replay did not "
                  f"reproduce it", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="train dir, or a metrics.jsonl path")
    ap.add_argument("--num-workers", type=int, default=0,
                    help="worker count (default: status.json, else "
                         "inferred from the present masks)")
    ap.add_argument("--thresholds", type=str, default="",
                    help="detector threshold overrides, the same "
                         "'<det>.<key>=<float>' grammar as "
                         "--incident-thresholds (must match the run's for "
                         "the diff to be meaningful)")
    ap.add_argument("--json", default="",
                    help="report output path (default: "
                         "incidents_report.json next to the metrics file)")
    args = ap.parse_args(argv)

    files = replay.find_run_files(args.path)
    metrics_path, incidents_path = files.metrics, files.incidents
    report = make_report(metrics_path, incidents_path, args.num_workers,
                         args.thresholds)
    print_table(report)
    out_path = args.json or os.path.join(os.path.dirname(metrics_path),
                                         "incidents_report.json")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=1)
    # a clean (empty ledger) run, a subsampled stream (strict diff not
    # applicable), and a matching ledger all exit 0; a record-sourced
    # divergence on a full stream is THE failure this tool exists to catch
    diff = report["diff"]
    return 0 if (not diff["have_ledger"] or not diff["full_coverage"]
                 or diff["match"]) else 1


if __name__ == "__main__":
    sys.exit(main())
