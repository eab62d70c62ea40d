#!/usr/bin/env python
"""Fold a run's metrics.jsonl into a per-worker forensics report.

The coded training steps ship their per-worker accusation, presence, and
seeded-adversary masks as packed bitmask columns riding the metric block
(draco_tpu/obs/forensics.py, PERF_HISTORY.md §10). This tool replays the host
ledger over a run's ``metrics.jsonl`` — per-worker accusation counters,
detection precision/recall vs the seeded schedule, exponentially-weighted
trust, and attack **episodes** ("worker 3 was adversarial for steps
120..400") — prints the timeline table, and writes ``forensics.json`` next
to the metrics file (``--json`` overrides):

  python tools/forensics_report.py train_out/          # a train dir
  python tools/forensics_report.py path/to/metrics.jsonl --num-workers 8

No jax import — the packed words live in the JSONL as exact integers and
the ledger fold is pure host arithmetic (a sibling of trace_report.py,
usable on a laptop against artifacts scp'd from a chip job). It tolerates
the partial-artifact states a killed run leaves behind: a missing or empty
metrics.jsonl folds to an empty report, a torn JSONL tail line is skipped,
and records without forensics columns (baseline routes, eval records,
mixed-route train dirs) are ignored.

The worker count comes from ``--num-workers``, else the run's status.json
(schema >= 2 carries it in the ``forensics`` block), else the highest
worker ever marked present in the packed masks — the inference only
under-counts workers that never sent a single row, which contribute
nothing to any counter.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# draco_tpu.obs is importable without jax (packing imports it lazily and
# this tool never packs) — one ledger implementation for the live heartbeat
# and this offline fold, so the two cannot drift; the torn-tolerant JSONL
# reading is the shared replay scaffold (obs/replay.py, ISSUE 13 satellite)
from draco_tpu.obs import replay  # noqa: E402
from draco_tpu.obs.forensics import AccusationLedger  # noqa: E402


def load_records(path: str) -> list:
    """Train records from metrics.jsonl; blank/torn lines skipped, eval
    records dropped. [] when the file is missing or empty — a killed run
    must not take the report down with it (obs/replay.py). Mask-only
    records without a loss still fold (require_loss=False: the ledger
    ignores whatever lacks masks anyway)."""
    return replay.train_records(path, require_loss=False)


def infer_num_workers(records: list, status_path: str) -> int:
    """--num-workers fallback chain — the ONE shared implementation
    (obs/replay.infer_num_workers; incident_report uses it too)."""
    return replay.infer_num_workers(records, status_path,
                                    "tools/forensics_report.py")


def make_report(metrics_path: str, num_workers: int = 0) -> dict:
    records = load_records(metrics_path)
    n = num_workers or infer_num_workers(
        records, replay.find_run_files(metrics_path).status)
    # n > MAX_WORKERS raises the ledger's named bound — an explicit
    # --num-workers above it must error, not silently truncate the table
    ledger = AccusationLedger(n)
    folded = sum(ledger.observe(rec) for rec in records)
    report = ledger.to_dict()
    report.update({
        "tool": "tools/forensics_report.py",
        "metrics": metrics_path,
        "records_seen": len(records),
        "records_with_masks": int(folded),
    })
    return report


def print_table(report: dict, out=None) -> None:
    out = out if out is not None else sys.stdout  # resolve at call time
    print(f"forensics: {report['metrics']}   "
          f"{report['records_with_masks']}/{report['records_seen']} records "
          f"carried masks   workers: {report['num_workers']}", file=out)
    if not report["records_with_masks"]:
        print("no forensics columns found (baseline route, eval-only file, "
              "or a pre-forensics run)", file=out)
        return
    hdr = (f"{'worker':>6}{'present':>9}{'accused':>9}{'tp':>6}{'fp':>6}"
           f"{'fn':>6}{'precision':>11}{'recall':>9}{'trust':>8}"
           f"{'episodes':>10}")
    print(hdr, file=out)
    print("-" * len(hdr), file=out)
    for r in report["workers"]:
        print(f"{r['worker']:>6}{r['present']:>9}{r['accused']:>9}"
              f"{r['tp']:>6}{r['fp']:>6}{r['fn']:>6}"
              f"{r['precision']:>11.3f}{r['recall']:>9.3f}"
              f"{r['trust']:>8.3f}{r['episodes']:>10}", file=out)
    eps = report["episodes"]
    if eps:
        print(f"episodes ({len(eps)}):", file=out)
        for ep in eps:
            tail = "  (open)" if ep.get("open") else ""
            span = (f"step {ep['start']}" if ep["start"] == ep["end"]
                    else f"steps {ep['start']}-{ep['end']}")
            print(f"  worker {ep['worker']}: {span} "
                  f"({ep['steps']} accused){tail}", file=out)
    top = report["summary"]["top_suspects"]
    if top:
        sus = ", ".join(f"w{t['worker']} (accused {t['accused']}, trust "
                        f"{t['trust']:.2f})" for t in top)
        print(f"top suspects: {sus}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="metrics.jsonl, or a directory holding it")
    ap.add_argument("--num-workers", type=int, default=0,
                    help="worker count (default: status.json, else inferred "
                         "from the present masks)")
    ap.add_argument("--json", default="",
                    help="report output path (default: forensics.json next "
                         "to the metrics file)")
    args = ap.parse_args(argv)

    metrics_path = replay.find_run_files(args.path).metrics
    report = make_report(metrics_path, args.num_workers)
    print_table(report)
    out_path = args.json or os.path.join(os.path.dirname(metrics_path),
                                         "forensics.json")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
