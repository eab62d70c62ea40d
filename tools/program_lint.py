#!/usr/bin/env python
"""Repo-wide static audit of every registered chip-bound program.

Runs the nine lint rules (draco_tpu/analysis/rules.py: constant_bloat,
donation, dtype, collectives, host_traffic, memory_budget, plus the
static sharding auditor's sharding_contract, collective_axes and
replication_leaks — draco_tpu/analysis/sharding.py against the partition
tables in draco_tpu/parallel/partition.py) against every program in the
registry (draco_tpu/analysis/registry.py — the coded-DP CNN
train_step/train_many and all five LM token routes including the K-fused
scan drivers), on the CPU-host mesh via the cross-platform-export
methodology of the lowering-check tools. Then runs the seeded-defect
NEGATIVE CONTROLS (analysis/controls.py); a control row is ``ok`` iff it
trips exactly its rule — a linter that stops seeing defects fails its own
artifact.

The memory_budget rows double as the per-program memory/cost LEDGER
(argument/output/temp/generated-code bytes, peak estimate, analytic
flops): the committed artifact is what tools/perf_watch.py diffs
round-over-round (PERF_HISTORY.md §8).

  python tools/program_lint.py [--out baselines_out/program_lint.json]
      [--fast] [--programs name|regex,...] [--only rule,...]
      [--skip-controls]

``--fast`` skips the non-fast programs (currently only the big-d
constant-bloat guard, which builds ~3.3M params); the fast subset runs in
roughly a minute on the CI host and is what the ``core``-tier test
exercises (tests/test_program_lint.py, PERF_HISTORY.md §6).

The report is rewritten after every row (incremental-artifact discipline).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/program_lint.json")
    ap.add_argument("--fast", action="store_true",
                    help="skip programs registered fast=False (the big-d "
                         "constant-bloat guard, ~3.3M params)")
    ap.add_argument("--programs", type=str, default="",
                    help="comma-separated subset of registered programs; "
                         "each token is an exact name or a regex matched "
                         "with re.search (e.g. --programs 'lm_sp_.*,tree_')")
    ap.add_argument("--only", type=str, default="",
                    help="run only these comma-separated rules (e.g. "
                         "--only sharding_contract,collective_axes); "
                         "implies --skip-controls (controls assert the "
                         "full rule set) and does NOT overwrite the "
                         "default artifact unless --out is given")
    ap.add_argument("--skip-controls", action="store_true",
                    help="skip the seeded-defect negative controls")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU devices (the CI mesh size)")
    args = ap.parse_args(argv)

    from tools._lowering_common import lint_row, run_rows, setup_cpu_host

    setup_cpu_host(args.devices)

    from draco_tpu.analysis import RULE_NAMES, collect
    from draco_tpu.analysis.controls import control_programs

    only = None
    if args.only:
        only = tuple(v.strip() for v in args.only.split(",") if v.strip())
        unknown = set(only) - set(RULE_NAMES)
        if unknown:
            raise SystemExit(f"unknown rules {sorted(unknown)}; "
                             f"rules: {list(RULE_NAMES)}")
        # a partial-rule sweep is a scratch run, never the committed
        # artifact (whose rows must carry the full rule set)
        args.skip_controls = True
        if args.out == "baselines_out/program_lint.json":
            args.out = "baselines_out/program_lint_only.json"

    programs = collect()
    if args.fast:
        programs = [p for p in programs if p.fast]
    if args.programs:
        import re

        tokens = [v.strip() for v in args.programs.split(",") if v.strip()]
        names = {p.name for p in programs}
        keep = set()
        unknown = []
        for tok in tokens:
            if tok in names:  # exact-name compat
                keep.add(tok)
                continue
            hits = {n for n in names if re.search(tok, n)}
            if not hits:
                unknown.append(tok)
            keep |= hits
        if unknown:
            raise SystemExit(f"no registered program matches {unknown}; "
                             f"registered: {sorted(names)}")
        programs = [p for p in programs if p.name in keep]

    named = [(p.name, (lambda p=p: lint_row(p, only=only)))
             for p in programs]
    if not args.skip_controls:
        def control_thunk(c):
            row = lint_row(c.program)
            tripped = row.get("failed_rules", [])
            live = tripped == [c.expected_fail]
            return {**row, "ok": live, "expected_fail": c.expected_fail,
                    "control": True,
                    **({} if live else
                       {"error": f"control must trip exactly "
                                 f"[{c.expected_fail}], tripped {tripped}"})}

        named += [(c.program.name, (lambda c=c: control_thunk(c)))
                  for c in control_programs()]

    report = run_rows(
        args.out,
        "nine static rules (constant_bloat, donation, dtype, collectives, "
        "host_traffic, memory_budget, sharding_contract, collective_axes, "
        "replication_leaks) over jit.trace jaxprs + jax.export StableHLO + "
        "compiled memory/cost analysis + compiled I/O shardings on the "
        "CPU-host mesh; rows named control_* are seeded-defect negative "
        "controls whose ok means 'tripped exactly its rule'",
        named,
        extra={"fast": args.fast, "devices": args.devices,
               "rules": list(only or RULE_NAMES)},
    )
    print(json.dumps({"all_ok": report["all_ok"],
                      "rows": len(report["rows"])}))
    return 0 if report["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
