#!/usr/bin/env python
"""Run the five BASELINE.json configurations end-to-end and record results.

  python tools/run_baselines.py --smoke            # short runs, any hardware
  python tools/run_baselines.py --max-steps 2000   # real grid

Writes one JSON line per config to stdout and baselines_out/results.jsonl:
per-step wall-clock of the eager Trainer loop (its first step's compile
included, so give a real grid enough steps to carry it) + final
loss/accuracy. --smoke shrinks steps and swaps in synthetic data so the
grid runs anywhere in minutes. A step time to quote comes from a cell of
benchmark/run.py, not from here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-steps", type=int, default=50)
    ap.add_argument("--cpu-mesh", type=int, default=0)
    ap.add_argument("--out-dir", type=str, default="baselines_out")
    ap.add_argument("--fresh", action="store_true",
                    help="truncate results.jsonl first (default appends), so "
                         "stale rows from older code can't shadow a re-run")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated subset of preset names to run "
                         "(default: all five)")
    ap.add_argument("--vote-check", type=str, default="",
                    choices=["", "fingerprint", "exact"],
                    help="override the maj_vote row-equality method for the "
                         "rep presets (empty: preset default) — lets the "
                         "chip decide fingerprint-vs-exact at equal config")
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)  # shared bootstrap: compile cache (+ cpu mesh)

    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.presets import PRESETS, get_preset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    os.makedirs(args.out_dir, exist_ok=True)
    results_path = os.path.join(args.out_dir, "results.jsonl")
    rc = 0
    names = list(PRESETS)
    if args.only:
        keep = {v.strip() for v in args.only.split(",") if v.strip()}
        unknown = keep - set(names)
        if unknown:
            raise SystemExit(f"unknown presets {sorted(unknown)}; have {names}")
        names = [n for n in names if n in keep]
    with open(results_path, "w" if args.fresh else "a") as fh:
        for name in names:
            overrides = dict(max_steps=args.max_steps, eval_freq=0,
                             train_dir="", log_every=10**9)
            if args.vote_check and name.startswith("rep-"):
                # only the rep presets run maj_vote; stamping the override
                # into other rows would split equal-config groupings on an
                # inert field
                overrides["vote_check"] = args.vote_check
            if args.smoke:
                overrides.update(
                    dataset="synthetic-mnist" if "lenet" in name else "synthetic-cifar10",
                    batch_size=4, max_steps=min(args.max_steps, 12),
                    # shared: algebraically identical to the r× redundant
                    # compute (see config.redundancy) at 1/r the FLOPs —
                    # keeps the smoke grid tractable on CPU
                    redundancy="shared",
                )
            cfg = get_preset(name, **overrides)
            ds = load_dataset(cfg.dataset, cfg.data_dir,
                              synthetic_train=1024, synthetic_test=128)
            try:
                tr = Trainer(cfg, mesh=make_mesh(cfg.num_workers),
                             dataset=ds, quiet=True)
                t0 = time.perf_counter()
                last = tr.run()
                wall = time.perf_counter() - t0
                rec = {
                    "preset": name,
                    "steps": cfg.max_steps,
                    "ms_per_step": round(1000 * wall / cfg.max_steps, 2),
                    "final_loss": round(last.get("loss", float("nan")), 4),
                    "final_prec1": round(last.get("prec1", float("nan")), 4),
                    "protocol": "eager",
                    "dataset": ds.name,
                    "config": dataclasses.asdict(cfg),
                }
                tr.close()
            except Exception as e:  # record the failure, keep the grid going
                rec = {"preset": name, "error": repr(e)}
                rc = 1
            line = json.dumps(rec)
            print(line, flush=True)
            fh.write(line + "\n")
            fh.flush()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
