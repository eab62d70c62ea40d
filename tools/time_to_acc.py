#!/usr/bin/env python
"""Time-to-accuracy measurement (BASELINE.md's second north-star axis).

The bench image has no real MNIST/CIFAR files and no network egress
(documented in PERF_HISTORY.md §5): the strongest available substitute is the
deterministic class-conditional synthetic sets (draco_tpu/data/datasets.py
``_synthetic`` — learnable, with a held-out test split), standing in for the
reference's convergence oracle (src/distributed_evaluator.py:92-110).

Trains a config, evaluating every ``--eval-every`` steps, until test top-1
reaches --target or --max-steps; records the (wall-clock, step, accuracy)
curve. Wall-clock covers train steps only (eval excluded), timed with the
fetch-synchronised protocol per eval block.

Output: one JSON (default baselines_out/time_to_acc.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="baselines_out/time_to_acc.json")
    ap.add_argument("--network", type=str, default="LeNet")
    ap.add_argument("--dataset", type=str, default="synthetic-mnist")
    ap.add_argument("--approach", type=str, default="cyclic")
    ap.add_argument("--mode", type=str, default="normal",
                    help="aggregation for --approach baseline")
    ap.add_argument("--worker-fail", type=int, default=1)
    ap.add_argument("--err-mode", type=str, default="rev_grad")
    ap.add_argument("--adversarial", type=float, default=-100.0,
                    help="attack magnitude (reference default -100; alie/ipm "
                         "scale linearly relative to it)")
    ap.add_argument("--redundancy", type=str, default="simulate",
                    help="cyclic compute regime: simulate (reference-parity "
                         "2s+1 lanes) | shared (one-copy fast path)")
    ap.add_argument("--group-size", type=int, default=3,
                    help="repetition redundancy r for --approach maj_vote")
    ap.add_argument("--num-workers", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--target", type=float, default=0.98)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--max-steps", type=int, default=1500)
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="K steps fused per device program (the production "
                         "scan-chunked loop); keep 1 on CPU (PERF_HISTORY.md §4)")
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)

    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer
    from tools._timing import fetch_scalar, measure_rtt

    cfg = TrainConfig(
        network=args.network, dataset=args.dataset, approach=args.approach,
        mode=args.mode, redundancy=args.redundancy,
        group_size=args.group_size,
        batch_size=args.batch_size, lr=args.lr, momentum=0.9,
        num_workers=args.num_workers, worker_fail=args.worker_fail,
        err_mode=args.err_mode, adversarial=args.adversarial,
        max_steps=args.max_steps, eval_freq=0,
        steps_per_call=args.steps_per_call,
        train_dir="", log_every=10**9,
    )
    ds = load_dataset(cfg.dataset, cfg.data_dir)
    mesh = make_mesh(cfg.num_workers)
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    dev = jax.devices()[0]
    rtt = measure_rtt()

    curve = []
    train_s = 0.0
    reached = None
    step = 1
    try:
        while step <= args.max_steps:
            hi = min(step + args.eval_every - 1, args.max_steps)
            t0 = time.perf_counter()
            # run() advances its cursor on return, so successive calls train
            # blocks [step, hi] without retraining from step 1
            last = tr.run(max_steps=hi)
            fetch_scalar(tr.state.params)
            train_s += max(time.perf_counter() - t0 - rtt, 0.0)
            rec = tr.evaluate(hi)
            curve.append({
                "step": hi,
                "train_wall_s": round(train_s, 3),
                "prec1_test": round(rec["prec1_test"], 4),
                "loss": round(last.get("loss", float("nan")), 4),
            })
            if rec["prec1_test"] >= args.target and reached is None:
                reached = curve[-1]
                break
            step = hi + 1
    finally:
        tr.close()

    report = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "config": {
            "network": args.network, "dataset": ds.name,
            "approach": args.approach, "mode": args.mode,
            "redundancy": args.redundancy, "group_size": args.group_size,
            "worker_fail": args.worker_fail,
            "err_mode": args.err_mode, "adversarial": args.adversarial,
            "num_workers": args.num_workers,
            "batch_size_per_worker": args.batch_size, "lr": args.lr,
            "steps_per_call": args.steps_per_call,
        },
        "target_prec1": args.target,
        "reached": reached,
        "curve": curve,
        "real_data_available": not ds.synthetic,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    return 0 if reached is not None else 1


if __name__ == "__main__":
    raise SystemExit(main())
