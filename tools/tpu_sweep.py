#!/usr/bin/env python
"""Flagship-config utilization frontier: per-worker batch × dtype sweep.

VERDICT r2 item 4: the round-2 headline led with per-worker batch 32 / f32
(MFU 11.5%) with no evidence of where the flagship config's MFU tops out.
This sweep measures ms/step and MFU for the cyclic (simulate) flagship step —
ResNet-18 / CIFAR-10 shapes, n=8 coded workers, one rev_grad adversary — at
per-worker batch {32, 64, 128, 256} × {float32, bfloat16}, same
fetch-synchronised scanned protocol as bench.py.

The JSON is (re)written after every point, so a run cut short keeps the
completed points.

Usage: python tools/tpu_sweep.py [--batches 32,64,128,256]
       [--dtypes float32,bfloat16] [--remat] [--cpu-mesh 8] [--out PATH]
       (--out defaults to baselines_out/tpu_sweep.json, or
       tpu_sweep_remat.json under --remat so the two frontiers never
       clobber each other)
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
    ap.add_argument("--out", type=str, default=None,
                    help="default baselines_out/tpu_sweep.json, or "
                         "tpu_sweep_remat.json under --remat (so a remat "
                         "sweep never clobbers the no-remat frontier)")
    ap.add_argument("--network", type=str, default="ResNet18")
    ap.add_argument("--num-workers", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batches", type=str, default="32,64,128,256")
    ap.add_argument("--dtypes", type=str, default="float32,bfloat16")
    ap.add_argument("--redundancy", type=str, default="simulate")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialise activations (jax.checkpoint) — the "
                         "memory-for-FLOPs trade that unlocks b256+ (the "
                         "no-remat simulate path OOMs HBM there)")
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = ("baselines_out/tpu_sweep_remat.json" if args.remat
                    else "baselines_out/tpu_sweep.json")

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)

    import jax

    import bench
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh

    ds = load_dataset("Cifar10", data_dir="./data")
    mesh = make_mesh(args.num_workers)
    dev = jax.devices()[0]
    device_kind = getattr(dev, "device_kind", dev.platform)
    # no peak, hence no MFU, for the --cpu-mesh plumbing smoke; on a TPU an
    # unknown device_kind is an error (bench._PEAK_BF16)
    peak = bench._peak_flops(device_kind) if dev.platform == "tpu" else None

    report = {
        "platform": dev.platform,
        "device_kind": device_kind,
        "network": args.network,
        "num_workers": args.num_workers,
        "redundancy": args.redundancy,
        "remat": args.remat,
        "mfu_note": ("mfu includes remat recompute FLOPs (hardware "
                     "utilization)" if args.remat else
                     "mfu is model-useful FLOPs / peak"),
        "steps_per_scan": args.steps,
        "peak_bf16_flops": peak,
        "points": [],
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    for dtype in args.dtypes.split(","):
        for bs in [int(b) for b in args.batches.split(",")]:
            kw = dict(
                network=args.network, dataset="Cifar10", batch_size=bs,
                lr=0.01, momentum=0.9, num_workers=args.num_workers,
                worker_fail=1, err_mode="rev_grad",
                approach="cyclic", redundancy=args.redundancy,
                compute_dtype=dtype, remat=args.remat,
                max_steps=args.steps + 1, eval_freq=0, train_dir="",
                log_every=10**9,
            )
            label = f"b{bs}_{dtype}" + ("_remat" if args.remat else "")
            print(f"[tpu_sweep] {label} ...", file=sys.stderr, flush=True)
            t0 = time.time()
            try:
                dt, loss, flops, compile_s = bench.run(kw, ds, mesh,
                                                       args.steps, warmup=1,
                                                       reps=2,
                                                       want_flops=True)
            except Exception as e:
                print(f"[tpu_sweep] {label} FAILED: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
                report["points"].append({"label": label, "batch": bs,
                                         "dtype": dtype,
                                         "error": f"{type(e).__name__}: {e}"[:300]})
                with open(args.out, "w") as fh:
                    json.dump(report, fh, indent=1)
                continue
            # NOTE under --remat the compiled program re-executes the
            # forward inside the backward, so flops (and hence this MFU)
            # include recompute — hardware utilization, not model-useful
            # utilization; the report carries a flag and best_point uses
            # throughput, which is comparable across remat settings
            mfu = (flops / dt / peak) if (flops and peak and dt > 0) else None
            pt = {
                "label": label, "batch": bs, "dtype": dtype,
                "step_ms": round(dt * 1e3, 3),
                "compile_ms": round(compile_s * 1e3, 1),
                "flops_per_step": flops,
                "mfu_vs_bf16_peak": round(mfu, 4) if mfu else None,
                "examples_per_s": round(bs * args.num_workers / dt, 1),
                "measure_s": round(time.time() - t0, 1),
            }
            report["points"].append(pt)
            print(f"[tpu_sweep] {label}: {pt['step_ms']} ms/step, "
                  f"MFU {pt['mfu_vs_bf16_peak']}", file=sys.stderr, flush=True)
            with open(args.out, "w") as fh:
                json.dump(report, fh, indent=1)

    best = max((p for p in report["points"] if p.get("examples_per_s")),
               key=lambda p: p["examples_per_s"], default=None)
    report["best_point"] = best and best["label"]
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
