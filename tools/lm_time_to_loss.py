#!/usr/bin/env python
"""Transformer-scale time-to-target-loss under attack (the LM analogue of
tools/time_to_acc.py — VERDICT r3 evidence item: convergence curves at
ResNet-18/LM scale on TPU).

For each variant (cyclic simulate/shared, geo-median, mean under attack,
mean no-attack) the coded LM step (parallel/tp_step.py, n logical workers
vmapped over the available chips) trains on the deterministic synthetic
token stream, pausing every --eval-every steps to score a FIXED held-out
token set (disjoint seed namespace), until eval loss <= --target or
--max-steps. The reference's convergence oracle is held-out metrics from a
separate evaluator process (src/distributed_evaluator.py:92-110); here the
oracle is the same held-out principle at transformer scale.

Wall-clock: train blocks are ONE jitted lax.scan each (tools/_timing.py
fetch-sync protocol), synced by a device->host loss fetch, RTT subtracted;
eval time is excluded from the train clock. Mean-under-attack is expected
NOT to reach the target — its curve records the damage an undefended
aggregator takes at LM scale.

Output JSON (--out): per-variant curves [(step, train_wall_s, eval_loss)],
reached/missed target, plus config. Rewritten after every variant so a
run cut short keeps finished variants.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EVAL_SEED_STRIDE = 999_983  # disjoint from every training (seed, step) pair


def run_variant(cfg_kwargs, mesh, args, rtt):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu import rng as drng
    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.sp_step import synthetic_text
    from draco_tpu.parallel.tp_step import build_tp_train_setup
    from tools._timing import fetch_scalar

    cfg = TrainConfig(**cfg_kwargs)
    setup = build_tp_train_setup(cfg, mesh)
    # blocks are fixed-shape compiled scans, so the last block runs whole
    # even when max_steps isn't a multiple of eval_every (up to
    # eval_every-1 extra steps, reported in the curve); the schedule must
    # cover that overhang
    adv = drng.adversary_schedule(
        cfg.seed, args.max_steps + args.eval_every + 1,
        cfg.num_workers, cfg.num_adversaries)
    # held-out eval set: same distribution, disjoint seed namespace
    eval_toks = jnp.asarray(synthetic_text(
        cfg.seed + EVAL_SEED_STRIDE, 0, args.eval_batches, cfg.batch_size,
        cfg.seq_len, cfg.vocab))

    def loop(state, xs, ms):
        def body(st, batch):
            toks, mask = batch
            st, metrics = setup.train_step(st, toks, mask)
            return st, metrics["loss"]
        return jax.lax.scan(body, state, (xs, ms))

    block = args.eval_every

    def stage(lo):  # train batches for steps [lo, lo+block)
        xs = jnp.asarray(np.stack([
            synthetic_text(cfg.seed, s, cfg.num_workers, cfg.batch_size,
                           cfg.seq_len, cfg.vocab)
            for s in range(lo, lo + block)
        ]))
        ms = jnp.asarray(np.stack(
            [np.asarray(adv[s]) for s in range(lo, lo + block)]))
        return xs, ms

    with mesh:
        xs0, ms0 = stage(1)
        compiled = jax.jit(loop).lower(setup.state, xs0, ms0).compile()

    state = setup.state
    curve, wall, reached = [], 0.0, None
    e0 = float(setup.eval_step(state.params, eval_toks))
    curve.append({"step": 0, "train_wall_s": 0.0, "eval_loss": round(e0, 4)})
    step = 1
    while step <= args.max_steps:
        xs, ms = (xs0, ms0) if step == 1 else stage(step)
        jax.block_until_ready((xs, ms))  # stage off the timed path
        t0 = time.perf_counter()
        state, losses = compiled(state, xs, ms)
        fetch_scalar(losses)  # completion barrier (tools/_timing.py)
        wall += max(time.perf_counter() - t0 - rtt, 0.0)
        hi = step + block - 1
        eloss = float(setup.eval_step(state.params, eval_toks))
        curve.append({"step": hi, "train_wall_s": round(wall, 3),
                      "eval_loss": round(eloss, 4)})
        if eloss <= args.target and reached is None:
            reached = curve[-1]
            break
        step = hi + 1
    return {"curve": curve, "reached": reached,
            "final_eval_loss": curve[-1]["eval_loss"],
            "train_wall_s": round(wall, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/lm_time_to_loss.json")
    ap.add_argument("--num-workers", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--model-dim", type=int, default=768)
    ap.add_argument("--model-heads", type=int, default=12)
    ap.add_argument("--model-layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--target", type=float, default=1.0)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--max-steps", type=int, default=120)
    ap.add_argument("--variants", type=str, default="",
                    help="comma-separated subset to run")
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)

    import jax

    from draco_tpu.parallel.mesh import make_folded_wtp_mesh
    from tools._timing import measure_rtt

    mesh = make_folded_wtp_mesh(args.num_workers)
    dev = jax.devices()[0]

    common = dict(
        network="TransformerLM", dataset="synthetic-text",
        batch_size=args.batch_size, lr=args.lr, momentum=0.9,
        num_workers=args.num_workers, worker_fail=1, err_mode="rev_grad",
        seq_len=args.seq_len, vocab=args.vocab, model_dim=args.model_dim,
        model_heads=args.model_heads, model_layers=args.model_layers,
        compute_dtype="bfloat16", max_steps=args.max_steps + 1, eval_freq=0,
        train_dir="", log_every=10**9,
    )
    variants = {
        "lm_cyclic_s1_simulate": dict(common, approach="cyclic",
                                      redundancy="simulate"),
        "lm_cyclic_s1_shared": dict(common, approach="cyclic",
                                    redundancy="shared"),
        "lm_geomedian": dict(common, approach="baseline",
                             mode="geometric_median"),
        "lm_mean_under_attack": dict(common, approach="baseline",
                                     mode="normal"),
        "lm_mean_no_attack": dict(common, approach="baseline", mode="normal",
                                  worker_fail=0),
    }
    if args.variants:
        keep = {v.strip() for v in args.variants.split(",")}
        variants = {k: v for k, v in variants.items() if k in keep}
        if not variants:
            raise SystemExit(f"no variants match {sorted(keep)}")

    rtt = 0.0 if dev.platform == "cpu" else measure_rtt()
    report = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "num_workers": args.num_workers,
        "batch_size_per_worker": args.batch_size,
        "seq_len": args.seq_len, "model_dim": args.model_dim,
        "model_layers": args.model_layers, "vocab": args.vocab,
        "target_eval_loss": args.target, "eval_every": args.eval_every,
        "rtt_s": round(rtt, 4),
        "variants": {},
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rc = 0
    for name, kw in variants.items():
        print(f"[lm_tta] {name} ...", file=sys.stderr, flush=True)
        try:
            res = run_variant(kw, mesh, args, rtt)
        except Exception as e:
            res = {"error": f"{type(e).__name__}: {e}"[:300]}
            rc = 1
        print(f"[lm_tta] {name}: "
              f"{json.dumps({k: v for k, v in res.items() if k != 'curve'})}",
              file=sys.stderr, flush=True)
        report["variants"][name] = res
        with open(args.out, "w") as fh:  # keep finished variants on loss
            json.dump(report, fh, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "variants"}))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
