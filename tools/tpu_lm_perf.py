#!/usr/bin/env python
"""Transformer-scale perf evidence for the coded-DP step (single chip).

The CNN headline (tools/tpu_perf.py) is HBM-bound at 32×32 — MFU 11.5%
says nothing about the framework on MXU-shaped work. This tool measures the
TransformerLM coded step at a size where the matmuls dominate, in bfloat16,
and shows how the paper's decode-vs-geomedian gap (reference README.md:2,
baseline_master.py:271-276) grows with gradient dimension d: Weiszfeld is
80 full passes over the (n, d) stack per step, the cyclic decode a handful.

Variants (all n logical coded workers vmapped on the available devices via
the GSPMD LM path, parallel/tp_step.py):
  * cyclic s=1 in both redundancy regimes: shared (one-copy fast path) and
    simulate (reference-parity 2s+1-lane redundant compute)
  * geometric median (80 Weiszfeld iterations)
  * krum
  * plain mean, no attack (lower bound)

Timing: utils/timing.py protocol — steps folded into ONE jitted lax.scan
over pre-staged token batches, device→host fetch sync, minus RTT. FLOPs
from XLA cost analysis of the compiled scan (counts the body once). Run
with the host otherwise idle (PERF_HISTORY.md §4).

``--production-loop`` re-times the same variants on the PRODUCTION chunked
token loop (parallel/token_loop.run_token_loop driving train_token_many
with --steps-per-call, PERF_HISTORY.md §4b) instead of this tool's private scan
harness — since the production loop became scan-chunked the two measure the
same fold, and the artifact records ``steps_per_call``/``loop`` so which one
produced each number is explicit.

Usage: python tools/tpu_lm_perf.py [--cpu-mesh N for smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def stage_scan_inputs(cfg, steps):
    """Pre-staged (xs tokens, adversary masks) for `steps` scanned steps —
    the one source of truth for the LM timing/audit input protocol (also
    imported by tools/tpu_lm_lowering_check.py)."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu import rng as drng
    from draco_tpu.parallel.sp_step import synthetic_text

    adv = drng.adversary_schedule(cfg.seed, steps + 1, cfg.num_workers,
                                  cfg.num_adversaries)
    xs = jnp.asarray(np.stack([
        synthetic_text(cfg.seed, s, cfg.num_workers, cfg.batch_size,
                       cfg.seq_len, cfg.vocab)
        for s in range(1, steps + 1)
    ]))
    ms = jnp.asarray(np.stack([np.asarray(adv[s]) for s in range(1, steps + 1)]))
    return xs, ms


def make_scan_loop(setup):
    """The scanned multi-step train loop the timing protocol jits — shared
    with the lowering audit so both always export/compile the same program."""
    import jax

    def loop(state, xs, ms):
        def body(st, batch):
            toks, mask = batch
            st, metrics = setup.train_step(st, toks, mask)
            return st, metrics["loss"]
        return jax.lax.scan(body, state, (xs, ms))

    return loop


def run_lm(cfg, mesh, steps, warmup=1, reps=2):
    """(ms/step, flops/step, last loss) of the jitted LM train step scan."""
    import jax
    import numpy as np

    import bench
    from draco_tpu.parallel.tp_step import build_tp_train_setup
    from draco_tpu.utils.timing import time_scanned_steps

    setup = build_tp_train_setup(cfg, mesh)
    xs, ms = stage_scan_inputs(cfg, steps)
    loop = make_scan_loop(setup)

    with mesh:
        compiled = jax.jit(loop).lower(setup.state, xs, ms).compile()
    flops = bench._compiled_flops(compiled)

    if jax.devices()[0].platform == "cpu":
        # local CPU: block_until_ready is a real barrier; smoke only
        st, losses = compiled(setup.state, xs, ms)
        jax.block_until_ready(losses)
        t0 = time.perf_counter()
        st, losses = compiled(st, xs, ms)
        jax.block_until_ready(losses)
        dt = (time.perf_counter() - t0) / steps
        return dt * 1e3, flops, float(np.asarray(losses)[-1])

    dt, losses = time_scanned_steps(
        compiled, setup.state, (xs, ms), steps=steps, warmup=warmup, reps=reps
    )
    return dt * 1e3, flops, float(np.asarray(jax.device_get(losses))[-1])


def run_lm_production(cfg, mesh, steps):
    """(ms/step, flops/step, last loss) of the PRODUCTION chunked token loop
    (parallel/token_loop.run_token_loop with cfg.steps_per_call) — the loop
    users run, not this tool's private scan harness. A warmup pass on a
    deep-copied state settles compilation of the chunk-shaped programs
    (cached on the setup's jitted callables); the timed pass drives the
    setup's own state (the carries are donated, so each state tree feeds at
    most one loop). The loop's terminal metric flush is a device→host fetch
    (DeferredMetricWriter.sync), i.e. a true execution barrier even on
    remote-dispatch backends; the final fetch_scalar adds the state sync."""
    import jax
    import jax.numpy as jnp

    import bench
    from draco_tpu.parallel.token_loop import run_token_loop
    from draco_tpu.parallel.tp_step import build_tp_train_setup
    from draco_tpu.utils.timing import fetch_scalar, measure_rtt

    if steps % max(cfg.steps_per_call, 1):
        # a remainder chunk would compile its own program INSIDE the timed
        # region (the warmup only settles the K-sized chunk) — reject like
        # tools/host_loop_overhead.py rather than record the inflated number
        raise SystemExit(
            f"--production-loop: --steps {steps} must be divisible by "
            f"--steps-per-call {cfg.steps_per_call}"
        )
    setup = build_tp_train_setup(cfg, mesh)
    K = max(cfg.steps_per_call, 1)
    rtt = 0.0 if jax.devices()[0].platform == "cpu" else measure_rtt()
    warm = setup._replace(state=jax.tree.map(jnp.copy, setup.state))
    st, _ = run_token_loop(warm, cfg, steps=K, quiet=True)
    fetch_scalar(st.step)
    t0 = time.perf_counter()
    st, metrics = run_token_loop(setup, cfg, steps=steps, quiet=True)
    fetch_scalar(st.step)
    dt = max(time.perf_counter() - t0 - rtt, 0.0) / steps
    flops = None
    if K > 1:
        # flops of the actual chunked program, from an explicit lowering of
        # the same jitted callable the loop dispatches. AFTER the timed run
        # on purpose: AOT compile does not share the jit dispatch cache, so
        # doing it first would pay the flagship multi-minute compile twice
        # on a cold persistent cache (warm cache absorbs this one).
        from draco_tpu import rng as drng
        from draco_tpu.parallel.sp_step import synthetic_text
        import numpy as np

        adv = drng.adversary_schedule(cfg.seed, K + 1, cfg.num_workers,
                                      cfg.num_adversaries)
        if cfg.token_gen == "device":
            toks = np.arange(1, K + 1, dtype=np.int32)
        else:
            toks = np.stack([
                synthetic_text(cfg.seed, s, cfg.num_workers, cfg.batch_size,
                               cfg.seq_len, cfg.vocab)
                for s in range(1, K + 1)
            ])
        with mesh:
            # st is the live final state (setup/warm states were donated)
            compiled = setup.train_token_many.lower(
                st, toks, np.asarray(adv[1 : K + 1]), None
            ).compile()
        # XLA cost analysis counts a scan body ONCE regardless of trip count
        # (bench.py), so this already is the per-step figure
        flops = bench._compiled_flops(compiled)
    return dt * 1e3, flops, float(metrics["loss"])


def build_lm_variants(*, batch_size, num_workers, seq_len, vocab, model_dim,
                      model_heads, model_layers, remat, max_steps,
                      scan_layers=False):
    """The canonical LM benchmark variant configs (one source of truth —
    also imported by tools/tpu_lm_lowering_check.py so the offline lowering
    audit can never drift from what this tool measures on chip)."""
    common = dict(
        network="TransformerLM", dataset="synthetic-text",
        batch_size=batch_size, lr=0.01, momentum=0.9,
        num_workers=num_workers, worker_fail=1, err_mode="rev_grad",
        seq_len=seq_len, vocab=vocab, model_dim=model_dim,
        model_heads=model_heads, model_layers=model_layers,
        compute_dtype="bfloat16", remat=remat, scan_layers=scan_layers,
        max_steps=max_steps, eval_freq=0,
        train_dir="", log_every=10**9,
    )
    return {
        # redundancy must be EXPLICIT here: the LM paths honour it now
        # (parallel/tp_step.py simulate lanes); the shared variant would
        # otherwise silently inherit the config default "simulate"
        "lm_cyclic_s1_shared_bf16": dict(common, approach="cyclic",
                                         redundancy="shared"),
        # reference-parity r=2s+1 redundant compute at LM scale
        # (cyclic_worker.py:122-146) — the r-cost VERDICT r2 item 6 asks for
        "lm_cyclic_s1_simulate_bf16": dict(common, approach="cyclic",
                                           redundancy="simulate"),
        # the same coded step with the Pallas flash kernel in place of
        # dense attention — the long-context hot-op on the training path
        "lm_cyclic_s1_shared_bf16_flash": dict(common, approach="cyclic",
                                               redundancy="shared",
                                               attn_impl="flash"),
        "lm_geomedian_bf16": dict(common, approach="baseline",
                                  mode="geometric_median"),
        "lm_krum_bf16": dict(common, approach="baseline", mode="krum"),
        "lm_mean_no_attack_bf16": dict(common, approach="baseline",
                                       mode="normal", worker_fail=0),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="baselines_out/tpu_lm_perf.json")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--num-workers", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--model-dim", type=int, default=768)
    ap.add_argument("--model-heads", type=int, default=12)
    ap.add_argument("--model-layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--remat", action="store_true",
                    help="per-block rematerialisation — buys bigger "
                         "batch × seq at ~1/3 extra fwd FLOPs")
    ap.add_argument("--scan-layers", action="store_true",
                    help="compile the layer stack as one nn.scan body — "
                         "~layers× smaller XLA program, for configs that "
                         "hit compile-time/service ceilings (PERF_HISTORY.md §4)")
    ap.add_argument("--variants", type=str, default="",
                    help="comma-separated subset of variants to run")
    ap.add_argument("--production-loop", action="store_true",
                    help="time the production chunked token loop "
                         "(parallel/token_loop.run_token_loop with "
                         "--steps-per-call) instead of this tool's private "
                         "scan harness — the §1b variants re-timed on the "
                         "path users run")
    ap.add_argument("--steps-per-call", type=int, default=0,
                    help="K for --production-loop (0 = --steps, i.e. the "
                         "whole timed run is one chunk, matching the "
                         "private harness's fold)")
    ap.add_argument("--token-gen", type=str, default="host",
                    choices=["host", "device"],
                    help="--production-loop token stream (config.token_gen)")
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)

    import jax

    import bench
    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.mesh import make_folded_wtp_mesh

    mesh = make_folded_wtp_mesh(args.num_workers)
    dev = jax.devices()[0]
    n_dev = mesh.devices.size

    variants = build_lm_variants(
        batch_size=args.batch_size, num_workers=args.num_workers,
        seq_len=args.seq_len, vocab=args.vocab, model_dim=args.model_dim,
        model_heads=args.model_heads, model_layers=args.model_layers,
        remat=args.remat, max_steps=args.steps + 1,
        scan_layers=args.scan_layers,
    )

    if args.variants:
        keep = {v.strip() for v in args.variants.split(",")}
        variants = {k: v for k, v in variants.items() if k in keep}
        if not variants:
            raise SystemExit(f"no variants match {sorted(keep)}")

    steps_per_call = ((args.steps_per_call or args.steps)
                      if args.production_loop else 1)
    report = {
        "platform": dev.platform,
        "remat": args.remat,
        "scan_layers": args.scan_layers,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "num_workers": args.num_workers,
        "devices_used": n_dev,
        "batch_size_per_worker": args.batch_size,
        "seq_len": args.seq_len,
        "model_dim": args.model_dim,
        "model_layers": args.model_layers,
        "vocab": args.vocab,
        "tokens_per_step": args.num_workers * args.batch_size * args.seq_len,
        "steps_per_scan": args.steps,
        # which loop produced the numbers (bench.py records the same key):
        # production = parallel/token_loop.run_token_loop chunked driver;
        # 1 = this tool's private scan harness folding --steps eagerly
        "steps_per_call": steps_per_call,
        "loop": ("production_run_token_loop" if args.production_loop
                 else "private_scan_harness"),
        "token_gen": args.token_gen if args.production_loop else "host",
    }
    # no peak, hence no MFU, for the --cpu-mesh plumbing smoke; on a TPU an
    # unknown device_kind is an error (bench._PEAK_BF16)
    peak = (bench._peak_flops(report["device_kind"])
            if report["platform"] == "tpu" else None)
    for name, kw in variants.items():
        print(f"[tpu_lm_perf] measuring {name} ...", file=sys.stderr, flush=True)
        t0 = time.time()
        if args.production_loop:
            cfg = TrainConfig(**dict(kw, steps_per_call=steps_per_call,
                                     token_gen=args.token_gen,
                                     max_steps=args.steps + steps_per_call))
            ms, flops, loss = run_lm_production(cfg, mesh, args.steps)
        else:
            ms, flops, loss = run_lm(TrainConfig(**kw), mesh, args.steps,
                                     reps=args.reps)
        print(f"[tpu_lm_perf] {name}: {ms:.2f} ms/step ({time.time()-t0:.0f}s)",
              file=sys.stderr, flush=True)
        report[f"{name}_step_ms"] = round(ms, 3)
        report[f"{name}_loss"] = round(loss, 4)
        if flops:
            report[f"{name}_flops_per_step"] = flops
            if peak:
                report[f"{name}_mfu_vs_bf16_peak"] = round(
                    flops / (ms * 1e-3) / peak, 4
                )
    if ("lm_geomedian_bf16_step_ms" in report
            and "lm_cyclic_s1_shared_bf16_step_ms" in report):
        report["lm_cyclic_vs_geomedian_step_speedup"] = round(
            report["lm_geomedian_bf16_step_ms"]
            / report["lm_cyclic_s1_shared_bf16_step_ms"], 3
        )

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
