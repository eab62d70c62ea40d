#!/usr/bin/env python
"""Host-loop overhead microbench: eager per-step dispatch vs the scan-chunked
trainer (cfg.steps_per_call = K), measured on the PRODUCTION ``Trainer.run``
path — not a synthetic harness.

The eager loop pays, per step: one jitted dispatch, a per-metric device
fetch, a ``block_until_ready``, and a fresh device_put (tens of microseconds
each on a local backend, but still per-step; what they cost on an attached
chip is ROADMAP S5's open measurement). The chunked loop
pays them once per K steps. This tool times both regimes over the same
config/seed/steps and emits a JSON artifact so the win (or the CPU caveat)
is recorded per-platform.

Model default is FC on synthetic MNIST: matmul-only, so XLA:CPU's
single-threaded scan-body conv execution (PERF_HISTORY.md §4) does not distort the
host-overhead comparison on the CPU mesh. Conv nets on CPU should keep
steps_per_call=1 regardless of what this tool reports for FC.

``--lm`` switches the measured loop to the production TransformerLM token
loop (parallel/token_loop.run_token_loop on the folded tp route): eager
per-step dispatch vs the scan-chunked ``train_token_many`` driver, same
config/seed/steps. TransformerLM is matmul-dominated like FC, so the
XLA:CPU scanned-conv caveat does not apply there either — the artifact
records that directly (chunked vs eager on the same CPU mesh).

Per K the artifact now records compile and steady-state wall SEPARATELY
(ISSUE 5): the warmup pass's executable-build cost (lower + backend
compile seconds observed via the compile sentinel's process-wide counters,
obs/compile_watch.py ``global_stats``) lands in
``compile_ms_by_steps_per_call`` while the timed pass remains pure
steady-state — and ``timed_builds_by_steps_per_call`` records how many
builds fired DURING the timed window (must be 0; anything else means the
timed number silently included a retrace). That split is what makes the
K-sweep comparable across rounds: tools/perf_watch.py diffs both series
against the committed snapshot.

Output: one JSON (default baselines_out/host_loop_overhead.json;
--lm defaults to baselines_out/host_loop_overhead_lm.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_split(fn_warm, fn_timed):
    """Run warmup then the timed section, splitting executable-build cost
    (lower + backend compile seconds, process-wide jax.monitoring counters:
    obs/compile_watch.global_stats) out of each: returns
    ``(timed_result, {"compile_ms", "timed_builds", "timed_compile_ms"})``.
    ``timed_builds`` must be 0 — a build inside the timed window means the
    steady-state number silently absorbed a retrace."""
    from draco_tpu.obs.compile_watch import global_stats, install

    install()
    t_start = global_stats()
    fn_warm()
    t_mid = global_stats()
    result = fn_timed()
    t_end = global_stats()

    def cost_ms(a, b):
        return round((b["lower_s"] - a["lower_s"]
                      + b["compile_s"] - a["compile_s"]) * 1000.0, 1)

    return result, {
        "compile_ms": cost_ms(t_start, t_mid),
        "timed_builds": t_end["builds"] - t_mid["builds"],
        "timed_compile_ms": cost_ms(t_mid, t_end),
    }


def measure_loop(cfg_kwargs: dict, ds, mesh, warmup_steps: int,
                 timed_steps: int) -> "tuple[float, dict]":
    """(ms/step, compile split) of Trainer.run over ``timed_steps`` steps,
    after a warmup run that settles compilation (main chunk shape) and the
    prefetch pipeline."""
    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.training.trainer import Trainer

    cfg = TrainConfig(**cfg_kwargs)
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    try:
        def warm():
            tr.run(max_steps=warmup_steps)
            jax.block_until_ready(tr.state.params)

        def timed():
            t0 = time.perf_counter()
            tr.run(max_steps=warmup_steps + timed_steps)
            jax.block_until_ready(tr.state.params)
            return (time.perf_counter() - t0) / timed_steps * 1000.0

        return _build_split(warm, timed)
    finally:
        tr.close()


def measure_lm_loop(cfg_kwargs: dict, mesh, warmup_steps: int,
                    timed_steps: int) -> "tuple[float, dict]":
    """(ms/step, compile split) of the production run_token_loop over
    ``timed_steps`` steps.

    A warmup pass on a deep-copied state settles compilation (the jitted
    programs are cached on the setup's callables, keyed by chunk shape), then
    the timed pass runs the setup's own state — train_step/train_token_many
    donate their carry, so each state tree drives at most one loop."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.token_loop import run_token_loop
    from draco_tpu.parallel.tp_step import build_tp_train_setup

    cfg = TrainConfig(**cfg_kwargs)
    setup = build_tp_train_setup(cfg, mesh)
    warm_setup = setup._replace(state=jax.tree.map(jnp.copy, setup.state))

    def warm():
        st, _ = run_token_loop(warm_setup, cfg, steps=warmup_steps,
                               quiet=True)
        jax.block_until_ready(st.params)

    def timed():
        t0 = time.perf_counter()
        st, _ = run_token_loop(setup, cfg, steps=timed_steps, quiet=True)
        jax.block_until_ready(st.params)
        return (time.perf_counter() - t0) / timed_steps * 1000.0

    return _build_split(warm, timed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="")
    ap.add_argument("--lm", action="store_true",
                    help="measure the TransformerLM token loop "
                         "(parallel/token_loop.py, folded tp route) instead "
                         "of the CNN Trainer")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--model-dim", type=int, default=64)
    ap.add_argument("--model-heads", type=int, default=2)
    ap.add_argument("--model-layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--network", type=str, default="FC")
    ap.add_argument("--dataset", type=str, default="synthetic-mnist")
    ap.add_argument("--approach", type=str, default="cyclic")
    ap.add_argument("--worker-fail", type=int, default=1)
    ap.add_argument("--err-mode", type=str, default="rev_grad")
    ap.add_argument("--num-workers", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--steps", type=int, default=64,
                    help="timed steps per regime (each K must divide it)")
    ap.add_argument("--ks", type=str, default="1,8,16",
                    help="comma list of steps_per_call values; 1 = eager")
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)

    import jax

    ks = sorted({max(int(k), 1) for k in args.ks.split(",")})
    if 1 not in ks:
        ks = [1] + ks
    for k in ks:
        if args.steps % k:
            raise SystemExit(f"--steps {args.steps} must be divisible by K={k}")

    dev = jax.devices()[0]
    if args.lm:
        from draco_tpu.parallel.mesh import make_folded_wtp_mesh

        mesh = make_folded_wtp_mesh(args.num_workers)
        common = dict(
            network="TransformerLM", dataset="synthetic-text",
            approach=args.approach, worker_fail=args.worker_fail,
            err_mode=args.err_mode, num_workers=args.num_workers,
            batch_size=args.batch_size, lr=0.01, momentum=0.9,
            seq_len=args.seq_len, vocab=args.vocab,
            model_dim=args.model_dim, model_heads=args.model_heads,
            model_layers=args.model_layers,
            max_steps=2 * args.steps + max(ks), eval_freq=0, train_dir="",
            log_every=10**9,
        )
        cfg_report = {
            "network": "TransformerLM", "dataset": "synthetic-text",
            "loop": "parallel/token_loop.run_token_loop (folded tp route)",
            "approach": args.approach, "worker_fail": args.worker_fail,
            "err_mode": args.err_mode, "num_workers": args.num_workers,
            "batch_size_per_worker": args.batch_size,
            "seq_len": args.seq_len, "model_dim": args.model_dim,
            "model_heads": args.model_heads,
            "model_layers": args.model_layers, "vocab": args.vocab,
            "timed_steps": args.steps,
        }
    else:
        from draco_tpu.data.datasets import load_dataset
        from draco_tpu.runtime import make_mesh

        ds = load_dataset(args.dataset, synthetic_train=4096,
                          synthetic_test=128)
        mesh = make_mesh(args.num_workers)
        common = dict(
            network=args.network, dataset=args.dataset,
            approach=args.approach, worker_fail=args.worker_fail,
            err_mode=args.err_mode, num_workers=args.num_workers,
            batch_size=args.batch_size, lr=0.01, momentum=0.9,
            max_steps=2 * args.steps + max(ks), eval_freq=0, train_dir="",
            log_every=10**9,
        )
        cfg_report = {
            "network": args.network, "dataset": args.dataset,
            "approach": args.approach, "worker_fail": args.worker_fail,
            "err_mode": args.err_mode, "num_workers": args.num_workers,
            "batch_size_per_worker": args.batch_size,
            "timed_steps": args.steps,
        }

    rows, compile_rows, timed_builds = {}, {}, {}
    for k in ks:
        if args.lm:
            ms, split = measure_lm_loop(dict(common, steps_per_call=k), mesh,
                                        warmup_steps=k,
                                        timed_steps=args.steps)
        else:
            ms, split = measure_loop(dict(common, steps_per_call=k), ds,
                                     mesh, warmup_steps=k,
                                     timed_steps=args.steps)
        rows[str(k)] = round(ms, 4)
        compile_rows[str(k)] = split["compile_ms"]
        timed_builds[str(k)] = split["timed_builds"]
        print(f"K={k}: {ms:.3f} ms/step steady "
              f"(compile {split['compile_ms']:.0f} ms in warmup, "
              f"{split['timed_builds']} builds in the timed window)",
              flush=True)

    eager = rows["1"]
    big_ks = [k for k in ks if k >= 8]
    best_big = min((rows[str(k)] for k in big_ks), default=None)
    report = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "mode": "lm_token_loop" if args.lm else "cnn_trainer",
        "config": cfg_report,
        "ms_per_step_by_steps_per_call": rows,
        # compile vs steady-state split (ISSUE 5): warmup-pass executable
        # build cost per K, and builds observed during the timed window
        # (must be 0 — else ms/step silently absorbed a retrace); both are
        # perf_watch series
        "compile_ms_by_steps_per_call": compile_rows,
        "timed_builds_by_steps_per_call": timed_builds,
        "eager_ms_per_step": eager,
        "best_chunked_k8plus_ms_per_step": best_big,
        "overhead_saved_ms_per_step": (
            round(eager - best_big, 4) if best_big is not None else None
        ),
        "chunked_k8plus_lowers_overhead": (
            best_big is not None and best_big < eager
        ),
    }
    if not args.out:
        args.out = ("baselines_out/host_loop_overhead_lm.json" if args.lm
                    else "baselines_out/host_loop_overhead.json")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
