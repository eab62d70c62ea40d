"""Command-line entry point — flag parity with the reference's
``mpirun -n P+1 python distributed_nn.py`` (reference: src/distributed_nn.py:23-77),
minus the MPI: one process drives the whole mesh (or one per host under
multi-host jax.distributed).

Usage examples:
  python -m draco_tpu.cli --approach cyclic --network LeNet --dataset MNIST \\
      --num-workers 8 --worker-fail 1 --err-mode rev_grad --max-steps 500
  python -m draco_tpu.cli --approach baseline --mode geometric_median ...
"""

from __future__ import annotations

import argparse

from draco_tpu.config import AGG_MODES, SEED, TOKEN_NETWORKS, TrainConfig


def add_fit_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Reference: add_fit_args, distributed_nn.py:23-77."""
    p = parser
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--test-batch-size", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--optimizer", type=str, default="sgd",
                   choices=["sgd", "adam", "adamw"])
    p.add_argument("--weight-decay", type=float, default=0.01,
                   help="adamw's decoupled weight decay (sgd/adam ignore it)")
    p.add_argument("--lr-schedule", type=str, default="constant",
                   choices=["constant", "cosine"],
                   help="cosine: linear warmup then cosine decay to 10%% "
                        "of --lr over --max-steps")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--clip-norm", type=float, default=0.0,
                   help=">0: clip the decoded/aggregated gradient by global "
                        "norm before the optimizer (post-aggregation, so it "
                        "never changes what the Byzantine filter sees)")
    p.add_argument("--max-steps", type=int, default=10000)
    p.add_argument("--network", type=str, default="LeNet")
    p.add_argument("--dataset", type=str, default="MNIST")
    p.add_argument("--data-dir", type=str, default="./data")
    p.add_argument("--approach", type=str, default="baseline",
                   choices=["baseline", "maj_vote", "cyclic", "approx"])
    p.add_argument("--mode", type=str, default="normal",
                   choices=list(AGG_MODES),
                   help="aggregation for --approach baseline (first three "
                        "mirror the reference; the rest are beyond-reference "
                        "robust baselines)")
    p.add_argument("--num-workers", type=int, default=8,
                   help="logical workers n (the reference's mpirun -n minus the PS)")
    p.add_argument("--group-size", type=int, default=3,
                   help="repetition redundancy r for maj_vote")
    p.add_argument("--vote-check", type=str, default="fingerprint",
                   choices=["fingerprint", "exact"],
                   help="maj_vote row-equality check: salted O(r*d) "
                        "fingerprints vs collision-free O(r^2*d) exact "
                        "bit-equality (for mutually-untrusting deployments)")
    p.add_argument("--worker-fail", type=int, default=0, help="s Byzantine workers")
    # approximate code family (--approach approx; coding/approx.py, ISSUE 8)
    p.add_argument("--code-redundancy", type=float, default=1.5,
                   help="approx family: computational redundancy r in "
                        "[1, n] — each worker computes ~r batches (exact "
                        "codes pay r = 2s+1); decode error under drops is "
                        "bounded by the optimal-decoding least squares and "
                        "measured per step (decode_residual vs "
                        "decode_residual_bound metric columns)")
    p.add_argument("--straggler-alpha", type=float, default=0.25,
                   help="approx family design point: the decode is "
                        "dimensioned for up to ceil(alpha*n) absent workers "
                        "per step (--straggle-count is validated against it)")
    p.add_argument("--assignment-scheme", type=str, default="pairwise",
                   choices=["pairwise", "clustered"],
                   help="approx batch-to-worker assignment: pair-wise "
                        "balanced cyclic windows (any r) or clustered "
                        "fractional repetition (integer r dividing n; any "
                        "one survivor per cluster keeps the decode exact)")
    p.add_argument("--err-mode", type=str, default="rev_grad",
                   choices=["rev_grad", "constant", "random", "alie", "ipm"],
                   help="reference modes + colluding attacks on approximate "
                        "robust aggregation (alie: Baruch'19, ipm: Xie'20)")
    p.add_argument("--adversarial", type=float, default=-100.0,
                   help="attack magnitude (reference hardcoded -100)")
    p.add_argument("--adversary-count", type=int, default=None,
                   help="live adversaries per step (default: worker-fail); set "
                        "lower to leave decode budget for stragglers")
    p.add_argument("--straggle-mode", type=str, default="none",
                   choices=["none", "drop"],
                   help="drop: straggle-count workers miss each step's "
                        "deadline and are decoded around as erasures")
    p.add_argument("--straggle-count", type=int, default=0)
    p.add_argument("--redundancy", type=str, default=None,
                   choices=["simulate", "shared"],
                   help="simulate: r-times redundant compute like the reference; "
                        "shared: algebraically identical compute-once fast path "
                        "(default: simulate, except approach=approx which only "
                        "has the shared path)")
    p.add_argument("--decode-granularity", type=str, default="global",
                   choices=["global", "layer"],
                   help="cyclic decode: one locator on the flat gradient, or "
                        "one per parameter tensor like the reference "
                        "(cyclic_master.py:125-129)")
    p.add_argument("--decode-impl", type=str, default="auto",
                   choices=["auto", "xla", "pallas"],
                   help="coded-decode lowering (ops/decode_kernels.py): "
                        "auto = fused Pallas kernels on a one-device TPU "
                        "mesh / historical XLA path on a mesh that spans "
                        "devices and off-TPU; xla pins the historical "
                        "path; pallas demands the fused kernels (an error "
                        "on a multi-device TPU mesh; off-TPU their "
                        "reference XLA lowering, announced on stderr)")
    p.add_argument("--eval-freq", type=int, default=50)
    p.add_argument("--train-dir", type=str, default="./train_out/")
    p.add_argument("--job-name", type=str, default="",
                   help="operator-facing job label stamped into "
                        "status.json (schema 5) — the fleet observatory "
                        "(tools/fleet_report.py) labels runs by it")
    p.add_argument("--checkpoint-step", type=int, default=0)
    p.add_argument("--compress-ckpt", action="store_true",
                   help="write compressed .dcg checkpoints (the reference's "
                        "--compress-grad, applied where bytes still cross a "
                        "slow link in the SPMD design)")
    p.add_argument("--seed", type=int, default=SEED)
    p.add_argument("--log-every", type=int, default=10)
    # long-context / sequence parallelism (TPU-native addition; no reference
    # counterpart — the reference zoo is CNN-only, SURVEY.md §5.7)
    p.add_argument("--seq-shards", type=int, default=1,
                   help="sp mesh-axis size for network=TransformerLM")
    p.add_argument("--sp-attn", type=str, default="ring",
                   choices=["ring", "a2a"],
                   help="sequence-parallel attention: ring (ppermute K/V "
                        "blocks) or a2a (Ulysses head-scatter all_to_all)")
    p.add_argument("--attn-impl", type=str, default="dense",
                   choices=["dense", "flash"],
                   help="single-shard attention: dense (T,T) scores or the "
                        "Pallas blockwise flash kernel (long context on one "
                        "chip; ops/flash_attention.py — on a TPU a T that "
                        "does not tile is an error, off-TPU the dense path "
                        "is the lowering)")
    p.add_argument("--tensor-shards", type=int, default=1,
                   help="tp mesh-axis size (Megatron GSPMD path, tp_step.py)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="Switch-MoE experts per block (0 = dense MLP)")
    p.add_argument("--expert-shards", type=int, default=1,
                   help="ep mesh-axis size sharding the expert stacks")
    p.add_argument("--pipeline-shards", type=int, default=1,
                   help="pp mesh-axis size (GPipe schedule, pp_step.py)")
    p.add_argument("--pp-microbatches", type=int, default=0,
                   help="microbatches per pipeline step (0 = pipeline-shards)")
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--model-dim", type=int, default=128)
    p.add_argument("--model-heads", type=int, default=4)
    p.add_argument("--model-layers", type=int, default=2)
    p.add_argument("--model-spec", type=str, default="",
                   help="network=LatentMoeLM | HybridMoeLM | WindowedMoeLM | "
                        "LoopedLM | ShortConvMoeLM | KdaMoeLM: a JSON file "
                        "holding the one mapping that states the model (a "
                        "published config.json's keys plus layers / "
                        "experts_held / vocab_rows; models/latent_moe.py, "
                        "models/hybrid_moe.py, models/windowed_moe.py, "
                        "models/looped.py, models/conv_moe.py, "
                        "models/kda_moe.py)")
    p.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                   help="force an N-device virtual CPU mesh (testing without TPUs)")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="K training steps fused into one device program "
                        "(lax.scan) — the CNN Trainer and every "
                        "TransformerLM route (sp/tp/ep/pp); hides per-step "
                        "host dispatch/RTT. Eval/checkpoint snap to chunk "
                        "boundaries. Keep 1 for conv nets on CPU (XLA:CPU "
                        "serializes conv thunks in scan bodies, PERF_HISTORY.md §4); "
                        "raise on accelerators and for matmul-dominated "
                        "models (TransformerLM/FC) everywhere")
    p.add_argument("--token-gen", type=str, default="host",
                   choices=["host", "device"],
                   help="TransformerLM token stream: host-generated numpy "
                        "batches, or regenerated in-graph from the scalar "
                        "(seed, step) so the chunked loop uploads K scalars "
                        "per dispatch (parallel/token_loop.py)")
    p.add_argument("--compute-dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"],
                   help="forward/backward dtype; bfloat16 runs the MXU at "
                        "full rate (params/BN stats/logits stay float32)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialise activations in backward (jax.checkpoint)")
    p.add_argument("--profile-dir", type=str, default="",
                   help="capture a jax.profiler device trace of a few steps "
                        "into this directory (SURVEY.md §5.1) — every "
                        "route: the coded-DP trainer and all five "
                        "TransformerLM token routes. With --steps-per-call "
                        "K > 1 the capture window snaps to whole chunks "
                        "(the chunks containing the profiled steps), since "
                        "a chunk is one indivisible device program")
    p.add_argument("--trace-dir", type=str, default="",
                   help="write a Chrome-trace-event trace.json of the HOST "
                        "phases (gather/upload/dispatch/sync/flush/eval/"
                        "ckpt + prefetcher lanes) into this directory — "
                        "open in Perfetto; complements --profile-dir's "
                        "device trace (draco_tpu/obs)")
    from draco_tpu.obs.compile_watch import GUARD_MODES

    p.add_argument("--compile-guard", type=str, default="warn",
                   choices=list(GUARD_MODES),
                   help="steady-state recompilation guard "
                        "(obs/compile_watch.py): every XLA executable build "
                        "is recorded in compiles.jsonl + the trace's "
                        "compile lane; after --compile-warmup builds per "
                        "program a further build warns (default) or raises "
                        "— a mid-run retrace re-pays the compile the "
                        "scan-chunked loops exist to amortize (PERF_HISTORY.md §8)")
    p.add_argument("--numerics-watch", type=str, default="off",
                   choices=["off", "on"],
                   help="numerics observatory (obs/numerics.py, ISSUE 10): "
                        "per-step dynamic-range columns (absmax/rms/"
                        "underflow-overflow fractions at the bf16 and "
                        "int8-per-block thresholds/exponent histogram) for "
                        "the pre-encode gradients, the wire codewords, and "
                        "the decoded aggregate — riding the (K, m) metric "
                        "block at zero extra device fetches (coded "
                        "approaches only)")
    p.add_argument("--wire-dtype", type=str, default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="the REAL worker→aggregator wire dtype (ISSUE 15): "
                        "f32 keeps today's wire bit-for-bit; bf16/int8 "
                        "round the codewords into real narrow buffers "
                        "(int8 with per-block scales over --shadow-block "
                        "elements; --shadow-round stochastic = shared-draw "
                        "stochastic rounding) that cross the sharding "
                        "boundary narrow and widen to f32 only inside the "
                        "decode — 2–4× wire bytes/HBM (PERF_HISTORY.md §17). The "
                        "cyclic decode runs the quantization-aware flag "
                        "threshold + Tikhonov-regularized locator; coded "
                        "approaches only, exclusive with --shadow-wire")
    p.add_argument("--wire-segments", type=int, default=1,
                   help="streaming segmented wire (ISSUE 16): split the d "
                        "dimension of the coded wire into this many "
                        "segments — workers emit per-segment codeword "
                        "buffers and the aggregator decodes each segment "
                        "as it arrives (per-segment syndromes / partial-"
                        "recovery tails, health folded to one per-step "
                        "verdict). 1 keeps today's single-message wire "
                        "bit-for-bit; cuts align to the segment quantum "
                        "(TILE_D, else --shadow-block) so narrow buffers "
                        "are segment-invariant. Coded approaches only")
    p.add_argument("--topology", type=str, default="flat",
                   choices=["flat", "tree"],
                   help="aggregation topology (ISSUE 17, CodedReduce "
                        "arXiv:1902.01981): flat keeps the star — all n "
                        "codewords decode at one logical point; tree "
                        "partitions the worker axis into n/g leaf groups "
                        "of constant fan-in g (--tree-fanout), each "
                        "running the ONE shared small code at the per-"
                        "group budget s_g = min(s, (g-1)//4), decoded "
                        "partials combining level-structured — per-node "
                        "decode cost and ingest bytes are O(g·d), "
                        "independent of n. Cyclic/approx families, "
                        "shared redundancy, global decode granularity")
    p.add_argument("--tree-fanout", type=int, default=4,
                   help="leaf-group fan-in g under --topology tree: must "
                        "divide num-workers with at least 2 groups; the "
                        "per-group Byzantine budget is min(worker-fail, "
                        "(g-1)//4)")
    p.add_argument("--tree-levels", type=int, default=0,
                   help="tree depth L under --topology tree (0 = auto: "
                        "1 + ceil(log_g(n/g))); interior levels combine "
                        "decoded partials with fan-in ≤ g")
    p.add_argument("--shadow-wire", type=str, default="off",
                   choices=["off", "bf16", "int8"],
                   help="shadow-quantized coded wire: round the codewords "
                        "to this dtype in-graph, decode the shadow copy "
                        "alongside the f32 path (which alone updates "
                        "params), and emit shadow_err/shadow_residual/"
                        "shadow_flag_agree + shadow detection columns — "
                        "the ROADMAP item 4 measurement harness "
                        "(tools/wire_study.py drives the committed matrix)")
    p.add_argument("--shadow-round", type=str, default="nearest",
                   choices=["nearest", "stochastic"],
                   help="shadow quantizer rounding: deterministic nearest "
                        "or per-step seeded stochastic rounding (noise "
                        "shared across wire rows, so identical rows stay "
                        "identical)")
    p.add_argument("--shadow-block", type=int, default=256,
                   help="int8 shadow per-block scale granularity "
                        "(elements per f32 scale along the wire row)")
    p.add_argument("--incident-watch", type=str, default="off",
                   choices=["off", "on"],
                   help="incident engine (obs/incidents.py, ISSUE 13): "
                        "fold the telemetry column families + heartbeat "
                        "beats into typed, attributed run-health "
                        "incidents (throughput/residual-drift/trust-"
                        "collapse/guard-burn/numerics/compile-storm/"
                        "prefetch-starvation) with onset/offset "
                        "hysteresis — streamed to train_dir/"
                        "incidents.jsonl + the status.json incidents "
                        "block; host-side only, bitwise-transparent "
                        "(tools/incident_report.py replays it jax-free)")
    p.add_argument("--incident-thresholds", type=str, default="",
                   help="per-detector threshold overrides, comma-"
                        "separated '<detector>.<key>=<float>' (e.g. "
                        "'trust.floor=0.4'); keys validated against the "
                        "declarative registry (PERF_HISTORY.md §15 table)")
    p.add_argument("--autopilot", type=str, default="off",
                   choices=["off", "on"],
                   help="adaptive coding autopilot (draco_tpu/control): "
                        "consume the incident stream at chunk boundaries "
                        "and emit remediations — quarantine trust-"
                        "collapsed workers, dial cyclic redundancy down "
                        "to approx under sustained straggle/starvation "
                        "(and back up on clean evidence), drop the "
                        "shadow dtype on numerics_drift; warm cached "
                        "program swaps, every decision an attributed "
                        "remediation event + control status block. Needs "
                        "--incident-watch on, a --train-dir and "
                        "--steps-per-call > 1")
    p.add_argument("--autopilot-policy", type=str, default="",
                   help="autopilot policy overrides, comma-separated "
                        "'<key>=<float>' (e.g. 'r_low=1.2,"
                        "clean_boundaries=3'); keys validated against "
                        "control.autopilot.DEFAULT_POLICY (PERF_HISTORY.md §16)")
    p.add_argument("--compile-warmup", type=int, default=1,
                   help="XLA builds allowed per registered program (per "
                        "chunk shape) before the compile guard treats a "
                        "build as a steady-state recompilation")
    # resilience layer (draco_tpu/resilience; ISSUE 6)
    p.add_argument("--step-guard", type=str, default="off",
                   choices=["off", "on"],
                   help="in-graph step guard (resilience/guards.py): fold "
                        "decode-health signals + a global-finite check and "
                        "SKIP untrusted optimizer updates via branch-free "
                        "carry passthrough; emits guard_trips/"
                        "skipped_steps metric columns at zero extra device "
                        "fetches. Bitwise-transparent on clean steps")
    p.add_argument("--guard-residual-tol", type=float, default=1e-3,
                   help="decode_residual above this is a guard trip "
                        "(clean decodes sit at f32 solve noise ~1e-6)")
    p.add_argument("--fault-spec", type=str, default="",
                   help="deterministic fault-injection plan "
                        "(resilience/faults.py): comma-separated "
                        "'kind@step[:w<worker>][:d<seconds>]' events — "
                        "nan_grad/inf_grad/over_budget in-graph, "
                        "prefetch_crash/prefetch_hang/sigterm on the host; "
                        "tools/chaos_run.py drives the full matrix")
    p.add_argument("--prefetch-timeout", type=float, default=300.0,
                   dest="prefetch_timeout_s", metavar="SECONDS",
                   help="bound on a token-prefetch worker-thread queue "
                        "wait (0 = wait forever): a dead/hung worker "
                        "raises the named PrefetchStallError instead of "
                        "wedging the main loop (the CNN prefetchers' "
                        "native gather surfaces failures synchronously)")
    p.add_argument("--prefetch-restarts", type=int, default=2,
                   help="bounded prefetcher supervision: on a worker "
                        "exception/stall, abandon + rebuild the prefetcher "
                        "with exponential backoff up to N times before the "
                        "error propagates (0 disables)")
    p.add_argument("--keep-checkpoints", type=int, default=0, metavar="N",
                   help="retain-last-N checkpoint GC after every save (0 = "
                        "keep all, the historical behavior); the newest "
                        "checkpoint always survives")
    return p


def maybe_force_cpu_mesh(args: argparse.Namespace) -> None:
    """Tool bootstrap: enable the persistent XLA compile cache
    (runtime.enable_compile_cache — one policy, every backend), then apply
    --cpu-mesh N (an N-device virtual CPU mesh instead of accelerators).
    Must run before any jax computation; safe to call twice. Every tool and
    the benchmark route through here: cache policy lives in one place."""
    from draco_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    if getattr(args, "cpu_mesh", 0):
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_mesh}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")


def _load_model_spec(path: str):
    if not path:
        return None
    import json

    with open(path) as fh:
        return json.load(fh)


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    return TrainConfig(
        network=args.network,
        dataset=args.dataset,
        data_dir=args.data_dir,
        batch_size=args.batch_size,
        test_batch_size=args.test_batch_size,
        optimizer=args.optimizer,
        weight_decay=args.weight_decay,
        lr_schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        clip_norm=args.clip_norm,
        lr=args.lr,
        momentum=args.momentum,
        max_steps=args.max_steps,
        num_workers=args.num_workers,
        approach=args.approach,
        mode=args.mode,
        group_size=args.group_size,
        vote_check=args.vote_check,
        worker_fail=args.worker_fail,
        code_redundancy=args.code_redundancy,
        straggler_alpha=args.straggler_alpha,
        assignment_scheme=args.assignment_scheme,
        err_mode=args.err_mode,
        adversarial=args.adversarial,
        adversary_count=args.adversary_count,
        straggle_mode=args.straggle_mode,
        straggle_count=args.straggle_count,
        # approx only has the shared (compute-once) encode path; resolve the
        # unset flag to it there so `--approach approx` works bare, while an
        # explicit --redundancy simulate still errors loudly in validate()
        redundancy=args.redundancy if args.redundancy is not None
        else ("shared" if args.approach == "approx" else "simulate"),
        decode_granularity=args.decode_granularity,
        decode_impl=args.decode_impl,
        compute_dtype=args.compute_dtype,
        steps_per_call=args.steps_per_call,
        token_gen=args.token_gen,
        trace_dir=args.trace_dir,
        compile_guard=args.compile_guard,
        compile_warmup=args.compile_warmup,
        numerics_watch=args.numerics_watch,
        wire_dtype=args.wire_dtype,
        wire_segments=args.wire_segments,
        topology=args.topology,
        tree_fanout=args.tree_fanout,
        tree_levels=args.tree_levels,
        shadow_wire=args.shadow_wire,
        shadow_round=args.shadow_round,
        shadow_block=args.shadow_block,
        incident_watch=args.incident_watch,
        incident_thresholds=args.incident_thresholds,
        autopilot=args.autopilot,
        autopilot_policy=args.autopilot_policy,
        step_guard=args.step_guard,
        guard_residual_tol=args.guard_residual_tol,
        fault_spec=args.fault_spec,
        prefetch_timeout_s=args.prefetch_timeout_s,
        prefetch_restarts=args.prefetch_restarts,
        keep_checkpoints=args.keep_checkpoints,
        remat=args.remat,
        eval_freq=args.eval_freq,
        train_dir=args.train_dir,
        job_name=args.job_name,
        checkpoint_step=args.checkpoint_step,
        compress_ckpt=args.compress_ckpt,
        seed=args.seed,
        log_every=args.log_every,
        seq_shards=args.seq_shards,
        sp_attn=args.sp_attn,
        attn_impl=args.attn_impl,
        tensor_shards=args.tensor_shards,
        moe_experts=args.moe_experts,
        expert_shards=args.expert_shards,
        pipeline_shards=args.pipeline_shards,
        pp_microbatches=args.pp_microbatches,
        seq_len=args.seq_len,
        vocab=args.vocab,
        model_dim=args.model_dim,
        model_heads=args.model_heads,
        model_layers=args.model_layers,
        model_spec=_load_model_spec(args.model_spec),
    ).validate()


def main(argv=None):
    parser = add_fit_args(argparse.ArgumentParser(description="draco_tpu trainer"))
    parser.add_argument("--preset", type=str, default="",
                        help="named BASELINE.json config (draco_tpu.presets); "
                             "other flags still override max-steps/eval/etc.")
    args = parser.parse_args(argv)

    maybe_force_cpu_mesh(args)

    from draco_tpu.runtime import init_distributed
    from draco_tpu.training.trainer import Trainer

    init_distributed()
    if args.preset:
        from draco_tpu.presets import get_preset

        cfg = get_preset(
            args.preset, max_steps=args.max_steps, eval_freq=args.eval_freq,
            train_dir=args.train_dir, checkpoint_step=args.checkpoint_step,
            log_every=args.log_every, compute_dtype=args.compute_dtype,
            data_dir=args.data_dir, trace_dir=args.trace_dir,
        )
    else:
        cfg = config_from_args(args)
    profile_dir = args.profile_dir or None
    if cfg.network in TOKEN_NETWORKS:
        # model-parallel paths compose with coded DP on 2-D (w × axis)
        # meshes; config.validate() guarantees at most one axis is active.
        # One mesh rule for all of them (parallel/mesh._make_mesh_w2): the
        # model axis takes its shards, the n logical workers fold onto the
        # devices that are left — 1 chip, 4 chips or a full n × shards slice.
        # --profile-dir routes to every one of them (run_token_loop;
        # chunk-snapped under steps_per_call > 1)
        if cfg.tensor_shards > 1:
            from draco_tpu.parallel import make_mesh_wtp
            from draco_tpu.parallel.tp_step import train_tp

            _, last = train_tp(cfg, make_mesh_wtp(cfg.num_workers,
                                                  cfg.tensor_shards),
                               profile_dir=profile_dir)
        elif cfg.expert_shards > 1:
            from draco_tpu.parallel import make_mesh_wep
            from draco_tpu.parallel.ep_step import train_ep

            _, last = train_ep(cfg, make_mesh_wep(cfg.num_workers,
                                                  cfg.expert_shards),
                               profile_dir=profile_dir)
        elif cfg.pipeline_shards > 1 or cfg.pp_microbatches > 0:
            # pp_microbatches alone still selects the pipeline path: the
            # GPipe schedule runs at S=1 with M microbatches (validated
            # above), rather than silently dropping the flag
            from draco_tpu.parallel import make_mesh_wpp
            from draco_tpu.parallel.pp_step import train_pp

            _, last = train_pp(cfg, make_mesh_wpp(cfg.num_workers,
                                                  cfg.pipeline_shards),
                               profile_dir=profile_dir)
        else:
            # long-context default: (w × sp) mesh, ring/a2a attention
            from draco_tpu.parallel import make_mesh_2d
            from draco_tpu.parallel.sp_step import train_sp

            _, last = train_sp(cfg, make_mesh_2d(cfg.num_workers,
                                                 cfg.seq_shards),
                               profile_dir=profile_dir)
        return last
    trainer = Trainer(cfg)
    try:
        last = trainer.run(profile_dir=profile_dir)
    finally:
        # drains the buffered MetricWriter (tail safety) and writes the
        # final trace.json window
        trainer.close()
    return last


if __name__ == "__main__":
    main()
