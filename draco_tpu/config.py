"""Experiment configuration.

Knob parity with the reference CLI (reference: src/distributed_nn.py:23-77 and
src/single_machine.py:27-54), folded into one dataclass instead of per-entry
argparse. Quirks intentionally dropped: ``--comm-type`` (admitted fake,
reference README.md:111), ``--num-aggregate`` (unused, distributed_nn.py:60).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# Deterministic seed shared by every participant, mirroring the reference's
# global SEED_=428 (reference: src/util.py:17). Every device derives the
# adversary schedule / group seeds / shuffles from this, so all agree.
SEED = 428

# Aggregation modes for approach=baseline. First three mirror the reference
# (baseline_master.py:118-129); the rest are beyond-reference robust
# baselines (aggregation.py). Lives here (jax-free) so the CLI's --mode
# choices and validate() share one source of truth.
AGG_MODES = ("normal", "geometric_median", "krum", "coord_median",
             "trimmed_mean", "multi_krum", "bulyan")

# Networks that train on token sequences through the shared token loop
# (parallel/token_loop.py) and come from models.build_lm; everything else is
# an image model on the CNN Trainer.
TOKEN_NETWORKS = ("TransformerLM", "LatentMoeLM", "HybridMoeLM",
                  "WindowedMoeLM", "LoopedLM", "ShortConvMoeLM", "KdaMoeLM")
# the token models stated by ONE mapping of a published config's keys
# (TrainConfig.model_spec), each with the module that checks and builds it
SPEC_NETWORKS = {"LatentMoeLM": "draco_tpu.models.latent_moe",
                 "HybridMoeLM": "draco_tpu.models.hybrid_moe",
                 "WindowedMoeLM": "draco_tpu.models.windowed_moe",
                 "LoopedLM": "draco_tpu.models.looped",
                 "ShortConvMoeLM": "draco_tpu.models.conv_moe",
                 "KdaMoeLM": "draco_tpu.models.kda_moe"}


@dataclasses.dataclass
class TrainConfig:
    # --- model / data (reference: distributed_nn.py:27-37) ---
    # LeNet | FC | ResNet18/34/50/101/152 | VGG11/13/16/19[_bn] | the token
    # models TransformerLM | LatentMoeLM | HybridMoeLM | WindowedMoeLM |
    # LoopedLM | ShortConvMoeLM | KdaMoeLM (TOKEN_NETWORKS)
    network: str = "LeNet"
    dataset: str = "MNIST"  # MNIST | Cifar10 | synthetic variants
    data_dir: str = "./data"
    batch_size: int = 128  # per-worker batch size
    test_batch_size: int = 1000

    # --- optimization (reference: distributed_nn.py:31-43) ---
    optimizer: str = "sgd"  # sgd | adam (reference parity) | adamw (decoupled decay)
    lr: float = 0.01
    momentum: float = 0.9
    weight_decay: float = 0.01  # adamw's decoupled decay (unused by sgd/adam)
    lr_schedule: str = "constant"  # constant | cosine (warmup + cosine to 10%)
    warmup_steps: int = 0  # linear warmup length for lr_schedule=cosine
    clip_norm: float = 0.0  # >0: global-norm clip of the aggregated gradient
    max_steps: int = 10000

    # --- distributed topology ---
    num_workers: int = 8  # n logical workers = size of mesh axis `w`
    # Approach selects the training runtime, mirroring --approach
    # (reference: distributed_nn.py:87-133):
    #   baseline : plain data parallel + robust aggregation per `mode`
    #   maj_vote : repetition code, groups of size `group_size`, majority vote
    #   cyclic   : cyclic (DFT) code, tolerates s Byzantine workers
    #   approx   : approximate gradient code (coding/approx.py) — straggler
    #              tolerance at fractional redundancy `code_redundancy`
    #              close to 1, bounded decode error instead of exactness
    approach: str = "baseline"
    # Aggregation mode for approach=baseline. Reference parity
    # (baseline_master.py:118-129): normal | geometric_median | krum.
    # Beyond-reference robust baselines under the same attack schedules:
    # coord_median | trimmed_mean | multi_krum | bulyan (aggregation.py).
    mode: str = "normal"
    group_size: int = 3  # r, repetition redundancy (reference: distributed_nn.py:70)
    # maj_vote row-equality check: "fingerprint" = O(r·d) salted-hash vote
    # (per-step key, sound unless adversaries know the experiment seed);
    # "exact" = O(r²·d) full pairwise bit-equality, the reference's
    # exact-recovery semantics (rep_master.py:162) with no collision
    # surface — pick it for mutually-untrusting deployments
    # (coding/repetition.py module docstring, threat-model ladder).
    vote_check: str = "fingerprint"
    worker_fail: int = 0  # s, number of Byzantine workers (distributed_nn.py:68)

    # --- approximate code family (approach="approx"; ISSUE 8) ---
    # Computational redundancy r ∈ [1, n]: each worker computes ~r batches
    # (exact codes pay r = 2s+1). Fractional r mixes ⌊r⌋/⌊r⌋+1 loads
    # (coding/assignment.py); the decode error under drops is bounded by
    # the optimal-decoding least squares (coding/approx.py docstring).
    code_redundancy: float = 1.5
    # Straggler design point: the decode is dimensioned for up to
    # ⌈straggler_alpha · n⌉ absent workers per step — validate() holds
    # straggle_count to it, and tools/straggler_study.py sweeps it.
    straggler_alpha: float = 0.25
    # Batch-to-worker assignment: "pairwise" (pair-wise balanced cyclic
    # windows, any r) or "clustered" (fractional repetition, integer r
    # dividing n — any one survivor per cluster keeps the decode exact).
    assignment_scheme: str = "pairwise"

    # --- adversary simulation (reference: distributed_nn.py:64-67) ---
    err_mode: str = "rev_grad"  # rev_grad | constant | random | alie | ipm
    adversarial: float = -100.0  # attack magnitude (model_ops/utils.py:3-4)

    # --- straggler simulation (TPU-native; supersedes the reference's
    # unreferenced tag-77 kill switch, resnet_split.py:625-737) ---
    # "none": every gradient arrives. "drop": straggle_count workers per step
    # miss the deadline; their rows are treated as *erasures* (known-missing):
    # cyclic decodes around them (up to 2s erasure-only, or jointly with
    # adversaries when straggle_count + worker_fail <= s), maj_vote votes
    # among present members, baseline aggregates over present rows.
    straggle_mode: str = "none"  # none | drop
    straggle_count: int = 0
    # Actual adversaries injected per step. None = worker_fail (reference
    # semantics: the code parameter s doubles as the live attack count,
    # distributed_nn.py:68). Set lower to reserve locator budget for
    # stragglers (joint regime: adversary_count + straggle_count <= worker_fail).
    adversary_count: Optional[int] = None

    # --- coded-path execution strategy (TPU-native addition) ---
    # "simulate": every worker really computes its (2s+1) redundant batches,
    #             matching the reference's r× compute cost (cyclic_worker.py:122).
    # "shared":   each distinct batch gradient is computed once on the mesh and
    #             encoded rows are formed algebraically — identical semantics
    #             (per-batch gradients are deterministic), r× less compute.
    redundancy: str = "simulate"
    # Decode granularity: "global" locates the corrupt-row set once on the
    # flattened gradient (valid: corruption is per-worker, shared by layers);
    # "layer" re-runs the locator per layer like the reference
    # (cyclic_master.py:126-128).
    decode_granularity: str = "global"
    # Decode implementation (ISSUE 12; ops/decode_kernels.py). "auto":
    # the fused Pallas decode kernels on a one-device TPU mesh, the
    # historical XLA lowering on a mesh that spans devices (GSPMD cannot
    # partition a Mosaic kernel) and off-TPU — CI and CPU runs keep the
    # bitwise path. "xla": pin the historical lowering everywhere.
    # "pallas": demand the fused kernels — an error on a multi-device TPU
    # mesh; off-TPU their reference lowering (the same fused algorithm
    # through XLA — bounded-err vs xla, identical honest/flag sets), said
    # on stderr. Resolved once per setup. Applies to the cyclic locator
    # chain and the approx partial-recovery decode on every route; the
    # shadow-quantized decode (obs/numerics.py) stays on the xla path its
    # thresholds were calibrated on.
    decode_impl: str = "auto"

    # --- long context / sequence parallelism (TPU-native addition; the
    # reference is CNN-only, SURVEY.md §5.7) ---
    seq_shards: int = 1  # sp mesh-axis size; sequence parallelism spans these
    # SP strategy: "ring" streams K/V blocks over ppermute hops (O(T·T/sp)
    # peak scores, sp hops); "a2a" is Ulysses-style head-scatter all_to_all
    # (2 collectives total, needs model_heads % sp == 0, materialises the
    # full (T,T) score block per head group)
    sp_attn: str = "ring"
    # Single-shard attention implementation (seq_shards == 1): "dense"
    # materialises (T, T) scores per head; "flash" is the Pallas blockwise
    # kernel (ops/flash_attention.py) — O(T·Dh) memory, for long sequences
    # on one chip. On a TPU a T that does not tile raises; off-TPU the
    # dense path is the lowering.
    attn_impl: str = "dense"
    # tp mesh-axis size for the GSPMD tensor-parallel path (parallel/
    # tp_step.py); composes with the coded worker axis on a (w, tp) mesh
    tensor_shards: int = 1
    # Switch-MoE: experts per block (0 = dense MLP) and the ep mesh-axis
    # size sharding the expert stacks (parallel/ep_step.py, models/moe.py)
    moe_experts: int = 0
    expert_shards: int = 1
    # pp mesh-axis size for the GPipe-style pipeline path (parallel/
    # pp_step.py): transformer blocks split into pipeline_shards stages,
    # microbatches flow stage-to-stage over ppermute hops
    pipeline_shards: int = 1
    # microbatches per step for the pipeline schedule (0 = pipeline_shards);
    # more microbatches shrink the bubble: S-1 of M+S-1 ticks are idle
    pp_microbatches: int = 0
    seq_len: int = 256  # tokens per sequence (global, pre-sharding)
    vocab: int = 256
    model_dim: int = 128
    model_heads: int = 4
    model_layers: int = 2
    # network=LatentMoeLM | HybridMoeLM | WindowedMoeLM | LoopedLM |
    # ShortConvMoeLM | KdaMoeLM (SPEC_NETWORKS): the ONE mapping that states
    # the model — a published config.json's keys verbatim plus ``layers``,
    # ``vocab_rows`` and, for the sparse-expert blocks, ``experts_held``
    # ([first, count]; kda_moe.py's ``heads_held`` likewise): the chip's
    # share of a deployment (models/latent_moe.py, hybrid_moe.py,
    # windowed_moe.py, looped.py, conv_moe.py, kda_moe.py).
    # The model_* fields above are TransformerLM's and are not read for it.
    # CLI: --model-spec <file.json>.
    model_spec: Optional[dict] = None

    # --- precision ---
    compute_dtype: str = "float32"  # forward/backward dtype (bfloat16|float32)

    # --- eval / checkpoint (reference: distributed_nn.py:56-75) ---
    eval_freq: int = 50
    train_dir: str = "./train_out/"
    # operator-facing job label stamped into status.json (STATUS_SCHEMA
    # 5, obs/heartbeat.py) — purely observational: the fleet registry
    # (obs/fleet.py) groups/labels runs by it. "" omits the field.
    job_name: str = ""
    # resume from this step if >0; -1 resumes from the NEWEST loadable
    # checkpoint in train_dir (corrupt ones are skipped — the automatic
    # walk-back of resilience/supervisor.restore_with_walkback)
    checkpoint_step: int = 0
    # write checkpoints as shuffled-deflate .dcg archives instead of Orbax
    # dirs — the descendant of the reference's --compress-grad wire toggle
    # (compress_gradient.py:7-15), for train_dirs crossing a slow link.
    # Single-host only (utils/checkpoint.py).
    compress_ckpt: bool = False

    # --- host-loop fusion (TPU-native addition; PERF_HISTORY.md §0/§4b) ---
    # K training steps fused into ONE jitted lax.scan per device program
    # (training/step.py train_many for the coded-DP CNN Trainer;
    # parallel/common.py make_token_train_many + parallel/token_loop.py for
    # every TransformerLM route — single-shard, sp, tp, pp, ep): the host
    # dispatches once per K steps and fetches one (K, m) metrics block
    # instead of per-step scalars, hiding the ~70 ms/dispatch RTT of remote
    # backends behind useful work. Eval/checkpoint cadence snaps to chunk
    # boundaries (explicit remainder chunks, so max_steps need not divide
    # by K). K=1 keeps today's eager per-step loop bit-for-bit. CPU caveat:
    # XLA:CPU runs conv thunks inside scan bodies single-threaded
    # (PERF_HISTORY.md §4), so the default stays 1 for conv nets — raise it on
    # accelerators (and freely for the matmul-dominated TransformerLM /
    # FC, where the caveat does not apply — PERF_HISTORY.md §4b).
    steps_per_call: int = 1
    # Where the synthetic token stream is generated (TransformerLM routes):
    # "host" — numpy synthetic_text per step, uploaded per step/chunk (the
    # historical stream); "device" — the chunked driver regenerates each
    # step's batch in-graph from the scalar (seed, step)
    # (sp_step.synthetic_text_in_graph), so a chunk's upload is K int32
    # scalars and the host token path disappears. The two streams are
    # distinct deterministic draws (jax PRNG vs numpy MT19937); either is
    # internally bitwise-reproducible across K.
    token_gen: str = "host"

    # rematerialise activations in backward (jax.checkpoint) — memory for FLOPs
    remat: bool = False
    # compile the LM's layer stack as one nn.scan over stacked block weights
    # instead of `model_layers` unrolled block programs — identical math,
    # ~layers× smaller XLA program (keeps deep/large configs under
    # compile-time ceilings). LM paths only; changes the params tree layout
    # (one stacked "blocks" subtree), so checkpoints don't interchange with
    # the unrolled form.
    scan_layers: bool = False

    # --- telemetry (draco_tpu/obs; ISSUE 4) ---
    # When set, the production loops write a Chrome-trace-event
    # ``trace_dir/trace.json`` of the HOST phases the chunked regime
    # otherwise hides (gather/upload/dispatch/sync/flush/eval/ckpt +
    # prefetcher worker-thread lanes + queue-depth counters) — open it in
    # chrome://tracing or https://ui.perfetto.dev. Disabled (the default)
    # the tracer is a shared no-op object: no allocation, no clock reads,
    # and never any device fetch either way. Device-side phase attribution
    # is the separate jax.profiler capture (--profile-dir), aligned via the
    # jax.named_scope phase names inside the step programs.
    trace_dir: str = ""
    # Compile/retrace sentinel (obs/compile_watch.py; ISSUE 5). Every XLA
    # executable build is recorded in ``compiles.jsonl`` (next to trace.json
    # when trace_dir is set, else next to metrics.jsonl) and surfaced in the
    # status.json heartbeat. After ``compile_warmup`` builds per registered
    # program (per chunk shape), any further build is a steady-state
    # recompilation — it silently re-pays the multi-second compile the
    # scan-chunk design exists to amortize. compile_guard: "warn" (default)
    # emits RetraceWarning, "raise" fails the dispatch (the test/CI mode the
    # K∈{1,4} equivalence suites run under), "off" records only.
    compile_guard: str = "warn"
    compile_warmup: int = 1
    # Numerics observatory (obs/numerics.py; ISSUE 10). "on" adds per-step
    # dynamic-range columns (absmax / rms / bf16- and int8-threshold
    # underflow-overflow fractions / exponent histogram) for the pre-encode
    # gradients, the post-encode codewords, and the decoded aggregate,
    # riding the existing (K, m) metric block — zero extra device fetches,
    # zero retraces. Coded approaches only (cyclic / maj_vote / approx):
    # the baseline path ships no codewords and emits no optional columns.
    numerics_watch: str = "off"
    # --- the REAL narrow coded wire (obs/numerics.py; ISSUE 15) ---
    # What the worker→aggregator wire PHYSICALLY carries. "f32" keeps
    # today's wire bit-for-bit (no ops added). "bf16"/"int8": the step
    # body rounds the codewords into REAL narrow buffers (bf16 casts;
    # int8 with per-block scales over shadow_block elements and — under
    # shadow_round="stochastic" — shared-draw stochastic rounding) which
    # cross the worker-sharding boundary narrow and are widened to f32
    # only inside the decode (f32 accumulation throughout): the 2–4×
    # wire-bytes/HBM win of PERF_HISTORY.md §13's ledger, landed on the actual
    # coded path. The cyclic decode then runs the quantization-aware flag
    # threshold (per-(n, s, dtype) table derived by tools/wire_study.py)
    # and the Tikhonov-regularized locator (λ scaled to the dtype's noise
    # floor — the PR 10 large-n blocker's fix); the step guard and the
    # decode_residual incident detector widen their tolerances by the
    # dtype's residual slack. Coded approaches only; mutually exclusive
    # with shadow_wire (the shadow is the CALIBRATION mode — it measures
    # a candidate dtype against the f32 wire, which a narrow wire no
    # longer ships).
    wire_dtype: str = "f32"  # f32 | bf16 | int8
    # --- streaming segmented wire (ISSUE 16; ROADMAP item 3) ---
    # Split the d dimension of the coded wire into this many segments:
    # workers emit per-segment codeword buffers (narrow under wire_dtype,
    # with per-segment int8 block scales) and the aggregator decodes each
    # segment as it arrives instead of waiting for the full (n, d) wire —
    # the arXiv:1903.01974 multi-message communication pattern. 1 (the
    # default) keeps today's single-message wire bit-for-bit. S > 1 cuts
    # at multiples of the segment quantum (obs/numerics.wire_segment_bounds:
    # TILE_D when d admits it, else shadow_block), which keeps the int8
    # per-block scales and the shared stochastic-rounding draws segment-
    # invariant — quantize-then-slice equals slice-then-quantize bitwise,
    # so the narrow buffers are unchanged and only the DECODE is
    # segmented. Syndromes and located-row sets are computed per segment;
    # the health/forensics columns fold across segments (residual = max,
    # flagged/loud = union) so guards, detection P/R, incidents and the
    # autopilot see one verdict per step. Coded approaches (cyclic/approx)
    # only; d smaller than the quantum collapses back to one segment.
    wire_segments: int = 1
    # --- hierarchical CodedReduce aggregation (ISSUE 17; ROADMAP item 2) ---
    # topology="tree" partitions the (n,) worker axis into n/tree_fanout
    # leaf groups of constant fan-in g (coding/topology.py — the
    # clustered-assignment window algebra); each group runs its OWN small
    # code (cyclic at s_g = min(worker_fail, (g-1)//4), capped further by
    # the per-(g, s, dtype) narrow-wire threshold table; approx at the
    # configured fractional redundancy), decodes locally, and parents
    # combine decoded (d,) partials level by level — per-node decode cost
    # and ingest bytes stay O(g·d) as n grows (arXiv:1902.01981). The
    # per-group health verdicts fold to one per-step verdict exactly like
    # the wire-segment fold (residual=max, flagged/accused=union), so
    # detection P/R is identical to flat. Coded families only
    # (cyclic/approx, shared redundancy, global decode granularity);
    # composes with wire_dtype and wire_segments.
    topology: str = "flat"  # flat | tree
    tree_fanout: int = 4  # leaf-group size g (must divide num_workers)
    # total tree levels including the leaf level; 0 = auto
    # (1 + ceil(log_g(n/g)), coding/topology.auto_levels)
    tree_levels: int = 0
    # Shadow-quantized wire (obs/numerics.py): round the codewords to the
    # narrow dtype INSIDE the step body, decode the shadow copy alongside
    # the f32 path, and emit shadow_err / shadow_residual /
    # shadow_flag_agree (+ shadow detection counts) columns. The f32 path
    # alone updates params — K∈{1,4} equivalence stays bitwise with the
    # shadow enabled. This is the measurement ROADMAP item 4's real
    # bf16/int8 wire will be built and regression-gated on.
    shadow_wire: str = "off"  # off | bf16 | int8
    # Shadow rounding mode: "nearest" (deterministic round-to-nearest) or
    # "stochastic" (per-step seeded noise, shared across wire rows so
    # bitwise-identical rows quantize identically — maj_vote's soundness
    # condition survives).
    shadow_round: str = "nearest"
    # int8 per-block scale granularity: one f32 scale per this many
    # elements along the wire row (also the blocking the numerics columns'
    # int8 underflow threshold uses).
    shadow_block: int = 256
    # Incident engine (obs/incidents.py; ISSUE 13). "on" folds the
    # per-step column families + the heartbeat beat extras into typed,
    # attributed run-health incidents (throughput regression, decode-
    # residual drift, trust collapse, guard budget burn, numerics drift,
    # compile storms, prefetch starvation) with onset/offset hysteresis —
    # streamed to train_dir/incidents.jsonl and the ``incidents`` block of
    # status.json (STATUS_SCHEMA 4). Host-side only: zero extra device
    # fetches, zero retraces, bitwise-transparent to training. Needs a
    # train_dir (the stream and the heartbeat live there). Any approach:
    # detectors silently skip column families the route does not emit.
    incident_watch: str = "off"
    # Per-detector threshold overrides, comma-separated
    # "<detector>.<key>=<float>" (e.g. "trust.floor=0.4,guard.off_count=2")
    # — keys validated against the declarative detector registry at config
    # time. "" keeps every registered default (PERF_HISTORY.md §15 table).
    incident_thresholds: str = ""

    # --- adaptive coding autopilot (draco_tpu/control; ROADMAP item 5) ---
    # "on": a host-side policy engine consumes the incident stream at
    # chunk boundaries and emits remediations — quarantine a
    # trust-collapsed worker (present-mask exclusion), dial exact cyclic
    # redundancy down to the approx family under sustained
    # straggle/starvation episodes (and back up on sustained clean
    # evidence), drop the shadow wire dtype on numerics_drift. Family
    # swaps are warm cached program swaps (0 steady retraces within a
    # regime); every decision is an attributed `remediation` event in
    # incidents.jsonl + a `control` status.json block. Requires
    # incident_watch="on" (the sensing layer), a train_dir, the chunked
    # regime (steps_per_call > 1 — chunk boundaries are the actuation
    # points; the LM device-token-gen driver runs chunked at any K), and
    # a cyclic/approx starting family.
    autopilot: str = "off"
    # "key=value,..." overrides of control.autopilot.DEFAULT_POLICY
    # (hysteresis boundary counts, trust floor, r_low, budgets) —
    # validated against the policy table at config time.
    autopilot_policy: str = ""

    # --- resilience (draco_tpu/resilience; ISSUE 6) ---
    # In-graph step guard: fold the decode-health signals (loud
    # decode_residual, located rows beyond the s budget, vote disagreement
    # past budget) with a global-finite check on the aggregated gradient
    # and SKIP the optimizer update via branchless carry passthrough when a
    # step is untrusted (resilience/guards.py). The guard emits
    # guard_trips/skipped_steps metric columns riding the existing (K, m)
    # block — zero extra device fetches, zero retraces (the guard is
    # config-static). "off" keeps today's unguarded update bit-for-bit;
    # "on" is bitwise identical on clean steps (jnp.where select) and the
    # bounded-degradation posture under faults the code does not model
    # (non-finite gradients from faulty-but-honest workers, beyond-budget
    # corruption — the Stochastic Gradient Coding framing, PAPERS.md).
    step_guard: str = "off"
    # decode_residual above this is "loud" (clean decodes sit at f32 solve
    # noise, ~1e-6 relative; a mislocated beyond-budget decode is O(1))
    guard_residual_tol: float = 1e-3
    # Deterministic fault-injection plan (resilience/faults.py): comma-
    # separated "kind@step[:w<worker>][:d<seconds>]" events, same seeded
    # discipline as the adversary schedules. In-graph kinds (nan_grad /
    # inf_grad / over_budget) corrupt the step inputs; host kinds
    # (prefetch_crash / prefetch_hang / sigterm) fire in the host loop.
    # "" (default) injects nothing and compiles the exact unfaulted
    # programs. tools/chaos_run.py drives the fault × loop matrix.
    fault_spec: str = ""
    # Bound on a worker-THREAD prefetch queue wait (seconds; 0 disables):
    # a dead/hung token-prefetch worker (TokenChunkPrefetcher — the one
    # prefetcher whose assembly runs user code on a thread) raises the
    # named PrefetchStallError instead of blocking the main loop forever
    # (data/prefetch.py). The CNN prefetchers' native row gather has no
    # bounded-wait API; its failures surface synchronously as exceptions,
    # which the same supervision retries.
    prefetch_timeout_s: float = 300.0
    # Bounded prefetcher supervision (resilience/supervisor.py): on a
    # worker-thread exception or stall the prefetcher is abandoned and
    # rebuilt with exponential backoff, up to this many restarts per
    # request before the error propagates. 0 disables supervision.
    prefetch_restarts: int = 2
    # Retain-last-N checkpoint GC (utils/checkpoint.py gc_checkpoints):
    # after each save, delete all but the newest N checkpoints in
    # train_dir. 0 (default) keeps everything (current behavior); GC never
    # deletes the newest checkpoint. N >= 2 leaves the corrupt-newest
    # walk-back (checkpoint_step=-1) an older checkpoint to fall back to.
    keep_checkpoints: int = 0

    # --- misc ---
    seed: int = SEED
    geomedian_iters: int = 80  # Weiszfeld iterations (replaces hdmedians dep)
    log_every: int = 10

    @property
    def s(self) -> int:
        return self.worker_fail

    @property
    def hat_s(self) -> int:
        """Batches per worker under the cyclic code (reference: cyclic_worker.py:29)."""
        return 2 * self.worker_fail + 1

    @property
    def num_groups(self) -> int:
        return self.num_workers // self.group_size

    @property
    def tree_group_fail(self) -> int:
        """Per-group cyclic error budget under topology='tree':
        min(worker_fail, (g-1)//4) — coding/topology.group_worker_fail."""
        from draco_tpu.coding.topology import group_worker_fail

        return group_worker_fail(self.tree_fanout, self.worker_fail)

    @property
    def num_adversaries(self) -> int:
        """Live adversaries per step (defaults to the code parameter s)."""
        return self.worker_fail if self.adversary_count is None else self.adversary_count

    def validate(self) -> "TrainConfig":
        if self.approach not in ("baseline", "maj_vote", "cyclic", "approx"):
            raise ValueError(f"unknown approach: {self.approach}")
        if self.approach == "baseline" and self.mode not in AGG_MODES:
            raise ValueError(
                f"baseline supports mode in {'|'.join(AGG_MODES)}, "
                f"got: {self.mode}"
            )
        if (self.mode in ("krum", "multi_krum", "bulyan")
                and self.num_workers < self.worker_fail + 3):
            raise ValueError(f"{self.mode} requires num_workers >= worker_fail + 3")
        if (self.mode in ("trimmed_mean", "bulyan")
                and self.num_workers <= 2 * self.worker_fail):
            raise ValueError(
                f"{self.mode} requires num_workers > 2 * worker_fail"
            )
        if self.lr_schedule not in ("constant", "cosine"):
            raise ValueError(f"unknown lr_schedule: {self.lr_schedule}")
        if self.warmup_steps < 0:
            raise ValueError(f"warmup_steps must be >= 0, got {self.warmup_steps}")
        if self.clip_norm < 0:
            raise ValueError(f"clip_norm must be >= 0, got {self.clip_norm}")
        if self.warmup_steps > 0 and self.lr_schedule == "constant":
            raise ValueError(
                "warmup_steps > 0 has no effect with lr_schedule=constant — "
                "set --lr-schedule cosine (or drop --warmup-steps)"
            )
        if self.err_mode not in ("rev_grad", "constant", "random",
                                 "alie", "ipm"):
            raise ValueError(f"unknown err_mode: {self.err_mode}")
        if self.err_mode in ("alie", "ipm") and self.approach == "cyclic":
            raise ValueError(
                f"err_mode={self.err_mode} targets approximate robust "
                f"aggregation (baseline modes / maj_vote); the cyclic path's "
                f"attack surface is the encoded rows, where decode is exact "
                f"and any per-row corruption is removed — use rev_grad/"
                f"constant there (attacks.py)"
            )
        if self.approach == "maj_vote":
            if self.vote_check not in ("fingerprint", "exact"):
                raise ValueError(
                    f"vote_check must be 'fingerprint' or 'exact', got "
                    f"{self.vote_check!r}"
                )
            if self.num_workers % self.group_size != 0:
                raise ValueError(
                    "maj_vote requires num_workers divisible by group_size "
                    f"(got {self.num_workers} % {self.group_size})"
                )
            if self.worker_fail > 0 and self.group_size < 2 * self.worker_fail + 1:
                # the repetition code's guarantee is r = 2s+1 (reference
                # README.md:9); with r < 2s+1 all s adversaries can land in one
                # group and break its majority
                raise ValueError(
                    f"maj_vote with worker_fail={self.worker_fail} requires "
                    f"group_size >= {2 * self.worker_fail + 1} (r = 2s+1)"
                )
        if self.approach == "cyclic" and self.topology == "flat":
            if self.num_workers <= 4 * self.worker_fail:
                # decode needs n-2s honest rows to span C1's n-2s columns and
                # the locator solve needs 2s syndrome equations
                raise ValueError(
                    f"cyclic code needs n > 4s (got n={self.num_workers}, s={self.worker_fail})"
                )
        if self.approach == "approx":
            if self.num_adversaries > 0:
                # the optimal-decoding weights average whatever arrives —
                # there is no error locator, so a single live Byzantine row
                # poisons the decode undetectably. Stragglers are this
                # family's fault model (coding/approx.py docstring).
                raise ValueError(
                    "approach=approx carries no Byzantine certificate: set "
                    "worker_fail=0 (or adversary_count=0 to keep worker_fail "
                    "as a nominal code parameter) — use cyclic/maj_vote for "
                    "live adversaries"
                )
            if self.redundancy != "shared":
                # fractional loads make the r×-redundant lanes ragged; the
                # shared encode is algebraically identical and is the whole
                # point of redundancy near 1
                raise ValueError(
                    "approach=approx requires redundancy='shared' (the "
                    "assignment's fractional loads have no fixed-lane "
                    "simulate shape)"
                )
            if not (1.0 <= self.code_redundancy <= self.num_workers):
                raise ValueError(
                    f"code_redundancy must lie in [1, num_workers], got "
                    f"{self.code_redundancy} at n={self.num_workers}"
                )
            if not (0.0 <= self.straggler_alpha < 1.0):
                raise ValueError(
                    f"straggler_alpha must lie in [0, 1), got "
                    f"{self.straggler_alpha}"
                )
            # construction-time errors (scheme name, clustered divisibility/
            # integrality) surface at config time, not mid-run
            from draco_tpu.coding.assignment import build_assignment

            build_assignment(self.num_workers, self.code_redundancy,
                             self.assignment_scheme)
        from draco_tpu.coding.topology import TOPOLOGIES, tree_plan

        if self.topology not in TOPOLOGIES:
            raise ValueError(
                f"topology must be one of {'|'.join(TOPOLOGIES)}, got "
                f"{self.topology!r}"
            )
        if self.topology == "tree":
            if self.approach not in ("cyclic", "approx"):
                raise ValueError(
                    "topology='tree' supports the algebraic code families "
                    f"(cyclic|approx), got approach={self.approach!r} — "
                    "maj_vote's repetition groups are already a one-level "
                    "tree of constant fan-in 2s+1"
                )
            if self.redundancy != "shared":
                raise ValueError(
                    "topology='tree' requires redundancy='shared': each "
                    "leaf group's code mixes its own batch rows in place "
                    "(the simulate lanes have no per-group shape)"
                )
            if self.decode_granularity != "global":
                raise ValueError(
                    "topology='tree' requires decode_granularity='global' "
                    "— the tree already partitions the locator per group; "
                    "per-layer cuts do not align with the per-group wire "
                    "blocks (compose with --wire-segments instead)"
                )
            if self.shadow_wire != "off":
                raise ValueError(
                    "topology='tree' composes with the REAL narrow wire "
                    "(--wire-dtype) but not the flat shadow decode "
                    "(--shadow-wire measures the FLAT locator's "
                    "quantization amplification; run it at topology='flat' "
                    "before narrowing, then ship the tree)"
                )
            # shape errors (divisibility, group count, level feasibility)
            # surface at config time
            tree_plan(self.num_workers, self.tree_fanout, self.tree_levels)
            if self.approach == "cyclic":
                s_g = self.tree_group_fail
                if self.num_adversaries > s_g:
                    # worst case every adversary lands in ONE leaf group
                    # (the schedules are independent): the small code must
                    # carry them alone
                    raise ValueError(
                        f"tree per-group budget exceeded: adversary_count="
                        f"{self.num_adversaries} > s_g={s_g} (= min("
                        f"worker_fail, (tree_fanout-1)//4) — raise "
                        f"tree_fanout past {4 * self.num_adversaries} or "
                        f"reduce the adversary load)"
                    )
        if self.worker_fail > self.num_workers:
            raise ValueError("worker_fail cannot exceed num_workers")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"compute_dtype must be float32|bfloat16, got {self.compute_dtype}")
        if self.steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {self.steps_per_call}"
            )
        if self.token_gen not in ("host", "device"):
            raise ValueError(
                f"token_gen must be host|device, got {self.token_gen}"
            )
        if self.token_gen == "device" and self.network not in TOKEN_NETWORKS:
            # the CNN Trainer trains on dataset rows, not a generated token
            # stream — there is nothing for the in-graph generator to replace
            raise ValueError(
                "token_gen='device' applies to the TransformerLM token "
                "routes only (the CNN Trainer reads dataset batches)"
            )
        from draco_tpu.obs.compile_watch import GUARD_MODES

        if self.compile_guard not in GUARD_MODES:
            raise ValueError(
                f"compile_guard must be one of {'|'.join(GUARD_MODES)}, "
                f"got {self.compile_guard!r}"
            )
        if self.compile_warmup < 0:
            raise ValueError(
                f"compile_warmup must be >= 0, got {self.compile_warmup}"
            )
        if self.numerics_watch not in ("off", "on"):
            raise ValueError(
                f"numerics_watch must be off|on, got {self.numerics_watch!r}"
            )
        if self.shadow_wire not in ("off", "bf16", "int8"):
            raise ValueError(
                f"shadow_wire must be off|bf16|int8, got {self.shadow_wire!r}"
            )
        if self.wire_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"wire_dtype must be f32|bf16|int8, got {self.wire_dtype!r}"
            )
        if self.wire_dtype != "f32":
            if self.approach not in ("cyclic", "maj_vote", "approx"):
                # the narrow wire quantizes the CODED wire; the baseline
                # path ships raw rows to approximate robust rules with no
                # certificate to re-threshold — same rule as the shadow
                raise ValueError(
                    "wire_dtype != f32 requires a coded approach "
                    f"(cyclic|maj_vote|approx), got {self.approach!r}"
                )
            if self.shadow_wire != "off":
                raise ValueError(
                    "wire_dtype and shadow_wire are mutually exclusive: "
                    "the shadow is the calibration mode — it measures a "
                    "candidate dtype AGAINST the f32 wire, which a narrow "
                    "wire no longer ships (set shadow_wire=off, or keep "
                    "wire_dtype=f32 while calibrating)"
                )
            if self.approach == "cyclic":
                # shapes whose certificate still degrades under the
                # regularized locator must route through the approx family
                # (arXiv:1802.03475's communication-efficient coding) —
                # the committed threshold table is the contract
                from draco_tpu.obs.numerics import wire_rel_tol

                # tree decodes per GROUP: the threshold that matters is the
                # small code's shape (g, s_g), not (n, s)
                wn, ws = ((self.tree_fanout, self.tree_group_fail)
                          if self.topology == "tree"
                          else (self.num_workers, self.worker_fail))
                if not (wire_rel_tol(wn, ws, self.wire_dtype) < 1.0):
                    raise ValueError(
                        f"no usable narrow-wire flag threshold at "
                        f"(n={wn}, s={ws}, "
                        f"{self.wire_dtype}) — run tools/wire_study.py at "
                        f"this shape, or route the narrow wire through "
                        f"approach=approx (no locator to amplify the "
                        f"quantization noise)"
                    )
        if self.wire_segments < 1:
            raise ValueError(
                f"wire_segments must be >= 1, got {self.wire_segments}"
            )
        if self.wire_segments > 1 and self.approach not in (
                "cyclic", "maj_vote", "approx"):
            # segmentation slices the coded wire; the baseline path ships
            # raw rows with no decode to segment. (maj_vote's group-replica
            # vote is row-wise, not d-separable — its segmentation is
            # wire/ledger-only and the vote verdict is unchanged.)
            raise ValueError(
                "wire_segments > 1 requires a coded approach "
                f"(cyclic|maj_vote|approx), got {self.approach!r}"
            )
        if self.shadow_round not in ("nearest", "stochastic"):
            raise ValueError(
                f"shadow_round must be nearest|stochastic, got "
                f"{self.shadow_round!r}"
            )
        if self.shadow_block < 1:
            raise ValueError(
                f"shadow_block must be >= 1, got {self.shadow_block}"
            )
        if ((self.numerics_watch == "on" or self.shadow_wire != "off")
                and self.approach not in ("cyclic", "maj_vote", "approx")):
            # the observatory measures the CODED wire (encode → decode);
            # the baseline path ships raw rows, emits no optional metric
            # columns at all (no exactness certificate), and has no decode
            # to shadow — keeping it column-free preserves the PR 4
            # "baseline emits nothing" invariant
            raise ValueError(
                "numerics_watch/shadow_wire require a coded approach "
                f"(cyclic|maj_vote|approx), got {self.approach!r}"
            )
        if self.incident_watch not in ("off", "on"):
            raise ValueError(
                f"incident_watch must be off|on, got {self.incident_watch!r}"
            )
        if self.incident_thresholds:
            # unknown detector/threshold names surface at config time, not
            # mid-run (the registry is the contract); parse result is
            # rebuilt where it is consumed (obs/incidents.make_engine)
            from draco_tpu.obs.incidents import parse_thresholds

            parse_thresholds(self.incident_thresholds)
        if self.autopilot not in ("off", "on"):
            raise ValueError(
                f"autopilot must be off|on, got {self.autopilot!r}"
            )
        if self.autopilot == "on":
            if self.incident_watch != "on":
                raise ValueError(
                    "autopilot='on' requires incident_watch='on' — the "
                    "incident stream IS the sensing layer the policy "
                    "engine actuates on (control/autopilot.py)"
                )
            if not self.train_dir:
                raise ValueError(
                    "autopilot='on' needs a train_dir (the incident "
                    "stream and the control status block live there)"
                )
            if self.steps_per_call <= 1 and not (
                    self.network == "TransformerLM"
                    and self.token_gen == "device"):
                raise ValueError(
                    "autopilot='on' requires the chunked regime "
                    "(steps_per_call > 1): chunk boundaries are the "
                    "actuation points — remediations apply between "
                    "dispatched chunks, never inside one"
                )
            if self.approach not in ("cyclic", "approx"):
                raise ValueError(
                    "autopilot='on' supports the algebraic code families "
                    f"(cyclic|approx), got approach={self.approach!r} — "
                    "the redundancy dial swaps between exactly those two"
                )
        if self.autopilot_policy:
            # unknown policy keys surface at config time (DEFAULT_POLICY
            # is the contract); the parsed dict is rebuilt where it is
            # consumed (control.autopilot.make_autopilot)
            from draco_tpu.control.autopilot import parse_policy

            parse_policy(self.autopilot_policy)
        if self.step_guard not in ("off", "on"):
            raise ValueError(
                f"step_guard must be off|on, got {self.step_guard!r}"
            )
        if self.guard_residual_tol <= 0:
            raise ValueError(
                f"guard_residual_tol must be > 0, got "
                f"{self.guard_residual_tol}"
            )
        if self.prefetch_timeout_s < 0:
            raise ValueError(
                f"prefetch_timeout_s must be >= 0, got "
                f"{self.prefetch_timeout_s}"
            )
        if self.prefetch_restarts < 0:
            raise ValueError(
                f"prefetch_restarts must be >= 0, got "
                f"{self.prefetch_restarts}"
            )
        if self.keep_checkpoints < 0:
            raise ValueError(
                f"keep_checkpoints must be >= 0, got {self.keep_checkpoints}"
            )
        if self.checkpoint_step < -1:
            raise ValueError(
                "checkpoint_step must be >= -1 (-1 resumes from the newest "
                f"loadable checkpoint), got {self.checkpoint_step}"
            )
        if self.fault_spec:
            # parse errors surface here (config time), not mid-run; the
            # parsed plan itself is rebuilt (cached) where it is consumed
            from draco_tpu.resilience.faults import FaultPlan

            plan = FaultPlan.parse(self.fault_spec, self.seed,
                                   self.num_workers)
            if self.approach == "approx" \
                    and plan.of_kind("over_budget", "adversary"):
                # both kinds mark schedule rows as live adversaries, but
                # the approx family injects no attacks (no Byzantine
                # certificate) — the event would be silently inert while
                # still flipping the packed adversary-mask telemetry
                raise ValueError(
                    "fault kinds over_budget/adversary are not expressible "
                    "under approach=approx (the family injects no "
                    "adversaries); use straggle/nan_grad/host kinds, or "
                    "cyclic/maj_vote for Byzantine-budget faults"
                )
        if self.straggle_mode not in ("none", "drop"):
            raise ValueError(f"unknown straggle_mode: {self.straggle_mode}")
        if self.decode_granularity not in ("global", "layer"):
            raise ValueError(
                f"decode_granularity must be global|layer, got {self.decode_granularity}"
            )
        if self.decode_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"decode_impl must be auto|xla|pallas, got {self.decode_impl}"
            )
        if self.redundancy not in ("simulate", "shared"):
            raise ValueError(f"redundancy must be simulate|shared, got {self.redundancy}")
        if self.adversary_count is not None and self.adversary_count > self.worker_fail:
            raise ValueError(
                "adversary_count cannot exceed worker_fail (the code is only "
                f"built to tolerate worker_fail={self.worker_fail})"
            )
        e = self.straggle_count if self.straggle_mode == "drop" else 0
        if e > 0:
            s, t, n = self.worker_fail, self.num_adversaries, self.num_workers
            if self.approach == "cyclic":
                # Erasures cost one redundancy unit, unknown errors two. The
                # decoder covers erasure-only (t=0, e <= 2s) and the joint
                # regime (t + e <= s), where the locator treats missing rows
                # as one error each. Under topology='tree' the budget is the
                # PER-GROUP one (worst case every straggler and adversary
                # lands in a single leaf group — the schedules are
                # independent of the group partition).
                s_eff = self.tree_group_fail if self.topology == "tree" \
                    else s
                if not ((t == 0 and e <= 2 * s_eff) or (t + e <= s_eff)):
                    label = ("per-group (tree) " if self.topology == "tree"
                             else "")
                    raise ValueError(
                        f"cyclic {label}straggler budget exceeded: need "
                        f"adversary_count + straggle_count <= s "
                        f"({t}+{e} <= {s_eff}), or adversary_count == 0 "
                        f"with straggle_count <= 2*s ({e} <= {2 * s_eff})"
                    )
            if self.approach == "approx":
                import math

                budget = math.ceil(self.straggler_alpha * n)
                if e > budget:
                    raise ValueError(
                        f"approx straggler budget exceeded: straggle_count "
                        f"{e} > ceil(straggler_alpha * n) = {budget} — raise "
                        f"--straggler-alpha (and code_redundancy with it) or "
                        f"drop fewer workers"
                    )
            if self.approach == "maj_vote":
                if e >= self.group_size:
                    raise ValueError(
                        f"straggle_count {e} >= group_size {self.group_size} can "
                        "silence an entire repetition group"
                    )
                # Worst case all e stragglers AND all t adversaries land in one
                # group (the schedules are independent): the vote among the
                # group_size - e present members needs an honest majority,
                # i.e. group_size - e > 2t — the joint budget, mirroring the
                # cyclic t + e <= s check above.
                if t > 0 and self.group_size - e <= 2 * t:
                    raise ValueError(
                        f"maj_vote joint budget exceeded: group_size - "
                        f"straggle_count must exceed 2*adversaries "
                        f"({self.group_size} - {e} <= {2 * t}); an unlucky "
                        "group could be voted over by adversarial rows"
                    )
            if self.approach == "baseline":
                if e >= n:
                    raise ValueError("straggle_count must leave at least one worker")
                if (self.mode in ("krum", "multi_krum", "bulyan")
                        and n - e < s + 3):
                    raise ValueError(
                        f"{self.mode} needs num_workers - straggle_count >= "
                        f"worker_fail + 3 ({n} - {e} < {s} + 3)"
                    )
                if (self.mode in ("coord_median", "trimmed_mean", "bulyan")
                        and n - e <= 2 * s):
                    # the median-based rules need an honest majority among
                    # the rows that actually arrive: with p <= 2s present
                    # rows, s Byzantine rows control the per-coordinate
                    # median (and hence the trim fill) outright
                    raise ValueError(
                        f"{self.mode} needs num_workers - straggle_count > "
                        f"2 * worker_fail ({n} - {e} <= {2 * s})"
                    )
        if self.network in TOKEN_NETWORKS and self.approach == "maj_vote":
            # the vote runs on the single-shard token route
            # (parallel/sp_step.py at seq_shards == 1): the loop feeds a
            # group's members the same rows and the lanes agree bitwise.
            # Everything else about it on a token model is refused by name.
            sharded = [name for name in ("seq_shards", "tensor_shards",
                                         "expert_shards", "pipeline_shards")
                       if getattr(self, name) > 1]
            if self.pp_microbatches > 0:
                sharded.append("pp_microbatches")
            if sharded:
                raise ValueError(
                    f"approach=maj_vote on {self.network} runs on the "
                    f"single-shard token route only, got {sharded} > 1: "
                    "under a model-parallel axis a group member is a whole "
                    "mesh row, which the token loop does not replicate"
                )
            for name, off in (("wire_dtype", "f32"),
                              ("numerics_watch", "off"),
                              ("shadow_wire", "off")):
                if getattr(self, name) != off:
                    raise ValueError(
                        f"approach=maj_vote on {self.network} with "
                        f"{name}={getattr(self, name)!r} is not implemented "
                        "(the narrow wire and the numerics observatory of "
                        "the vote live on the CNN path, training/step.py)"
                    )
        if self.network in SPEC_NETWORKS:
            import importlib

            importlib.import_module(
                SPEC_NETWORKS[self.network]).check_spec(self.model_spec)
            for name in ("seq_shards", "tensor_shards", "expert_shards",
                         "pipeline_shards"):
                if getattr(self, name) > 1:
                    raise ValueError(
                        f"{name}={getattr(self, name)} with network="
                        f"{self.network} is not implemented: the block runs on "
                        "the single-shard token route (the chip's share of "
                        "a deployment is stated in model_spec — layers, "
                        "experts_held, vocab_rows — not by a mesh axis)"
                    )
            if self.pp_microbatches > 0 or self.moe_experts > 0 \
                    or self.scan_layers:
                raise ValueError(
                    "pp_microbatches / moe_experts / scan_layers are "
                    f"TransformerLM's and do not apply to network={self.network} "
                    "(its experts come from model_spec)"
                )
            if self.attn_impl not in ("dense", "flash"):
                raise ValueError(
                    f"attn_impl must be dense|flash, got {self.attn_impl}"
                )
            if self.vocab != self.model_spec["vocab_rows"]:
                raise ValueError(
                    f"vocab {self.vocab} must equal model_spec['vocab_rows'] "
                    f"{self.model_spec['vocab_rows']}: token ids are drawn "
                    "from the slice the head scores"
                )
            if self.seq_len < 2:
                raise ValueError(f"{self.network} needs seq_len >= 2")
        elif self.network == "TransformerLM":
            if self.model_dim % self.model_heads != 0:
                raise ValueError(
                    f"model_dim {self.model_dim} not divisible by "
                    f"model_heads {self.model_heads}"
                )
            if (self.model_dim // self.model_heads) % 2 != 0:
                raise ValueError(
                    "head dim must be even for the rotary embedding "
                    f"(model_dim/model_heads = {self.model_dim // self.model_heads})"
                )
            if self.seq_len % max(self.seq_shards, 1) != 0:
                raise ValueError(
                    f"seq_len {self.seq_len} not divisible by seq_shards {self.seq_shards}"
                )
            if self.sp_attn not in ("ring", "a2a"):
                raise ValueError(f"sp_attn must be ring|a2a, got {self.sp_attn}")
            if self.attn_impl not in ("dense", "flash"):
                raise ValueError(
                    f"attn_impl must be dense|flash, got {self.attn_impl}"
                )
            # attn_impl=flash composes with BOTH sp modes: a2a runs the
            # kernel on each device's full-sequence head group after the
            # scatter; ring runs it per visiting K/V block with an lse merge
            # (parallel/ring_attention.ring_flash_attention)
            if self.attn_impl == "flash" and (
                self.tensor_shards > 1 or self.expert_shards > 1
                or self.moe_experts > 0
            ):
                raise ValueError(
                    "attn_impl=flash runs on the shard_map paths (sp/pp): "
                    "the GSPMD paths (tensor_shards/expert_shards/moe) "
                    "cannot partition an opaque Pallas call over the mesh"
                )
            # pp_microbatches alone activates the pipeline path (cli.py),
            # so it counts as the pp axis being in use
            pp_active = self.pipeline_shards > 1 or self.pp_microbatches > 0
            if (
                sum(int(x > 1) for x in
                    (self.tensor_shards, self.seq_shards, self.expert_shards))
                + int(pp_active)
                > 1
            ):
                raise ValueError(
                    "tensor_shards / seq_shards / expert_shards / "
                    "pipeline_shards are separate paths (tp_step / sp_step / "
                    "ep_step / pp_step); combining model-parallel axes is "
                    "not implemented"
                )
            if self.expert_shards > 1:
                if self.moe_experts <= 0:
                    raise ValueError("expert_shards > 1 needs moe_experts > 0")
                if self.moe_experts % self.expert_shards:
                    raise ValueError(
                        f"expert_shards={self.expert_shards} must divide "
                        f"moe_experts {self.moe_experts}"
                    )
            if self.moe_experts < 0:
                raise ValueError("moe_experts must be >= 0")
            if self.moe_experts > 0 and self.seq_shards > 1:
                # MoeMlp computes capacity and arrival-order drops from its
                # LOCAL token count; under sp sharding that breaks the
                # documented sp layout-invariance (global routing is not
                # implemented)
                raise ValueError(
                    "moe_experts > 0 with seq_shards > 1 is not implemented: "
                    "per-shard MoE routing/capacity would break sp "
                    "layout-invariance"
                )
            if self.tensor_shards > 1:
                if self.moe_experts > 0:
                    raise ValueError(
                        "tensor_shards with moe_experts is not implemented "
                        "(the tp partition rules cover the dense MLP only)"
                    )
                if (
                    self.model_dim % self.tensor_shards
                    or self.model_heads % self.tensor_shards
                ):
                    raise ValueError(
                        f"tensor_shards={self.tensor_shards} must divide "
                        f"model_dim {self.model_dim} and model_heads "
                        f"{self.model_heads}"
                    )
            if (
                self.sp_attn == "a2a"
                and self.seq_shards > 1
                and self.model_heads % self.seq_shards != 0
            ):
                raise ValueError(
                    f"sp_attn=a2a needs model_heads % seq_shards == 0 "
                    f"({self.model_heads} % {self.seq_shards})"
                )
            if self.pp_microbatches < 0 or self.pipeline_shards < 1:
                raise ValueError(
                    "pipeline_shards must be >= 1 and pp_microbatches >= 0"
                )
            if pp_active:
                if self.moe_experts > 0:
                    raise ValueError(
                        "the pipeline path with moe_experts is not implemented "
                        "(pp_step's scanned block stack covers the dense "
                        "MLP only)"
                    )
                if self.model_layers % max(self.pipeline_shards, 1):
                    raise ValueError(
                        f"pipeline_shards={self.pipeline_shards} must divide "
                        f"model_layers {self.model_layers}"
                    )
                mb = self.pp_microbatches or self.pipeline_shards
                if self.batch_size % mb:
                    raise ValueError(
                        f"pipeline microbatch count {mb} must divide "
                        f"batch_size {self.batch_size}"
                    )
            if self.seq_len < 2 or self.vocab < 2:
                raise ValueError("TransformerLM needs seq_len >= 2 and vocab >= 2")
        elif self.model_spec is not None:
            raise ValueError("model_spec requires network="
                             + " | ".join(SPEC_NETWORKS))
        elif self.seq_shards > 1:
            raise ValueError("seq_shards > 1 requires network=TransformerLM")
        elif self.tensor_shards > 1:
            raise ValueError("tensor_shards > 1 requires network=TransformerLM")
        elif self.expert_shards > 1 or self.moe_experts > 0:
            raise ValueError(
                "moe_experts / expert_shards require network=TransformerLM"
            )
        elif self.pipeline_shards > 1:
            raise ValueError("pipeline_shards > 1 requires network=TransformerLM")
        return self
