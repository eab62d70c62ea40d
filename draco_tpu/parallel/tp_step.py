"""Coded data parallelism × tensor parallelism: the (w, tp) GSPMD step.

Megatron-style tensor parallelism for the TransformerLM, expressed the
TPU-native way: parameters carry ``NamedSharding`` annotations over mesh
axis ``tp`` (column-parallel qkv/mlp_in, row-parallel proj/mlp_out) and the
training step is ONE plain ``jit`` — no manual collectives, no shard_map;
XLA's SPMD partitioner inserts the all-reduces at the row-parallel
boundaries and shards every matmul. This is deliberately the other
idiomatic-JAX parallelism style from the ``sp`` path (sp_step.py uses
explicit shard_map + ppermute/all_to_all; this path uses sharding
propagation), so the framework demonstrates both.

Composition with Draco (SURVEY.md §2.3): per-worker gradients inherit the
``tp`` shardings leaf-by-leaf; flattening to the (n, d) gradient matrix
re-lays them out over ``w`` (XLA inserts the tp-gather), and the coding /
robust-aggregation machinery is unchanged. After the update the new
parameters are constrained back onto their ``tp`` shards.

No reference counterpart (the reference is CNN-only, single-axis DP);
this axis is part of the TPU build's scale-out surface.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from draco_tpu import optim, rng as drng
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.config import TrainConfig
from draco_tpu.models.transformer import TransformerLM
from draco_tpu.parallel.common import (
    TOKEN_METRIC_NAMES,
    aggregate_flat_grads,
    build_code_from_cfg,
    finish_flat_step,
    decode_health_metrics,
    make_token_train_many,
    masked_loss_metric,
    token_metric_names,
)
from draco_tpu.parallel.mesh import TP_AXIS
from draco_tpu.parallel.partition import (
    REPLICATED,
    TP_STEP_RULES,
    WORKER_ROWS,
    WORKER_ROWS3,
    norm_spec,
    override,
    sharding,
)
# re-export: historical home
from draco_tpu.parallel.token_loop import run_token_loop  # noqa: F401
from draco_tpu.runtime import WORKER_AXIS
from draco_tpu.training.step import TrainState, _flatten_tree, _make_unravel


class TPTrainSetup(NamedTuple):
    model: TransformerLM
    state: TrainState
    # (state, tokens (n,B,T), adv_mask (n,)) -> (state, metrics)
    train_step: any
    eval_step: any  # (params, tokens) -> loss
    code: Optional[cyclic_mod.CyclicCode]
    unravel: any
    dim: int
    # K fused LM steps in ONE device program (parallel/common.py):
    # (state, toks (K,n,B,T) | steps (K,), masks (K,n), presents (K,n)|None)
    #   -> (state, metrics (K, len(metric_names)) float32)
    train_token_many: any = None
    metric_names: tuple = TOKEN_METRIC_NAMES


def param_partition_spec(path) -> P:
    """Megatron partitioning by parameter name.

    Column-parallel (output dim sharded): ``qkv``, ``mlp_in``.
    Row-parallel (input dim sharded): ``proj``, ``mlp_out`` — XLA inserts
    the psum over ``tp`` where their outputs meet the residual stream.
    Everything 1-D or shared (embeddings, layer norms, biases of
    row-parallel layers) stays replicated.
    """
    names = [getattr(k, "key", str(k)) for k in path]
    leaf = names[-1]
    layer = names[-2] if len(names) >= 2 else ""
    if leaf == "kernel" and layer in ("qkv", "mlp_in"):
        spec = (None, TP_AXIS)
    elif leaf == "kernel" and layer in ("proj", "mlp_out"):
        spec = (TP_AXIS, None)
    elif leaf == "bias" and layer == "mlp_in":
        spec = (TP_AXIS,)
    else:
        return P()
    # scan_layers stacks block params under a "blocks" subtree with a
    # leading layer axis — the Megatron dims shift right by one
    if "blocks" in names:
        spec = (None,) + spec
    return P(*spec)


# The trailing-None spec normalizer this route's PR 6 fix introduced now
# lives in parallel/partition.norm_spec (the canonical copy every route
# and the static sharding auditor share); re-exported under the old name
# for the retrace-regression tests.
_norm_spec = norm_spec


def shard_params(params, mesh, partition_fn=param_partition_spec):
    """Annotate a parameter pytree with its (w-replicated, mp-sharded)
    placement."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jax.device_put(
            x, NamedSharding(mesh, _norm_spec(partition_fn(path)))
        ),
        params,
    )


def _constrain_params(params, mesh, partition_fn):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, _norm_spec(partition_fn(path)))
        ),
        params,
    )


def build_tp_train_setup(cfg: TrainConfig, mesh) -> TPTrainSetup:
    """mesh must have axes (w, tp) — see make_mesh_wtp."""
    # experts honoured even at tensor_shards=1 (validate() forbids MoE with
    # tensor_shards>1; at 1 shard the tp rules just replicate expert params)
    return _build_gspmd_train_setup(
        cfg, mesh, mp_axis=TP_AXIS, mp_size=max(cfg.tensor_shards, 1),
        partition_fn=param_partition_spec, experts=cfg.moe_experts,
    )


def _build_gspmd_train_setup(cfg: TrainConfig, mesh, *, mp_axis: str,
                             mp_size: int, partition_fn,
                             experts: int) -> TPTrainSetup:
    """Shared GSPMD builder for the sharding-annotation model-parallel paths
    (tensor parallelism here; expert parallelism in ep_step.py). The paths
    differ only in the mesh axis, the parameter partition rules, and the
    model's expert count."""
    cfg.validate()
    if cfg.approach not in ("baseline", "cyclic", "approx"):
        raise ValueError(
            f"MP path supports baseline|cyclic|approx, got {cfg.approach}")
    n = cfg.num_workers
    # logical workers fold onto the available w-axis devices in equal blocks
    # (same discipline as runtime.make_mesh for the CNN path) — a single
    # chip can still run the n-lane coded step, vmapped
    if n % mesh.shape[WORKER_AXIS]:
        raise ValueError(
            f"num_workers {n} must be a multiple of the mesh's w axis "
            f"({mesh.shape[WORKER_AXIS]})"
        )
    # the mesh defines the actual mp shard count — it must be the one the
    # config's divisibility checks validated, or GSPMD silently pads
    if mesh.shape[mp_axis] != mp_size:
        raise ValueError(
            f"mesh {mp_axis} axis is {mesh.shape[mp_axis]} but the config "
            f"requests {mp_size} shards"
        )

    cdtype = jnp.dtype(cfg.compute_dtype)
    # flash applies in the folded (tp=1) regime the perf/convergence tools
    # run in; real tensor sharding with flash is rejected by cfg.validate()
    # (GSPMD cannot partition the opaque pallas_call across head shards —
    # the sp paths compose it explicitly instead, sp_step.py)
    from draco_tpu.ops.flash_attention import attn_impl_fn

    attn_fn = attn_impl_fn(cfg) if mp_size == 1 else None
    model = TransformerLM(
        vocab=cfg.vocab, dim=cfg.model_dim, heads=cfg.model_heads,
        layers=cfg.model_layers, attn_fn=attn_fn, experts=experts,
        dtype=cdtype, remat=cfg.remat, scan_layers=cfg.scan_layers,
    )
    root = jax.random.key(cfg.seed)
    init_toks = jnp.zeros((1, min(cfg.seq_len, 8)), jnp.int32)
    params = model.init({"params": root}, init_toks, train=True)["params"]

    opt = optim.build_optimizer_from_cfg(cfg)
    unravel, dim, leaf_offsets = _make_unravel(params)

    repl = sharding(mesh, REPLICATED)
    shard_w = sharding(mesh, WORKER_ROWS)
    params = shard_params(params, mesh, partition_fn)
    # opt.init is zeros_like on the sharded params, so the slots inherit
    # the tp layout with no host round-trip (multi-host safe) — but its
    # bookkeeping scalars (schedule count, sgd's initialized flag) come out
    # as fresh single-device arrays. Live they are uncommitted and jit
    # transfers them freely; an Orbax restore however round-trips them
    # COMMITTED to device 0, which jit then rejects next to the
    # mesh-committed params — pin them mesh-replicated up front so the
    # checkpoint template carries a placement that restores clean.
    opt_state = jax.tree.map(
        lambda x: x
        if isinstance(getattr(x, "sharding", None), NamedSharding)
        else jax.device_put(x, repl),
        opt.init(params),
    )
    state = TrainState(
        params=params,
        opt_state=opt_state,
        batch_stats=None,
        step=jax.device_put(jnp.asarray(1, jnp.int32), repl),
    )
    # pin the step's output opt state to the carry's INPUT layout: left
    # unconstrained, GSPMD is free to reshard momentum buffers on the
    # first execution (e.g. a replicated LayerNorm-scale slot coming back
    # tp-sharded), and the K-fused program then RETRACES on its second
    # dispatch against the drifted shardings (_norm_spec docstring)
    opt_shardings = jax.tree.map(lambda x: x.sharding, state.opt_state)
    constrain_opt = lambda o: jax.tree.map(  # noqa: E731
        jax.lax.with_sharding_constraint, o, opt_shardings)

    def lane_loss(params, toks, train: bool):
        """Whole-sequence next-token CE for one worker's (B, T) batch.

        The model sees all T tokens and the last logit row is discarded
        (identical math on the dense/flash attention paths: causal row i
        attends keys <= i, so rows < T-1 cannot see token T-1). Feeding
        toks[:, :-1] instead would hand the attention a T-1-length
        sequence (1023 at T=1024), which fails the flash kernel's t%8
        tiling (an error now; once a silent dense path — the kernel never
        actually ran on the LM path before this).

        Deliberate deviation when moe_experts > 0: Switch capacity
        routing (models/moe.py) is cross-token over the flattened B*T
        stream, so the now-included last-position tokens compete for
        arrival-order capacity slots. cap = int(1.25*n_tok/e) scales with
        the stream, so capacity pressure is ~unchanged, but individual
        evictions can differ from the pre-change B*(T-1) stream — a
        routing-statistics perturbation of order 1/T, not an objective
        change (and matches inference, where the last token routes too)."""
        logits = model.apply({"params": params}, toks, train=train)[:, :-1]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(nll)

    code = build_code_from_cfg(cfg)
    # reference-parity r× redundant compute: each worker really evaluates
    # its hat_s = 2s+1 assigned batch rows (cyclic_worker.py:122-146); the
    # "shared" fast path computes each row once and forms encoded rows
    # algebraically (identical semantics — per-batch gradients are
    # deterministic under XLA)
    simulate = cfg.approach == "cyclic" and cfg.redundancy == "simulate"
    batch_ids = jnp.asarray(code.batch_ids) if simulate else None
    shard_w3 = sharding(mesh, WORKER_ROWS3)

    def step_body(state: TrainState, tokens, adv_mask, present=None):
        def lane(toks):
            loss, g = jax.value_and_grad(lane_loss)(state.params, toks, True)
            return _flatten_tree(g), loss

        with jax.named_scope("draco_comp"):
            if simulate:
                toks_w = tokens[batch_ids]  # (n, hat_s, B, T) redundant rows
                # (n, hat_s, d)
                grads, losses = jax.vmap(jax.vmap(lane))(toks_w)
                grads = jax.lax.with_sharding_constraint(grads, shard_w3)
                losses = jnp.mean(losses, axis=1)
            else:
                grads, losses = jax.vmap(lane)(tokens)  # (n, d), (n,)
                grads = jax.lax.with_sharding_constraint(grads, shard_w)
        # decode projection generated in-graph from the scalar seed — a
        # closed-over (d,) constant serializes into the program (638 MB at
        # d~159M: the remote-compile ceiling, rng.py docstring); the approx
        # decode is projection-free
        with jax.named_scope("draco_input"):
            rand_factor = (
                drng.random_projection_factors_in_graph(cfg.seed, dim)
                if cfg.approach == "cyclic" else None)
        agg, health = aggregate_flat_grads(grads, adv_mask, cfg, code,
                                           rand_factor, present=present,
                                           leaf_offsets=leaf_offsets,
                                           step=state.step, mesh=mesh)
        new_state, guard_cols = finish_flat_step(
            cfg, state, agg, health, opt, unravel, present=present,
            constrain=lambda p: _constrain_params(p, mesh, partition_fn),
            constrain_opt=constrain_opt,
        )
        with jax.named_scope("draco_health"):
            metrics = {"loss": masked_loss_metric(losses, present)}
            metrics.update(decode_health_metrics(health, adv_mask, present))
        metrics.update(guard_cols)
        return new_state, metrics

    def eval_body(params, tokens):
        return jnp.mean(
            jax.vmap(lambda t: lane_loss(params, t, False))(tokens))

    from draco_tpu.parallel.sp_step import token_fn_from_cfg

    metric_names = token_metric_names(cfg)
    # the carry's layout is pinned at the JIT boundary: out_shardings for
    # the state output = the state input's shardings. A with_sharding_
    # constraint inside the scanned body does not win the scan carry's
    # unified layout — GSPMD still resharded replicated momentum slots to
    # tp-sharded on the real tp mesh, and the second dispatch then
    # retraced against the drifted input (_norm_spec docstring). The
    # boundary pin makes state-in == state-out by construction (and lets
    # donation alias cleanly). The metrics output stays compiler-chosen.
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    with mesh:
        train_step = jax.jit(step_body, donate_argnums=(0,),
                             out_shardings=(state_shardings, None))
        eval_step = jax.jit(eval_body)
        train_token_many = jax.jit(
            make_token_train_many(step_body, token_fn_from_cfg(cfg),
                                  metric_names=metric_names),
            donate_argnums=(0,),
            out_shardings=(state_shardings, None),
        )

    return TPTrainSetup(
        model=model, state=state, train_step=train_step, eval_step=eval_step,
        code=code, unravel=unravel, dim=dim,
        train_token_many=train_token_many, metric_names=metric_names,
    )


# ---- program-lint registration (draco_tpu/analysis) -----------------------


def lint_programs():
    """The GSPMD tensor-parallel route's chip-bound programs, plus the
    folded single-shard regime every perf/convergence tool runs in.

    All-zero explicit-collective manifests are the POINT here: this route
    is pure sharding propagation (module docstring) — the tp all-reduces
    exist only after the XLA SPMD partitioner runs, so any explicit
    collective in the exported module means shard_map leaked in.

    ``lm_fold_big_bf16_many_k2`` is the constant-bloat guard at a d where a
    closed-over (d,) constant would dominate (d ≈ 3.3 M → +13 MB against a
    ~0.2 MB honest module): the round-5 wedge generalized from
    tests/test_program_size.py to the production K-fused program. It builds
    a real 3.3M-param state, so it is not in the --fast subset, and exports
    for cpu (its rule is serialized bytes, not TPU lowering).
    """
    from draco_tpu.analysis.registry import (
        BF16_DTYPES, LintProgram, Manifest, built_token_program,
        ci_lm_config,
    )
    from draco_tpu.parallel.mesh import make_folded_wtp_mesh, make_mesh_wtp

    # the devgen program's token input is the (K,) step vector, not a
    # host batch — it rides replicated (partition.override docstring)
    devgen_rules = override(TP_STEP_RULES, (r"^tokens$", REPLICATED))

    def _tp2(name, many, **overrides):
        cfg = ci_lm_config(tensor_shards=2, **overrides)
        mesh = make_mesh_wtp(4, 2)  # 8 CI devices; n=8 folds 2 lanes/device
        setup = build_tp_train_setup(cfg, mesh)
        return built_token_program(name, cfg, mesh, setup,
                                   Manifest(collectives={},
                                            collective_axes={}),
                                   many=many,
                                   partition_rules=TP_STEP_RULES)

    def _fold(name, many, **overrides):
        cfg = ci_lm_config(tensor_shards=1, **overrides)
        mesh = make_folded_wtp_mesh(cfg.num_workers)
        setup = build_tp_train_setup(cfg, mesh)
        allowed = (BF16_DTYPES if cfg.compute_dtype == "bfloat16"
                   else Manifest.allowed_dtypes)
        rules = (devgen_rules if cfg.token_gen == "device"
                 else TP_STEP_RULES)
        return built_token_program(
            name, cfg, mesh, setup,
            Manifest(collectives={}, collective_axes={},
                     allowed_dtypes=allowed), many=many,
            partition_rules=rules)

    def _fold_big(name):
        cfg = ci_lm_config(
            tensor_shards=1, compute_dtype="bfloat16", remat=True,
            seq_len=64, vocab=512, model_dim=256, model_heads=4,
            model_layers=4, batch_size=1,
        )
        mesh = make_folded_wtp_mesh(cfg.num_workers)
        setup = build_tp_train_setup(cfg, mesh)
        if setup.dim < 3_000_000:  # guard only meaningful if d is CI-large
            raise ValueError(
                f"big-d lint program built d={setup.dim} < 3M — the "
                f"constant-bloat guard no longer covers a d-dominating "
                f"constant; grow the config")
        # a closed-over (d,) f32 would add 4*d bytes; the honest program is
        # a few hundred KB. 2*d sits far from both (test_program_size
        # lineage).
        manifest = Manifest(collectives={}, collective_axes={},
                            allowed_dtypes=BF16_DTYPES,
                            max_module_bytes=2 * setup.dim,
                            max_constant_bytes=1 << 20)
        return built_token_program(name, cfg, mesh, setup, manifest,
                                   many=True, partition_rules=TP_STEP_RULES)

    mk = lambda name, build, **kw: LintProgram(  # noqa: E731
        name=name, route="tp", build=build, **kw)
    return [
        mk("lm_tp2_step", lambda: _tp2("lm_tp2_step", False)),
        mk("lm_tp2_many_k2", lambda: _tp2("lm_tp2_many_k2", True)),
        mk("lm_fold_bf16_step",
           lambda: _fold("lm_fold_bf16_step", False,
                         compute_dtype="bfloat16")),
        # the production chunked driver with the in-graph token stream: the
        # program whose whole input is K int32 scalars (token_loop.py)
        mk("lm_fold_devgen_many_k2",
           lambda: _fold("lm_fold_devgen_many_k2", True, token_gen="device",
                         steps_per_call=2)),
        # guarded production program (ISSUE 6): the in-graph step guard on
        # the GSPMD route — still zero explicit collectives, no host traffic
        mk("lm_tp2_many_guard_k2",
           lambda: _tp2("lm_tp2_many_guard_k2", True, step_guard="on")),
        # the approx family on the real tp mesh, xla + fused decode
        # lowerings (ISSUE 12): the optimal-decoding tail must stay pure
        # GSPMD under BOTH impls (zero explicit collectives, donation,
        # zero host traffic); these are the device-profile join rows for
        # the lm_tp_approx_k4 / lm_tp_approx_pallas_k4 claim cells.
        # fast=False: impl/family variants of the fast-swept tp rows —
        # the full tool covers them without growing the --fast budget
        mk("lm_tp2_approx_many_k2",
           lambda: _tp2("lm_tp2_approx_many_k2", True, approach="approx",
                        worker_fail=0, code_redundancy=1.5,
                        step_guard="on"),
           fast=False),
        mk("lm_tp2_approx_pallas_many_k2",
           lambda: _tp2("lm_tp2_approx_pallas_many_k2", True,
                        approach="approx", worker_fail=0,
                        code_redundancy=1.5, step_guard="on",
                        decode_impl="pallas"),
           fast=False),
        mk("lm_fold_big_bf16_many_k2",
           lambda: _fold_big("lm_fold_big_bf16_many_k2"),
           fast=False, export_platforms=("cpu",)),
    ]


def train_tp(cfg: TrainConfig, mesh, steps: Optional[int] = None,
             quiet: bool = False, profile_dir: Optional[str] = None):
    """TP training loop; returns (state, last metrics)."""
    return run_token_loop(build_tp_train_setup(cfg, mesh), cfg, steps, quiet,
                          profile_dir=profile_dir,
                          tag="tp")
