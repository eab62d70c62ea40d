"""Coded data parallelism × pipeline parallelism: the (w, pp) GPipe step.

Pipeline parallelism the TPU-native way: the TransformerLM's blocks are a
``nn.scan`` stack whose stacked parameters shard their leading layer axis
over mesh axis ``pp`` (each device holds ``layers / pp`` consecutive
blocks = one stage), and the classic GPipe schedule is an explicit
``lax.scan`` over ``M + S - 1`` ticks inside ``shard_map``: each tick a
stage runs its blocks on the activation in flight and hands the result to
its successor with ONE ``ppermute`` hop.  Backward needs no hand-written
schedule — the pipeline loop is traced, ``ppermute`` is linear, and
``jax.grad`` transposes the whole thing into the reverse-flowing backward
pipeline automatically (cotangents ride the same ring, reversed).

Composition with Draco (SURVEY.md §2.3): parameters are broadcast along a
leading worker axis sharded over ``w`` (free: each worker column just uses
its replica), so ``jax.grad`` yields *per-worker* gradients laid out
(n, ...) over ``w`` with stage slices over ``pp``; flattening to the (n, d)
gradient matrix re-lays them over ``w`` (XLA inserts the pp-gather) and the
coding / robust-aggregation machinery is unchanged, exactly as in the tp
path.

No reference counterpart: the reference's *Split* models stream per-layer
gradients over MPI but every worker holds the full model
(/root/reference/src/model_ops/resnet_split.py:210-234 — grad streaming,
not pipeline stages; SURVEY.md §2.3 "Pipeline parallelism: absent"). This
axis is part of the TPU build's scale-out surface: models deeper than one
chip's HBM span the ``pp`` axis.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from draco_tpu import optim, rng as drng
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.config import TrainConfig
from draco_tpu.models.transformer import Block
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
from draco_tpu.parallel.mesh import PP_AXIS
from draco_tpu.parallel.partition import PP_STEP_RULES
from draco_tpu.parallel.tp_step import _constrain_params, shard_params
from draco_tpu.runtime import WORKER_AXIS
from draco_tpu.training.step import TrainState, _make_unravel


class _PipeBlock(nn.Module):
    """scan cell: one transformer block, (carry, broadcast args) contract."""

    dim: int
    heads: int
    dtype: Any
    remat: bool = False
    attn_fn: Any = None

    @nn.compact
    def __call__(self, x, positions):
        # static_argnums counts self as 0, so `train` is 3; CSE prevention
        # is unnecessary inside nn.scan (flax checkpoint docs) and would
        # put a barrier in every scanned body
        blk_cls = Block if not self.remat else nn.remat(
            Block, static_argnums=(3,), prevent_cse=False
        )
        x = blk_cls(self.dim, self.heads, attn_fn=self.attn_fn,
                    dtype=self.dtype, name="b")(x, positions, True)
        return x, None


class StageBlocks(nn.Module):
    """``layers`` transformer blocks as one scanned stack.

    Parameters carry a leading ``layers`` axis, so a contiguous slice of the
    full stack IS a pipeline stage's parameter tree: the same module class
    applies the full model (layers=L) and a stage (layers=L/S) alike.
    """

    dim: int
    heads: int
    layers: int
    dtype: Any = jnp.float32
    remat: bool = False
    attn_fn: Any = None

    @nn.compact
    def __call__(self, x, positions):
        scan = nn.scan(
            _PipeBlock,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=self.layers,
            in_axes=nn.broadcast,
        )
        x, _ = scan(self.dim, self.heads, self.dtype, self.remat,
                    self.attn_fn, name="loop")(x, positions)
        return x


class PPTrainSetup(NamedTuple):
    state: TrainState
    # (state, tokens (n,B,T), adv_mask (n,)) -> (state, metrics)
    train_step: any
    eval_step: any  # (params, tokens) -> mean loss
    per_worker_loss: any  # (params, tokens (n,B,T)) -> (n,) losses
    # (params, tokens) -> ((n, d) flat grads, (n,) losses)
    per_worker_grads: any
    code: Optional[cyclic_mod.CyclicCode]
    unravel: any
    dim: int
    # K fused LM steps in ONE device program (parallel/common.py):
    # (state, toks (K,n,B,T) | steps (K,), masks (K,n), presents (K,n)|None)
    #   -> (state, metrics (K, len(metric_names)) float32)
    train_token_many: any = None
    metric_names: tuple = TOKEN_METRIC_NAMES


def _flatten_rows(tree) -> jnp.ndarray:
    """(n, ...)-leaved tree -> (n, d), same leaf order as _make_unravel."""
    leaves = jax.tree.leaves(tree)
    n = leaves[0].shape[0]
    return jnp.concatenate([jnp.reshape(x, (n, -1)) for x in leaves], axis=1)


def build_pp_train_setup(cfg: TrainConfig, mesh) -> PPTrainSetup:
    """mesh must have axes (w, pp) — see make_mesh_wpp."""
    cfg.validate()
    if cfg.approach not in ("baseline", "cyclic", "approx"):
        raise ValueError(
            f"PP path supports baseline|cyclic|approx, got {cfg.approach}")
    n = cfg.num_workers
    S = mesh.shape[PP_AXIS]
    # logical workers fold onto the available w-axis devices in equal
    # lane blocks (same discipline as tp_step / runtime.make_mesh): a
    # single chip can still run the n-lane coded step, vmapped
    if n % mesh.shape[WORKER_AXIS]:
        raise ValueError(
            f"num_workers {n} must be a multiple of the mesh's w axis "
            f"({mesh.shape[WORKER_AXIS]})"
        )
    if cfg.approach == "cyclic" and cfg.redundancy == "simulate":
        # sp/tp/ep carry true 2s+1-lane redundant compute; here the r×
        # regime would multiply the whole pipeline schedule per lane for
        # no semantic difference (per-batch gradients are deterministic, so
        # the shared encode is algebraically identical) — say so instead of
        # silently reinterpreting the config
        import warnings

        warnings.warn(
            "pp path: redundancy='simulate' is not implemented; using the "
            "algebraically-identical 'shared' encode",
            stacklevel=2,
        )
    L = cfg.model_layers
    if L % S:
        raise ValueError(f"model_layers {L} not divisible by pp={S}")
    l_loc = L // S
    M = cfg.pp_microbatches or S
    if cfg.batch_size % M:
        raise ValueError(
            f"microbatches {M} must divide batch_size {cfg.batch_size}")
    mb = cfg.batch_size // M
    # the pipeline carries all T positions and the loss drops the last
    # logit row (identical next-token math — causal rows < T-1 cannot see
    # token T-1); a T-1 carry would break the flash kernel's t%8 tiling
    # (1023 at T=1024) and silently ride the dense fallback
    t_in = cfg.seq_len

    cdtype = jnp.dtype(cfg.compute_dtype)
    from draco_tpu.ops.flash_attention import attn_impl_fn

    attn_fn = attn_impl_fn(cfg)
    embed = nn.Embed(cfg.vocab, cfg.model_dim, name="embed")
    blocks_full = StageBlocks(cfg.model_dim, cfg.model_heads, layers=L,
                              dtype=cdtype, remat=cfg.remat, attn_fn=attn_fn)
    blocks_stage = StageBlocks(cfg.model_dim, cfg.model_heads, layers=l_loc,
                               dtype=cdtype, remat=cfg.remat, attn_fn=attn_fn)
    final_ln = nn.LayerNorm(use_bias=False, name="final_ln")

    root = jax.random.key(cfg.seed)
    k_emb, k_blk, k_ln = jax.random.split(root, 3)
    init_toks = jnp.zeros((1, min(t_in, 8)), jnp.int32)
    init_x = jnp.zeros((1, min(t_in, 8), cfg.model_dim), cdtype)
    init_pos = jnp.arange(init_x.shape[1])
    params = {
        "embed": embed.init(k_emb, init_toks)["params"],
        "blocks": blocks_full.init(k_blk, init_x, init_pos)["params"],
        "final_ln": final_ln.init(k_ln, init_x.astype(jnp.float32))["params"],
    }

    opt = optim.build_optimizer_from_cfg(cfg)
    unravel, dim, leaf_offsets = _make_unravel(params)

    # parameter residence between steps: stage stacks shard their leading
    # layer axis over pp, everything else replicated
    def _leaf_spec(path):
        # membership, not names[0]: opt_state paths reach the stage stacks
        # as 0/momentum_buf/blocks/... — a leading-name test left every
        # momentum slot replicated at rest while the compiled step emitted
        # it pp-sharded, i.e. a resharding retrace on the second dispatch
        # (the exact PR 6 failure mode, caught by lint rule 7)
        names = [getattr(k, "key", str(k)) for k in path]
        if "blocks" in names:
            return P(PP_AXIS)
        return P()

    def _leaf_spec_n(path):
        """Same, with the per-worker broadcast axis leading."""
        return P(WORKER_AXIS, *_leaf_spec(path))

    params = shard_params(params, mesh, _leaf_spec)
    state = TrainState(
        params=params,
        opt_state=shard_params(opt.init(params), mesh, _leaf_spec),
        batch_stats=None,
        step=jax.device_put(jnp.asarray(1, jnp.int32),
                            NamedSharding(mesh, P())),
    )

    params_n_specs = jax.tree_util.tree_map_with_path(
        lambda path, _: _leaf_spec_n(path), params
    )

    def device_loss(params_n_local, tokens_local):
        """One device = one (worker-block, stage) cell of the mesh.

        params_n_local: this device's worker replicas, this stage's block
        slice — leaves (lanes, [l_loc,] ...) where lanes = num_workers /
        mesh w-axis (1 on a full mesh). tokens_local: (lanes, B, T).
        Returns each lane worker's mean next-token CE, replicated over pp,
        shape (lanes,)."""
        return jax.vmap(_lane_loss)(params_n_local, tokens_local)

    def _lane_loss(p, toks):
        inp, tgt = toks, toks[:, 1:]
        my = lax.axis_index(PP_AXIS)
        positions = jnp.arange(t_in)

        # stage 0's injections: embedded microbatches, padded with S-1
        # bubble ticks (every stage computes the embedding locally — it is
        # one gather; only stage 0's enters the pipeline, so only stage 0
        # contributes its cotangent)
        x = embed.apply({"params": p["embed"]}, inp).astype(cdtype)
        x_mb = x.reshape(M, mb, t_in, cfg.model_dim)
        feed = jnp.concatenate(
            [x_mb, jnp.zeros((S - 1, mb, t_in, cfg.model_dim), cdtype)], axis=0
        ) if S > 1 else x_mb

        def stage(xin):
            return blocks_stage.apply({"params": p["blocks"]}, xin, positions)

        if S == 1:
            outs = jax.vmap(stage)(x_mb)
        else:
            def tick(carry, t):
                cur, outs = carry
                xin = lax.dynamic_index_in_dim(feed, t, 0, keepdims=False)
                xin = jnp.where(my == 0, xin, cur)
                out = stage(xin)
                # hand to the successor stage; stage 0 receives nothing
                # (ppermute leaves unaddressed receivers zero)
                nxt = lax.ppermute(
                    out, PP_AXIS, [(i, i + 1) for i in range(S - 1)]
                )
                idx = t - (S - 1)
                upd = lax.dynamic_update_index_in_dim(
                    outs, out, jnp.clip(idx, 0, M - 1), 0
                )
                outs = jnp.where(idx >= 0, upd, outs)
                return (nxt, outs), None

            outs0 = jnp.zeros((M, mb, t_in, cfg.model_dim), cdtype)
            (_, outs), _ = lax.scan(
                tick, (jnp.zeros((mb, t_in, cfg.model_dim), cdtype), outs0),
                jnp.arange(M + S - 1),
            )

        # head on the last stage (all stages run it SPMD-uniformly; the
        # where selects, and non-last contributions are exact zeros)
        h = final_ln.apply({"params": p["final_ln"]},
                           outs.astype(jnp.float32))
        logits = embed.apply({"params": p["embed"]}, h, method="attend")
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))[:, :, :-1]
        tgt_mb = tgt.reshape(M, mb, t_in - 1)
        nll = -jnp.take_along_axis(logp, tgt_mb[..., None], axis=-1)[..., 0]
        loss = jnp.where(my == S - 1, jnp.mean(nll), 0.0)
        return lax.psum(loss, PP_AXIS)

    losses_fn = shard_map(
        device_loss,
        mesh=mesh,
        in_specs=(params_n_specs, P(WORKER_AXIS, None, None)),
        out_specs=P(WORKER_AXIS),
        check_vma=False,
    )

    def _broadcast_n(params):
        bcast = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params
        )
        return _constrain_params(bcast, mesh, _leaf_spec_n)

    def per_worker_loss(params, tokens):
        return losses_fn(_broadcast_n(params), tokens)

    def per_worker_grads(params, tokens):
        def total(params_n):
            losses = losses_fn(params_n, tokens)
            return jnp.sum(losses), losses

        grads_n, losses = jax.grad(total, has_aux=True)(_broadcast_n(params))
        flat = _flatten_rows(grads_n)
        return lax.with_sharding_constraint(
            flat, NamedSharding(mesh, P(WORKER_AXIS))
        ), losses

    code = build_code_from_cfg(cfg)

    def step_body(state: TrainState, tokens, adv_mask, present=None):
        with jax.named_scope("draco_comp"):
            grads, losses = per_worker_grads(state.params, tokens)
        # in-graph decode projection — no d-length program constant
        # (rng.random_projection_factors_in_graph docstring); the approx
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
            constrain=lambda p: _constrain_params(p, mesh, _leaf_spec),
        )
        with jax.named_scope("draco_health"):
            metrics = {"loss": masked_loss_metric(losses, present)}
            metrics.update(decode_health_metrics(health, adv_mask, present))
        metrics.update(guard_cols)
        return new_state, metrics

    def eval_body(params, tokens):
        return jnp.mean(per_worker_loss(params, tokens))

    from draco_tpu.parallel.sp_step import token_fn_from_cfg

    metric_names = token_metric_names(cfg)
    # state-in == state-out at the JIT boundary, tp_step-style: the carry
    # pin stops GSPMD from electing a different at-rest layout for the
    # momentum stacks than shard_params installed (lint rule 7 audits this
    # contract on every registered program)
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    with mesh:
        train_step = jax.jit(step_body, donate_argnums=(0,),
                             out_shardings=(state_shardings, None))
        eval_step = jax.jit(eval_body)
        loss_jit = jax.jit(per_worker_loss)
        grads_jit = jax.jit(per_worker_grads)
        train_token_many = jax.jit(
            make_token_train_many(step_body, token_fn_from_cfg(cfg),
                                  metric_names=metric_names),
            donate_argnums=(0,),
            out_shardings=(state_shardings, None),
        )

    return PPTrainSetup(
        state=state, train_step=train_step, eval_step=eval_step,
        per_worker_loss=loss_jit, per_worker_grads=grads_jit,
        code=code, unravel=unravel, dim=dim,
        train_token_many=train_token_many, metric_names=metric_names,
    )


# ---- program-lint registration (draco_tpu/analysis) -----------------------

# The route's explicit-collective budget at the audited shape (2 stages,
# 2 microbatches, 2 layers): the forward tick loop plus its transposed
# backward ride 2 collective_permute ops, and the loss/grad psums over pp
# contribute 4 all_reduce. Static op counts — layout-independent (same on
# the 16-device chip audit and the folded 8-device CI mesh), shared with
# tools/tpu_parallel_lowering_check.py; a legitimate schedule change
# updates it HERE, once (PERF_HISTORY.md §6).
LINT_COLLECTIVES = {"all_reduce": 4, "collective_permute": 2}


def lint_programs():
    """The GPipe pipeline route's chip-bound programs. The schedule's hop
    structure is explicit (shard_map + ppermute inside the traced pipeline
    loop), so the manifest pins it (LINT_COLLECTIVES above). A count drift
    here means the pipeline schedule itself changed."""
    from draco_tpu.analysis.registry import (
        LintProgram, Manifest, built_token_program, ci_lm_config,
    )
    from draco_tpu.parallel.mesh import make_mesh_wpp

    # all explicit hops and psums lower over the pp axis — a w-axis
    # collective here would mean the coding tail left pure GSPMD
    manifest = Manifest(collectives=LINT_COLLECTIVES,
                        collective_axes={"pp": dict(LINT_COLLECTIVES)})

    def _build(name, many):
        cfg = ci_lm_config(pipeline_shards=2, pp_microbatches=2,
                           model_layers=2)
        mesh = make_mesh_wpp(4, 2)  # 8 CI devices; n=8 folds 2 lanes/device
        setup = build_pp_train_setup(cfg, mesh)
        return built_token_program(name, cfg, mesh, setup, manifest,
                                   many=many,
                                   partition_rules=PP_STEP_RULES)

    return [
        LintProgram("lm_pp_step", route="pp",
                    build=lambda: _build("lm_pp_step", False)),
        LintProgram("lm_pp_many_k2", route="pp",
                    build=lambda: _build("lm_pp_many_k2", True)),
    ]


def train_pp(cfg: TrainConfig, mesh, steps: Optional[int] = None,
             quiet: bool = False, profile_dir: Optional[str] = None):
    """PP training loop; returns (state, last metrics)."""
    from draco_tpu.parallel.token_loop import run_token_loop

    setup = build_pp_train_setup(cfg, mesh)
    return run_token_loop(setup, cfg, steps, quiet, tag="pp",
                          profile_dir=profile_dir)
