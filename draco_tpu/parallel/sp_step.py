"""Coded data parallelism × sequence parallelism: the 2-D-mesh training step.

Composition (SURVEY.md §5.7): ring attention makes each logical worker's
sequence span the ``sp`` axis; the per-shard gradients psum over ``sp`` into
exact whole per-worker gradients; Draco's coding/aggregation then acts on the
(n, d) gradient matrix over ``w`` exactly as in the CNN path
(draco_tpu/training/step.py) — Byzantine resilience is oblivious to how each
worker's compute was sharded.

Supported approaches here: ``baseline`` (mean / geo-median / krum),
``cyclic`` with either redundancy mode — ``simulate`` (reference-parity
2s+1-lane redundant compute per worker, cyclic_worker.py:122-146) or
``shared`` (each batch gradient computed once, rows formed algebraically) —
``approx``, and ``maj_vote`` at ``seq_shards == 1``: the token loop feeds
every member of a repetition group the same rows (token_loop.step_tokens),
the lanes run the identical program on them and so agree bitwise, and the
vote (coding/repetition.py) acts on the raw (n, d) rows. (Under a sharded
sequence a group member is a whole mesh row; config.validate refuses that
by name.)

The token model comes from ``models.build_lm``: ``TransformerLM`` on every
mesh this route builds; the published-config blocks ``LatentMoeLM`` (latent
attention, routed and shared experts), ``HybridMoeLM`` (Gated DeltaNet
beside gated attention) and ``WindowedMoeLM`` (sliding-window beside full
attention), all over the same expert layer, at ``seq_shards == 1``. Where the
(lanes, d) stack of per-lane gradients computed side by side would not fit
beside the rest of the step (``LANES_IN_TURN_BYTES``), the lanes are
evaluated in turn (a loop), each layer is rematerialised in the
backward pass and the vote's stack is kept in the chip's tiles
(``STACK_LANES``) as the carry of the lanes' loop, each lane writing its
leaves into its row of it in whole 128-wide lines: ONE size test, and the
shape decides, no option does. The vote then reads that stack once — finite
check, simulated attack and fingerprints in one sweep, nothing stack-sized
stored — and the winner's row is copied once, the leaves cut from it where
it lies
(parallel/common.aggregate_flat_grads, coding/repetition.majority_vote).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from draco_tpu import optim, rng as drng
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.config import TrainConfig
from draco_tpu.models import build_lm
from draco_tpu.obs import compile_watch
from draco_tpu.obs.tracer import setup_span
from draco_tpu.parallel.a2a_attention import a2a_attention
from draco_tpu.parallel.common import (
    TOKEN_METRIC_NAMES,
    aggregate_flat_grads,
    build_code_from_cfg,
    decode_health_metrics,
    finish_flat_step,
    make_token_train_many,
    masked_loss_metric,
    token_metric_names,
)
from draco_tpu.parallel.mesh import SEQ_AXIS
from draco_tpu.parallel.partition import (
    REPLICATED, SP_STEP_RULES, WORKER_ROWS, WORKER_ROWS3, approx_rules,
    sharding,
)
from draco_tpu.parallel.ring_attention import ring_attention
from draco_tpu.runtime import WORKER_AXIS
from draco_tpu.training.step import TrainState, _flatten_tree, _make_unravel


# A (lanes, d) float32 stack of per-lane gradients above this, built by
# vmapped lanes (each with its own gradient tree and activations alive at
# once), does not fit one 16 GB chip beside weights, momentum and the decoded
# gradient: such a step evaluates its lanes in turn and rematerialises per
# layer. d = 425 M x 3 lanes is 5.1 GB: the large side is the cells
# kanana2.maj_vote_r3, qwen3next.maj_vote_r3 and, with this constant set to 0,
# tests/test_lm_maj_vote.py::test_lanes_in_turn_train_the_same_as_side_by_side;
# every other LM the tests build is on the small side.
LANES_IN_TURN_BYTES = 2**30
# Such a stack is kept (lanes, d / 128, 128), d closed to a whole (8, 128)
# tile with zeros, where the vote reads it (coding/repetition.py takes rows
# of several axes and hashes them a block at a time): one lane's row as the
# chip's own (8, 128) tiles in order, which is the flat row's bytes as they
# lie, so the reshape moves nothing, no lane is padded and a lane's row is
# one contiguous block — and a leaf of the model is a range of the row's
# lines (every offset and size of a published width is a multiple of 128;
# a model keeps what is not, per-head vectors, last in ravel order), so the
# winner's row is cut into leaves where it lies
# (training/step._make_unravel). Measured at d = 425 M on the chip (PERF.md
# section 6; there as (lanes, d / 1024, 8, 128), the same bytes): as
# (lanes, d) the tiling is (4, 128) — three lanes padded to four, 6.3 GB
# for 4.75, and writing ONE lane's row rewrites every tile of the stack,
# 36 ms a lane and again for each row an attack touches; as
# (lanes, 8, d / 8) building a lane's row is eight such rewrites, 49 ms; as
# (lanes, 1, d) the stack is linear but every pass over it uses one
# sublane of eight (the fingerprints 80 ms for 10).
# A lane's row is WRITTEN in whole lines too (``row_layout``,
# ``_write_row``): the leaves are walked in ravel order and cut into pieces
# wherever the running offset is a multiple of 128; a leaf that is whole
# lines at a whole-line offset is a piece by itself, laid out (lines, 128)
# by a reshape that moves nothing; what is not whole lines is the ravel's
# tail, joined flat with the zeros that close the row into one small piece
# of whole lines; the stack is the carry of the lanes' loop and each piece is
# written once, into its range of the lane's lines — no row exists beside
# the stack, flat or in lines. The measured cost of breaking it (ledger,
# PR 35, qwen3next.maj_vote_r3): 192 floats of d = 424 340 544 in two
# (3, 32) leaves made the flat row's length no multiple of 128; the
# compiler, which otherwise turns the flat concatenate into one in-place
# update-slice a leaf, kept ONE concatenate of the 66 leaves at 42 % of the
# memory rate, 29.2 ms a step, ahead of the row's 15.5 ms copy into the
# stack (that copy every large cell paid: PERF.md section 6, PR 36).
STACK_LANES = 128


class RowLayout(NamedTuple):
    """The static layout of a lane's row in a tiled stack: what
    ``_write_row`` does for this model's leaf table."""
    lines: int  # 128-wide lines of a row, the closing zeros included
    zeros: int  # zeros that close the row's last (8, 128) tile
    # leaves that reach a line's end only together with their neighbours
    # (or with the zeros), so are joined flat first, and their elements
    joined_leaves: int
    joined_size: int
    # (first leaf, one past the last) of each piece in ravel order, the
    # zeros counted as one more leaf after the last
    pieces: tuple


def row_layout(sizes) -> RowLayout:
    """Cut the leaves' ravel (``sizes`` in ravel order) into pieces that
    each start and end on a 128-wide line."""
    sizes = [int(s) for s in sizes]
    zeros = -sum(sizes) % (8 * STACK_LANES)
    pieces, start, offset = [], 0, 0
    for i, size in enumerate(sizes + [zeros] * bool(zeros)):
        offset += size
        if offset % STACK_LANES == 0:
            pieces.append((start, i + 1))
            start = i + 1
    joined = [size for lo, hi in pieces if hi - lo > 1
              for size in sizes[lo:hi]]  # the slice leaves the zeros out
    return RowLayout(lines=offset // STACK_LANES, zeros=zeros,
                     joined_leaves=len(joined), joined_size=sum(joined),
                     pieces=tuple(pieces))


def _write_row(stack, lane, tree, layout: RowLayout):
    """A gradient tree written into row ``lane`` of the tiled
    (lanes, lines, 128) stack, a whole-line piece at a time, each into its
    range of the row's lines (``STACK_LANES``). Byte for byte the row is
    ``jnp.pad(_flatten_tree(tree), (0, layout.zeros))`` in lines."""
    leaves = jax.tree.leaves(tree)
    if layout.zeros:
        leaves.append(jnp.zeros((layout.zeros,), stack.dtype))
    line = 0
    for lo, hi in layout.pieces:
        piece = _flatten_tree(leaves[lo:hi]).reshape(1, -1, STACK_LANES)
        stack = lax.dynamic_update_slice(stack, piece, (lane, line, 0))
        line += piece.shape[1]
    return stack


class SPTrainSetup(NamedTuple):
    model: Any  # models.build_lm's token model
    state: TrainState
    # (state, tokens (n,B,T), adv_mask (n,)) -> (state, metrics)
    train_step: Any
    eval_step: Any  # (params, tokens) -> loss (no donation, no update)
    code: Optional[cyclic_mod.CyclicCode]
    unravel: Any
    dim: int
    # K fused LM steps in ONE device program (parallel/common.py):
    # (state, toks (K,n,B,T) | steps (K,), masks (K,n), presents (K,n)|None)
    #   -> (state, metrics (K, len(metric_names)) float32)
    train_token_many: Any = None
    metric_names: tuple = TOKEN_METRIC_NAMES
    # how a lane's row is assembled where the stack is kept in tiles
    # (STACK_LANES); None on the small side, whose rows are flat
    row_layout: Optional[RowLayout] = None


def synthetic_text(seed: int, step: int, n: int, batch: int,
                   seq_len: int, vocab: int):
    """Deterministic learnable token stream: ramps t_{i+1} = t_i + stride
    with
    per-sequence stride ∈ {1, 2}. Same (seed, step) ⇒ same batch everywhere."""
    r = np.random.RandomState((seed * 1_000_003 + step) % (2**31 - 1))
    start = r.randint(0, vocab, size=(n, batch, 1))
    stride = r.randint(1, 3, size=(n, batch, 1))
    idx = np.arange(seq_len)[None, None, :]
    return ((start + stride * idx) % vocab).astype(np.int32)


def synthetic_text_in_graph(seed: int, step, n: int, batch: int, seq_len: int,
                            vocab: int):
    """In-graph counterpart of :func:`synthetic_text` (cfg.token_gen ==
    "device"): the same ramp construction (start + stride·i mod vocab,
    stride ∈ {1, 2}), generated INSIDE the jitted program from the scalar
    (seed, step) — ``step`` may be traced, so a scanned K-step driver feeds
    it per-iteration from the (K,) step vector and the host never assembles
    or uploads a token block at all (the discipline of
    rng.random_projection_factors_in_graph). Values come from the jax PRNG,
    not numpy's MT19937, so the two streams differ draw-by-draw while
    sharing distribution and the property that matters: every participant
    derives the identical batch from (seed, step)."""
    key = jax.random.fold_in(jax.random.key(seed), step)
    k_start, k_stride = jax.random.split(key)
    start = jax.random.randint(k_start, (n, batch, 1), 0, vocab)
    stride = jax.random.randint(k_stride, (n, batch, 1), 1, 3)
    idx = jnp.arange(seq_len)[None, None, :]
    return ((start + stride * idx) % vocab).astype(jnp.int32)


def token_fn_from_cfg(cfg: TrainConfig):
    """The in-graph per-step token generator for cfg.token_gen == "device"
    (None for the default host-generated stream) — shared by every LM route
    builder so the scanned drivers can't disagree on the stream."""
    if cfg.token_gen != "device":
        return None
    rows, repeat = token_rows(cfg)
    return lambda step: jnp.repeat(synthetic_text_in_graph(
        cfg.seed, step, rows, cfg.batch_size, cfg.seq_len, cfg.vocab,
    ), repeat, axis=0)


def token_rows(cfg: TrainConfig):
    """(distinct rows a step draws, copies of each): n rows once, or under
    ``maj_vote`` one row a repetition group, held by all its members — the
    vote's soundness condition (coding/repetition.py)."""
    if cfg.approach == "maj_vote":
        return cfg.num_workers // cfg.group_size, cfg.group_size
    return cfg.num_workers, 1


def build_sp_train_setup(cfg: TrainConfig, mesh) -> SPTrainSetup:
    """mesh must have axes (w, sp) — see make_mesh_2d."""
    cfg.validate()
    if cfg.approach not in ("baseline", "cyclic", "approx", "maj_vote"):
        raise ValueError(
            f"SP path supports baseline|cyclic|approx|maj_vote, got "
            f"{cfg.approach}")
    n = cfg.num_workers
    sp = mesh.shape[SEQ_AXIS]
    # logical workers fold onto the available w-axis devices in equal
    # lane blocks (same discipline as tp_step / runtime.make_mesh): a
    # single chip can still run the n-lane coded step, vmapped
    if n % mesh.shape[WORKER_AXIS]:
        raise ValueError(
            f"num_workers {n} must be a multiple of the mesh's w axis "
            f"({mesh.shape[WORKER_AXIS]})"
        )
    if cfg.seq_len % sp:
        raise ValueError(f"seq_len {cfg.seq_len} not divisible by sp={sp}")
    t_local = cfg.seq_len // sp

    # set-up keeps its own ledger (obs/tracer.py): no tracer exists yet,
    # and the builds the eager init pays are the process-wide dispatcher's
    compile_watch.install()
    with setup_span("setup.model_init"):
        from draco_tpu.ops.flash_attention import attn_impl_fn

        flash = attn_impl_fn(cfg)
        if flash is not None and sp == 1:
            # single-shard long-context path: the Pallas blockwise kernel
            # (per-device inside shard_map — no GSPMD partitioning involved)
            attn = flash
        elif flash is not None and cfg.sp_attn == "ring":
            # ring + flash: the kernel attends each visiting K/V block
            # (causal self hop, unmasked past hops, future hops skipped) and
            # per-hop outputs merge by differentiable lse weights
            from draco_tpu.parallel.ring_attention import ring_flash_attention

            attn = functools.partial(ring_flash_attention,
                                     axis_name=SEQ_AXIS)
        elif flash is not None:
            # Ulysses + flash: head-scatter a2a, then the flash kernel on
            # each device's full-sequence head group
            attn = functools.partial(a2a_attention, axis_name=SEQ_AXIS,
                                     inner=flash)
        else:
            attn_impl = (ring_attention if cfg.sp_attn == "ring"
                         else a2a_attention)
            attn = functools.partial(
                attn_impl, axis_name=SEQ_AXIS if sp > 1 else None
            )
        # the route's attention and the bare kernel: each model takes the
        # one it can use (models.build_lm)
        model = build_lm(cfg, attn, kernel_fn=flash)
        # ready, so that the span ends with the work
        params = jax.block_until_ready(model.init(jax.random.key(cfg.seed)))

    with setup_span("setup.state"):
        opt = optim.build_optimizer_from_cfg(cfg)
        unravel, dim, leaf_offsets = _make_unravel(params)
        lanes = n // mesh.shape[WORKER_AXIS]
        lanes_in_turn = 4 * lanes * dim > LANES_IN_TURN_BYTES
        tiled_stack = lanes_in_turn and cfg.approach == "maj_vote"
        # the row in whole lines; its zeros close the last (8, 128) tile
        # (none at kanana2's d = 415 001 tiles, 960 at qwen3next's
        # d = 424 340 544, 768 at mellum2's); every lane writes the same,
        # so the vote is unmoved
        layout = row_layout(np.diff(leaf_offsets)) if tiled_stack else None
        if lanes_in_turn and not cfg.remat:
            model = build_lm(dataclasses.replace(cfg, remat=True), attn,
                             kernel_fn=flash)

        repl = sharding(mesh, REPLICATED)
        shard_w = sharding(mesh, WORKER_ROWS)
        state = jax.block_until_ready(TrainState(
            params=jax.device_put(params, repl),
            opt_state=jax.device_put(opt.init(params), repl),
            batch_stats=None,
            step=jax.device_put(jnp.asarray(1, jnp.int32), repl),
        ))

    # the rest is setup.step_build: the code, the closures, the jit wrappers
    with setup_span("setup.step_build"):
        # ---- per-device worker-gradient computation (manual SPMD) ---------
        def _shard_objective(params, toks, train: bool):
            """This shard's masked next-token CE contribution (scalar); the
            psum over sp equals the single-shard mean CE: each shard also
            predicts its successor shard's first token (fetched with one
            ppermute hop), the global last position is masked, and per-shard
            sums are normalised by the global (T−1)·B — so sp is
            trajectory-invariant (asserted in tests/test_parallel_sp.py)."""
            idx = lax.axis_index(SEQ_AXIS)
            off = idx * t_local
            # shard i receives shard (i+1)'s first token (garbage on the last
            # shard, masked below)
            nxt_first = lax.ppermute(
                toks[:, :1], SEQ_AXIS, [(j, (j - 1) % sp) for j in range(sp)]
            )
            # (B, t_local)
            targets = jnp.concatenate([toks[:, 1:], nxt_first], axis=1)
            pos_valid = jnp.where(
                idx == sp - 1,
                (jnp.arange(t_local) < t_local - 1).astype(jnp.float32),
                jnp.ones((t_local,), jnp.float32),
            )
            denom = toks.shape[0] * (cfg.seq_len - 1)
            return model.weighted_nll(
                params, toks, targets, weights=pos_valid,
                denom=denom, pos_offset=off, train=train)

        def over_lanes(fn, tokens):
            """``fn`` on every lane's tokens: side by side, or in turn where
            the lanes' gradients side by side would not fit
            (LANES_IN_TURN_BYTES)."""
            return (lax.map(fn, tokens) if lanes_in_turn
                    else jax.vmap(fn)(tokens))

        def device_grads(params, tokens):
            """tokens: (lanes, B, t_local) — this device's shard of its
            workers' batches (lanes = num_workers / mesh w-axis; 1 on a full
            mesh).
            Returns (flat_grads (lanes, d) — (lanes, lines, 128) where the
            stack is tiled —, losses (lanes,), the model's counters (lanes,)
            each) — each worker's FULL gradient,
            psum-assembled over sp and replicated along it."""
            def lane_grad(toks):
                (loss, stats), g = jax.value_and_grad(
                    lambda p: _shard_objective(p, toks, train=True),
                    has_aux=True)(params)
                return g, loss, stats

            if tiled_stack:
                # the stack is the loop's carry and every lane writes its
                # leaves into it where they belong: no row exists beside it
                def into_stack(stack, lane_toks):
                    lane, toks = lane_toks
                    g, loss, stats = lane_grad(toks)
                    return _write_row(stack, lane, g, layout), (loss, stats)

                g, (loss, stats) = lax.scan(
                    into_stack,
                    # every element is written: the pieces tile the row
                    lax.empty((lanes, layout.lines, STACK_LANES),
                              jnp.result_type(*jax.tree.leaves(params))),
                    (jnp.arange(lanes), tokens))
            else:
                def one_lane(toks):
                    g, loss, stats = lane_grad(toks)
                    return _flatten_tree(g), loss, stats

                g, loss, stats = over_lanes(one_lane, tokens)
            # exact per-worker grad: cotangents already routed through the
            # ring's transpose; psum folds the shard contributions
            g = lax.psum(g, SEQ_AXIS)
            loss = lax.psum(loss, SEQ_AXIS)
            return g, loss, stats

        def device_loss(params, tokens):
            """Forward-only held-out loss (no backward, no gradient ICI
            traffic)."""
            loss = over_lanes(
                lambda toks: _shard_objective(params, toks, train=False)[0],
                tokens)
            return lax.psum(loss, SEQ_AXIS)

        grads_fn = shard_map(
            device_grads,
            mesh=mesh,
            in_specs=(P(), P(WORKER_AXIS, None, SEQ_AXIS)),
            out_specs=(P(WORKER_AXIS, None), P(WORKER_AXIS), P(WORKER_AXIS)),
            check_vma=False,
        )

        def device_grads_sim(params, tokens):
            """Reference-parity r× redundant compute under SP: tokens
            (lanes, hat_s, B, t_local) — each lane worker really evaluates
            its hat_s = 2s+1 assigned batch rows (cyclic_worker.py:122-146).
            Returns ((lanes, hat_s, d), (lanes, hat_s))."""
            def one_row(toks):
                (loss, _stats), g = jax.value_and_grad(
                    lambda p: _shard_objective(p, toks, train=True),
                    has_aux=True)(params)
                return _flatten_tree(g), loss

            g, loss = jax.vmap(jax.vmap(one_row))(tokens)
            return lax.psum(g, SEQ_AXIS), lax.psum(loss, SEQ_AXIS)

        grads_fn_sim = shard_map(
            device_grads_sim,
            mesh=mesh,
            in_specs=(P(), P(WORKER_AXIS, None, None, SEQ_AXIS)),
            out_specs=(P(WORKER_AXIS, None, None), P(WORKER_AXIS, None)),
            check_vma=False,
        )

        # ---- aggregation over w (identical machinery to the CNN path) -----
        code = build_code_from_cfg(cfg)
        simulate = cfg.approach == "cyclic" and cfg.redundancy == "simulate"
        batch_ids = jnp.asarray(code.batch_ids) if simulate else None
        shard_w3 = sharding(mesh, WORKER_ROWS3)

        def step_body(state: TrainState, tokens, adv_mask, present=None):
            with jax.named_scope("draco_comp"):
                if simulate:
                    # gather each worker's redundant rows (n, hat_s, B, T);
                    # GSPMD inserts the w-axis collective for the
                    # cross-worker rows
                    toks_w = tokens[batch_ids]
                    grads, losses = grads_fn_sim(state.params, toks_w)
                    grads = lax.with_sharding_constraint(grads, shard_w3)
                    losses = jnp.mean(losses, axis=1)
                    stats = {}
                else:
                    grads, losses, stats = grads_fn(state.params, tokens)
                    grads = lax.with_sharding_constraint(
                        grads, sharding(mesh, P(WORKER_AXIS))
                        if tiled_stack else shard_w)
            # in-graph decode projection — no d-length program constant
            # (rng.random_projection_factors_in_graph docstring); the approx
            # decode is projection-free (real least squares, no syndrome)
            with jax.named_scope("draco_input"):
                rand_factor = (
                    drng.random_projection_factors_in_graph(cfg.seed, dim)
                    if cfg.approach == "cyclic" else None)
            agg, health = aggregate_flat_grads(grads, adv_mask, cfg, code,
                                               rand_factor, present=present,
                                               leaf_offsets=leaf_offsets,
                                               step=state.step, mesh=mesh)
            new_state, guard_cols = finish_flat_step(cfg, state, agg, health,
                                                     opt, unravel,
                                                     present=present)
            with jax.named_scope("draco_health"):
                metrics = {"loss": masked_loss_metric(losses, present)}
                metrics.update(
                    decode_health_metrics(health, adv_mask, present))
                # the token model's own counters (a mean over the lanes)
                metrics.update({k: jnp.mean(v) for k, v in stats.items()})
            metrics.update(guard_cols)
            return new_state, metrics

        loss_fn = shard_map(
            device_loss,
            mesh=mesh,
            in_specs=(P(), P(WORKER_AXIS, None, SEQ_AXIS)),
            out_specs=P(WORKER_AXIS),
            check_vma=False,
        )

        def eval_body(params, tokens):
            return jnp.mean(loss_fn(params, tokens))

        metric_names = token_metric_names(cfg, model.stat_names)
        with mesh:
            train_step = jax.jit(step_body, donate_argnums=(0,))
            eval_step = jax.jit(eval_body)
            train_token_many = jax.jit(
                make_token_train_many(step_body, token_fn_from_cfg(cfg),
                                      metric_names=metric_names),
                donate_argnums=(0,),
            )

        return SPTrainSetup(
            model=model, state=state, train_step=train_step,
            eval_step=eval_step, code=code, unravel=unravel, dim=dim,
            train_token_many=train_token_many, metric_names=metric_names,
            row_layout=layout,
        )


# ---- program-lint registration (draco_tpu/analysis) -----------------------

# The route's explicit-collective budget at the audited shape (1 layer,
# sp=2): each layer's ring attention is sp-1 ppermute hops plus the
# target-handoff hop, and the per-worker gradient/loss assembly is two
# psums over sp. Static op counts — layout-independent (the 16-device
# chip audit and the folded 8-device CI mesh observe the same counts), so
# tools/tpu_parallel_lowering_check.py imports this same constant. A
# legitimate schedule change updates it HERE, once (PERF_HISTORY.md §6).
LINT_COLLECTIVES = {"all_reduce": 2, "collective_permute": 5}


def lint_programs():
    """The SP route's chip-bound programs. This is the explicit-collective
    route (LINT_COLLECTIVES above). An extra all_gather here means GSPMD
    started resharding the ring, exactly the drift the budget exists to
    catch."""
    from draco_tpu.analysis.registry import (
        BF16_DTYPES, LintProgram, Manifest, built_token_program,
        ci_lm_config,
    )
    from draco_tpu.parallel.mesh import make_mesh_2d

    # every explicit collective in the route lowers over the sp axis (ring
    # hops + the two gradient/loss psums); a w- or cross-axis collective
    # here means the coding tail stopped being pure GSPMD
    LINT_COLLECTIVE_AXES = {"sp": dict(LINT_COLLECTIVES)}

    manifest = Manifest(collectives=LINT_COLLECTIVES,
                        collective_axes=LINT_COLLECTIVE_AXES)
    # the shadow-watch program's bf16 rounds are whitelisted converts;
    # everything else in its manifest matches the ring budget exactly
    manifest_bf16 = Manifest(collectives=LINT_COLLECTIVES,
                             collective_axes=LINT_COLLECTIVE_AXES,
                             allowed_dtypes=BF16_DTYPES)

    def _build(name, many, mf=None, **overrides):
        cfg = ci_lm_config(seq_shards=2, **overrides)
        mesh = make_mesh_2d(4, 2)  # 8 CI devices; n=8 folds 2 lanes/device
        setup = build_sp_train_setup(cfg, mesh)
        return built_token_program(
            name, cfg, mesh, setup, mf or manifest, many=many,
            partition_rules=(approx_rules(SP_STEP_RULES)
                             if cfg.approach == "approx" else SP_STEP_RULES))

    return [
        LintProgram("lm_sp_ring_step", route="sp",
                    build=lambda: _build("lm_sp_ring_step", False)),
        LintProgram("lm_sp_ring_many_k2", route="sp",
                    build=lambda: _build("lm_sp_ring_many_k2", True)),
        # guarded production program (ISSUE 6): the step guard must not
        # change the ring's explicit-collective budget or donation
        LintProgram("lm_sp_ring_many_guard_k2", route="sp",
                    build=lambda: _build("lm_sp_ring_many_guard_k2", True,
                                         step_guard="on")),
        # the approx family on the ring (ISSUE 8): swapping the cyclic
        # decode for the optimal-decoding least squares must leave the
        # ring's explicit-collective budget untouched — the coding tail is
        # pure GSPMD either way, so extra collectives here would mean the
        # (n, n) solve started resharding
        LintProgram("lm_sp_ring_approx_many_k2", route="sp",
                    build=lambda: _build("lm_sp_ring_approx_many_k2", True,
                                         approach="approx", worker_fail=0,
                                         code_redundancy=1.5,
                                         step_guard="on")),
        # the fused-decode lowering of the same program (ISSUE 12):
        # decode_impl="pallas" resolves to the kernels' fused reference
        # path on the CPU host — the restructured O(n·d) decode tail must
        # keep the identical ring budget, donation and zero host traffic,
        # and this row is the device-profile join row for the
        # lm_sp_approx_pallas_k4 cell (tools/device_profile.py).
        # fast=False: an impl variant of the fast-swept approx row — the
        # full tool covers it without growing the --fast sweep budget
        LintProgram("lm_sp_ring_approx_pallas_many_k2", route="sp",
                    fast=False,
                    build=lambda: _build("lm_sp_ring_approx_pallas_many_k2",
                                         True,
                                         approach="approx", worker_fail=0,
                                         code_redundancy=1.5,
                                         step_guard="on",
                                         decode_impl="pallas")),
        # shadow-watch production program (obs/numerics.py, ISSUE 10): the
        # numerics columns + bf16 shadow decode ride the shared flat-grad
        # tail — the ring's explicit-collective budget and donation must
        # not move (the shadow is reductions + a second GSPMD decode of
        # already-gathered rows, never a shard_map collective)
        LintProgram("lm_sp_ring_shadow_many_k2", route="sp",
                    build=lambda: _build("lm_sp_ring_shadow_many_k2", True,
                                         mf=manifest_bf16,
                                         numerics_watch="on",
                                         shadow_wire="bf16",
                                         step_guard="on")),
        # REAL narrow-wire production program (ISSUE 15): the flat-grad
        # tail's codewords cross the sharding boundary as actual bf16
        # buffers and the λ-regularized locator decodes them — ring
        # budget, donation and host traffic unchanged, and the manifest
        # REQUIRES bf16 in the module (a silently-f32 "narrow" ring
        # program trips the dtype rule)
        LintProgram("lm_sp_ring_wire_bf16_many_k2", route="sp",
                    build=lambda: _build(
                        "lm_sp_ring_wire_bf16_many_k2", True,
                        mf=Manifest(collectives=LINT_COLLECTIVES,
                                    collective_axes=LINT_COLLECTIVE_AXES,
                                    allowed_dtypes=BF16_DTYPES,
                                    required_dtypes=frozenset({"bf16"})),
                        wire_dtype="bf16", step_guard="on")),
    ]


def train_sp(cfg: TrainConfig, mesh, steps: Optional[int] = None,
             quiet: bool = False, profile_dir: Optional[str] = None):
    """SP training loop on the synthetic text stream; returns the final state
    and last-step metrics. Checkpoint/eval/resume/chunking semantics live in
    the shared token loop (parallel/token_loop.py); ``profile_dir`` captures
    a jax.profiler device trace there (chunk-snapped under K>1)."""
    from draco_tpu.parallel.token_loop import run_token_loop

    return run_token_loop(build_sp_train_setup(cfg, mesh), cfg, steps, quiet,
                          tag="sp", profile_dir=profile_dir,
                          # autopilot family swaps rebuild the route setup
                          # for the new regime cfg (warm-cached per regime)
                          rebuild=lambda c: build_sp_train_setup(c, mesh))
