"""Decoder LM built from a published config mapping: latent key/value
attention (MLA), sigmoid-routed experts with shared experts, RMS norm,
SwiGLU, untied head — the ``deepseek_v3`` family's block (kakaocorp
kanana-2-30b-a3b is the configuration the benchmark runs).

One mapping states the model (``TrainConfig.model_spec``): the published
``config.json`` keys verbatim, plus three that say what THIS chip holds of a
deployment that shares each layer among several chips:

  ``layers``        depth kept (further layers lie on further pipeline stages)
  ``experts_held``  [first, count]: the routed experts this chip holds
  ``vocab_rows``    rows of the vocabulary slice (ids, logits and loss are
                    over the slice)

Per layer, x (T, hidden):

  h = RMSNorm(x). q = h·Wq → (T, H, nope+rope). h·Wkva → [c | k_rope];
  c = RMSNorm(c); c·Wkvb → (T, H, nope+v) = [k_nope | v]. RoPE (interleaved
  pairs) on q_rope and on the ONE k_rope all heads share. k = [k_nope |
  k_rope]; x += causal softmax(q·kᵀ/√(nope+rope))·v · Wo.
  h = RMSNorm(x). Layers < first_k_dense_replace: x += SwiGLU(h). Expert
  layers: s = sigmoid(h·Wg) (float32, ``highest``); chosen = top-k of s + b
  (b = ``e_score_correction_bias``: a leaf of the tree so the seeded weights
  reach it, but it takes no gradient — its update rule is not in the config,
  it is held fixed); w = s[chosen]/(Σ s[chosen] + 1e-20) · routed_scaling;
  x += Σ_{e ∈ chosen ∩ held} w_e·SwiGLU_e(h) + SwiGLU_shared(h).

The expert layer is TOLD which experts it holds, scores all of them, takes
the top-k of all of them and computes its own experts' part: what the
experts held elsewhere would add is left out (no code stands in for the
absent chips or their exchange). The T·k (token, choice) pairs are sorted by
expert with this chip's experts first — T·k *scalars*; the rows that move
are the pairs that landed here. The dispatch buffer holds C rows
(``LatentMoeLM.dispatch_rows``: ``DISPATCH_SHARE`` times what uniform
routing sends this chip, T·k when it holds every expert): the first C
sorted pairs' tokens are gathered, go through one grouped product per held
expert (on a TPU jax's own megablox kernel, which skips the tiles of
experts not held — chosen over ``lax.ragged_dot`` after measuring both on
the chip, PERF.md section 6; elsewhere a dense masked product) and are
summed back into their tokens under their combine weights. No token is ever
dropped, at any routing: when more than C pairs land here the same code
runs over the next C sorted pairs, and the next, until it has passed
``landed`` (``routed_experts``: a loop whose trip count is the data's, one
in the likely case); the counter ``moe_full_dispatch`` says how many such
further buffers the step's expert layers ran.

Seeded init, head and loss are ``spec_lm.SpecLM``'s, the base every
published-config model builds on (the dense models/looped.py too); the
expert layer is ``RoutedExpertLM``'s, the sparse-expert models' part of it
(models/hybrid_moe.py, windowed_moe.py and conv_moe.py are the others): ONE
``_choose`` (scores, top-k, combine weights) and ONE ``_route`` /
``_buffer`` / ``grouped_dot`` path,
told by ``MoeSpec`` which score function ranks the experts (``sigmoid``
with the selection bias and the routed scale, or ``softmax``), whether the
model has a shared expert at all — one that has none carries no ``shared``
leaf and adds the routed part alone — and whether it is under a sigmoid
gate, and which experts this chip holds.

A model whose held experts each expect a large share of the tokens
(``DENSE_SHARE``: top-8 of 64 is an eighth) says ``MoeSpec.dense``: its
held experts run over EVERY row as three plain products
(``_every_token``), a row's combine weight zero where it did not choose
the expert — the same sum, no sort, no buffer, no gather, no scatter-add,
and work that no routing moves: the sorted path's C-row gathers and
scatter-adds cost more there than the 8 x products do, and run faster or
slower by the index pattern (PERF.md section 6, PR 35).

Device scopes (nested in the step's ``draco_comp``): ``draco_attn`` (MLA
whole), ``draco_route`` (scores, top-k, sort, gather, combine),
``draco_experts`` (every feed-forward: layer 0's dense one, the shared and
the routed experts), ``draco_head`` (final norm, logits, loss).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from draco_tpu.models.spec_lm import (  # noqa: F401  (helpers by this name)
    EMBED_STD, SpecLM, _dot, _operand, dense_causal_attention, rms_norm,
    rope_half, swiglu,
)
from draco_tpu.ops.coded import use_pallas

# the published config keys the block reads (model_spec must carry them)
SPEC_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "rope_theta",
    "rope_interleave", "rope_scaling", "rms_norm_eps",
    "first_k_dense_replace", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "scoring_func", "topk_method", "n_group", "topk_group",
    "tie_word_embeddings", "hidden_act",
    # the chip's share
    "layers", "experts_held", "vocab_rows",
)
BIAS_STD = 0.02  # e_score_correction_bias: moves the top-6, not the load
# The dispatch buffer's rows, in units of what uniform routing sends this
# chip (T·k·held/n_routed_experts pairs). A deployment's balancing keeps a
# chip's share near uniform; seeded weights do not: the benchmark's cell
# counted up to 1.55 × uniform as the mean of its four expert layers, with
# single experts at 17 × the held experts' mean — nearly every token of a
# layer (PERF.md section 6). At 4 × the likely step needs no second buffer,
# and the buffer is still a quarter of T·k where a chip holds a sixteenth
# of the experts; a routing that overflows it costs one more pass over C
# rows, not a wrong result.
DISPATCH_SHARE = 4
ROW_TILE = 256  # the grouped product's row tile; C is a multiple of it
# A model whose held experts each expect at least this share of the tokens
# (top_k / experts) may run them over EVERY token (``MoeSpec.dense``;
# models/windowed_moe.py does), the rows that did not choose an expert under
# weight zero (``RoutedExpertLM._every_token``): no sort, no buffer, no
# gather, no scatter-add, three plain products a layer whatever the routing.
# At an eighth the plain products do 8 x the chosen pairs' work and the step
# is 20-30 ms FASTER than on the sorted path, whose C-row gathers and
# scatter-adds cost more than its grouped products and follow the index
# pattern (PERF.md section 6, PR 35: measured at this one point); at
# kanana2's 1/21 and qwen3next's 1/51 they would do 21 x and 51 x.
DENSE_SHARE = 1 / 8
# the plain products' results a rematerialised layer keeps (the backward
# pass reads them; recomputing them costs the cell 18 ms a step, keeping
# them 0.54 GB)
DENSE_NAME = "draco_dense_experts"
KEEP_DENSE = jax.checkpoint_policies.save_only_these_names(DENSE_NAME)
# per-step counters of the expert layers (token-expert pairs that landed on
# the experts held, over all expert layers; the fullest held expert over the
# mean one; pairs that got no row; dispatch buffers run beyond each layer's
# first), averaged over lanes by the caller
STAT_NAMES = ("moe_assignments_held", "moe_load_max_over_mean",
              "moe_dropped", "moe_full_dispatch")


def check_spec(spec) -> None:
    """Raise ValueError, naming the key, for a mapping this block cannot
    state. What the block does not implement is refused by name."""
    if not isinstance(spec, dict):
        raise ValueError("model_spec must be a mapping of the published "
                         "config keys plus layers/experts_held/vocab_rows")
    missing = [k for k in SPEC_KEYS if k not in spec]
    if missing:
        raise ValueError(f"model_spec lacks {missing}")
    want = {"q_lora_rank": None, "rope_scaling": None,
            "rope_interleave": True, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "tie_word_embeddings": False, "hidden_act": "silu"}
    for key, value in want.items():
        if spec[key] != value:
            raise ValueError(
                f"model_spec[{key!r}] = {spec[key]!r}: this block implements "
                f"{value!r} only")
    first, count = spec["experts_held"]
    if not (0 <= first and count >= 1
            and first + count <= spec["n_routed_experts"]):
        raise ValueError(
            f"model_spec['experts_held'] = {spec['experts_held']}: a "
            f"[first, count] range inside the {spec['n_routed_experts']} "
            f"routed experts")
    if spec["num_experts_per_tok"] > spec["n_routed_experts"]:
        raise ValueError("num_experts_per_tok exceeds n_routed_experts")
    if spec["qk_rope_head_dim"] % 2:
        raise ValueError("qk_rope_head_dim must be even for the rotary pairs")
    if not 1 <= spec["first_k_dense_replace"] <= spec["layers"]:
        raise ValueError("first_k_dense_replace must lie in [1, layers]")
    if spec["vocab_rows"] < 2:
        raise ValueError("vocab_rows must be >= 2")


def rope_interleaved(x, positions, theta):
    """Rotate the last axis' pairs (x[2i], x[2i+1]) by pos·theta^(-2i/dim)."""
    if theta is None: return x  # noqa: E701 — no positions (models/kda_moe.py)
    dim = x.shape[-1]
    freqs = theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (dim // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _all_buffers(buffer, h, w, e, dispatch, needed):
    """Σ_j ``buffer(j, h, w, e, dispatch)`` over the ``needed`` dispatch
    buffers that hold a landed pair: one in the likely case, more while
    pairs lie past them — a loop whose trip count is the data's, which
    autodiff cannot reverse. So the backward pass is stated here: the same
    loop, each buffer's vjp recomputed from the layer's inputs, which are
    all that is kept. (Under the per-layer rematerialisation that costs
    nothing: the recomputed layer no longer runs this forward, nothing
    reads it. A ``lax.cond`` between a small and a full path hands back the
    untaken side's residuals as zeros, full-size, on every step; a first
    buffer outside the loop, with its own residuals kept, runs as fast and
    compiles every product twice: +22 s on a cold start, PERF.md section
    6.)"""
    return lax.fori_loop(
        0, needed, lambda j, acc: acc + buffer(j, h, w, e, dispatch),
        jnp.zeros_like(h))


def _routed_bwd(buffer, res, g):
    h, w, e, dispatch, needed = res

    def add_buffer(j, grads):
        _, pull = jax.vjp(lambda h, w, e: buffer(j, h, w, e, dispatch),
                          h, w, e)
        return jax.tree.map(jnp.add, grads, pull(g))

    zeros = jax.tree.map(jnp.zeros_like, (h, w, e))
    return (*lax.fori_loop(0, needed, add_buffer, zeros), None, None)


routed_experts = jax.custom_vjp(_all_buffers, nondiff_argnums=(0,))
routed_experts.defvjp(
    lambda buffer, *args: (_all_buffers(buffer, *args), args), _routed_bwd)


def grouped_dot(xs, kernels, sizes, held: int):
    """Rows of ``xs`` (M, K), sorted by group with the ``held`` groups this
    chip holds first, times their group's matrix in ``kernels`` (held, K,
    N). ``sizes`` counts the rows of every group, the absent ones too; rows
    of absent groups come out zero."""
    if use_pallas():
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        m, k = xs.shape
        n = kernels.shape[-1]
        tiling = (min(m, ROW_TILE), min(k, 1024), min(n, 1024))
        return megablox.gmm(_operand(xs), _operand(kernels), sizes,
                            jnp.float32, tiling,
                            jnp.zeros((), jnp.int32))
    # off the TPU (the CPU tests, under vmapped lanes, where lax.ragged_dot
    # has no batching rule): every held group's product over every row,
    # kept where the row is of that group
    ends = jnp.cumsum(sizes[:held])
    row = jnp.arange(xs.shape[0])
    mine = (row[:, None] < ends) & (row[:, None] >= ends - sizes[:held])
    full = jnp.einsum("mk,gkn->mgn", xs, kernels.astype(xs.dtype))
    return jnp.einsum("mg,mgn->mn", mine.astype(xs.dtype), full)


class MoeSpec(NamedTuple):
    """What the shared expert layer is told about its model. A model may
    have no shared expert (``shared`` None): its layers then carry no
    ``shared`` leaves and add the routed experts' part alone."""

    experts: int  # routed experts of the deployment: the router's width
    top_k: int  # experts a token takes, of all of them
    first: int  # this chip holds experts [first, first + held)
    held: int
    # "sigmoid": chosen by sigmoid score + the selection bias (a leaf that
    # takes no gradient); "softmax": chosen by the softmax over all
    # experts, no bias
    scoring: str
    norm_topk: bool  # weights renormalised over the chosen top_k
    scale: float  # the routed experts' weights times this
    # the shared expert every token takes: "plain" (added as it is),
    # "gated" (times sigmoid(h · w_sg)), or None (the model has none)
    shared: str | None
    # the held experts run over every token (``_every_token``) instead of
    # the sorted pairs' buffers: the model says so (``DENSE_SHARE``)
    dense: bool = False


class RoutedExpertLM(SpecLM):
    """The sparse-expert models' part of ``spec_lm.SpecLM`` (seeded
    ``init``, head and loss are the base's): the expert layer over the
    experts held, told by ``moe`` what its model's router and shared expert
    are."""

    stat_names = STAT_NAMES

    def __init__(self, spec: dict, moe: MoeSpec, attn_fn=None,
                 dtype=jnp.float32, remat: bool = False):
        super().__init__(spec, attn_fn, dtype, remat)
        self.moe = moe

    # ---- the expert layer ---------------------------------------------
    def dispatch_rows(self, tokens: int) -> int:
        """C, the dispatch buffer's rows for ``tokens`` rows of input: from
        the shapes alone (module constant ``DISPATCH_SHARE``), T·k where
        the chip holds every expert."""
        m = self.moe
        pairs = tokens * m.top_k
        share = -(-DISPATCH_SHARE * pairs * m.held // m.experts)
        return min(pairs, -(-share // ROW_TILE) * ROW_TILE)

    def _choose(self, h, p):
        """h (N, hidden) -> the (N, k) experts each row takes, of all of
        them, and their (N, k) combine weights."""
        m = self.moe
        k, n_exp = m.top_k, m.experts
        logits = jnp.matmul(h.astype(jnp.float32), p["kernel"],
                            precision=lax.Precision.HIGHEST)
        if m.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            bias = lax.stop_gradient(p["e_score_correction_bias"])
            _, chosen = lax.top_k(scores + bias, k)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            _, chosen = lax.top_k(scores, k)
        # scores[chosen] under a dense mask: the chip runs a gather of T·k
        # scalars, and the scatter-add its transpose is, far slower
        experts = jnp.arange(n_exp, dtype=chosen.dtype)
        w = jnp.sum(jnp.where(chosen[..., None] == experts,
                              scores[:, None, :], 0.0), axis=-1)
        if m.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * m.scale, chosen

    def _route(self, h, p):
        """h (N, hidden) -> (N, k) combine weights (zero where the chosen
        expert is not held); the dispatch, all of it scalars: the (token,
        choice) pairs' sorted order (this chip's experts first), the
        groups' sizes, the count of pairs that landed here; the count of
        dispatch buffers that hold a landed pair; and the counters."""
        m = self.moe
        first, held, n_exp = m.first, m.held, m.experts
        w, chosen = self._choose(h, p)
        experts = jnp.arange(n_exp, dtype=chosen.dtype)
        # this chip's experts become groups 0..held-1, the others follow
        group = (chosen.reshape(-1) - first) % n_exp
        order = jnp.argsort(group)  # stable: arrival order within a group
        sizes = jnp.sum(group[:, None] == experts, axis=0, dtype=jnp.int32)
        here = (group < held).reshape(chosen.shape)
        landed = jnp.sum(sizes[:held])
        rows = self.dispatch_rows(h.shape[0])
        needed = -(-landed // rows)
        stats = {"load": sizes[:held].astype(jnp.float32),
                 # pairs that chose an expert held here and lie in no
                 # buffer that is run: none, the buffers run past `landed`
                 "dropped": (jnp.sum(here) - jnp.minimum(
                     landed, needed * rows)).astype(jnp.float32),
                 "further": jnp.maximum(needed - 1, 0).astype(jnp.float32)}
        return (jnp.where(here, w, 0.0), (order, sizes, landed), needed,
                stats)

    def _buffer(self, j, h, w, e, dispatch):
        """What the sorted pairs [j·C, (j+1)·C) add to the routed experts'
        output (N, hidden): their tokens' rows gathered, one grouped product
        per held expert, the rows summed back into their tokens under
        their combine weights. C rows throughout, forward and backward."""
        order, sizes, landed = dispatch
        k, held = self.moe.top_k, self.moe.held
        rows = self.dispatch_rows(h.shape[0])
        with jax.named_scope("draco_route"):
            slot = j * rows + jnp.arange(rows, dtype=jnp.int32)
            live = slot < landed
            # (a last buffer that reaches past T·k reads padding, not live)
            pair = lax.dynamic_slice(
                jnp.pad(order, (0, -order.shape[0] % rows)), (j * rows,),
                (rows,))
            token = pair // k
            weight = jnp.where(live, w.reshape(-1)[pair], 0.0)
            # this buffer's part of every group
            ends = jnp.cumsum(sizes)
            lo, hi = j * rows, (j + 1) * rows
            part = jnp.clip(ends, lo, hi) - jnp.clip(ends - sizes, lo, hi)
            xs = h[token]
        with jax.named_scope("draco_experts"):
            mid = (jax.nn.silu(grouped_dot(xs, e["gate"]["kernel"], part,
                                           held))
                   * grouped_dot(xs, e["up"]["kernel"], part, held))
            ys = grouped_dot(mid.astype(xs.dtype), e["down"]["kernel"],
                             part, held)
            # rows of experts not held: exactly zero, whatever the
            # grouped product left there
            ys = jnp.where(live[:, None], ys, 0.0).astype(h.dtype)
        with jax.named_scope("draco_route"):
            return jax.ops.segment_sum(
                weight[:, None].astype(h.dtype) * ys, token,
                num_segments=h.shape[0])

    def _every_token(self, h, chosen, w, e):
        """What the held experts add to the routed output (N, hidden) when
        each runs over every row: gate and up each ONE product against
        the held experts' matrices side by side, the rows' combine weights
        (zero where a row did not choose the expert) on the result, and
        the down product summing over experts and width at once. Returns
        it with the held experts' loads."""
        m = self.moe
        slots = m.first + jnp.arange(m.held, dtype=chosen.dtype)
        with jax.named_scope("draco_route"):
            took = chosen[..., None] == slots  # (N, k, held)
            weight = jnp.sum(jnp.where(took, w[..., None], 0.0), axis=1)
            load = jnp.sum(took, axis=(0, 1), dtype=jnp.int32)
        with jax.named_scope("draco_experts"):
            xs = _operand(h)

            def product(kernel):
                return checkpoint_name(jnp.einsum(
                    "nd,edf->nef", xs, kernel.astype(xs.dtype),
                    preferred_element_type=jnp.float32), DENSE_NAME)

            # silu written out, so that what ``KEEP_DENSE`` keeps is the
            # two named results and nothing a jitted function made of them
            gate = product(e["gate"]["kernel"])
            mid = (gate * lax.logistic(gate) * product(e["up"]["kernel"])
                   * weight[..., None])
            mid = _operand(mid.reshape(h.shape[0], -1).astype(h.dtype))
            down = e["down"]["kernel"].astype(mid.dtype)
            ys = jnp.dot(mid, down.reshape(-1, down.shape[-1]),
                         preferred_element_type=jnp.float32)
        return ys.astype(h.dtype), load

    def _experts(self, x, p):
        """x (N, hidden) -> x + the routed (held) and, where the model has
        one, the shared expert of its normalised rows; the norm counts as
        the experts' (it feeds them)."""
        with jax.named_scope("draco_experts"):
            h = self.norm(x, p["mlp_norm"])
            # the products' operands, once a layer: every buffer reads the
            # same copies (made inside the loop over further buffers, the
            # compiler hoists a second set out of it and keeps it alive
            # through the step: +0.2 GB of peak memory)
            e = jax.tree.map(_operand, p["experts"])
        if self.moe.dense:
            with jax.named_scope("draco_route"):
                w, chosen = self._choose(h, p["router"])
            routed, load = self._every_token(h, chosen, w, e)
            stats = {"load": load.astype(jnp.float32),
                     "dropped": jnp.float32(0), "further": jnp.float32(0)}
        else:
            with jax.named_scope("draco_route"):
                w, dispatch, needed, stats = self._route(h, p["router"])
            routed = routed_experts(self._buffer, h, w, e, dispatch, needed)
        with jax.named_scope("draco_experts"):
            if self.moe.shared is None:
                return x + routed, stats
            shared = swiglu(h, p["shared"])
            if self.moe.shared == "gated":
                shared = jax.nn.sigmoid(
                    _dot(h, p["shared_gate"]["kernel"])) * shared
            return x + (routed + shared), stats


class LatentMoeLM(RoutedExpertLM):
    """The ``deepseek_v3`` family's block (module docstring)."""

    init_rules = {"scale": "ones", "embedding": EMBED_STD,
                  "e_score_correction_bias": BIAS_STD}

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        check_spec(spec)
        super().__init__(spec, MoeSpec(
            experts=spec["n_routed_experts"],
            top_k=spec["num_experts_per_tok"],
            first=spec["experts_held"][0], held=spec["experts_held"][1],
            scoring="sigmoid", norm_topk=spec["norm_topk_prob"],
            scale=spec["routed_scaling_factor"], shared="plain"),
            attn_fn, dtype, remat)

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.spec["rms_norm_eps"])

    # ---- parameters ---------------------------------------------------
    def param_shapes(self) -> dict:
        s = self.spec
        d, h = s["hidden_size"], s["num_attention_heads"]
        nope, rp, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                        s["v_head_dim"])
        rank, held = s["kv_lora_rank"], s["experts_held"][1]
        tree = {"embed": {"embedding": (s["vocab_rows"], d)},
                "final_norm": {"scale": (d,)},
                "head": {"kernel": (d, s["vocab_rows"])}}
        for i in range(s["layers"]):
            layer = {
                "attn_norm": {"scale": (d,)},
                "q": {"kernel": (d, h * (nope + rp))},
                "kv_a": {"kernel": (d, rank + rp)},
                "kv_norm": {"scale": (rank,)},
                "kv_b": {"kernel": (rank, h * (nope + vd))},
                "o": {"kernel": (h * vd, d)},
                "mlp_norm": {"scale": (d,)},
            }
            if i < s["first_k_dense_replace"]:
                layer["mlp"] = self.mlp_shapes(s["intermediate_size"])
            else:
                width = s["moe_intermediate_size"]
                layer["router"] = {
                    "kernel": (d, s["n_routed_experts"]),
                    "e_score_correction_bias": (s["n_routed_experts"],)}
                layer["shared"] = self.mlp_shapes(
                    width * s["n_shared_experts"])
                layer["experts"] = self.mlp_shapes(width, (held,))
            tree[f"layer{i}"] = layer
        return tree

    # ---- the block ----------------------------------------------------
    def _attention(self, h, p, positions):
        s = self.spec
        b, t, _ = h.shape
        heads = s["num_attention_heads"]
        nope, rp, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                        s["v_head_dim"])
        rank = s["kv_lora_rank"]
        q = _dot(h, p["q"]["kernel"]).reshape(b, t, heads, nope + rp)
        kva = _dot(h, p["kv_a"]["kernel"])
        c = self.norm(kva[..., :rank], p["kv_norm"])
        k_rope = rope_interleaved(kva[..., rank:].astype(jnp.float32),
                                  positions, s["rope_theta"])
        kvb = _dot(c, p["kv_b"]["kernel"]).reshape(b, t, heads, nope + vd)
        q = jnp.concatenate([
            q[..., :nope].astype(jnp.float32),
            rope_interleaved(q[..., nope:].astype(jnp.float32), positions,
                             s["rope_theta"])], axis=-1)
        k = jnp.concatenate([
            kvb[..., :nope].astype(jnp.float32),
            jnp.broadcast_to(k_rope[:, :, None, :], (b, t, heads, rp))],
            axis=-1)
        v = kvb[..., nope:]
        o = self.attn_fn(_operand(q), _operand(k), _operand(v))
        return _dot(o.astype(h.dtype).reshape(b, t, heads * vd),
                    p["o"]["kernel"])

    def _layer(self, x, p, positions, dense: bool):
        with jax.named_scope("draco_attn"):
            x = x + self._attention(self.norm(x, p["attn_norm"]), p,
                                    positions)
        b, t, d = x.shape
        if dense:
            with jax.named_scope("draco_experts"):
                h = self.norm(x, p["mlp_norm"])
                return x + swiglu(h, p["mlp"]), None
        y, stats = self._experts(x.reshape(b * t, d), p)
        return y.reshape(b, t, d), stats

    def hidden(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (the last layer's output (B, T, hidden), the
        STAT_NAMES counters)."""
        s = self.spec
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        positions = pos_offset + jnp.arange(tokens.shape[1])
        per_layer = []
        for i in range(s["layers"]):
            fn = functools.partial(self._layer, positions=positions,
                                   dense=i < s["first_k_dense_replace"])
            if self.remat:
                fn = jax.checkpoint(fn)
            x, stats = fn(x, params[f"layer{i}"])
            if stats is not None:
                per_layer.append(stats)
        return x, fold_stats(per_layer)


def fold_stats(per_layer: list) -> dict:
    """The expert layers' counters as the record's columns."""
    if not per_layer:
        return {}
    load = jnp.stack([s["load"] for s in per_layer])  # (layers, held)
    return {
        "moe_assignments_held": jnp.sum(load),
        "moe_load_max_over_mean": jnp.max(load) / jnp.maximum(
            jnp.mean(load), 1e-9),
        "moe_dropped": sum(s["dropped"] for s in per_layer),
        "moe_full_dispatch": sum(s["further"] for s in per_layer),
    }
