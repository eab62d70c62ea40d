"""Decoder LM whose mixers are of two kinds, built from a published config
mapping: a double-gated short convolution three layers in four and
grouped-query softmax attention (per-head RMS norm on q and k) the fourth,
a few leading layers with a dense SwiGLU and every later one with
sigmoid-scored, bias-selected routed experts and NO shared expert, the head
tied to the embedding — the ``lfm2_moe`` family's block (LiquidAI
LFM2-8B-A1B is the configuration the benchmark runs).

``TrainConfig.model_spec`` states the model as models/latent_moe.py's does:
the published ``config.json`` keys verbatim plus what THIS chip holds —
``layers`` (depth kept), ``layers_held`` (the published indices of the kept
layers, increasing: a kept layer's kind is ``layer_types[index]``, read
verbatim, and it is dense iff index < ``num_dense_layers``),
``experts_held`` ([first, count]) and ``vocab_rows``. ``head_dim`` is
hidden_size / num_attention_heads (the family has no key for it).
``tie_word_embeddings`` may be absent and is then true, as the library
reads it: the tree has no ``head`` leaf and the head reads the embedding's
(``spec_lm.SpecLM.head_kernel``); false gives the untied twin.

Every norm is the plain RMS norm, y = x·rsqrt(mean x² + eps)·w (leaf
``scale``, ones at init; eps = ``norm_eps``). No bias anywhere. Kept layer
j, x (T, hidden), h = norm(x; ``operator_norm``):

  ``conv`` (``draco_conv``, its norm included): [B | C | X] = h·W_in
  (hidden → 3·hidden, three contiguous column ranges in this order);
  u = B ⊙ X; v_t = Σ_j taps[j] ⊙ u_{t−(L−1)+j} (depthwise, causal, zeros
  before the row's start, L = ``conv_L_cache`` taps:
  ``hybrid_moe.causal_depthwise_conv``); y = (C ⊙ v)·W_out. No activation
  function: the two products are the nonlinearity, and the operator is
  cubic in its input — the counter ``short_conv_absmax`` is max |C ⊙ v|
  over the step's conv layers.

  ``full_attention`` (``draco_attn``): q = h·Wq (H heads of Dh), k, v =
  h·Wk, h·Wv (Hkv heads); q and k each under an RMS norm over their Dh
  dims (ONE (Dh,) weight for q, one for k); rotary θ = ``rope_theta`` over
  all Dh dims, half-rotation pairs; causal softmax(q·kᵀ/√Dh)·v, each k/v
  head serving H / Hkv query heads; y = o·Wo.

  x += y; g = norm(x; ``ffn_norm``); dense layers: x += SwiGLU(g) at
  ``intermediate_size`` (``draco_experts``); the others
  ``latent_moe.RoutedExpertLM._experts``, told: s = sigmoid(g·W_r) over all
  ``num_experts`` (float32 at ``highest``), chosen = top-k of s + b
  (``use_expert_bias``: b takes no gradient), weights s[chosen]
  renormalised over the chosen (``norm_topk_prob``) times
  ``routed_scaling_factor``, no shared expert; where a held expert expects
  an eighth of the tokens or more (``DENSE_SHARE``: the published top-4 of
  32 is an eighth) the held experts run over every token.

Leaves of less than a 128-wide line — the per-head norm weights of q and k
and the selection biases — are kept after every layer in ravel order
(``qk_norm``, ``router_bias``: one row a layer that has them), so that
every other leaf lies on the vote's stack's lines (parallel/sp_step.py).

Not read: ``max_position_embeddings``, ``num_hidden_layers`` (``layer_types``
is given). Refused by name: ``conv_bias`` true, ``use_expert_bias`` false,
``norm_topk_prob`` false, a layer kind outside ``conv`` /
``full_attention``, ``layers_held`` out of order (a dense layer after a
sparse one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from draco_tpu.models.hybrid_moe import causal_depthwise_conv
from draco_tpu.models.latent_moe import (
    BIAS_STD, DENSE_SHARE, KEEP_DENSE, STAT_NAMES, MoeSpec, RoutedExpertLM,
    fold_stats,
)
from draco_tpu.models.spec_lm import (
    _dot, _operand, rms_norm, rope_half, swiglu,
)

# the published config keys the block reads (model_spec must carry them)
SPEC_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "layer_types",
    "conv_L_cache", "conv_bias", "norm_eps", "rope_theta",
    "num_dense_layers", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
    # the chip's share
    "layers", "layers_held", "experts_held", "vocab_rows",
)
LAYER_KINDS = ("conv", "full_attention")


def check_spec(spec) -> None:
    """Raise ValueError, naming the key, for a mapping this block cannot
    state. What the block does not implement is refused by name."""
    if not isinstance(spec, dict):
        raise ValueError("model_spec must be a mapping of the published "
                         "config keys plus layers/layers_held/experts_held/"
                         "vocab_rows")
    missing = [k for k in SPEC_KEYS if k not in spec]
    if missing:
        raise ValueError(f"model_spec lacks {missing}")
    want = {"conv_bias": False, "use_expert_bias": True,
            "norm_topk_prob": True}
    for key, value in want.items():
        if spec[key] != value:
            raise ValueError(
                f"model_spec[{key!r}] = {spec[key]!r}: this block implements "
                f"{value!r} only")
    if not isinstance(spec.get("tie_word_embeddings", True), bool):
        raise ValueError("model_spec['tie_word_embeddings'] is true, false "
                         "or absent (read as true)")
    held, types = list(spec["layers_held"]), spec["layer_types"]
    if len(held) != spec["layers"] or not held:
        raise ValueError(
            f"model_spec['layers_held'] = {held}: one published index for "
            f"each of the layers = {spec['layers']} kept")
    if any(not (isinstance(i, int) and 0 <= i < len(types)) for i in held):
        raise ValueError(
            f"model_spec['layers_held'] = {held}: indices into the "
            f"{len(types)} entries of layer_types")
    if any(b <= a for a, b in zip(held, held[1:])):
        raise ValueError(
            f"model_spec['layers_held'] = {held}: increasing published "
            f"indices (a dense layer after a sparse one is not implemented)")
    for i in held:
        if types[i] not in LAYER_KINDS:
            raise ValueError(
                f"model_spec['layer_types'][{i}] = {types[i]!r}: one of "
                f"{LAYER_KINDS}")
    if not (isinstance(spec["conv_L_cache"], int)
            and spec["conv_L_cache"] >= 1):
        raise ValueError(f"model_spec['conv_L_cache'] = "
                         f"{spec['conv_L_cache']!r}: a whole number of taps "
                         f">= 1")
    if spec["num_dense_layers"] < 0:
        raise ValueError("num_dense_layers must be >= 0")
    first, count = spec["experts_held"]
    if not (0 <= first and count >= 1
            and first + count <= spec["num_experts"]):
        raise ValueError(
            f"model_spec['experts_held'] = {spec['experts_held']}: a "
            f"[first, count] range inside the {spec['num_experts']} "
            f"routed experts")
    if spec["num_experts_per_tok"] > spec["num_experts"]:
        raise ValueError("num_experts_per_tok exceeds num_experts")
    if spec["num_attention_heads"] % spec["num_key_value_heads"]:
        raise ValueError("num_key_value_heads must divide "
                         "num_attention_heads")
    if spec["hidden_size"] % (2 * spec["num_attention_heads"]):
        raise ValueError("hidden_size / num_attention_heads must be an even "
                         "whole number: the head size, in rotary pairs")
    if spec["vocab_rows"] < 2:
        raise ValueError("vocab_rows must be >= 2")


class ShortConvMoeLM(RoutedExpertLM):
    """The ``lfm2_moe`` family's block (module docstring)."""

    stat_names = STAT_NAMES + ("short_conv_layers", "short_conv_absmax",
                               "tied_head")

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        check_spec(spec)
        super().__init__(spec, MoeSpec(
            experts=spec["num_experts"], top_k=spec["num_experts_per_tok"],
            first=spec["experts_held"][0], held=spec["experts_held"][1],
            scoring="sigmoid", norm_topk=spec["norm_topk_prob"],
            scale=spec["routed_scaling_factor"], shared=None,
            # top-4 of 32: a held expert expects an eighth of the tokens
            dense=(spec["experts_held"][1] < spec["num_experts"]
                   and spec["num_experts_per_tok"]
                   >= DENSE_SHARE * spec["num_experts"])),
            attn_fn, dtype, remat)
        self.tied_head = spec.get("tie_word_embeddings", True)
        self.layer_types = [spec["layer_types"][i]
                            for i in spec["layers_held"]]
        self.dense_layers = [i < spec["num_dense_layers"]
                             for i in spec["layers_held"]]
        self.head_dim = spec["hidden_size"] // spec["num_attention_heads"]
        self.rope = (float(spec["rope_theta"]) ** (
            -np.arange(0, self.head_dim, 2, dtype=np.float64)
            / self.head_dim)).astype(np.float32)
        # the embedding at the matrices' std (it is the head too: at unit
        # scale a unit-RMS row would meet logits of std sqrt(hidden)); the
        # taps at variance 1 / taps (fan-in)
        self.init_rules = {"scale": "ones", "expert_bias": BIAS_STD,
                           "taps": spec["conv_L_cache"] ** -0.5}

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.spec["norm_eps"])

    # ---- parameters ---------------------------------------------------
    def param_shapes(self) -> dict:
        s = self.spec
        d, dh = s["hidden_size"], self.head_dim
        heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
        held = s["experts_held"][1]
        tree = {"embed": {"embedding": (s["vocab_rows"], d)},
                "final_norm": {"scale": (d,)}}
        if not self.tied_head:
            tree["head"] = {"kernel": (d, s["vocab_rows"])}
        for j, (kind, dense) in enumerate(zip(self.layer_types,
                                              self.dense_layers)):
            if kind == "conv":
                layer = {"in_proj": {"kernel": (d, 3 * d)},
                         "conv": {"taps": (s["conv_L_cache"], d)},
                         "out_proj": {"kernel": (d, d)}}
            else:
                layer = {"q": {"kernel": (d, heads * dh)},
                         "k": {"kernel": (d, kv * dh)},
                         "v": {"kernel": (d, kv * dh)},
                         "o": {"kernel": (heads * dh, d)}}
            layer["operator_norm"] = {"scale": (d,)}
            layer["ffn_norm"] = {"scale": (d,)}
            if dense:
                layer["mlp"] = self.mlp_shapes(s["intermediate_size"])
            else:
                layer["router"] = {"kernel": (d, s["num_experts"])}
                layer["experts"] = self.mlp_shapes(
                    s["moe_intermediate_size"], (held,))
            tree[f"layer{j}"] = layer
        # what is less than a line wide, after every layer in ravel order
        # (module docstring): a row an attention layer, a row a sparse one
        attn = self.layer_types.count("full_attention")
        if attn:
            tree["qk_norm"] = {"q": {"scale": (attn, dh)},
                               "k": {"scale": (attn, dh)}}
        sparse = self.dense_layers.count(False)
        if sparse:
            tree["router_bias"] = {"expert_bias": (sparse, s["num_experts"])}
        return tree

    # ---- the block ----------------------------------------------------
    def _short_conv(self, h, p):
        """-> (the operator's output (B, T, hidden), max |C ⊙ v|)."""
        d = self.spec["hidden_size"]
        bcx = _dot(h, p["in_proj"]["kernel"])
        b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        gated = c * causal_depthwise_conv(b * x, p["conv"]["taps"])
        return (_dot(gated, p["out_proj"]["kernel"]),
                jnp.max(jnp.abs(lax.stop_gradient(gated))))

    def _attention(self, h, p, qk_scale, positions):
        s = self.spec
        b, t, _ = h.shape
        heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                         self.head_dim)
        q = _dot(h, p["q"]["kernel"]).reshape(b, t, heads, dh)
        k = _dot(h, p["k"]["kernel"]).reshape(b, t, kv, dh)
        v = _dot(h, p["v"]["kernel"]).reshape(b, t, kv, dh)
        q_scale, k_scale = qk_scale
        q = rope_half(rms_norm(q, q_scale, s["norm_eps"]).astype(jnp.float32),
                      positions, self.rope)
        k = rope_half(rms_norm(k, k_scale, s["norm_eps"]).astype(jnp.float32),
                      positions, self.rope)
        o = self.attn_fn(_operand(q), _operand(k), _operand(v))
        return _dot(o.astype(h.dtype).reshape(b, t, heads * dh),
                    p["o"]["kernel"])

    def _layer(self, x, p, qk_scale, bias, positions, kind: str,
               dense: bool):
        """-> (the layer's output, (the expert layer's counters or None,
        max |C ⊙ v| or None))."""
        peak = None
        if kind == "conv":
            with jax.named_scope("draco_conv"):
                mixed, peak = self._short_conv(
                    self.norm(x, p["operator_norm"]), p)
                x = x + mixed
        else:
            with jax.named_scope("draco_attn"):
                x = x + self._attention(self.norm(x, p["operator_norm"]), p,
                                        qk_scale, positions)
        if dense:
            with jax.named_scope("draco_experts"):
                return (x + swiglu(self.norm(x, p["ffn_norm"]), p["mlp"]),
                        (None, peak))
        b, t, d = x.shape
        # the expert layer's names for the norm that feeds it and for the
        # selection bias (a leaf of the tree that takes no gradient)
        p = dict(p, mlp_norm=p["ffn_norm"], router=dict(
            p["router"], e_score_correction_bias=bias))
        y, stats = self._experts(x.reshape(b * t, d), p)
        return y.reshape(b, t, d), (stats, peak)

    def hidden(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (the last layer's output (B, T, hidden), the
        ``stat_names`` counters)."""
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        positions = pos_offset + jnp.arange(tokens.shape[1])
        per_layer, peaks, attn, sparse = [], [], 0, 0
        for j, (kind, dense) in enumerate(zip(self.layer_types,
                                              self.dense_layers)):
            qk_scale = bias = None
            if kind == "full_attention":
                qk_scale = (params["qk_norm"]["q"]["scale"][attn],
                            params["qk_norm"]["k"]["scale"][attn])
                attn += 1
            if not dense:
                bias = params["router_bias"]["expert_bias"][sparse]
                sparse += 1
            fn = functools.partial(self._layer, positions=positions,
                                   kind=kind, dense=dense)
            if self.remat:
                fn = jax.checkpoint(
                    fn, policy=KEEP_DENSE if self.moe.dense else None)
            x, (stats, peak) = fn(x, params[f"layer{j}"], qk_scale, bias)
            if stats is not None:
                per_layer.append(stats)
            if peak is not None:
                peaks.append(peak)
        out = fold_stats(per_layer) or dict.fromkeys(STAT_NAMES,
                                                     jnp.float32(0))
        out["short_conv_layers"] = jnp.float32(len(peaks))
        out["short_conv_absmax"] = (jnp.max(jnp.stack(peaks)) if peaks
                                    else jnp.float32(0))
        # 1 where the head reads the embedding's leaf
        out["tied_head"] = jnp.float32(self.tied_head)
        return x, out
