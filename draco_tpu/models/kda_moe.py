"""Decoder LM whose mixers are of two kinds, built from a published config
mapping: Kimi Delta Attention — a delta rule whose decay is per key channel,
its gates low-rank projections — three layers in four, latent key/value
attention with NO positional signal the fourth, a leading layer with a dense
SwiGLU and every later one with sigmoid-scored, bias-selected routed experts
plus a shared expert, untied head — the ``kimi_linear`` family's block
(moonshotai Kimi-Linear-48B-A3B is the configuration the benchmark runs).

``TrainConfig.model_spec`` states the model as models/latent_moe.py's does:
the published ``config.json`` keys verbatim plus what THIS chip holds —
``layers`` (depth kept), ``layers_held`` (the PUBLISHED indices of the kept
layers, 1-based as the config's own lists count them, increasing: a kept
layer is Kimi Delta Attention iff its index is in ``linear_attn_config.
kda_layers``, latent attention iff in ``full_attn_layers``, and dense iff
index <= ``first_k_dense_replace``), ``heads_held`` ([first, count]: the
heads of BOTH mixers this chip holds), ``experts_held`` ([first, count]) and
``vocab_rows``.

The mixers are TOLD which heads they hold, as the expert layer is told its
experts: the q / k / v / gate projections carry the held heads' columns, the
output projection their rows, and what the layer adds to the stream is the
held heads' partial sum — what the heads held elsewhere would add is left
out (no code stands in for the absent chips or their reduction). What is
whole on every chip: the latent projection ``kv_a`` and its norm, the gates'
first factors ``f_a`` / ``g_a`` (hidden -> rank), and the output norm's one
(Dv,) weight.

Every norm is the plain RMS norm (leaf ``scale``, ones at init; eps =
``rms_norm_eps``). No bias but ``dt_bias``. Kept layer j, x (T, hidden),
h = norm(x; ``attn_norm``):

  Kimi Delta Attention (``draco_kda``, its norm included; the rule itself
  under ``draco_kdarule`` nested in it), H heads held of Dk = Dv =
  ``linear_attn_config.head_dim``: q, k, v = SiLU(conv(h·Wq)), … — three
  causal depthwise convolutions of ``short_conv_kernel_size`` taps, no bias
  (``hybrid_moe.causal_depthwise_conv``); q, k L2-normalised per head, q
  scaled Dk^-½; g = −exp(A_log[head]) · softplus(h·Wfa·Wfb + dt_bias), one
  decay a head AND key channel; β = σ(h·Wb) a head; the rule chunk-wise
  (ops/delta_rule.chunked_gated_delta_rule with g of four dimensions:
  ops/kda_rule.py); out = (rmsnorm(o)·w ⊙ σ(h·Wga·Wgb))·Wo, the norm over
  each head's Dv. The gates' rank is the head size (the config has no key).

  Latent attention (``draco_attn``): ``latent_moe.LatentMoeLM._attention``
  itself — q = h·Wq, [c | k_shared] = h·Wkva, c normed, [k_nope | v] =
  c·Wkvb, the ``qk_rope_head_dim`` shared dims appended to every head's key,
  causal softmax at (nope + rope)^-½ — with its rotation switched off by
  ``mla_use_nope`` (``rope_interleaved`` is handed no base): the shared
  dims stay in the product, unrotated.

  x += mixer; g = norm(x; ``mlp_norm``); dense: x += SwiGLU(g) at
  ``intermediate_size`` (``draco_experts``); the others
  ``latent_moe.RoutedExpertLM._experts``, told: s = sigmoid(g·W_r) over all
  ``num_experts``, chosen = top-k of s + b (one group; b takes no gradient),
  weights s[chosen] renormalised (``moe_renormalize``) times
  ``routed_scaling_factor``, plus ``num_shared_experts`` shared experts'
  width on every token.

``A_log`` (one number a held head and KDA layer: less than a 128-wide line)
is kept for all KDA layers together, ``params["linear_heads"]``, a group
that sorts after every layer, so that every other leaf lies on the vote's
stack's lines (parallel/sp_step.py).

Not read: ``head_dim``, ``num_key_value_heads``, ``rope_theta``,
``model_max_length``, ``num_hidden_layers`` (the two index lists are given),
``use_grouped_topk`` (one group of all experts is the plain top-k).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax

from draco_tpu.models.hybrid_moe import (
    KEEP_SOLVE, L2_EPS, causal_depthwise_conv,
)
from draco_tpu.models.latent_moe import (
    BIAS_STD, STAT_NAMES, LatentMoeLM, MoeSpec, RoutedExpertLM, fold_stats,
)
from draco_tpu.models.spec_lm import EMBED_STD, _dot, rms_norm, swiglu
from draco_tpu.ops.delta_rule import CHUNK, chunked_gated_delta_rule
from draco_tpu.ops.kda_rule import chunk_decay_min, kda_runs_in_kernels

# the published config keys the block reads (model_spec must carry them)
SPEC_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "mla_use_nope",
    "rope_scaling", "rms_norm_eps", "linear_attn_config",
    "first_k_dense_replace", "moe_layer_freq", "num_experts",
    "num_experts_per_token", "num_shared_experts", "moe_renormalize",
    "moe_router_activation_func", "routed_scaling_factor",
    "num_expert_group", "topk_group", "num_nextn_predict_layers",
    "tie_word_embeddings", "hidden_act",
    # the chip's share
    "layers", "layers_held", "heads_held", "experts_held", "vocab_rows",
)
LINEAR_KEYS = ("kda_layers", "full_attn_layers", "head_dim", "num_heads",
               "short_conv_kernel_size")
CONV_TAPS = 4  # the taps' seeded variance, 1 / 4, is tied to it


def check_spec(spec) -> None:
    """Raise ValueError, naming the key, for a mapping this block cannot
    state. What the block does not implement is refused by name."""
    if not isinstance(spec, dict):
        raise ValueError("model_spec must be a mapping of the published "
                         "config keys plus layers/layers_held/heads_held/"
                         "experts_held/vocab_rows")
    missing = [k for k in SPEC_KEYS if k not in spec]
    if missing:
        raise ValueError(f"model_spec lacks {missing}")
    linear = spec["linear_attn_config"]
    missing = [k for k in LINEAR_KEYS
               if not isinstance(linear, dict) or k not in linear]
    if missing:
        raise ValueError(f"model_spec['linear_attn_config'] lacks {missing}")
    want = {"q_lora_rank": None, "mla_use_nope": True, "rope_scaling": None,
            "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
            "hidden_act": "silu", "moe_router_activation_func": "sigmoid",
            "moe_renormalize": True}
    for key, value in want.items():
        if spec[key] != value:
            raise ValueError(
                f"model_spec[{key!r}] = {spec[key]!r}: this block implements "
                f"{value!r} only")
    if linear["short_conv_kernel_size"] != CONV_TAPS:
        raise ValueError(
            f"model_spec['linear_attn_config']['short_conv_kernel_size'] = "
            f"{linear['short_conv_kernel_size']!r}: this block implements "
            f"{CONV_TAPS} only")
    heads = spec["num_attention_heads"]
    if linear["num_heads"] != heads:
        raise ValueError(
            f"model_spec['linear_attn_config']['num_heads'] = "
            f"{linear['num_heads']!r}: heads_held is ONE range over both "
            f"mixers, which takes num_attention_heads = {heads} of each")
    first, count = spec["heads_held"]
    if not (0 <= first and count >= 1 and first + count <= heads):
        raise ValueError(
            f"model_spec['heads_held'] = {spec['heads_held']}: a [first, "
            f"count] range inside the {heads} heads")
    held = list(spec["layers_held"])
    if len(held) != spec["layers"] or not held:
        raise ValueError(
            f"model_spec['layers_held'] = {held}: one published index for "
            f"each of the layers = {spec['layers']} kept")
    if any(b <= a for a, b in zip(held, held[1:])):
        raise ValueError(
            f"model_spec['layers_held'] = {held}: increasing published "
            f"indices")
    for i in held:
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(
                f"model_spec['layers_held'] names layer {i!r}: in exactly "
                f"one of linear_attn_config's kda_layers and "
                f"full_attn_layers (published, 1-based)")
    first, count = spec["experts_held"]
    if not (0 <= first and count >= 1
            and first + count <= spec["num_experts"]):
        raise ValueError(
            f"model_spec['experts_held'] = {spec['experts_held']}: a "
            f"[first, count] range inside the {spec['num_experts']} "
            f"routed experts")
    if spec["num_experts_per_token"] > spec["num_experts"]:
        raise ValueError("num_experts_per_token exceeds num_experts")
    if spec["first_k_dense_replace"] < 0 or spec["num_shared_experts"] < 0:
        raise ValueError("first_k_dense_replace and num_shared_experts "
                         "must be >= 0")
    if spec["vocab_rows"] < 2:
        raise ValueError("vocab_rows must be >= 2")


def kept_layers(spec: dict) -> list:
    """(``"kda"`` | ``"mla"``, dense) of each kept layer, from the
    published 1-based indices."""
    kda = spec["linear_attn_config"]["kda_layers"]
    return [("kda" if i in kda else "mla",
             i <= spec["first_k_dense_replace"])
            for i in spec["layers_held"]]


class KdaMoeLM(RoutedExpertLM):
    """The ``kimi_linear`` family's block (module docstring)."""

    stat_names = STAT_NAMES + ("kda_layers", "kda_kernel_layers",
                               "kda_state_absmax", "kda_decay_min",
                               "heads_held")
    # conv taps at variance 1 / taps (fan-in); log A ~ normal(0, 1) — the
    # family draws A from uniform(1, 16) and stores its log, a rule the
    # seeded weights have no name for (models/hybrid_moe.py's departure)
    init_rules = {"scale": "ones", "embedding": EMBED_STD,
                  "e_score_correction_bias": BIAS_STD, "dt_bias": "ones",
                  "A_log": 1.0, "taps": CONV_TAPS ** -0.5}

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        check_spec(spec)
        shared = spec["num_shared_experts"]
        super().__init__(spec, MoeSpec(
            experts=spec["num_experts"],
            top_k=spec["num_experts_per_token"],
            first=spec["experts_held"][0], held=spec["experts_held"][1],
            scoring="sigmoid", norm_topk=spec["moe_renormalize"],
            scale=spec["routed_scaling_factor"],
            shared="plain" if shared else None), attn_fn, dtype, remat)
        self.kept = kept_layers(spec)
        self.heads = spec["heads_held"][1]
        # what ``LatentMoeLM._attention`` reads of its model, for the heads
        # held here; no rotary base: ``mla_use_nope``
        self._latent = types.SimpleNamespace(
            spec={"num_attention_heads": self.heads, "rope_theta": None,
                  **{k: spec[k] for k in (
                      "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                      "kv_lora_rank")}},
            norm=self.norm, attn_fn=self.attn_fn)

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.spec["rms_norm_eps"])

    # ---- parameters ---------------------------------------------------
    def param_shapes(self) -> dict:
        s = self.spec
        d, h = s["hidden_size"], self.heads
        dk = s["linear_attn_config"]["head_dim"]
        taps = s["linear_attn_config"]["short_conv_kernel_size"]
        nope, rp, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                        s["v_head_dim"])
        rank, held = s["kv_lora_rank"], s["experts_held"][1]
        tree = {"embed": {"embedding": (s["vocab_rows"], d)},
                "final_norm": {"scale": (d,)},
                "head": {"kernel": (d, s["vocab_rows"])}}
        for j, (kind, dense) in enumerate(self.kept):
            if kind == "kda":
                layer = {name: {"kernel": (d, h * dk)} for name in "qkv"}
                layer.update({f"{name}_conv": {"taps": (taps, h * dk)}
                              for name in "qkv"})
                layer.update({
                    "f_a": {"kernel": (d, dk)},
                    "f_b": {"kernel": (dk, h * dk), "dt_bias": (h * dk,)},
                    "b": {"kernel": (d, h)},
                    "g_a": {"kernel": (d, dk)},
                    "g_b": {"kernel": (dk, h * dk)},
                    "o_norm": {"scale": (dk,)},
                    "o": {"kernel": (h * dk, d)}})
            else:
                layer = {"q": {"kernel": (d, h * (nope + rp))},
                         "kv_a": {"kernel": (d, rank + rp)},
                         "kv_norm": {"scale": (rank,)},
                         "kv_b": {"kernel": (rank, h * (nope + vd))},
                         "o": {"kernel": (h * vd, d)}}
            layer["attn_norm"] = {"scale": (d,)}
            layer["mlp_norm"] = {"scale": (d,)}
            if dense:
                layer["mlp"] = self.mlp_shapes(s["intermediate_size"])
            else:
                width = s["moe_intermediate_size"]
                layer["router"] = {
                    "kernel": (d, s["num_experts"]),
                    "e_score_correction_bias": (s["num_experts"],)}
                if self.moe.shared:
                    layer["shared"] = self.mlp_shapes(
                        width * s["num_shared_experts"])
                layer["experts"] = self.mlp_shapes(width, (held,))
            tree[f"layer{j}"] = layer
        kda = sum(kind == "kda" for kind, _ in self.kept)
        if kda:
            # per held head, all KDA layers together, after every layer in
            # ravel order (module docstring)
            tree["linear_heads"] = {"A_log": (kda, h)}
        return tree

    # ---- the block ----------------------------------------------------
    def _kda(self, x, p, a_log):
        """-> (the layer's output (B, T, hidden): the held heads' partial
        sum; (max |S| over heads of the state the row leaves behind, the
        least of a chunk's summed g, 1 where the rule took the Pallas
        kernels))."""
        s = self.spec
        b, t, _ = x.shape
        h, dk = self.heads, s["linear_attn_config"]["head_dim"]

        def mixed(name):
            return jax.nn.silu(causal_depthwise_conv(
                _dot(x, p[name]["kernel"]), p[f"{name}_conv"]["taps"])
            ).reshape(b, t, h, dk)

        def unit(y):
            y32 = y.astype(jnp.float32)
            return (y32 * lax.rsqrt(jnp.sum(
                jnp.square(y32), axis=-1, keepdims=True) + L2_EPS)
            ).astype(y.dtype)

        q, k, v = unit(mixed("q")) * dk ** -0.5, unit(mixed("k")), mixed("v")
        decay = _dot(_dot(x, p["f_a"]["kernel"]),
                     p["f_b"]["kernel"]).astype(jnp.float32)
        g = (-jnp.exp(a_log)[:, None] * jax.nn.softplus(
            decay + p["f_b"]["dt_bias"]).reshape(b, t, h, dk))
        beta = jax.nn.sigmoid(
            _dot(x, p["b"]["kernel"]).astype(jnp.float32)).astype(v.dtype)
        with jax.named_scope("draco_kdarule"):
            o, state = chunked_gated_delta_rule(q, k, v, g, beta, CHUNK)
            marks = (jnp.max(jnp.abs(lax.stop_gradient(state))),
                     chunk_decay_min(lax.stop_gradient(g), CHUNK),
                     jnp.float32(kda_runs_in_kernels(q.shape, v.shape,
                                                     CHUNK)))
        gate = _dot(_dot(x, p["g_a"]["kernel"]), p["g_b"]["kernel"])
        o = (rms_norm(o, p["o_norm"]["scale"], s["rms_norm_eps"])
             * jax.nn.sigmoid(gate).reshape(b, t, h, dk))
        return _dot(o.reshape(b, t, h * dk), p["o"]["kernel"]), marks

    def _mixer(self, x, p, a_log, positions, kind: str):
        """-> (x + the held heads' partial sum, the rule's marks or None)."""
        if kind == "kda":
            with jax.named_scope("draco_kda"):
                mixed, marks = self._kda(self.norm(x, p["attn_norm"]), p,
                                         a_log)
                return x + mixed, marks
        with jax.named_scope("draco_attn"):
            return x + LatentMoeLM._attention(
                self._latent, self.norm(x, p["attn_norm"]), p,
                positions), None

    def _feed_forward(self, x, p, dense: bool):
        """-> (the layer's output, the expert layer's counters or None)."""
        if dense:
            with jax.named_scope("draco_experts"):
                return x + swiglu(self.norm(x, p["mlp_norm"]), p["mlp"]), None
        b, t, d = x.shape
        y, stats = self._experts(x.reshape(b * t, d), p)
        return y.reshape(b, t, d), stats

    def hidden(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (the last layer's output (B, T, hidden), the
        ``stat_names`` counters). Rematerialised, a layer is TWO
        checkpoints, the mixer's and the feed-forward's: one alone would
        hold both halves' residuals through the layer's backward pass —
        the dense layer's five arrays of T x 9216 beside the rule's
        (PERF.md section 4 has the readings)."""
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        positions = pos_offset + jnp.arange(tokens.shape[1])
        per_layer, rules = [], []
        for j, (kind, dense) in enumerate(self.kept):
            a_log = None
            if kind == "kda":
                a_log = params["linear_heads"]["A_log"][len(rules)]
            mixer = functools.partial(self._mixer, positions=positions,
                                      kind=kind)
            feed_forward = functools.partial(self._feed_forward, dense=dense)
            if self.remat:
                # the rule's triangular solve is kept, not solved again
                mixer = jax.checkpoint(mixer, policy=KEEP_SOLVE)
                feed_forward = jax.checkpoint(feed_forward)
            x, marks = mixer(x, params[f"layer{j}"], a_log)
            x, stats = feed_forward(x, params[f"layer{j}"])
            if stats is not None:
                per_layer.append(stats)
            if marks is not None:
                rules.append(marks)
        out = fold_stats(per_layer) or dict.fromkeys(STAT_NAMES,
                                                     jnp.float32(0))
        absmax, least, took = (map(jnp.stack, zip(*rules)) if rules
                               else (jnp.zeros((1,), jnp.float32),) * 3)
        out["kda_layers"] = jnp.float32(len(rules))
        # the KDA layers whose rule took the Pallas kernels
        # (``kda_rule.kda_runs_in_kernels``: a TPU and the cell's shapes —
        # every layer there, none on a CPU)
        out["kda_kernel_layers"] = jnp.sum(took)
        out["kda_state_absmax"] = jnp.max(absmax)
        out["kda_decay_min"] = jnp.min(least)
        out["heads_held"] = jnp.float32(self.heads)
        return x, out
