"""Decoder LM whose attention layers are of two kinds, built from a published
config mapping: grouped-query softmax attention under a sliding window three
layers in four and over the whole row every fourth, each kind with rotary
parameters of its own (the full layers' stretched by YaRN), every layer
followed by softmax-routed experts and NO shared expert — the ``mellum``
family's block (JetBrains Mellum2-12B-A2.5B is the configuration the
benchmark runs).

``TrainConfig.model_spec`` states the model as models/latent_moe.py's does:
the published ``config.json`` keys verbatim plus ``layers`` (depth kept),
``experts_held`` ([first, count]) and ``vocab_rows``. The expert layer, the
head and the loss are ``latent_moe.RoutedExpertLM``'s — the SAME
``_choose`` the other two models run, here told: softmax over all
``num_experts``, top ``num_experts_per_tok`` of all of them, weights
renormalised over the chosen, no bias, no scale, no shared expert (no leaf
in the tree, nothing added). Where a held expert expects an eighth of the
tokens or more (``latent_moe.DENSE_SHARE``; the published top-8 of 64 is
an eighth) the held experts run over every token under the combine
weights (``RoutedExpertLM._every_token``; the two products' results are
kept through the layer's rematerialisation, ``KEEP_DENSE``); a sparser
``mellum`` mapping takes the sorted pairs' ``_route`` / ``_buffer`` /
``grouped_dot`` path as the other two models do.

Every norm is the plain RMS norm, y = x·rsqrt(mean x² + eps)·w (leaf
``scale``, ones at init). Layer i, x (T, hidden), is of the kind
``layer_types[i]`` says, read verbatim:

  x += attention(norm(x));  x += experts(norm(x))

Attention (``draco_attn``): q = h·Wq (H heads of Dh), k, v = h·Wk, h·Wv
(Hkv heads), no bias, no q/k norm; rotary on all Dh dims in the
half-rotation form (x[i], x[i + Dh/2] the pair), angle = position·f_i with
the f_i of the layer's kind (``rope_frequencies``: ``default`` f_i =
θ^(−2i/Dh); ``yarn`` blends f_i / factor into it over the dims the two
betas bound, and cos and sin are both multiplied by ``attention_factor``,
so the logits carry its square); causal softmax(q·kᵀ/√Dh)·v, each k/v head
serving H / Hkv query heads; out = attn·Wo. In a ``sliding_attention``
layer a query also sees only itself and the ``sliding_window`` − 1 tokens
before it (0 ≤ t − s < W): that layer's core — the k/v heads' spreading and
the kernel that skips the blocks outside the window, ops/flash_attention —
runs under ``draco_window`` nested in ``draco_attn``, and the counter
``window_kernel_layers`` says in how many sliding layers of the step it was
the kernel (0 on the plain lowering).

Left out: the multi-token-prediction head (the published config has no key
for it). Not read: ``intermediate_size`` (no layer is dense),
``max_window_layers`` (``layer_types`` is given), ``max_position_embeddings``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from draco_tpu.models.latent_moe import (
    DENSE_SHARE, KEEP_DENSE, STAT_NAMES, MoeSpec, RoutedExpertLM, fold_stats,
)
from draco_tpu.models.spec_lm import (
    EMBED_STD, _dot, _operand, dense_causal_attention, rms_norm, rope_half,
)
from draco_tpu.ops.flash_attention import runs_in_kernels

# the published config keys the block reads (model_spec must carry them)
SPEC_KEYS = (
    "hidden_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "attention_bias", "layer_types",
    "mlp_layer_types", "sliding_window", "use_sliding_window",
    "rope_parameters", "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "tie_word_embeddings", "hidden_act",
    # the chip's share
    "layers", "experts_held", "vocab_rows",
)
LAYER_KINDS = ("sliding_attention", "full_attention")
# what an entry of rope_parameters may say, by rope_type
ROPE_KEYS = {
    "default": {"rope_type", "rope_theta"},
    "yarn": {"rope_type", "rope_theta", "factor",
             "original_max_position_embeddings", "beta_fast", "beta_slow",
             "attention_factor"},
}


def check_spec(spec) -> None:
    """Raise ValueError, naming the key, for a mapping this block cannot
    state. What the block does not implement is refused by name."""
    if not isinstance(spec, dict):
        raise ValueError("model_spec must be a mapping of the published "
                         "config keys plus layers/experts_held/vocab_rows")
    missing = [k for k in SPEC_KEYS if k not in spec]
    if missing:
        raise ValueError(f"model_spec lacks {missing}")
    want = {"attention_bias": False, "use_sliding_window": True,
            "tie_word_embeddings": False, "hidden_act": "silu",
            "norm_topk_prob": True}
    for key, value in want.items():
        if spec[key] != value:
            raise ValueError(
                f"model_spec[{key!r}] = {spec[key]!r}: this block implements "
                f"{value!r} only")
    layers = spec["layers"]
    if not 1 <= layers <= len(spec["layer_types"]):
        raise ValueError(
            f"model_spec['layers'] = {layers}: from 1 to the "
            f"{len(spec['layer_types'])} entries of layer_types")
    if len(spec["mlp_layer_types"]) < layers:
        raise ValueError("model_spec['mlp_layer_types'] is shorter than "
                         "layers")
    for i in range(layers):
        if spec["layer_types"][i] not in LAYER_KINDS:
            raise ValueError(
                f"model_spec['layer_types'][{i}] = "
                f"{spec['layer_types'][i]!r}: one of {LAYER_KINDS}")
        if spec["mlp_layer_types"][i] != "sparse":
            raise ValueError(
                f"model_spec['mlp_layer_types'][{i}] = "
                f"{spec['mlp_layer_types'][i]!r}: this block implements "
                f"'sparse' layers only")
    if spec["head_dim"] % 2:
        raise ValueError("head_dim must be even for the rotary pairs")
    for kind in set(spec["layer_types"][:layers]):
        rope_frequencies(spec["rope_parameters"].get(kind), spec["head_dim"],
                         kind)
    window = spec["sliding_window"]
    if not (isinstance(window, int) and window >= 1):
        raise ValueError(f"model_spec['sliding_window'] = {window!r}: a "
                         f"whole number of tokens >= 1")
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
    if spec["vocab_rows"] < 2:
        raise ValueError("vocab_rows must be >= 2")


def rope_frequencies(rope, dim: int, kind: str = "") -> tuple:
    """One entry of the config's ``rope_parameters`` -> (f_i for the dim/2
    rotary pairs, float32; the factor cos and sin are multiplied by).

    ``default``: f_i = θ^(−2i/dim), factor 1. ``yarn``: with e_i = θ^(−2i/dim)
    and p_i = e_i / factor, c(n) = dim·ln(L / (2π n)) / (2 ln θ) (the pair
    that turns n times over the original L positions), low = ⌊c(β_fast)⌋,
    high = ⌈c(β_slow)⌉ (both clipped to [0, dim − 1]), ramp_i = clip((i −
    low) / (high − low), 0, 1): f_i = p_i·ramp_i + e_i·(1 − ramp_i) — pairs
    below ``low`` keep their frequency, pairs from ``high`` on are stretched
    ``factor`` times; the factor on cos and sin is the entry's
    ``attention_factor``. Computed in float64, handed on in float32. Raises ValueError, naming the key, for an entry
    it cannot state."""
    where = f"model_spec['rope_parameters'][{kind!r}]"
    if not isinstance(rope, dict) or "rope_type" not in rope:
        raise ValueError(f"{where}: an entry with its rope_type is needed")
    allowed = ROPE_KEYS.get(rope["rope_type"])
    if allowed is None:
        raise ValueError(f"{where}['rope_type'] = {rope['rope_type']!r}: "
                         f"one of {sorted(ROPE_KEYS)}")
    extra = sorted(set(rope) - allowed)
    if extra or "rope_theta" not in rope:
        raise ValueError(f"{where}: keys {extra or ['rope_theta']} are "
                         f"{'not implemented' if extra else 'missing'} for "
                         f"rope_type {rope['rope_type']!r}")
    theta = float(rope["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    missing = sorted(ROPE_KEYS["yarn"] - set(rope))
    if missing:
        raise ValueError(f"{where} lacks {missing}")
    factor = float(rope["factor"])

    def pair_of(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    freqs = plain / factor * ramp + plain * (1.0 - ramp)
    return freqs.astype(np.float32), float(rope["attention_factor"])


class WindowedMoeLM(RoutedExpertLM):
    """The ``mellum`` family's block (module docstring)."""

    stat_names = STAT_NAMES + ("window_kernel_layers",)
    init_rules = {"scale": "ones", "embedding": EMBED_STD}

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        check_spec(spec)
        super().__init__(spec, MoeSpec(
            experts=spec["num_experts"], top_k=spec["num_experts_per_tok"],
            first=spec["experts_held"][0], held=spec["experts_held"][1],
            scoring="softmax", norm_topk=spec["norm_topk_prob"], scale=1.0,
            shared=None,
            # top-8 of 64: a held expert expects an eighth of the tokens
            dense=(spec["experts_held"][1] < spec["num_experts"]
                   and spec["num_experts_per_tok"]
                   >= DENSE_SHARE * spec["num_experts"])),
            attn_fn, dtype, remat)
        self.layer_types = list(spec["layer_types"][:spec["layers"]])
        self.rope = {kind: rope_frequencies(spec["rope_parameters"][kind],
                                            spec["head_dim"], kind)
                     for kind in set(self.layer_types)}

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.spec["rms_norm_eps"])

    # ---- parameters ---------------------------------------------------
    def param_shapes(self) -> dict:
        s = self.spec
        d = s["hidden_size"]
        heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                         s["head_dim"])
        held = s["experts_held"][1]
        tree = {"embed": {"embedding": (s["vocab_rows"], d)},
                "final_norm": {"scale": (d,)},
                "head": {"kernel": (d, s["vocab_rows"])}}
        for i in range(s["layers"]):
            tree[f"layer{i}"] = {
                "attn_norm": {"scale": (d,)},
                "q": {"kernel": (d, heads * dh)},
                "k": {"kernel": (d, kv * dh)},
                "v": {"kernel": (d, kv * dh)},
                "o": {"kernel": (heads * dh, d)},
                "mlp_norm": {"scale": (d,)},
                "router": {"kernel": (d, s["num_experts"])},
                "experts": self.mlp_shapes(s["moe_intermediate_size"],
                                           (held,)),
            }
        return tree

    # ---- the block ----------------------------------------------------
    def _attention(self, h, p, positions, kind: str):
        s = self.spec
        b, t, _ = h.shape
        heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                         s["head_dim"])
        q = _dot(h, p["q"]["kernel"]).reshape(b, t, heads, dh)
        k = _dot(h, p["k"]["kernel"]).reshape(b, t, kv, dh)
        v = _dot(h, p["v"]["kernel"]).reshape(b, t, kv, dh)
        q = rope_half(q.astype(jnp.float32), positions, *self.rope[kind])
        k = rope_half(k.astype(jnp.float32), positions, *self.rope[kind])
        q, k, v = _operand(q), _operand(k), _operand(v)
        if kind == "sliding_attention":
            with jax.named_scope("draco_window"):
                o = self.attn_fn(q, k, v, window=s["sliding_window"])
        else:
            o = self.attn_fn(q, k, v)
        return _dot(o.astype(h.dtype).reshape(b, t, heads * dh),
                    p["o"]["kernel"])

    def _layer(self, x, p, positions, kind: str):
        with jax.named_scope("draco_attn"):
            x = x + self._attention(self.norm(x, p["attn_norm"]), p,
                                    positions, kind)
        b, t, d = x.shape
        y, stats = self._experts(x.reshape(b * t, d), p)
        return y.reshape(b, t, d), stats

    def hidden(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (the last layer's output (B, T, hidden), the
        ``stat_names`` counters)."""
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        positions = pos_offset + jnp.arange(tokens.shape[1])
        per_layer = []
        for i, kind in enumerate(self.layer_types):
            fn = functools.partial(self._layer, positions=positions,
                                   kind=kind)
            if self.remat:
                fn = jax.checkpoint(
                    fn, policy=KEEP_DENSE if self.moe.dense else None)
            x, stats = fn(x, params[f"layer{i}"])
            per_layer.append(stats)
        out = fold_stats(per_layer)
        # the sliding layers whose core ran in the block-skipping kernel:
        # all of them or none (a kernel handed to the model and selected;
        # a shape that does not tile raises there, it never falls back)
        in_kernels = (self.attn_fn is not dense_causal_attention
                      and runs_in_kernels())
        out["window_kernel_layers"] = jnp.float32(
            in_kernels * self.layer_types.count("sliding_attention"))
        return x, out
