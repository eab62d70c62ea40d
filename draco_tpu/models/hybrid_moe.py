"""Decoder LM whose layers are of two kinds, built from a published config
mapping: Gated DeltaNet linear attention three layers in four, gated softmax
attention every fourth, every layer followed by softmax-routed experts with
a sigmoid-gated shared expert — the ``qwen3_next`` family's block
(Qwen3-Next-80B-A3B is the configuration the benchmark runs).

``TrainConfig.model_spec`` states the model as models/latent_moe.py's does:
the published ``config.json`` keys verbatim plus ``layers`` (depth kept),
``experts_held`` ([first, count]) and ``vocab_rows``. The expert layer, the
head and the loss are ``latent_moe.RoutedExpertLM``'s — the SAME ``_route``
/ ``_buffer`` / ``grouped_dot`` path kanana-2 runs, here told: softmax over
all ``num_experts``, top ``num_experts_per_tok`` of all of them, weights
renormalised over the chosen, no bias, no scale, the shared expert under a
sigmoid gate.

Every norm but one is zero-centred, y = x·rsqrt(mean x² + eps)·(1 + w) (leaf
``centred_scale``, zeros at init); the DeltaNet output norm is plain ``w``
(leaf ``scale``). Layer i, x (T, hidden), is gated attention where
``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet:

  x += mixer(norm(x));  x += experts(norm(x))

Gated attention (``draco_attn``): [q | gate] = h·Wq per head (H·2·Dh
columns, a head's q then its gate); k, v = h·Wk, h·Wv (Hkv heads); q, k
RMS-normed over Dh (zero-centred); rotary in the half-rotation form
(x[:R/2], x[R/2:R] the pair) on the first R = ``partial_rotary_factor``·Dh
dims; causal softmax attention, each k/v head serving H / Hkv query heads
(ops/flash_attention spreads them); out = (attn ⊙ σ(gate))·Wo.

Gated DeltaNet (``draco_linattn``, the rule itself under
``draco_deltarule`` nested in it): [q | k | v | z] = h·Wqkvz (Hk q and k
heads, Hv v and z heads); [b | a] = h·Wba; a causal depthwise convolution
(``linear_conv_kernel_dim`` taps, no bias) then SiLU over the q, k, v
channels; q, k L2-normalised per head, q scaled Dk^-½; β = σ(b); g =
−exp(A_log)·softplus(a + dt_bias); the gated delta rule chunk-wise
(ops/delta_rule.py); out = (rmsnorm(o)·w ⊙ SiLU(z))·Wout, the norm over
each head's Dv. (The family's checkpoint lays the columns of Wqkvz and Wba
out per key head; a seeded matrix's columns have no order to keep.)

Left out: the multi-token-prediction head (the published config has no key
for it).

The per-head vectors ``A_log`` and ``dt_bias`` (Hv elements a layer) are
kept for all DeltaNet layers together, ``params["linear_heads"]``, a group
that sorts after every layer: in ravel order they come last and shift
nothing, so every other leaf starts and ends on a 128-wide line of the
vote's stack and is cut from the winner's row where it lies
(training/step._make_unravel says what a leaf off the lines costs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from draco_tpu.models.latent_moe import (
    STAT_NAMES, MoeSpec, RoutedExpertLM, fold_stats,
)
from draco_tpu.models.spec_lm import (
    EMBED_STD, _dot, _operand, rms_norm, rope_half,
)
from draco_tpu.ops.delta_rule import (
    CHUNK, SOLVE_NAME, chunked_gated_delta_rule, rule_runs_in_kernels,
)

# the published config keys the block reads (model_spec must carry them)
SPEC_KEYS = (
    "hidden_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor",
    "rope_theta", "rope_scaling", "rms_norm_eps", "full_attention_interval",
    "linear_conv_kernel_dim", "linear_key_head_dim",
    "linear_value_head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
    "tie_word_embeddings", "hidden_act", "use_sliding_window",
    # the chip's share
    "layers", "experts_held", "vocab_rows",
)
L2_EPS = 1e-6  # the family's q/k normalisation: x·rsqrt(Σx² + 1e-6)
KEEP_SOLVE = jax.checkpoint_policies.save_only_these_names(SOLVE_NAME)


def check_spec(spec) -> None:
    """Raise ValueError, naming the key, for a mapping this block cannot
    state. What the block does not implement is refused by name."""
    if not isinstance(spec, dict):
        raise ValueError("model_spec must be a mapping of the published "
                         "config keys plus layers/experts_held/vocab_rows")
    missing = [k for k in SPEC_KEYS if k not in spec]
    if missing:
        raise ValueError(f"model_spec lacks {missing}")
    want = {"rope_scaling": None, "decoder_sparse_step": 1,
            "mlp_only_layers": [], "tie_word_embeddings": False,
            "hidden_act": "silu", "use_sliding_window": False,
            "norm_topk_prob": True}
    for key, value in want.items():
        if spec[key] != value:
            raise ValueError(
                f"model_spec[{key!r}] = {spec[key]!r}: this block implements "
                f"{value!r} only")
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
    if spec["linear_num_value_heads"] % spec["linear_num_key_heads"]:
        raise ValueError("linear_num_key_heads must divide "
                         "linear_num_value_heads")
    rotary = spec["partial_rotary_factor"] * spec["head_dim"]
    if rotary != int(rotary) or int(rotary) % 2:
        raise ValueError("partial_rotary_factor * head_dim must be an even "
                         "whole number for the rotary pairs")
    if spec["full_attention_interval"] < 1 or spec["layers"] < 1:
        raise ValueError("full_attention_interval and layers must be >= 1")
    if spec["vocab_rows"] < 2:
        raise ValueError("vocab_rows must be >= 2")


def layer_types(spec: dict) -> list:
    """``"full_attention"`` or ``"linear_attention"`` for each kept layer."""
    every = spec["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(spec["layers"])]


def causal_depthwise_conv(x, taps):
    """y_t = Σ_j taps[j] ⊙ x_{t − (K − 1) + j}, zeros before the row's
    start. x (B, T, channels), taps (K, channels)."""
    k = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    t = x.shape[1]
    return sum(padded[:, j:j + t] * taps[j].astype(x.dtype)
               for j in range(k))


class HybridMoeLM(RoutedExpertLM):
    """The ``qwen3_next`` family's block (module docstring)."""

    stat_names = STAT_NAMES + ("linattn_state_absmax",
                               "linattn_kernel_layers")
    # conv taps: variance 1 / taps (fan-in); A_log: the family draws A from
    # uniform(0, 16) and stores its log — here log A ~ normal(0, 1), heads
    # of different memory, median A = 1
    init_rules = {"scale": "ones", "centred_scale": "zeros",
                  "embedding": EMBED_STD, "dt_bias": "ones", "A_log": 1.0,
                  "taps": 0.5}

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        check_spec(spec)
        super().__init__(spec, MoeSpec(
            experts=spec["num_experts"], top_k=spec["num_experts_per_tok"],
            first=spec["experts_held"][0], held=spec["experts_held"][1],
            scoring="softmax", norm_topk=spec["norm_topk_prob"], scale=1.0,
            shared="gated"), attn_fn, dtype, remat)
        self.layer_types = layer_types(spec)

    def norm(self, x, p):
        return rms_norm(x, 1.0 + p["centred_scale"],
                        self.spec["rms_norm_eps"])

    # ---- parameters ---------------------------------------------------
    def param_shapes(self) -> dict:
        s = self.spec
        d = s["hidden_size"]
        heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                         s["head_dim"])
        hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
        dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
        held = s["experts_held"][1]
        tree = {"embed": {"embedding": (s["vocab_rows"], d)},
                "final_norm": {"centred_scale": (d,)},
                "head": {"kernel": (d, s["vocab_rows"])}}
        for i, kind in enumerate(self.layer_types):
            if kind == "full_attention":
                layer = {"q": {"kernel": (d, heads * 2 * dh)},
                         "k": {"kernel": (d, kv * dh)},
                         "v": {"kernel": (d, kv * dh)},
                         "q_norm": {"centred_scale": (dh,)},
                         "k_norm": {"centred_scale": (dh,)},
                         "o": {"kernel": (heads * dh, d)}}
            else:
                layer = {"qkvz": {"kernel": (d, 2 * hk * dk + 2 * hv * dv)},
                         "ba": {"kernel": (d, 2 * hv)},
                         "conv": {"taps": (s["linear_conv_kernel_dim"],
                                           2 * hk * dk + hv * dv)},
                         "out_norm": {"scale": (dv,)},
                         "out": {"kernel": (hv * dv, d)}}
            layer.update({
                "attn_norm": {"centred_scale": (d,)},
                "mlp_norm": {"centred_scale": (d,)},
                "router": {"kernel": (d, s["num_experts"])},
                "shared": self.mlp_shapes(
                    s["shared_expert_intermediate_size"]),
                "shared_gate": {"kernel": (d, 1)},
                "experts": self.mlp_shapes(s["moe_intermediate_size"],
                                           (held,)),
            })
            tree[f"layer{i}"] = layer
        linear = self.layer_types.count("linear_attention")
        if linear:
            # per value head, all DeltaNet layers together, after every
            # layer in ravel order (module docstring)
            tree["linear_heads"] = {"A_log": (linear, hv),
                                    "dt_bias": (linear, hv)}
        return tree

    # ---- the block ----------------------------------------------------
    def _gated_attention(self, h, p, positions):
        s = self.spec
        b, t, _ = h.shape
        heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                         s["head_dim"])
        rotary = int(s["partial_rotary_factor"] * dh)
        qg = _dot(h, p["q"]["kernel"]).reshape(b, t, heads, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
        k = _dot(h, p["k"]["kernel"]).reshape(b, t, kv, dh)
        v = _dot(h, p["v"]["kernel"]).reshape(b, t, kv, dh)

        def rotate(x):
            # the first ``rotary`` dims at the default frequencies; the
            # table is made anew a call, so that q's and k's stay two
            # constants of the step (one shared table compiles to another
            # program than PR 34's: PERF.md section 6, PR 35)
            freqs = s["rope_theta"] ** (
                -np.arange(0, rotary, 2, dtype=np.float32) / rotary)
            return rope_half(x.astype(jnp.float32), positions, freqs)

        q = rotate(self.norm(q, p["q_norm"]))
        k = rotate(self.norm(k, p["k_norm"]))
        o = self.attn_fn(_operand(q), _operand(k), _operand(v))
        o = o.astype(h.dtype) * jax.nn.sigmoid(gate)
        return _dot(o.reshape(b, t, heads * dh), p["o"]["kernel"])

    def _linear_attention(self, h, p, a_log, dt_bias):
        """-> (the layer's output (B, T, hidden), max |S| over heads of the
        state the row leaves behind, 1.0 where the rule ran in the Pallas
        kernels and 0.0 where it took the ``jax.numpy`` path)."""
        s = self.spec
        b, t, _ = h.shape
        hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
        dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
        qkvz = _dot(h, p["qkvz"]["kernel"])
        ba = _dot(h, p["ba"]["kernel"]).astype(jnp.float32)
        mixed = jax.nn.silu(causal_depthwise_conv(
            qkvz[..., :2 * hk * dk + hv * dv], p["conv"]["taps"]))
        z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(b, t, hv, dv)
        q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
        v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)

        def unit(x):
            x32 = x.astype(jnp.float32)
            return (x32 * lax.rsqrt(jnp.sum(
                jnp.square(x32), axis=-1, keepdims=True) + L2_EPS)
            ).astype(x.dtype)

        q, k = unit(q) * dk ** -0.5, unit(k)
        beta = jax.nn.sigmoid(ba[..., :hv]).astype(v.dtype)
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        with jax.named_scope("draco_deltarule"):
            o, state = chunked_gated_delta_rule(q, k, v, g, beta, CHUNK)
            absmax = jnp.max(jnp.abs(lax.stop_gradient(state)))
        kernels = jnp.float32(rule_runs_in_kernels(q.shape, v.shape, CHUNK))
        o = rms_norm(o, p["out_norm"]["scale"], s["rms_norm_eps"])
        o = o * jax.nn.silu(z)
        return (_dot(o.reshape(b, t, hv * dv), p["out"]["kernel"]),
                (absmax, kernels))

    def _layer(self, x, p, heads, positions, kind: str):
        h = self.norm(x, p["attn_norm"])
        if kind == "full_attention":
            with jax.named_scope("draco_attn"):
                x = x + self._gated_attention(h, p, positions)
            rule = None
        else:
            with jax.named_scope("draco_linattn"):
                mixed, rule = self._linear_attention(h, p, *heads)
                x = x + mixed
        b, t, d = x.shape
        y, stats = self._experts(x.reshape(b * t, d), p)
        return y.reshape(b, t, d), (stats, rule)

    def hidden(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (the last layer's output (B, T, hidden), the
        ``stat_names`` counters)."""
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        positions = pos_offset + jnp.arange(tokens.shape[1])
        per_layer, rules, linear = [], [], 0
        for i, kind in enumerate(self.layer_types):
            heads = None
            if kind == "linear_attention":
                heads = (params["linear_heads"]["A_log"][linear],
                         params["linear_heads"]["dt_bias"][linear])
                linear += 1
            fn = functools.partial(self._layer, positions=positions,
                                   kind=kind)
            if self.remat:
                # the rule's triangular solve is kept, not solved again
                # (34 MB a layer; solving again was a fifth of the rule)
                fn = jax.checkpoint(fn, policy=KEEP_SOLVE)
            x, (stats, rule) = fn(x, params[f"layer{i}"], heads)
            per_layer.append(stats)
            if rule is not None:
                rules.append(rule)
        out = fold_stats(per_layer)
        absmax, kernels = (map(jnp.stack, zip(*rules)) if rules
                           else (jnp.zeros((1,), jnp.float32),) * 2)
        out["linattn_state_absmax"] = jnp.max(absmax)
        # the DeltaNet layers whose rule ran in the Pallas kernels
        out["linattn_kernel_layers"] = jnp.sum(kernels)
        return x, out
