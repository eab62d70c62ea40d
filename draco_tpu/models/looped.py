"""Looped decoder LM built from a published config mapping: ONE stack of
dense layers run ``total_ut_steps`` times over the same weights, an exit
gate after each pass, the training loss an expectation over the exits — the
``ouro`` family's block (ByteDance Ouro-2.6B is the configuration the
benchmark runs; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

``TrainConfig.model_spec`` states the model as the other published-config
blocks' does: the published ``config.json`` keys verbatim plus ``layers``
(depth kept: further layers lie on further pipeline stages) and
``vocab_rows`` (here the whole vocabulary: the model is dense, nothing
divides a layer). Seeded ``init``, head and loss are ``spec_lm.SpecLM``'s.

Every norm is the plain RMS norm, y = x·rsqrt(mean x² + eps)·w (leaf
``scale``, ones at init). One layer, x (T, hidden), has four of them — a
norm before and after each sub-block:

  a = Attn(norm(x; attn_norm));            x += norm(a; attn_out_norm)
  m = W_down(silu(W_gate h) ⊙ W_up h), h = norm(x; mlp_norm)
                                           x += norm(m; mlp_out_norm)

Attn (``draco_attn``, with its two norms): q, k, v = h·Wq, h·Wk, h·Wv as H
heads of Dh (plain multi-head attention: as many key/value heads as query
heads), no bias, no q/k norm; rotary on all Dh dims in the half-rotation
form, angle = position·θ^(−2i/Dh); causal softmax(q·kᵀ/√Dh)·v; ·Wo. The
SwiGLU with its two norms runs under ``draco_mlp``.

The loop: h⁰ = E[tokens]; for pass t = 1..R: hᵗ = norm(layer_L(…
layer_1(hᵗ⁻¹)); final_norm) — the SAME L layers and the SAME final norm in
every pass, the same positions; the normed state is both the pass's exit
and the next pass's input. The passes are a ``lax.scan`` over the one
layer stack (the leaves are the scan's constants, so autodiff sums the R
uses into each leaf); with ``remat`` each of the R·L layer applications is
its own ``jax.checkpoint``, and so is each pass's final norm (kept, its
two float32 intermediates are two more (R, T, hidden) stacks alive where
the head's state gradient is born: 268 MB of the cell's peak).

The exits (``draco_head``: logitsᵗ = hᵗ·W_head, the one head R times, and
CEᵗ its next-token cross-entropy; ``draco_exit``: the rest): gate λᵗ =
σ(hᵗ·w_g + b_g) (float32 at ``highest``), exit distribution pᵗ = λᵗ
∏_{j<t}(1 − λʲ) for t < R and p^R = ∏_{j<R}(1 − λʲ) — the last pass takes
what is left, Σ pᵗ = 1 —, and per position

  ℓ = Σₜ pᵗ·CEᵗ − β·H(p),  H(p) = −Σ pᵗ log pᵗ  (``ENTROPY_WEIGHT``)

which ``token_nll`` returns where the other blocks return a plain negative
log-likelihood (all R·T rows through ``spec_lm.blocked_nll``); the route's
mean over positions is the loss. The route trains through
``weighted_nll``, which is handed the positions' weights w: Σ w·ℓ is the
head's own weighted sum at the rows' weights w·pᵗ (``spec_lm.
weighted_nll``: a block's forward pass takes the block's gradients, three
products a block where a rematerialised block ran four) less β·Σ w·H(p),
and the gate's gradient arrives as the weights' cotangent, CEᵗ. The gate's
leaves (``loop_exit``: a one-element bias among them) sort last in ravel
order, so every leaf before them lies on the vote stack's 128-wide lines
(parallel/sp_step.row_layout).

Not read: ``early_exit_threshold`` (inference only), ``max_window_layers``,
``max_position_embeddings``, ``sliding_window`` (``use_sliding_window``
must be false).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from draco_tpu.models.spec_lm import (
    EMBED_STD, SpecLM, _dot, _operand, blocked_nll, head_blocks_fused,
    rms_norm, rope_half, swiglu, weighted_nll,
)

# the published config keys the block reads (model_spec must carry them)
SPEC_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "layer_types", "rope_theta",
    "rope_scaling", "rms_norm_eps", "use_sliding_window",
    "tie_word_embeddings", "hidden_act", "total_ut_steps",
    # the chip's share
    "layers", "vocab_rows",
)
# β of the objective: the weight of the exit distribution's entropy (the
# paper's stage-I objective; the published config has no key for it)
ENTROPY_WEIGHT = 0.1
# per-step counters: passes run; the mean over positions of Σ t·pᵗ, of H(p),
# and of the first and the last exit's cross-entropy (the loop is doing
# something when the last is below the first); the head blocks of a lane
# whose gradients the forward pass took (spec_lm.weighted_nll: 0 where the
# exits' rows are one block, and under ``token_nll``)
STAT_NAMES = ("loop_passes", "exit_pass_mean", "exit_entropy",
              "exit_ce_first", "exit_ce_last", "head_blocks_fused")


def check_spec(spec) -> None:
    """Raise ValueError, naming the key, for a mapping this block cannot
    state. What the block does not implement is refused by name."""
    if not isinstance(spec, dict):
        raise ValueError("model_spec must be a mapping of the published "
                         "config keys plus layers/vocab_rows")
    missing = [k for k in SPEC_KEYS if k not in spec]
    if missing:
        raise ValueError(f"model_spec lacks {missing}")
    want = {"use_sliding_window": False, "rope_scaling": None,
            "tie_word_embeddings": False, "hidden_act": "silu",
            "num_key_value_heads": spec["num_attention_heads"]}
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
    for i in range(layers):
        if spec["layer_types"][i] != "full_attention":
            raise ValueError(
                f"model_spec['layer_types'][{i}] = "
                f"{spec['layer_types'][i]!r}: this block implements "
                f"'full_attention' only")
    steps = spec["total_ut_steps"]
    if not (isinstance(steps, int) and steps >= 1):
        raise ValueError(f"model_spec['total_ut_steps'] = {steps!r}: a "
                         f"whole number of passes >= 1")
    if spec["head_dim"] % 2:
        raise ValueError("head_dim must be even for the rotary pairs")
    if spec["vocab_rows"] < 2:
        raise ValueError("vocab_rows must be >= 2")


def exit_log_probs(z):
    """Gate logits z (R, ...) -> log pᵗ (R, ...), float32: log λᵗ + Σ_{j<t}
    log(1 − λʲ), the last pass taking what is left."""
    nothing = jnp.zeros_like(z[:1])
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)  # log(1 − λ)s
    return (jnp.concatenate([nothing, stayed])
            + jnp.concatenate([jax.nn.log_sigmoid(z[:-1]), nothing]))


class LoopedLM(SpecLM):
    """The ``ouro`` family's block (module docstring)."""

    stat_names = STAT_NAMES
    init_rules = {"scale": "ones", "embedding": EMBED_STD, "bias": "zeros"}

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        check_spec(spec)
        super().__init__(spec, attn_fn, dtype, remat)
        dh = spec["head_dim"]
        self.freqs = (float(spec["rope_theta"]) ** (
            -np.arange(0, dh, 2, dtype=np.float64) / dh)).astype(np.float32)

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.spec["rms_norm_eps"])

    # ---- parameters ---------------------------------------------------
    def param_shapes(self) -> dict:
        s = self.spec
        d, width = s["hidden_size"], s["num_attention_heads"] * s["head_dim"]
        tree = {"embed": {"embedding": (s["vocab_rows"], d)},
                "final_norm": {"scale": (d,)},
                "head": {"kernel": (d, s["vocab_rows"])},
                # last in ravel order (module docstring)
                "loop_exit": {"kernel": (d,), "bias": (1,)}}
        for i in range(s["layers"]):
            tree[f"layer{i}"] = {
                "attn_norm": {"scale": (d,)},
                "q": {"kernel": (d, width)},
                "k": {"kernel": (d, width)},
                "v": {"kernel": (d, width)},
                "o": {"kernel": (width, d)},
                "attn_out_norm": {"scale": (d,)},
                "mlp_norm": {"scale": (d,)},
                "mlp": self.mlp_shapes(s["intermediate_size"]),
                "mlp_out_norm": {"scale": (d,)},
            }
        return tree

    # ---- the block ----------------------------------------------------
    def _attention(self, h, p, positions):
        s = self.spec
        b, t, _ = h.shape
        heads, dh = s["num_attention_heads"], s["head_dim"]
        q, k, v = (_dot(h, p[name]["kernel"]).reshape(b, t, heads, dh)
                   for name in "qkv")
        q = rope_half(q.astype(jnp.float32), positions, self.freqs)
        k = rope_half(k.astype(jnp.float32), positions, self.freqs)
        o = self.attn_fn(_operand(q), _operand(k), _operand(v))
        return _dot(o.astype(h.dtype).reshape(b, t, heads * dh),
                    p["o"]["kernel"])

    def _layer(self, x, p, positions):
        with jax.named_scope("draco_attn"):
            a = self._attention(self.norm(x, p["attn_norm"]), p, positions)
            x = x + self.norm(a, p["attn_out_norm"])
        with jax.named_scope("draco_mlp"):
            m = swiglu(self.norm(x, p["mlp_norm"]), p["mlp"])
            return x + self.norm(m, p["mlp_out_norm"])

    def passes(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> every pass's normed state (R, B, T, hidden)."""
        positions = pos_offset + jnp.arange(tokens.shape[1])
        layer = functools.partial(self._layer, positions=positions)
        norm = self.norm
        if self.remat:
            layer, norm = jax.checkpoint(layer), jax.checkpoint(norm)

        def one_pass(x, _):
            for i in range(self.spec["layers"]):
                x = layer(x, params[f"layer{i}"])
            x = norm(x, params["final_norm"])
            return x, x

        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        return lax.scan(one_pass, x, None,
                        length=self.spec["total_ut_steps"])[1]

    def head_rows(self, params, tokens, pos_offset=0):
        """What ``logits`` reads: the last pass's normed state (the last
        exit's rows), no counters."""
        return self.passes(params, tokens, pos_offset)[-1], {}

    def _exit_log_probs(self, params, h):
        z = jnp.matmul(h.astype(jnp.float32), params["loop_exit"]["kernel"],
                       precision=lax.Precision.HIGHEST)
        return exit_log_probs(z + params["loop_exit"]["bias"])

    def exit_terms(self, params, tokens, targets, pos_offset=0):
        """tokens, targets (B, T) -> (CEᵗ, log pᵗ), each (R, B, T) float32:
        every exit's next-token cross-entropy and the exit distribution."""
        h = self.passes(params, tokens, pos_offset)
        with jax.named_scope("draco_head"):
            ce = blocked_nll(h, params["head"]["kernel"],
                             jnp.broadcast_to(targets, h.shape[:-1]))
        with jax.named_scope("draco_exit"):
            return ce, self._exit_log_probs(params, h)

    def _exit_stats(self, ce, p, entropy, fused: int = 0) -> dict:
        order = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
        return {
            "loop_passes": jnp.float32(p.shape[0]),
            "exit_pass_mean": jnp.mean(jnp.tensordot(order, p, axes=1)),
            "exit_entropy": jnp.mean(entropy),
            "exit_ce_first": jnp.mean(ce[0]),
            "exit_ce_last": jnp.mean(ce[-1]),
            "head_blocks_fused": jnp.float32(fused),
        }

    def token_nll(self, params, tokens, targets, pos_offset=0,
                  train: bool = True):
        """tokens, targets (B, T) -> (the per-position objective ℓ (B, T)
        float32 — module docstring —, the ``stat_names`` counters)."""
        del train  # no dropout in this block
        ce, logp = self.exit_terms(params, tokens, targets, pos_offset)
        with jax.named_scope("draco_exit"):
            p = jnp.exp(logp)
            entropy = -jnp.sum(p * logp, axis=0)
            objective = jnp.sum(p * ce, axis=0) - ENTROPY_WEIGHT * entropy
            return objective, self._exit_stats(ce, p, entropy)

    def weighted_nll(self, params, tokens, targets, weights, denom=1.0,
                     pos_offset=0, train: bool = True):
        """Σ ``weights`` · ℓ / denom, a scalar, and the counters: the
        exits' term Σ w·pᵗ·CEᵗ is the head's own weighted sum (``spec_lm.
        weighted_nll`` at weights w·pᵗ), so the gate's gradient arrives as
        the weights' cotangent."""
        del train  # no dropout in this block
        h = self.passes(params, tokens, pos_offset)
        with jax.named_scope("draco_exit"):
            logp = self._exit_log_probs(params, h)
            p = jnp.exp(logp)
        with jax.named_scope("draco_head"):
            exits, ce = weighted_nll(
                h, params["head"]["kernel"],
                jnp.broadcast_to(targets, h.shape[:-1]), weights * p, denom)
        with jax.named_scope("draco_exit"):
            entropy = -jnp.sum(p * logp, axis=0)
            return (exits
                    - ENTROPY_WEIGHT * jnp.sum(weights * entropy) / denom,
                    self._exit_stats(ce, p, entropy, head_blocks_fused(
                        ce.size, self.spec["vocab_rows"])))
