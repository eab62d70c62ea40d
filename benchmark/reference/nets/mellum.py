"""Decoder whose attention layers are of two kinds — grouped-query softmax
attention under a sliding window, and over the whole row, each kind with
rotary parameters of its own (the full layers' stretched by YaRN) — with
softmax-routed experts and no shared expert after every attention layer, as
the ``mellum`` family's public config describes it (here:
Mellum2-12B-A2.5B), in plain ``jax.numpy``. No kernels, no block skipping,
no sorting, no dispatch buffer: attention is one plain softmax against EVERY
key under a mask written as the two inequalities, a block of queries at a
time (``lax.map``, each block rematerialised in the backward pass) so that
(heads, T, T) never exists — 32 heads of 8192 x 8192 float32 scores are 8.6
GB whole; every expert this chip holds runs over every token under a dense
mask of the tokens' weights. Each layer is rematerialised in the backward
pass, so that a full-width model fits beside its own gradient.

``spec`` is the configuration's mapping: the published config keys plus
``layers`` (depth kept), ``experts_held`` ([first, count] of the routed
experts this chip holds) and ``vocab_rows`` (rows of the vocabulary slice).

Norm: rms(x, w) = x rsqrt(mean x^2 + eps) w, everywhere.
Layer i, x (T, hidden), of the kind layer_types[i]:
  x += attention(rms(x)); x += experts(rms(x)).
Attention: q = h Wq (H heads of Dh); k, v = h Wk, h Wv (Hkv heads of Dh);
  no bias, no q/k norm; rotary on all Dh dims (half-rotation form: dims i
  and i + Dh/2 are a pair), angle = position f_i with the f_i of the layer's
  kind (rope_parameters[kind]):
    default: f_i = theta^(-2i/Dh);
    yarn: e_i = theta^(-2i/Dh), p_i = e_i / factor,
      c(n) = Dh ln(L / (2 pi n)) / (2 ln theta), L the original positions,
      low = floor(c(beta_fast)), high = ceil(c(beta_slow)), both clipped to
      [0, Dh - 1], ramp_i = clip((i - low) / (high - low), 0, 1),
      f_i = p_i ramp_i + e_i (1 - ramp_i); cos and sin are both multiplied
      by attention_factor, so the logits carry its square;
  query t sees key s iff 0 <= t - s, and in a sliding_attention layer also
  t - s < sliding_window (itself and the sliding_window - 1 tokens before
  it); softmax(q kT / sqrt(Dh)) v, key head j serving query heads
  j H/Hkv ..; out = attn Wo.
Experts: p = softmax(h Wg) over all num_experts (float32 at ``highest``
  whatever the precision of the rest: the configuration states it so);
  chosen = top-k of p; w = p[chosen] / sum p[chosen];
  x += sum over chosen AND held of w_e SwiGLU_e(h). Nothing else is added:
  the model has no shared expert. What the experts held elsewhere would add
  is left out.
Then rms, the untied head over the slice, next-token cross-entropy. The
multi-token-prediction head is left out (the config has no key for it)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.nets.common import operands

Q_BLOCK = 512


def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * w).astype(x.dtype)


def frequencies(rope: dict, dim: int):
    """(f_i for the dim/2 pairs, the factor on cos and sin) of one entry of
    rope_parameters; float64 arithmetic, float32 result."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = float(rope["rope_theta"]) ** (-2.0 * i / dim)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default or yarn")

    def c(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (2.0 * math.pi * turns))
                / (2.0 * math.log(rope["rope_theta"])))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    stretched = plain / rope["factor"]
    return ((stretched * ramp + plain * (1.0 - ramp)).astype(np.float32),
            float(rope["attention_factor"]))


def rope(x, positions, freqs, factor):
    """x (T, H, dim): dims i and i + dim/2 rotate by positions * freqs[i],
    cos and sin times ``factor``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(x.dtype)


def attention(h, p, spec, q, kind: str):
    t = h.shape[0]
    heads, kv, dh = (spec["num_attention_heads"],
                     spec["num_key_value_heads"], spec["head_dim"])
    freqs, factor = frequencies(spec["rope_parameters"][kind], dh)
    pos = jnp.arange(t)
    qs = (q(h) @ q(p["q"]["kernel"].astype(h.dtype))).reshape(t, heads, dh)
    k = (q(h) @ q(p["k"]["kernel"].astype(h.dtype))).reshape(t, kv, dh)
    v = (q(h) @ q(p["v"]["kernel"].astype(h.dtype))).reshape(t, kv, dh)
    qs = rope(qs, pos, freqs, factor)
    k = rope(k, pos, freqs, factor)
    # query head j*r + i reads key/value head j
    qs = qs.reshape(t, kv, heads // kv, dh)
    block = min(Q_BLOCK, t)
    pad = -t % block
    qs = jnp.pad(qs, ((0, pad), (0, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def rows(lo):
        """One block of queries against every key, under the mask."""
        qb = lax.dynamic_slice_in_dim(qs, lo, block, axis=0)
        s = jnp.einsum("qjid,kjd->jiqk", q(qb), q(k)) * dh ** -0.5
        back = (lo + jnp.arange(block))[:, None] - pos[None, :]  # t - s
        mask = back >= 0
        if kind == "sliding_attention":
            mask = mask & (back < spec["sliding_window"])
        s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("jiqk,kjd->qjid", q(pr), q(v))

    o = lax.map(rows, jnp.arange(0, t + pad, block))
    o = o.reshape(t + pad, heads * dh)[:t]
    return q(o) @ q(p["o"]["kernel"].astype(h.dtype))


def route(h, p, spec):
    """(chosen (T, k) expert ids, w (T, k) weights), float32 at highest."""
    pr = jax.nn.softmax(jnp.matmul(
        h.astype(jnp.float32), p["router"]["kernel"],
        precision=lax.Precision.HIGHEST), axis=-1)
    w, chosen = lax.top_k(pr, spec["num_experts_per_tok"])
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True)


def experts(h, p, spec, q):
    """Every held expert over every token, under a dense mask of weights:
    w_e[t] is the token's weight for expert e, zero where it did not choose
    it. No shared expert."""
    first, count = spec["experts_held"]
    chosen, w = route(h, p, spec)
    held = first + jnp.arange(count)
    w_e = jnp.sum(jnp.where(chosen[:, :, None] == held, w[:, :, None], 0.0),
                  axis=1)  # (T, count)
    e = jax.tree.map(lambda a: q(a.astype(h.dtype)), p["experts"])
    gate = jnp.einsum("td,edf->etf", q(h), e["gate"]["kernel"])
    up = jnp.einsum("td,edf->etf", q(h), e["up"]["kernel"])
    each = jnp.einsum("etf,efd->etd", q(jax.nn.silu(gate) * up),
                      e["down"]["kernel"])
    return jnp.einsum("te,etd->td", w_e.astype(h.dtype), each)


def layer(x, p, spec, q, kind: str):
    eps = spec["rms_norm_eps"]
    x = x + attention(rms(x, p["attn_norm"]["scale"], eps), p, spec, q, kind)
    return x + experts(rms(x, p["mlp_norm"]["scale"], eps), p, spec, q)


def logits(params, tokens, spec, dtype="float32"):
    """tokens (T,) int32 -> (T, vocab_rows) float32."""
    cast, q = operands(dtype)
    x = cast(params["embed"]["embedding"][tokens])
    for i in range(spec["layers"]):
        kind = spec["layer_types"][i]
        x = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, spec, q, kind))(
                x, params[f"layer{i}"])
    x = rms(x, params["final_norm"]["scale"], spec["rms_norm_eps"])
    return (q(x) @ q(params["head"]["kernel"].astype(x.dtype))).astype(
        jnp.float32)


def loss(params, tokens, spec, dtype="float32"):
    """Mean next-token cross-entropy of sequences ``tokens`` (B, T) over
    the vocabulary slice."""
    def one(seq):
        logp = jax.nn.log_softmax(logits(params, seq, spec, dtype)[:-1])
        return -jnp.take_along_axis(logp, seq[1:, None], axis=-1)[:, 0]

    return jnp.mean(jnp.stack([one(seq) for seq in tokens]))
