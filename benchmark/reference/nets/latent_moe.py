"""Decoder with latent key/value attention and sigmoid-routed experts, as
the ``deepseek_v3`` family's public configs describe it (here: kakaocorp
kanana-2-30b-a3b), in plain ``jax.numpy``. No kernels, no sorting, no
dispatch buffer: every expert this chip holds runs over every token, under
a dense mask of the tokens' weights; attention is one plain softmax against
every key, computed a block of queries at a time (``lax.map``) so that
(heads, T, T) never exists; each layer is rematerialised in the backward
pass so that a full-width model fits beside its own gradient.

``spec`` is the configuration's mapping: the published config keys plus
``layers`` (depth kept), ``experts_held`` ([first, count] of the routed
experts this chip holds) and ``vocab_rows`` (rows of the vocabulary slice).

Per layer, x (T, hidden):
  h = rms(x); q = h Wq -> (T, H, nope+rope); h Wkva -> [c | k_rope];
  c = rms(c); c Wkvb -> (T, H, nope+v) = [k_nope | v]; rope (interleaved
  pairs) on q_rope and on the one k_rope all heads share;
  x += softmax(causal(q kT / sqrt(nope+rope))) v  Wo
  h = rms(x); layer < first_k_dense_replace: x += SwiGLU(h), else
  s = sigmoid(h Wg); chosen = top-k of s + b; w = s[chosen] / (sum + 1e-20)
  * routed_scaling_factor; x += sum over chosen AND held of w_e SwiGLU_e(h)
  + SwiGLU_shared(h). What the experts held elsewhere would add is left out.
Then rms, the untied head over the slice, next-token cross-entropy.

The router's product is float32 at ``highest`` whatever the precision of
the rest (the configuration states it so); ``b`` is a leaf of the tree that
takes no gradient."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.nets.common import operands

Q_BLOCK = 512


def rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * scale).astype(x.dtype)


def rope(x, positions, theta):
    """Rotate the pairs (x[2i], x[2i+1]) of the last axis by
    positions * theta**(-2i/dim). x: (T, ..., dim), positions: (T,)."""
    dim = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def swiglu(h, p, q):
    gate = q(h) @ q(p["gate"]["kernel"].astype(h.dtype))
    up = q(h) @ q(p["up"]["kernel"].astype(h.dtype))
    return q(jax.nn.silu(gate) * up) @ q(p["down"]["kernel"].astype(h.dtype))


def attention(h, p, spec, q):
    t = h.shape[0]
    heads = spec["num_attention_heads"]
    nope, rp, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                    spec["v_head_dim"])
    rank, eps = spec["kv_lora_rank"], spec["rms_norm_eps"]
    pos = jnp.arange(t)
    qs = (q(h) @ q(p["q"]["kernel"].astype(h.dtype))).reshape(
        t, heads, nope + rp)
    kva = q(h) @ q(p["kv_a"]["kernel"].astype(h.dtype))
    c = rms(kva[:, :rank], p["kv_norm"]["scale"], eps)
    k_rope = rope(kva[:, rank:], pos, spec["rope_theta"])  # (T, rope)
    kvb = (q(c) @ q(p["kv_b"]["kernel"].astype(h.dtype))).reshape(
        t, heads, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    qs = jnp.concatenate(
        [qs[..., :nope], rope(qs[..., nope:], pos, spec["rope_theta"])],
        axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None, :], (t, heads, rp))],
        axis=-1)
    scale = (nope + rp) ** -0.5
    block = min(Q_BLOCK, t)

    def rows(lo):
        """One block of queries against every key, the future masked."""
        qb = lax.dynamic_slice_in_dim(qs, lo, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", q(qb), q(k)) * scale
        mask = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        s = jnp.where(mask[None], s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("hqk,khd->qhd", q(pr), q(v))

    o = lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * vd)
    return q(o) @ q(p["o"]["kernel"].astype(h.dtype))


def route(h, p, spec):
    """(chosen (T, k) expert ids, w (T, k) weights), float32 at highest."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), p["router"]["kernel"],
        precision=lax.Precision.HIGHEST))
    bias = lax.stop_gradient(p["router"]["e_score_correction_bias"])
    _, chosen = lax.top_k(s + bias, spec["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if spec["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * spec["routed_scaling_factor"]


def experts(h, p, spec, q):
    """Every held expert over every token, under a dense mask of weights:
    w_e[t] is the token's weight for expert e, zero where it did not choose
    it."""
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
    return swiglu(h, p["shared"], q) + jnp.einsum(
        "te,etd->td", w_e.astype(h.dtype), each)


def layer(x, p, spec, q, dense: bool):
    eps = spec["rms_norm_eps"]
    x = x + attention(rms(x, p["attn_norm"]["scale"], eps), p, spec, q)
    h = rms(x, p["mlp_norm"]["scale"], eps)
    return x + (swiglu(h, p["mlp"], q) if dense else experts(h, p, spec, q))


def logits(params, tokens, spec, dtype="float32"):
    """tokens (T,) int32 -> (T, vocab_rows) float32."""
    cast, q = operands(dtype)
    x = cast(params["embed"]["embedding"][tokens])
    for i in range(spec["layers"]):
        dense = i < spec["first_k_dense_replace"]
        x = jax.checkpoint(
            lambda x, p, dense=dense: layer(x, p, spec, q, dense))(
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
