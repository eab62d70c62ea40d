"""Decoder whose mixers are of two kinds — a double-gated short convolution,
and grouped-query softmax attention with an RMS norm on each head of q and
k — with a dense SwiGLU after the leading layers and sigmoid-scored,
bias-selected routed experts (no shared expert) after every later one, the
head tied to the embedding, as the ``lfm2_moe`` family's public config
describes it (here: LFM2-8B-A1B), in plain ``jax.numpy``. No kernels, no
sorting, no dispatch buffer: the convolution is its shifted sums written
out, one a tap; attention is one plain softmax against every key under an
explicit mask, a block of queries at a time (``lax.map``, each block
rematerialised in the backward pass) so that (heads, T, T) never exists;
every expert this chip holds runs over every token under a dense mask of
the tokens' weights; the head is ``h @ E.T`` with E the embedding. Each
layer is rematerialised in the backward pass, so that a full-width model
fits beside its own gradient.

``spec`` is the configuration's mapping: the published config keys plus
``layers`` (depth kept), ``layers_held`` (the published indices of the kept
layers), ``experts_held`` ([first, count] of the routed experts this chip
holds) and ``vocab_rows`` (rows of the vocabulary slice). The parameters
are the program's tree: ``layer<j>`` for the j-th kept layer; the per-head
norm weights of q and k (``qk_norm``: row a of q/k is the a-th kept
attention layer's) and the selection biases (``router_bias``: row e is the
e-th kept sparse layer's) stand after the layers.

Norm: rms(x, w) = x rsqrt(mean x^2 + norm_eps) w, everywhere. No bias.
Kept layer j is published layer i = layers_held[j], of the kind
layer_types[i], dense iff i < num_dense_layers. h = rms(x; operator_norm):
  conv: [B | C | X] = h W_in, three contiguous column ranges of hidden
    each, in this order; u = B X (elementwise);
    v_t = sum_{j<L} taps[j] u_{t-(L-1)+j}, per channel, u before the row's
    start zero, L = conv_L_cache; y = (C v) W_out. No activation function.
  full_attention: q = h Wq (H heads of Dh = hidden / H); k, v = h Wk, h Wv
    (Hkv heads); q and k each rms over their Dh dims (one (Dh,) weight for
    q, one for k); rotary on all Dh dims (half-rotation form: dims i and
    i + Dh/2 are a pair), angle = position theta^(-2i/Dh); query t sees key
    s iff s <= t; softmax(q kT / sqrt(Dh)) v, key head j serving query
    heads j H/Hkv ..; y = attn Wo.
  x += y; g = rms(x; ffn_norm).
  dense: x += (silu(g W1) (g W3)) W2 at intermediate_size.
  sparse: s = sigmoid(g Wr) over all num_experts (float32 at ``highest``
    whatever the precision of the rest: the configuration states it so);
    chosen = top-k of s + b (b the layer's expert_bias; it takes no
    gradient); w = s[chosen] / (sum s[chosen] + 1e-20) times
    routed_scaling_factor; x += sum over chosen AND held of w_e SwiGLU_e(g)
    at moe_intermediate_size. Nothing else is added: the model has no
    shared expert. What the experts held elsewhere would add is left out.
Then rms (the family's embedding_norm), logits = h ET over the slice (an
untied twin — tie_word_embeddings false — reads a ``head`` leaf instead),
next-token cross-entropy.

Departures from the transformers library's ``lfm2_moe`` that are known:
the renormalisation's denominator adds 1e-20 where the library adds 1e-6
(5e-7 of a sum of four sigmoid scores)."""

from __future__ import annotations

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


def short_conv(h, p, spec, q):
    """h (T, hidden) -> (T, hidden)."""
    t, d = h.shape
    bcx = q(h) @ q(p["in_proj"]["kernel"].astype(h.dtype))
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = b * x
    taps = p["conv"]["taps"].astype(h.dtype)
    n = spec["conv_L_cache"]
    v = jnp.zeros_like(u)
    for j in range(n):
        back = n - 1 - j  # tap j reads the token ``back`` places earlier
        shifted = jnp.concatenate(
            [jnp.zeros((back, d), u.dtype), u[:t - back]], axis=0)
        v = v + taps[j] * shifted
    return q(c * v) @ q(p["out_proj"]["kernel"].astype(h.dtype))


def rope(x, positions, theta):
    """x (T, H, dim): dims i and i + dim/2 rotate by
    positions * theta^(-2i/dim)."""
    dim = x.shape[-1]
    half = dim // 2
    freqs = (float(theta) ** (-2.0 * np.arange(half, dtype=np.float64)
                              / dim)).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(x.dtype)


def attention(h, p, q_scale, k_scale, spec, q):
    t = h.shape[0]
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec["hidden_size"] // heads
    eps = spec["norm_eps"]
    pos = jnp.arange(t)
    qs = (q(h) @ q(p["q"]["kernel"].astype(h.dtype))).reshape(t, heads, dh)
    k = (q(h) @ q(p["k"]["kernel"].astype(h.dtype))).reshape(t, kv, dh)
    v = (q(h) @ q(p["v"]["kernel"].astype(h.dtype))).reshape(t, kv, dh)
    qs = rope(rms(qs, q_scale, eps), pos, spec["rope_theta"])
    k = rope(rms(k, k_scale, eps), pos, spec["rope_theta"])
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
        mask = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("jiqk,kjd->qjid", q(pr), q(v))

    o = lax.map(rows, jnp.arange(0, t + pad, block))
    o = o.reshape(t + pad, heads * dh)[:t]
    return q(o) @ q(p["o"]["kernel"].astype(h.dtype))


def swiglu(g, p, q):
    def w(name):
        return q(p[name]["kernel"].astype(g.dtype))

    return q(jax.nn.silu(q(g) @ w("gate")) * (q(g) @ w("up"))) @ w("down")


def route(g, p, bias, spec):
    """(chosen (T, k) expert ids, w (T, k) weights), float32 at highest."""
    s = jax.nn.sigmoid(jnp.matmul(
        g.astype(jnp.float32), p["router"]["kernel"],
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(s + lax.stop_gradient(bias),
                          spec["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * spec["routed_scaling_factor"]


def experts(g, p, bias, spec, q):
    """Every held expert over every token, under a dense mask of weights:
    w_e[t] is the token's weight for expert e, zero where it did not choose
    it. No shared expert."""
    first, count = spec["experts_held"]
    chosen, w = route(g, p, bias, spec)
    held = first + jnp.arange(count)
    w_e = jnp.sum(jnp.where(chosen[:, :, None] == held, w[:, :, None], 0.0),
                  axis=1)  # (T, count)
    e = jax.tree.map(lambda a: q(a.astype(g.dtype)), p["experts"])
    gate = jnp.einsum("td,edf->etf", q(g), e["gate"]["kernel"])
    up = jnp.einsum("td,edf->etf", q(g), e["up"]["kernel"])
    each = jnp.einsum("etf,efd->etd", q(jax.nn.silu(gate) * up),
                      e["down"]["kernel"])
    return jnp.einsum("te,etd->td", w_e.astype(g.dtype), each)


def layer(x, p, extra, spec, q, kind: str, dense: bool):
    """``extra``: (q's norm weight, k's norm weight, the selection bias) of
    this layer, None where it has none."""
    q_scale, k_scale, bias = extra
    eps = spec["norm_eps"]
    h = rms(x, p["operator_norm"]["scale"], eps)
    if kind == "conv":
        x = x + short_conv(h, p, spec, q)
    elif kind == "full_attention":
        x = x + attention(h, p, q_scale, k_scale, spec, q)
    else:
        raise ValueError(f"layer kind {kind!r}: conv or full_attention")
    g = rms(x, p["ffn_norm"]["scale"], eps)
    if dense:
        return x + swiglu(g, p["mlp"], q)
    return x + experts(g, p, bias, spec, q)


def kept_layers(spec):
    """(kind, dense) of each kept layer, from the published indices."""
    return [(spec["layer_types"][i], i < spec["num_dense_layers"])
            for i in spec["layers_held"]]


def logits(params, tokens, spec, dtype="float32"):
    """tokens (T,) int32 -> (T, vocab_rows) float32."""
    cast, q = operands(dtype)
    embedding = params["embed"]["embedding"]
    x = cast(embedding[tokens])
    attn = sparse = 0
    for j, (kind, dense) in enumerate(kept_layers(spec)):
        q_scale = k_scale = bias = None
        if kind == "full_attention":
            q_scale = params["qk_norm"]["q"]["scale"][attn]
            k_scale = params["qk_norm"]["k"]["scale"][attn]
            attn += 1
        if not dense:
            bias = params["router_bias"]["expert_bias"][sparse]
            sparse += 1
        x = jax.checkpoint(
            lambda x, p, extra, kind=kind, dense=dense: layer(
                x, p, extra, spec, q, kind, dense))(
                    x, params[f"layer{j}"], (q_scale, k_scale, bias))
    x = rms(x, params["final_norm"]["scale"], spec["norm_eps"])
    if spec.get("tie_word_embeddings", True):
        head = embedding.T
    else:
        head = params["head"]["kernel"]
    return (q(x) @ q(head.astype(x.dtype))).astype(jnp.float32)


def loss(params, tokens, spec, dtype="float32"):
    """Mean next-token cross-entropy of sequences ``tokens`` (B, T) over
    the vocabulary slice."""
    def one(seq):
        logp = jax.nn.log_softmax(logits(params, seq, spec, dtype)[:-1])
        return -jnp.take_along_axis(logp, seq[1:, None], axis=-1)[:, 0]

    return jnp.mean(jnp.stack([one(seq) for seq in tokens]))
