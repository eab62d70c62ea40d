"""Decoder with Gated DeltaNet linear-attention layers three in four, gated
softmax attention every fourth, and softmax-routed experts with a
sigmoid-gated shared expert after every mixer, as the ``qwen3_next``
family's public config describes it (here: Qwen3-Next-80B-A3B), in plain
``jax.numpy``. No kernels, no chunked algebra, no sorting, no dispatch
buffer: the delta rule runs TOKEN BY TOKEN (a ``lax.scan`` over t of the
three lines below); every expert this chip holds runs over every token
under a dense mask of the tokens' weights; attention is one plain softmax
against every key, a block of queries at a time (``lax.map``) so that
(heads, T, T) never exists. Each layer is rematerialised in the backward
pass, and the scan in blocks of ``SCAN_BLOCK`` tokens (its backward would
else keep a (Hv, Dk, Dv) state for every token: 8.6 GB a layer at T = 4096
and the published widths), so that a full-width model fits beside its own
gradient.

``spec`` is the configuration's mapping: the published config keys plus
``layers`` (depth kept), ``experts_held`` ([first, count] of the routed
experts this chip holds) and ``vocab_rows`` (rows of the vocabulary slice).

Norm: rms(x, w) = x rsqrt(mean x^2 + eps) (1 + w), zero-centred, everywhere
but the DeltaNet output norm, which is x rsqrt(mean x^2 + eps) w.
Layer i, x (T, hidden): x += mixer(rms(x)); x += experts(rms(x)); the mixer
is gated attention if (i + 1) % full_attention_interval == 0, else DeltaNet.

Gated attention: [q | gate] = h Wq (H heads of 2 Dh: a head's q, then its
  gate); k, v = h Wk, h Wv (Hkv heads of Dh); q, k rms-normed over Dh;
  rotary (half-rotation form: dims i and i + R/2 are a pair) on the first
  R = partial_rotary_factor Dh dims; causal softmax(q kT / sqrt(Dh)) v, key
  head j serving query heads j H/Hkv ..; out = (attn * sigmoid(gate)) Wo.
Gated DeltaNet: [q | k | v | z] = h Wqkvz (Hk heads of Dk for q and k, Hv
  heads of Dv for v and z); [b | a] = h Wba (Hv each); causal depthwise
  convolution (K taps, zeros before the row) then SiLU over the q, k, v
  channels; q, k <- x rsqrt(sum x^2 + 1e-6) per head, q scaled Dk^-1/2, key
  head j serving value heads j Hv/Hk ..; beta = sigmoid(b); g = -exp(A_log)
  softplus(a + dt_bias); per value head with S (Dk, Dv) from zero:
      S <- exp(g_t) S;  S <- S + k_t (x) beta_t (v_t - ST k_t);  o_t = ST q_t
  out = (rmsnorm(o) w * SiLU(z)) Wout, the norm over each head's Dv.
Experts: p = softmax(h Wg) over all num_experts (float32 at ``highest``
  whatever the precision of the rest: the configuration states it so);
  chosen = top-k of p; w = p[chosen] / sum p[chosen];
  x += sum over chosen AND held of w_e SwiGLU_e(h)
       + sigmoid(h w_sg) SwiGLU_shared(h).
  What the experts held elsewhere would add is left out.
Then rms, the untied head over the slice, next-token cross-entropy. The
multi-token-prediction head is left out (the config has no key for it).

The parameter tree is the program's (the seeded weights are made over its
shapes): per layer the leaves named above; ``A_log`` and ``dt_bias`` of all
DeltaNet layers together under ``linear_heads``, one row a DeltaNet layer
in layer order."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.nets.common import operands

Q_BLOCK = 512
SCAN_BLOCK = 64  # tokens of the delta rule's scan rematerialised together


def rms(x, w, eps, centred=True):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * (1.0 + w if centred else w)).astype(x.dtype)


def rope(x, positions, theta, rotary):
    """x (T, H, dim): dims i and i + rotary/2 (i < rotary/2) rotate by
    positions * theta**(-2i/rotary); dims from ``rotary`` on pass."""
    half = rotary // 2
    freqs = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:rotary]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                           x32[..., rotary:]], axis=-1)
    return out.astype(x.dtype)


def swiglu(h, p, q):
    gate = q(h) @ q(p["gate"]["kernel"].astype(h.dtype))
    up = q(h) @ q(p["up"]["kernel"].astype(h.dtype))
    return q(jax.nn.silu(gate) * up) @ q(p["down"]["kernel"].astype(h.dtype))


def gated_attention(h, p, spec, q):
    t = h.shape[0]
    heads, kv, dh = (spec["num_attention_heads"],
                     spec["num_key_value_heads"], spec["head_dim"])
    eps = spec["rms_norm_eps"]
    rotary = int(spec["partial_rotary_factor"] * dh)
    pos = jnp.arange(t)
    qg = (q(h) @ q(p["q"]["kernel"].astype(h.dtype))).reshape(
        t, heads, 2 * dh)
    qs, gate = qg[..., :dh], qg[..., dh:]
    k = (q(h) @ q(p["k"]["kernel"].astype(h.dtype))).reshape(t, kv, dh)
    v = (q(h) @ q(p["v"]["kernel"].astype(h.dtype))).reshape(t, kv, dh)
    qs = rope(rms(qs, p["q_norm"]["centred_scale"], eps), pos,
              spec["rope_theta"], rotary)
    k = rope(rms(k, p["k_norm"]["centred_scale"], eps), pos,
             spec["rope_theta"], rotary)
    # query head j*r + i reads key/value head j
    qs = qs.reshape(t, kv, heads // kv, dh)
    block = min(Q_BLOCK, t)

    def rows(lo):
        """One block of queries against every key, the future masked."""
        qb = lax.dynamic_slice_in_dim(qs, lo, block, axis=0)
        s = jnp.einsum("qjid,kjd->jiqk", q(qb), q(k)) * dh ** -0.5
        mask = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("jiqk,kjd->qjid", q(pr), q(v))

    o = lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * dh)
    o = o * jax.nn.sigmoid(gate.reshape(t, heads * dh))
    return q(o) @ q(p["o"]["kernel"].astype(h.dtype))


def delta_rule(qs, ks, vs, g, beta, q):
    """qs, ks (T, Hv, Dk), vs (T, Hv, Dv), g, beta (T, Hv) -> o (T, Hv,
    Dv): the recurrence, one token a step."""
    t, hv, dk = qs.shape
    dv = vs.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t).astype(state.dtype)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", q(state), q(k_t))
        delta = b_t[:, None] * (v_t - read)
        state = state + jnp.einsum("hk,hv->hkv", q(k_t), q(delta))
        return state, jnp.einsum("hkv,hk->hv", q(state), q(q_t))

    def block(state, xs):
        return lax.scan(token, state, xs)

    size = min(SCAN_BLOCK, t)
    pad = -t % size

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, size) + x.shape[1:])

    # closing tokens (k = 0, beta = 0, g = 0) neither decay nor write
    xs = tuple(blocks(x) for x in (qs, ks, vs, g, beta))
    _, o = lax.scan(jax.checkpoint(block),
                    jnp.zeros((hv, dk, dv), vs.dtype), xs)
    return o.reshape(-1, hv, dv)[:t]


def gated_deltanet(h, p, heads, spec, q):
    t = h.shape[0]
    hk, hv = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    dk, dv = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    a_log, dt_bias = heads
    qkvz = q(h) @ q(p["qkvz"]["kernel"].astype(h.dtype))
    ba = (q(h) @ q(p["ba"]["kernel"].astype(h.dtype))).astype(jnp.float32)
    taps = p["conv"]["taps"].astype(h.dtype)
    n_taps = taps.shape[0]
    channels = 2 * hk * dk + hv * dv
    padded = jnp.pad(qkvz[:, :channels], ((n_taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[j:j + t] * taps[j]
                            for j in range(n_taps)))
    z = qkvz[:, channels:].reshape(t, hv, dv)

    def unit(x):
        x32 = x.astype(jnp.float32)
        return (x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1,
                                        keepdims=True) + 1e-6)
                ).astype(x.dtype)

    r = hv // hk
    qs = jnp.repeat(unit(mixed[:, :hk * dk].reshape(t, hk, dk))
                    * dk ** -0.5, r, axis=1)
    ks = jnp.repeat(unit(mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)),
                    r, axis=1)
    vs = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)
    o = delta_rule(qs, ks, vs, g, beta.astype(vs.dtype), q)
    o = rms(o, p["out_norm"]["scale"], spec["rms_norm_eps"], centred=False)
    o = (o * jax.nn.silu(z)).reshape(t, hv * dv)
    return q(o) @ q(p["out"]["kernel"].astype(h.dtype))


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
    it; plus the shared expert under its gate."""
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
    open_ = jax.nn.sigmoid(
        q(h) @ q(p["shared_gate"]["kernel"].astype(h.dtype)))
    return open_ * swiglu(h, p["shared"], q) + jnp.einsum(
        "te,etd->td", w_e.astype(h.dtype), each)


def is_full_attention(i: int, spec: dict) -> bool:
    return (i + 1) % spec["full_attention_interval"] == 0


def layer(x, p, heads, spec, q, full: bool):
    eps = spec["rms_norm_eps"]
    h = rms(x, p["attn_norm"]["centred_scale"], eps)
    x = x + (gated_attention(h, p, spec, q) if full
             else gated_deltanet(h, p, heads, spec, q))
    h = rms(x, p["mlp_norm"]["centred_scale"], eps)
    return x + experts(h, p, spec, q)


def logits(params, tokens, spec, dtype="float32"):
    """tokens (T,) int32 -> (T, vocab_rows) float32."""
    cast, q = operands(dtype)
    x = cast(params["embed"]["embedding"][tokens])
    linear = 0
    for i in range(spec["layers"]):
        full = is_full_attention(i, spec)
        heads = None
        if not full:
            heads = (params["linear_heads"]["A_log"][linear],
                     params["linear_heads"]["dt_bias"][linear])
            linear += 1
        x = jax.checkpoint(
            lambda x, p, heads, full=full: layer(x, p, heads, spec, q, full))(
                x, params[f"layer{i}"], heads)
    x = rms(x, params["final_norm"]["centred_scale"], spec["rms_norm_eps"])
    return (q(x) @ q(params["head"]["kernel"].astype(x.dtype))).astype(
        jnp.float32)


def loss(params, tokens, spec, dtype="float32"):
    """Mean next-token cross-entropy of sequences ``tokens`` (B, T) over
    the vocabulary slice."""
    def one(seq):
        logp = jax.nn.log_softmax(logits(params, seq, spec, dtype)[:-1])
        return -jnp.take_along_axis(logp, seq[1:, None], axis=-1)[:, 0]

    return jnp.mean(jnp.stack([one(seq) for seq in tokens]))
