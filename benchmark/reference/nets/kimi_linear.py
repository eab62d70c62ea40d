"""Decoder with Kimi Delta Attention layers three in four — a delta rule
whose decay is per key channel — latent key/value attention WITHOUT any
positional signal the fourth, a dense SwiGLU after the leading layer and
sigmoid-scored, bias-selected routed experts plus a shared expert after
every later one, untied head, as the ``kimi_linear`` family's public config
describes it (here: moonshotai Kimi-Linear-48B-A3B; arXiv:2510.26692), in
plain ``jax.numpy``. No kernels, no chunked algebra, no sorting, no dispatch
buffer: the rule runs TOKEN BY TOKEN (a ``lax.scan`` over t of the three
lines below); every expert this chip holds runs over every token under a
dense mask of the tokens' weights; attention is one plain softmax against
every key, a block of queries at a time (``lax.map``) so that (heads, T, T)
never exists. Each layer is rematerialised in the backward pass, and the
scan in blocks of ``SCAN_BLOCK`` tokens (its backward would else keep a
(heads, Dk, Dv) state for every token), so that a full-width model fits
beside its own gradient.

``spec`` is the configuration's mapping: the published config keys plus
``layers`` (depth kept), ``layers_held`` (the published indices of the kept
layers, 1-based as the config's own index lists count them), ``heads_held``
([first, count] of the heads of both mixers this chip holds),
``experts_held`` ([first, count] of the routed experts) and ``vocab_rows``
(rows of the vocabulary slice). The parameters are the program's tree:
``layer<j>`` for the j-th kept layer, each mixer's projections cut to the
held heads (q / k / v / gate columns, output rows); ``A_log`` of all KDA
layers together under ``linear_heads``, one row a KDA layer in layer order.

Norm: rms(x, w) = x rsqrt(mean x^2 + rms_norm_eps) w, everywhere. Kept layer
j is published layer i = layers_held[j]: Kimi Delta Attention iff i is in
linear_attn_config.kda_layers, latent attention iff in full_attn_layers;
dense iff i <= first_k_dense_replace. h = rms(x; attn_norm):
  KDA, H held heads of D = linear_attn_config.head_dim: for each of q, k, v
    a projection h W (hidden -> H D), then its own causal depthwise
    convolution (4 taps, zeros before the row, no bias), then SiLU;
    q, k <- x rsqrt(sum x^2 + 1e-6) per head, q scaled D^-1/2;
    g = -exp(A_log[head]) softplus((h Wfa) Wfb + dt_bias): (T, H, D), one
    log-decay a head AND key channel; beta = sigmoid(h Wb): (T, H);
    per head with S (D, D) from zero:
      S <- diag(exp(g_t)) S;  S <- S + beta_t k_t (x) (v_t - ST k_t);
      o_t = ST q_t
    y = (rms(o; o_norm, over each head's D) * sigmoid((h Wga) Wgb)) Wo.
  latent attention: q = h Wq -> (T, H, nope + rope); h Wkva -> [c | k_sh];
    c = rms(c; kv_norm); c Wkvb -> (T, H, nope + v) = [k_nope | v];
    k = [k_nope | k_sh], the ONE k_sh shared by every head, NOT rotated
    (mla_use_nope: no position enters the model anywhere);
    y = softmax(causal(q kT / sqrt(nope + rope))) v  Wo.
  x += y — the held heads' partial sum: what the heads held elsewhere would
  add is left out. m = rms(x; mlp_norm).
  dense: x += (silu(m W1) (m W3)) W2 at intermediate_size.
  sparse: s = sigmoid(m Wr) over all num_experts (float32 at ``highest``
    whatever the precision of the rest: the configuration states it so);
    chosen = top-k of s + b (b = e_score_correction_bias; it takes no
    gradient; num_expert_group = 1: the plain top-k); w = s[chosen] /
    (sum s[chosen] + 1e-20) * routed_scaling_factor;
    x += sum over chosen AND held of w_e SwiGLU_e(m) + SwiGLU_shared(m).
    What the experts held elsewhere would add is left out.
Then rms, the untied head over the slice, next-token cross-entropy.

Departures that are known: log A is whatever the seeded weights hold (the
family draws A from uniform(1, 16)); the gates' rank (Wfa, Wga: hidden ->
D) is the head size, which the config has no key for."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.nets.common import operands

Q_BLOCK = 512
SCAN_BLOCK = 64  # tokens of the rule's scan rematerialised together


def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * w).astype(x.dtype)


def swiglu(h, p, q):
    def w(name):
        return q(p[name]["kernel"].astype(h.dtype))

    return q(jax.nn.silu(q(h) @ w("gate")) * (q(h) @ w("up"))) @ w("down")


def delta_rule(qs, ks, vs, g, beta, q):
    """qs, ks, g (T, H, Dk), vs (T, H, Dv), beta (T, H) -> o (T, H, Dv):
    the recurrence, one token a step, the decay a row scale of S."""
    t, h, dk = qs.shape
    dv = vs.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t).astype(state.dtype)[:, :, None] * state
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
                    jnp.zeros((h, dk, dv), vs.dtype), xs)
    return o.reshape(-1, h, dv)[:t]


def kda(h, p, a_log, spec, q):
    """h (T, hidden) -> the held heads' part of the layer's output."""
    t = h.shape[0]
    heads = spec["heads_held"][1]
    d = spec["linear_attn_config"]["head_dim"]

    def dot(x, name):
        return q(x) @ q(p[name]["kernel"].astype(x.dtype))

    def mixed(name):
        x = dot(h, name)
        taps = p[f"{name}_conv"]["taps"].astype(h.dtype)
        n = taps.shape[0]
        padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[j:j + t] * taps[j] for j in range(n))
                           ).reshape(t, heads, d)

    def unit(x):
        x32 = x.astype(jnp.float32)
        return (x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1,
                                        keepdims=True) + 1e-6)
                ).astype(x.dtype)

    qs, ks, vs = unit(mixed("q")) * d ** -0.5, unit(mixed("k")), mixed("v")
    decay = dot(dot(h, "f_a"), "f_b").astype(jnp.float32)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        decay + p["f_b"]["dt_bias"]).reshape(t, heads, d)
    beta = jax.nn.sigmoid(dot(h, "b").astype(jnp.float32))
    o = delta_rule(qs, ks, vs, g, beta.astype(vs.dtype), q)
    o = rms(o, p["o_norm"]["scale"], spec["rms_norm_eps"])
    gate = jax.nn.sigmoid(dot(dot(h, "g_a"), "g_b"))
    return dot(o.reshape(t, heads * d) * gate, "o")


def latent_attention(h, p, spec, q):
    """h (T, hidden) -> the held heads' part of the layer's output; no
    rotation anywhere."""
    t = h.shape[0]
    heads = spec["heads_held"][1]
    nope, rp, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                    spec["v_head_dim"])
    rank, eps = spec["kv_lora_rank"], spec["rms_norm_eps"]
    pos = jnp.arange(t)
    qs = (q(h) @ q(p["q"]["kernel"].astype(h.dtype))).reshape(
        t, heads, nope + rp)
    kva = q(h) @ q(p["kv_a"]["kernel"].astype(h.dtype))
    c = rms(kva[:, :rank], p["kv_norm"]["scale"], eps)
    kvb = (q(c) @ q(p["kv_b"]["kernel"].astype(h.dtype))).reshape(
        t, heads, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(kva[:, None, rank:], (t, heads, rp))], axis=-1)
    v = kvb[..., nope:]
    scale = (nope + rp) ** -0.5
    block = min(Q_BLOCK, t)
    pad = -t % block
    qs = jnp.pad(qs, ((0, pad), (0, 0), (0, 0)))

    def rows(lo):
        """One block of queries against every key, the future masked."""
        qb = lax.dynamic_slice_in_dim(qs, lo, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", q(qb), q(k)) * scale
        mask = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        s = jnp.where(mask[None], s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("hqk,khd->qhd", q(pr), q(v))

    o = lax.map(rows, jnp.arange(0, t + pad, block))
    o = o.reshape(t + pad, heads * vd)[:t]
    return q(o) @ q(p["o"]["kernel"].astype(h.dtype))


def route(m, p, spec):
    """(chosen (T, k) expert ids, w (T, k) weights), float32 at highest."""
    s = jax.nn.sigmoid(jnp.matmul(
        m.astype(jnp.float32), p["router"]["kernel"],
        precision=lax.Precision.HIGHEST))
    bias = lax.stop_gradient(p["router"]["e_score_correction_bias"])
    _, chosen = lax.top_k(s + bias, spec["num_experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * spec["routed_scaling_factor"]


def experts(m, p, spec, q):
    """Every held expert over every token, under a dense mask of weights:
    w_e[t] is the token's weight for expert e, zero where it did not choose
    it; plus the shared expert on every token."""
    first, count = spec["experts_held"]
    chosen, w = route(m, p, spec)
    held = first + jnp.arange(count)
    w_e = jnp.sum(jnp.where(chosen[:, :, None] == held, w[:, :, None], 0.0),
                  axis=1)  # (T, count)
    e = jax.tree.map(lambda a: q(a.astype(m.dtype)), p["experts"])
    gate = jnp.einsum("td,edf->etf", q(m), e["gate"]["kernel"])
    up = jnp.einsum("td,edf->etf", q(m), e["up"]["kernel"])
    each = jnp.einsum("etf,efd->etd", q(jax.nn.silu(gate) * up),
                      e["down"]["kernel"])
    out = jnp.einsum("te,etd->td", w_e.astype(m.dtype), each)
    if spec["num_shared_experts"]:
        out = out + swiglu(m, p["shared"], q)
    return out


def kept_layers(spec):
    """(is KDA, is dense) of each kept layer, from the published indices."""
    linear = spec["linear_attn_config"]
    out = []
    for i in spec["layers_held"]:
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(f"published layer {i}: in exactly one of "
                             f"kda_layers and full_attn_layers")
        out.append((i in linear["kda_layers"],
                    i <= spec["first_k_dense_replace"]))
    return out


def layer(x, p, a_log, spec, q, linear: bool, dense: bool):
    eps = spec["rms_norm_eps"]
    h = rms(x, p["attn_norm"]["scale"], eps)
    x = x + (kda(h, p, a_log, spec, q) if linear
             else latent_attention(h, p, spec, q))
    m = rms(x, p["mlp_norm"]["scale"], eps)
    return x + (swiglu(m, p["mlp"], q) if dense else experts(m, p, spec, q))


def logits(params, tokens, spec, dtype="float32"):
    """tokens (T,) int32 -> (T, vocab_rows) float32."""
    cast, q = operands(dtype)
    x = cast(params["embed"]["embedding"][tokens])
    seen = 0
    for j, (linear, dense) in enumerate(kept_layers(spec)):
        a_log = None
        if linear:
            a_log = params["linear_heads"]["A_log"][seen]
            seen += 1
        x = jax.checkpoint(
            lambda x, p, a_log, linear=linear, dense=dense: layer(
                x, p, a_log, spec, q, linear, dense))(
                    x, params[f"layer{j}"], a_log)
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
