"""Looped decoder — one stack of dense layers run ``total_ut_steps`` times
over the same weights, an exit gate after each pass, the training loss an
expectation over the exits — as the ``ouro`` family's public config and
paper describe it (here: Ouro-2.6B; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741), in plain ``jax.numpy``. No kernels, no
scan: the passes are a Python loop over the same leaves, attention is one
plain softmax a block of queries at a time (``lax.map``, each block
rematerialised in the backward pass, so that (heads, T, T) never exists),
and each exit's head is whole — every row of the vocabulary — a block of
positions at a time, rematerialised, so that four exits' (T, V) logits and
log-probabilities never stand side by side (4 x 0.8 GB each at 4096 x 49152).
Each layer application is rematerialised in the backward pass.

``spec`` is the configuration's mapping: the published config keys plus
``layers`` (depth kept) and ``vocab_rows`` (rows of the vocabulary held:
all of them).

Norm: rms(x, w) = x rsqrt(mean x^2 + eps) w, everywhere.
Layer, x (T, hidden), four norms (a norm before and after each sub-block):
  a = attention(rms(x, attn_norm));   x += rms(a, attn_out_norm)
  m = Wdown(silu(Wgate h) * Wup h), h = rms(x, mlp_norm)
                                      x += rms(m, mlp_out_norm)
Attention: q, k, v = h Wq, h Wk, h Wv as H heads of Dh (as many key/value
  heads as query heads); no bias, no q/k norm; rotary on all Dh dims
  (half-rotation form: dims i and i + Dh/2 are a pair), angle = position
  theta^(-2i/Dh), no scaling; query t sees key s iff s <= t;
  softmax(q kT / sqrt(Dh)) v; out = attn Wo.
The loop: h0 = E[tokens]; for t = 1..R: ht = rms(layer_L(.. layer_1(ht-1)),
  final_norm): the same layers, the same final norm, the same positions in
  every pass; the normed state is the pass's exit and the next pass's input.
Exits: logits_t = ht Whead (the one untied head, R times); CE_t the
  next-token cross-entropy of logits_t; gate lambda_t = sigmoid(ht wg + bg)
  (float32 at ``highest`` whatever the precision of the rest: the
  configuration states it so); p_t = lambda_t prod_{j<t}(1 - lambda_j) for
  t < R, p_R = prod_{j<R}(1 - lambda_j): the last pass takes what is left.
Objective per position: sum_t p_t CE_t - BETA H(p), H(p) = -sum_t p_t log
  p_t; the loss is its mean over the T - 1 positions that have a target.

Departures from the published description, all because the catalog row's
config has no key for them (the family's released modelling code and paper,
from memory): the four-norm ("sandwich") layer; the final norm inside the
loop; the gate as a Linear(hidden, 1) with bias on the normed state; BETA
0.1 (the paper's stage-I objective; its later-stage gate objective is left
out). ``early_exit_threshold`` is inference's and is not read."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.nets.common import operands

Q_BLOCK = 512  # queries a block of attention
HEAD_BLOCK = 512  # positions a block of an exit's head
BETA = 0.1  # the weight of the exit distribution's entropy


def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * w).astype(x.dtype)


def rope(x, positions, theta):
    """x (T, H, dim): dims i and i + dim/2 rotate by positions *
    theta^(-2i/dim)."""
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


def attention(h, p, spec, q):
    t = h.shape[0]
    heads, dh = spec["num_attention_heads"], spec["head_dim"]
    pos = jnp.arange(t)
    qs, k, v = ((q(h) @ q(p[name]["kernel"].astype(h.dtype))).reshape(
        t, heads, dh) for name in ("q", "k", "v"))
    qs = rope(qs, pos, spec["rope_theta"])
    k = rope(k, pos, spec["rope_theta"])
    block = min(Q_BLOCK, t)
    pad = -t % block
    qs = jnp.pad(qs, ((0, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def rows(lo):
        """One block of queries against every key, under the causal mask."""
        qb = lax.dynamic_slice_in_dim(qs, lo, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", q(qb), q(k)) * dh ** -0.5
        seen = pos[None, :] <= (lo + jnp.arange(block))[:, None]
        s = jnp.where(seen, s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("hqk,khd->qhd", q(pr), q(v))

    o = lax.map(rows, jnp.arange(0, t + pad, block))
    o = o.reshape(t + pad, heads * dh)[:t]
    return q(o) @ q(p["o"]["kernel"].astype(h.dtype))


def mlp(h, p, q):
    def dot(x, name):
        return q(x) @ q(p[name]["kernel"].astype(x.dtype))

    return dot(jax.nn.silu(dot(h, "gate")) * dot(h, "up"), "down")


def layer(x, p, spec, q):
    eps = spec["rms_norm_eps"]
    a = attention(rms(x, p["attn_norm"]["scale"], eps), p, spec, q)
    x = x + rms(a, p["attn_out_norm"]["scale"], eps)
    m = mlp(rms(x, p["mlp_norm"]["scale"], eps), p["mlp"], q)
    return x + rms(m, p["mlp_out_norm"]["scale"], eps)


def states(params, tokens, spec, dtype="float32"):
    """tokens (T,) -> the list of every pass's normed state (T, hidden)."""
    cast, q = operands(dtype)
    x = cast(params["embed"]["embedding"][tokens])
    out = []
    for _ in range(spec["total_ut_steps"]):
        for i in range(spec["layers"]):
            x = jax.checkpoint(lambda x, p: layer(x, p, spec, q))(
                x, params[f"layer{i}"])
        x = rms(x, params["final_norm"]["scale"], spec["rms_norm_eps"])
        out.append(x)
    return out


def logits(params, tokens, spec, dtype="float32"):
    """tokens (T,) int32 -> the LAST exit's (T, vocab_rows) float32."""
    _, q = operands(dtype)
    h = states(params, tokens, spec, dtype)[-1]
    return (q(h) @ q(params["head"]["kernel"].astype(h.dtype))).astype(
        jnp.float32)


def cross_entropy(h, kernel, targets, q):
    """h (N, hidden), targets (N,) -> (N,): a whole head's next-token
    cross-entropy, HEAD_BLOCK positions at a time."""
    n = h.shape[0]
    block = min(HEAD_BLOCK, n)
    pad = -n % block
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[1])
    tb = jnp.pad(targets, (0, pad)).reshape(-1, block)

    @jax.checkpoint
    def rows(ht):
        logp = jax.nn.log_softmax(
            (q(ht[0]) @ q(kernel.astype(h.dtype))).astype(jnp.float32))
        return -jnp.take_along_axis(logp, ht[1][:, None], axis=-1)[:, 0]

    return lax.map(rows, (hb, tb)).reshape(-1)[:n]


def exits(params, tokens, spec, dtype="float32"):
    """One sequence ``tokens`` (T,) -> (CE (R, T - 1), p (R, T - 1)): every
    exit's cross-entropy and the exit distribution at the positions that
    have a target."""
    _, q = operands(dtype)
    hs = [h[:-1] for h in states(params, tokens, spec, dtype)]
    ce = [cross_entropy(h, params["head"]["kernel"], tokens[1:], q)
          for h in hs]
    gate = params["loop_exit"]
    lam = [jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), gate["kernel"],
        precision=lax.Precision.HIGHEST) + gate["bias"][0]) for h in hs]
    p, left = [], jnp.ones_like(lam[0])
    for t in range(len(hs) - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(left)  # the last pass takes what is left
    return jnp.stack(ce), jnp.stack(p)


def objective(ce, p):
    """(R, N) each -> (N,): sum_t p_t CE_t - BETA H(p)."""
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    return jnp.sum(p * ce, axis=0) + BETA * jnp.sum(plogp, axis=0)


def loss(params, tokens, spec, dtype="float32"):
    """Mean over sequences ``tokens`` (B, T) and their T - 1 target
    positions of the objective."""
    return jnp.mean(jnp.stack([
        objective(*exits(params, seq, spec, dtype)) for seq in tokens]))
