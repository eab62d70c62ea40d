"""The delta rule whose decay is per key channel, chunk-wise: the recurrence
of a Kimi Delta Attention layer (arXiv:2510.26692) without a state per token.
``ops/delta_rule.chunked_gated_delta_rule`` hands its calls with g of shape
(B, T, H, Dk) here.

Per head, with state S (Dk, Dv), log-decay g_t <= 0 a KEY CHANNEL (Dk of
them) and write strength β_t in (0, 1):

    S <- diag(e^{g_t}) S;  S <- S + β_t k_t ⊗ (v_t − Sᵀ k_t);  o_t = Sᵀ q_t

In chunks of C tokens (ops/delta_rule.py's algebra, its header) the writes
of a chunk are solved for at once, and one pass over the chunks carries S.
With G the running sum of g inside the chunk — now a vector a token — the
decay no longer stands beside the products k_c·k_e and q_c·k_e as one number
a token pair but INSIDE them:

    A[c, e] = Σ_i k_ci k_ei e^{G_ci − G_ei}   (c > e),    L = diag(β) A
    W[c, e] = Σ_i q_ci k_ei e^{G_ci − G_ei}   (c >= e)
    T = (I + L)⁻¹;   u = T (β v);   w = T (β k ⊙ e^{G})
    F = u − w S;   o = (q ⊙ e^{G}) S + W F
    S <- diag(e^{G_C}) S + (k ⊙ e^{G_C − G})ᵀ F

and the state's decay between chunks is a row scale of S. e^{G_c − G_e} does
not factor into (a row's) x (a column's) without the exponential of a
positive sum — e^{−G_e} overflows float32 once a chunk's summed g passes
−88 — so a chunk is cut into ``SUB``-token sub-blocks (the family's kernels
do the same):

* a block of rows I against every EARLIER column: both sides relative to
  the block's first row r, (k_c ⊙ e^{G_c − G_r}) · (k_e ⊙ e^{G_r − G_e}),
  c >= r > e, both exponents <= 0 — one product a block of rows;
* a block against itself, pairwise: e^{G_c − G_e} for c >= e formed as it
  stands, a (SUB, SUB, Dk) array a block that lives only inside
  ``_within_chunks`` (rematerialised in the backward pass, never a
  residual).

No exponential of a positive number is ever formed: where the decay is
strong the factors underflow to 0 — as the true value does, to float32 —
and nothing overflows. Every other exponent (G, G_C − G, G_C) is <= 0 as in
the scalar form. The solve is ``delta_rule._unit_lower_inverse`` (float32
at ``highest``, its result named ``SOLVE_NAME``: a layer's checkpoint that
saves the name keeps T). The backward pass is autodiff's of this form: one
reverse pass over the chunks, a state per CHUNK and never per token.

``jax.numpy`` on every backend: the rule has no Pallas kernels yet
(PERF.md section 5 says what the trace shows that costs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from draco_tpu.ops.delta_rule import CHUNK, _unit_lower_inverse

SUB = 16  # tokens of a sub-block; CHUNK is a multiple of it


def _within_chunks(q, k, run, sub: int):
    """q, k, run (..., C, Dk) -> (A, W) (..., C, C) of the module docstring,
    A zero on and above the diagonal, W zero above it."""
    c, dk = k.shape[-2:]
    blocks = c // sub
    lead = k.shape[:-2]

    def cut(x):
        return x.reshape(lead + (blocks, sub, dk))

    qb, kb, gb = cut(q), cut(k), cut(run)
    # a block against itself: every pair of its tokens, every channel
    seen = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        seen[..., None], gb[..., :, None, :] - gb[..., None, :, :],
        -jnp.inf))
    pair = kb[..., None, :, :] * decay
    own_a = jnp.sum(kb[..., :, None, :] * pair, axis=-1)
    own_w = jnp.sum(qb[..., :, None, :] * pair, axis=-1)
    own_a = jnp.where(jnp.tril(jnp.ones((sub, sub), bool), -1), own_a, 0.0)
    rows_a, rows_w = [], []
    for i in range(blocks):
        parts_a, parts_w = [own_a[..., i, :, :]], [own_w[..., i, :, :]]
        if i:
            first = gb[..., i, :1, :]  # G at the block's first row
            earlier = k[..., :i * sub, :] * jnp.exp(
                first - run[..., :i * sub, :])
            down = jnp.exp(gb[..., i, :, :] - first)
            parts_a.insert(0, jnp.einsum(
                "...cd,...ed->...ce", kb[..., i, :, :] * down, earlier))
            parts_w.insert(0, jnp.einsum(
                "...cd,...ed->...ce", qb[..., i, :, :] * down, earlier))
        if i < blocks - 1:
            later = jnp.zeros(lead + (sub, c - (i + 1) * sub), k.dtype)
            parts_a.append(later)
            parts_w.append(later)
        rows_a.append(jnp.concatenate(parts_a, axis=-1))
        rows_w.append(jnp.concatenate(parts_w, axis=-1))
    return (jnp.concatenate(rows_a, axis=-2),
            jnp.concatenate(rows_w, axis=-2))


def _pass_scan(u, w, a, q, k, keep):
    """``delta_rule._pass_scan`` with the state's decay a row scale: u (G,
    N, C, Dv), w, q, k (G, N, C, Dk), a (G, N, C, C), keep (G, N, Dk) ->
    (o (G, N, C, Dv), the state after the last chunk (G, Dk, Dv) float32)."""
    def step(state, xs):
        u_n, w_n, a_n, q_n, k_n, keep_n = xs
        s = state.astype(u_n.dtype)
        fresh = u_n - jnp.einsum("gcd,gdv->gcv", w_n, s)
        o_n = (jnp.einsum("gcd,gdv->gcv", q_n, s)
               + jnp.einsum("gce,gev->gcv", a_n, fresh))
        state = (state * keep_n[:, :, None]
                 + jnp.einsum("gcd,gcv->gdv", k_n, fresh,
                              preferred_element_type=jnp.float32))
        return state, o_n

    g, _, _, dv = u.shape
    state, o = lax.scan(
        step, jnp.zeros((g, w.shape[-1], dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, w, a, q, k, keep)))
    return jnp.moveaxis(o, 0, 1), state


def chunked_kda_rule(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB):
    """q, k (B, T, H, Dk), already normalised and scaled as the layer wants
    them; v (B, T, H, Dv); g (B, T, H, Dk) <= 0; beta (B, T, H). Returns (o
    (B, T, H, Dv), the state after the last token (B, H, Dk, Dv) float32).
    Any T: a last chunk is closed with tokens that neither decay nor write
    (g = 0, β = 0, k = 0)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    pad = n * chunk - t
    sub = sub if chunk % sub == 0 else chunk

    def chunks(x):
        """(B, T, H, ...) -> (B·H, N, C, ...), a last chunk closed."""
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)
        return x.reshape((b * h,) + x.shape[2:])

    q, k, v, beta = chunks(q), chunks(k), chunks(v), chunks(beta)
    run = jnp.cumsum(chunks(g.astype(jnp.float32)), axis=2)  # G
    a, within = jax.checkpoint(_within_chunks, static_argnums=3)(
        q.astype(jnp.float32), k.astype(jnp.float32), run, sub)
    solve = _unit_lower_inverse(
        beta.astype(jnp.float32)[..., None] * a).astype(v.dtype)
    grown = jnp.exp(run)  # e^{G}
    u = jnp.einsum("gnce,gnev->gncv", solve, beta[..., None] * v)
    w = jnp.einsum("gnce,gned->gncd", solve,
                   beta[..., None] * k * grown.astype(k.dtype))
    last = run[:, :, -1]  # (B·H, N, Dk)
    k_out = k * jnp.exp(last[:, :, None] - run).astype(k.dtype)
    o, state = _pass_scan(u, w, within.astype(v.dtype),
                          q * grown.astype(q.dtype), k_out, jnp.exp(last))
    o = jnp.moveaxis(o.reshape(b, h, n * chunk, dv), 1, 2)[:, :t]
    return o, state.reshape(b, h, dk, dv)


def chunk_decay_min(g, chunk: int = CHUNK):
    """min over heads, channels and chunks of a chunk's summed g: how far
    below zero the exponents of the chunked form reach (the number to read
    when a chunked form loses terms). g (B, T, H, Dk)."""
    b, t = g.shape[:2]
    pad = -t % chunk
    g = jnp.pad(g.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    return jnp.min(jnp.sum(g.reshape((b, -1, chunk) + g.shape[2:]), axis=2))
