"""The gated delta rule, chunk-wise: the recurrence of a Gated DeltaNet
linear-attention layer (arXiv:2412.06464) without a state per token.

Per value head, with state S (Dk, Dv), log-decay g_t <= 0 and write
strength β_t in (0, 1):

    S <- e^{g_t} S;  S <- S + k_t ⊗ β_t (v_t − Sᵀ k_t);  o_t = Sᵀ q_t

Token by token that is T sequential steps of rank-one work. In chunks of C
tokens (64, the family's) the writes of a chunk are solved for at once: with
G the running sum of g inside the chunk, D[c, e] = e^{G_c − G_e} for c >= e
(never the exponential of a positive number: at strongly negative g the
factors underflow to 0, they cannot overflow) and L = strictly-lower(β_c
k_c·k_e D[c, e]),

    T = (I + L)⁻¹;   u = T (β v);   w = T (β e^{G} k)

and then ONE pass over the N = T / C chunks carries S (``_pass_scan``):

    F = u − w S;   o = (q e^{G}) S + lower(q kᵀ ∘ D) F
    S <- e^{G_C} S + (k e^{G_C − G})ᵀ F

so the sequential part is N steps of (C, Dk) x (Dk, Dv) products, and
everything that does not read S is computed for all chunks side by side.
L is nilpotent (L^C = 0), so T = Π_j (I + (−L)^{2^j}), log2(C) squarings —
products the matrix unit runs, where a row-by-row substitution is C
sequential steps. They run in float32 at ``highest`` (the family's kernels
solve this system in float32 too): T multiplies every write of the chunk,
and a product that rounds its operands to bfloat16 eleven times over would
put its error on all of them.

The backward pass is autodiff's of this chunked forward — the same
algebra transposed, one reverse pass over the chunks that keeps a state per
CHUNK (N x (Dk, Dv) a head), never one per token — but for T, whose
cotangent is stated: dL = −Tᵀ dT Tᵀ, two products where the transpose of
the squarings is twenty-two. On the chip the time is not the pass over the
chunks (its N steps are a tenth of the rule's time) but every operation
that reads or writes a (chunks, heads, C, C) array in main memory — masks,
L, the squarings (PERF.md section 5): T is therefore also named
(``SOLVE_NAME``) so that a layer rematerialised in the backward pass can
keep it instead of solving again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

CHUNK = 64  # the family's chunk length
# the name T goes by under ``jax.checkpoint``: a policy that saves it
# (``save_only_these_names``) spares the rematerialised forward the solve
SOLVE_NAME = "delta_rule_solve"


def _mm(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _solve_by_squaring(low):
    """(I + low)⁻¹ for strictly lower-triangular ``low`` (..., C, C)."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)
    power = -low
    inv = eye + power
    for _ in range(max(c - 1, 1).bit_length() - 1):
        power = _mm(power, power)
        inv = _mm(inv, eye + power)
    return inv


def _solve_bwd(inv, d_inv):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-_mm(_mm(inv_t, d_inv), inv_t),)


def _solve_fwd(low):
    # named where it becomes the backward pass's residual: a checkpoint
    # that saves the name keeps T and drops the squarings from its
    # rematerialised forward
    inv = checkpoint_name(_solve_by_squaring(low), SOLVE_NAME)
    return inv, inv


_unit_lower_inverse = jax.custom_vjp(_solve_by_squaring)
_unit_lower_inverse.defvjp(_solve_fwd, _solve_bwd)


# ---- the pass over the chunks ---------------------------------------------
# Operands, heads first: u (G, N, C, Dv), w, q, k (G, N, C, Dk), a (G, N,
# C, C), keep (G, N); G = batch x value heads. Returns (o (G, N, C, Dv), the
# state after the last chunk (G, Dk, Dv) float32).

def _pass_scan(u, w, a, q, k, keep):
    def step(state, xs):
        u_n, w_n, a_n, q_n, k_n, keep_n = xs
        s = state.astype(u_n.dtype)
        fresh = u_n - jnp.einsum("gcd,gdv->gcv", w_n, s)
        o_n = (jnp.einsum("gcd,gdv->gcv", q_n, s)
               + jnp.einsum("gce,gev->gcv", a_n, fresh))
        state = (state * keep_n[:, None, None]
                 + jnp.einsum("gcd,gcv->gdv", k_n, fresh,
                              preferred_element_type=jnp.float32))
        return state, o_n

    g, _, _, dv = u.shape
    state, o = lax.scan(
        step, jnp.zeros((g, w.shape[-1], dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, w, a, q, k, keep)))
    return jnp.moveaxis(o, 0, 1), state


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (B, T, Hk, Dk), already normalised and scaled as the layer
    wants them; v (B, T, Hv, Dv) with Hv a multiple of Hk (key head h
    serves value heads h·r .. h·r + r − 1); g, beta (B, T, Hv), g <= 0.
    Returns (o (B, T, Hv, Dv), the state after the last token (B, Hv, Dk,
    Dv) float32). Any T: a last chunk is closed with tokens that neither
    decay nor write (g = 0, β = 0, k = 0)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    n = -(-t // chunk)
    pad = n * chunk - t

    def chunks(x):
        """(B, T, Hk, ...) -> (B, Hk, N, C, ...), a last chunk closed."""
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)

    def per_value_head(x):
        """(B, T, Hv, ...) -> (B, Hk, R, N, C, ...)."""
        x = chunks(x.reshape((b, t, hk, r) + x.shape[3:]))
        return jnp.moveaxis(x, 4, 2)

    q, k = chunks(q), chunks(k)  # (B, Hk, N, C, Dk)
    v, beta = per_value_head(v), per_value_head(beta)
    run = jnp.cumsum(per_value_head(g.astype(jnp.float32)), axis=-1)  # G
    # D[c, e]: (B, Hk, R, N, C, C), zero above the diagonal
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, run[..., :, None] - run[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bhncd,bhned->bhnce", k, k)[:, :, None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    low = jnp.where(
        strict, beta.astype(jnp.float32)[..., :, None] * kk * decay, 0.0)
    solve = _unit_lower_inverse(low).astype(v.dtype)
    k_r = k[:, :, None]  # a key head's rows, for each value head it serves
    u = jnp.einsum("bhrnce,bhrnev->bhrncv", solve, beta[..., None] * v)
    w = jnp.einsum("bhrnce,bhrned->bhrncd", solve,
                   (beta * jnp.exp(run).astype(beta.dtype))[..., None] * k_r)
    # what a query reads of its own chunk, and of the state it starts from
    qk = jnp.einsum("bhncd,bhned->bhnce", q, k)[:, :, None]
    within = (qk * decay).astype(v.dtype)
    q_in = q[:, :, None] * jnp.exp(run).astype(q.dtype)[..., None]
    last = run[..., -1]  # (B, Hk, R, N)
    k_out = k_r * jnp.exp(last[..., None] - run).astype(k.dtype)[..., None]

    def heads(x):
        return x.reshape((b * hv,) + x.shape[3:])

    o, state = _pass_scan(heads(u), heads(w), heads(within), heads(q_in),
                          heads(k_out), heads(jnp.exp(last)))
    o = jnp.moveaxis(o.reshape(b, hv, n * chunk, dv), 1, 2)[:, :t]
    return o, state.reshape(b, hv, dk, dv)
