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

**On the chip the rule is three Pallas kernels** (the last section of this
file; ``rule_runs_in_kernels`` says when: a TPU, chunks of 64, T whole
chunks, Dk and Dv whole lane tiles, Hv a multiple of Hk — anything else is
the ``jax.numpy`` path above, which every CPU test runs and the kernels are
held to). The same algebra, the same roundings at the same places (products
on bfloat16 operands with float32 sums, as the chip's default precision
gives the einsums; S, the exponentials and the solve in float32, the solve's
products at ``highest``); what changes is where the arrays live. Every array
that exists only inside a chunk — D, k·kᵀ, L, the squarings, q·kᵀ ∘ D, u, w,
F, and in the backward pass their cotangents — lives and dies in VMEM, and
the state S (and its cotangent) is carried in VMEM scratch along the chunk
axis of the grid. Main memory sees q, k, v, o and the five gradients; G (the
running sum of g inside each chunk, one ``cumsum`` of a (B, T, Hv) array
before the kernels, whose transpose autodiff supplies) and β, a head
group's columns on a lane tile of their own; and what crosses a kernel's
edge:

* ``solve_kernel`` (chunk-parallel) writes T, 34 MB a layer at the cell's
  shapes — named ``SOLVE_NAME`` like the ``jax.numpy`` path's, so a
  checkpoint that saves the name keeps it;
* ``pass_kernel`` reads T and writes o, the last state, and the state each
  chunk STARTS from (N x (Dk, Dv) a head: the backward's residual, the one
  a ``lax.scan`` would keep too);
* ``backward_kernel`` walks the chunks last to first: it builds a chunk's
  forward again from q, k, v, G, β, T and the chunk's starting state, and
  transposes it in place — dL = −Tᵀ dT Tᵀ included — with dS in VMEM.

The backward is a ``jax.custom_vjp``: it saves q, k, v, G, β, T and the
chunk-start states and reruns nothing itself. Under a layer's
``jax.checkpoint(policy=KEEP_SOLVE)`` the rematerialised forward is then the
pass alone (T is saved, the solve is dead code), which also hands the
backward its chunk-start states afresh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from draco_tpu.ops.coded import use_pallas

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


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK, *,
                             force=None, interpret: bool = False):
    """q, k (B, T, Hk, Dk) normalised and scaled as the layer wants them; v
    (B, T, Hv, Dv), key head h serving value heads h·r .. h·r + r − 1; g, beta
    (B, T, Hv), g <= 0 (g per key channel: the file's end) -> (o (B, T, Hv,
    Dv), the last state (B, Hv, Dk, Dv) float32). Any T: a last chunk is
    closed with tokens that neither decay nor write. ``force`` / ``interpret``:
    the tests' way to the kernels (``rule_runs_in_kernels``: which path)."""
    if g.ndim == 4:
        return _per_channel(q, k, v, g, beta, chunk, force, interpret)
    if rule_runs_in_kernels(q.shape, v.shape, chunk, force=force,
                            interpret=interpret):
        n = q.shape[1] // chunk
        run = jnp.cumsum(g.astype(jnp.float32).reshape(
            g.shape[0], n, chunk, g.shape[2]), axis=2).reshape(g.shape)
        return _rule(q, k, v, run, beta, interpret)
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


# ---- the kernels ------------------------------------------------------------
# One grid step holds ``hkb`` key heads (the ``hvb = hkb·r`` value heads they
# serve) over ``cb`` chunks, every head-chunk written out in line so that the
# products of different heads overlap; the chunk axis is the last, sequential
# grid axis and the states stay in VMEM scratch along it.
#
# Operands as main memory holds them: q, k (B, T, Hk, Dk), v, o (B, T, Hv, Dv)
# — the caller's arrays as they are (a reshape to (B, T, H·D) is a copy on
# the chip, whose tiles lie over the last two dimensions): a block holds
# whole sublane tiles of heads and a head's rows are read from it with a
# stride; T (the solve) (B, Hk,
# T, r·C), the r value heads of a key head side by side on the lanes (a 64-wide
# minor dimension would be padded to 128 in main memory, and side by side two
# heads' solves are one product: ``_solve_kernel``); the chunk-start states
# (B, N, Hv·Dk, Dv); and the per-token vectors G and β as ``cols`` (B, T,
# groups·128), a head group's 128 lanes [0, hvb) G and [hvb, 2·hvb) β: a
# head's (C, 1) column is what scales rows, and G along the lanes — what the
# decay mask subtracts — is that column laid down the diagonal and summed.

_KEY_HEADS_A_STEP = 8
_LANE = 128


def _dot(a, b, dims, pd):
    """A product as the jnp path's einsums run on the chip: operands in the
    product dtype ``pd`` (bfloat16 compiled, float32 interpreted), float32
    accumulation."""
    return lax.dot_general(a.astype(pd), b.astype(pd), (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot32(a, b, dims):
    """A float32 product at ``highest``."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _iotas(c):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _vectors(cols_ref, c, hv, hvb):
    """A head-chunk's G as a column and as a row, β as a column, and D."""
    rows = slice(c * CHUNK, (c + 1) * CHUNK)
    g_col = cols_ref[rows, hv:hv + 1]
    b_col = cols_ref[rows, hvb + hv:hvb + hv + 1]
    ci, ei = _iotas(CHUNK)
    # the column laid along the lanes: one term a sum, so exact
    g_row = jnp.sum(jnp.where(ci == ei, g_col, 0.0), axis=0, keepdims=True)
    decay = jnp.exp(jnp.where(ci >= ei, g_col - g_row, -jnp.inf))
    return g_col, b_col, g_row, decay


def _split(x):
    """A float32 value as three bfloat16-exact pieces that sum to it (8 + 8
    + 8 bits of mantissa), still float32: what a product at ``highest``
    makes of an operand."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, (rest - mid).astype(jnp.bfloat16).astype(jnp.float32)


def _dot6(a, b):
    """a·b at the accuracy of ``highest``, from the operands' pieces: the
    six products of pieces ``highest`` keeps, the small ones summed
    first."""
    (ah, am, al), (bh, bm, bl) = (
        tuple(x.astype(jnp.bfloat16) for x in pieces) for pieces in (a, b))

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return ((d(ah, bl) + d(al, bh) + d(am, bm)) + (d(ah, bm) + d(am, bh))
            + d(ah, bh))


def _solve_kernel(r, hkb, cb, pd, k_ref, cols_ref, t_ref):
    """T for the r value heads of a key head side by side on the lanes, (C,
    r·C): a product of two such matrices, head by head, is ONE product with
    the right operand laid out block-diagonally (r·C, r·C) — at r = 2 the
    whole width of the matrix unit, where a head alone fills a quarter."""
    width = r * CHUNK
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, width), 0)
    lane = lax.broadcasted_iota(jnp.int32, (CHUNK, width), 1)
    col = jnp.bitwise_and(lane, CHUNK - 1)  # the lane's column in its head
    of_head = [(lane >= j * CHUNK) & (lane < (j + 1) * CHUNK)
               for j in range(r)]
    eye = (row == col).astype(jnp.float32)

    def blocks(pieces):
        return tuple(jnp.concatenate(
            [jnp.where(mask, x, 0.0) for mask in of_head], axis=0)
            for x in pieces)

    for c in range(cb):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        for i in range(hkb):
            kc = k_ref[rows, i, :].astype(jnp.float32)
            kk = _dot(kc, jnp.concatenate([kc] * r, axis=0), _NT, pd)
            g_col = b_col = jnp.zeros((CHUNK, width), jnp.float32)
            for j in range(r):
                hv = i * r + j
                g_col = jnp.where(of_head[j], cols_ref[rows, hv:hv + 1],
                                  g_col)
                b_col = jnp.where(
                    of_head[j],
                    cols_ref[rows, hkb * r + hv:hkb * r + hv + 1], b_col)
            g_row = jnp.sum(jnp.where(row == col, g_col, 0.0), axis=0,
                            keepdims=True)
            decay = jnp.exp(jnp.where(row >= col, g_col - g_row, -jnp.inf))
            # _solve_by_squaring: inv (I + P) = inv + inv P
            power = -jnp.where(row > col, b_col * kk * decay, 0.0)
            inv = eye + power
            pieces = _split(power)
            for _ in range(max(CHUNK - 1, 1).bit_length() - 1):
                power = _dot6(pieces, blocks(pieces))
                pieces = _split(power)
                inv = inv + _dot6(_split(inv), blocks(pieces))
            t_ref[i, rows, :] = inv


def _chunk_forward(qc, kc, qk, vc, solve, state, vectors, pd, outputs=True):
    """A head-chunk's forward from the state it starts from: everything the
    backward pass wants again and, with ``outputs``, the output and the
    state it leaves."""
    g_col, b_col, g_row, decay = vectors
    e_col = jnp.exp(g_col)
    be_col = b_col * e_col
    vb, kb = b_col * vc, be_col * kc
    u, w = _dot(solve, vb, _NN, pd), _dot(solve, kb, _NN, pd)
    fresh = u - _dot(w, state, _NN, pd)
    within = qk * decay
    q_in = qc * e_col
    last = g_row[:, CHUNK - 1:CHUNK]  # (1, 1)
    keep = jnp.exp(last)
    # e^{G_C} on every lane of a row, by a sum and not from the (1, 1): the
    # compiler has no broadcast along both axes at once
    at_last = lax.broadcasted_iota(
        jnp.int32, (CHUNK, state.shape[1]), 0) == CHUNK - 1
    keep_row = jnp.exp(jnp.sum(jnp.where(at_last, g_col, 0.0), axis=0,
                               keepdims=True))
    to_last = jnp.exp(last - g_col)  # e^{G_C − G}
    k_out = kc * to_last
    f = dict(e_col=e_col, be_col=be_col, vb=vb, kb=kb, w=w, fresh=fresh,
             within=within, q_in=q_in, keep=keep, keep_row=keep_row,
             to_last=to_last, k_out=k_out)
    if outputs:
        f["o"] = _dot(q_in, state, _NN, pd) + _dot(within, fresh, _NN, pd)
        f["new"] = state * keep_row + _dot(k_out, fresh, _TN, pd)
    return f


def _pass_kernel(r, hkb, cb, dk, dv, pd, q_ref, k_ref, v_ref, cols_ref,
                 t_ref, o_ref, starts_ref, last_ref, state_ref):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    for c in range(cb):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        for i in range(hkb):
            qc = q_ref[rows, i, :].astype(jnp.float32)
            kc = k_ref[rows, i, :].astype(jnp.float32)
            qk = _dot(qc, kc, _NT, pd)
            for j in range(r):
                hv = i * r + j
                state = state_ref[hv]
                starts_ref[c, hv * dk:(hv + 1) * dk, :] = state
                f = _chunk_forward(
                    qc, kc, qk,
                    v_ref[rows, hv, :].astype(jnp.float32),
                    t_ref[i, rows, j * CHUNK:(j + 1) * CHUNK], state,
                    _vectors(cols_ref, c, hv, hkb * r), pd)
                o_ref[rows, hv, :] = f["o"].astype(o_ref.dtype)
                state_ref[hv] = f["new"]

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        for hv in range(hkb * r):
            last_ref[hv * dk:(hv + 1) * dk, :] = state_ref[hv]


def _backward_kernel(r, hkb, cb, dk, dv, pd, q_ref, k_ref, v_ref, cols_ref,
                     t_ref, starts_ref, do_ref, dlast_ref, dq_ref, dk_ref,
                     dv_ref, dcols_ref, dstate_ref):
    """The chunks last to first, the state's cotangent carried in VMEM."""
    hvb = hkb * r
    ci, ei = _iotas(CHUNK)

    @pl.when(pl.program_id(2) == 0)
    def _():
        for hv in range(hvb):
            dstate_ref[hv] = dlast_ref[hv * dk:(hv + 1) * dk, :].astype(
                jnp.float32)

    dcols_ref[...] = jnp.zeros(dcols_ref.shape, jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1)
    for c in reversed(range(cb)):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        for i in range(hkb):
            qc = q_ref[rows, i, :].astype(jnp.float32)
            kc = k_ref[rows, i, :].astype(jnp.float32)
            qk = _dot(qc, kc, _NT, pd)
            kk = _dot(kc, kc, _NT, pd)
            dq = jnp.zeros((CHUNK, dk), jnp.float32)
            dkey = jnp.zeros((CHUNK, dk), jnp.float32)
            for j in range(r):
                hv = i * r + j
                vectors = _vectors(cols_ref, c, hv, hvb)
                _, b_col, _, decay = vectors
                vc = v_ref[rows, hv, :].astype(jnp.float32)
                solve = t_ref[i, rows, j * CHUNK:(j + 1) * CHUNK]
                state = starts_ref[c, hv * dk:(hv + 1) * dk, :]
                f = _chunk_forward(qc, kc, qk, vc, solve, state, vectors, pd,
                                   outputs=False)
                do = do_ref[rows, hv, :].astype(jnp.float32)
                dnew = dstate_ref[hv]
                # o = q_in S + within F;  S' = keep S + k_outᵀ F
                dfresh = (_dot(f["within"], do, _TN, pd)
                          + _dot(f["k_out"], dnew, _NN, pd))
                dwithin = _dot(do, f["fresh"], _NT, pd)
                dq_in = _dot(do, state, _NT, pd)
                dk_out = _dot(f["fresh"], dnew, _NT, pd)
                # F = u − w S
                dw = -_dot(dfresh, state, _NT, pd)
                dstate_ref[hv] = (_dot(f["q_in"], do, _TN, pd)
                                  + f["keep_row"] * dnew
                                  - _dot(f["w"], dfresh, _TN, pd))
                dkeep = jnp.sum(jnp.sum(state * dnew, axis=0, keepdims=True),
                                axis=1, keepdims=True)  # (1, 1)
                # u = T vb;  w = T kb;  T = (I + L)⁻¹: dL = −Tᵀ dT Tᵀ
                dsolve = (_dot(dfresh, f["vb"], _NT, pd)
                          + _dot(dw, f["kb"], _NT, pd))
                dvb = _dot(solve, dfresh, _TN, pd)
                dkb = _dot(solve, dw, _TN, pd)
                dlow = jnp.where(ci > ei, -_dot32(
                    _dot32(solve, dsolve, _TN), solve, _NT), 0.0)
                # L = strict(β kkᵀ ∘ D);  within = lower(q kᵀ ∘ D)
                dlow_decay = dlow * decay
                dkk = b_col * dlow_decay
                dqk = dwithin * decay
                # what reaches D, times D: the decay's share of G
                through = dkk * kk + dqk * qk
                kb_rows = jnp.sum(dkb * kc, axis=1, keepdims=True)
                pushed = jnp.sum(dk_out * f["k_out"], axis=1, keepdims=True)
                dg_col = (jnp.sum(through, axis=1, keepdims=True)
                          + jnp.sum(dq_in * f["q_in"], axis=1, keepdims=True)
                          + f["be_col"] * kb_rows - pushed)
                # G_C: keep = e^{G_C}, k_out = k e^{G_C − G}
                dg_last = (f["keep"] * dkeep
                           + jnp.sum(pushed, axis=0, keepdims=True))
                dg_row = (jnp.where(lane == CHUNK - 1, dg_last, 0.0)
                          - jnp.sum(through, axis=0, keepdims=True))
                dbeta = (jnp.sum(dlow_decay * kk, axis=1, keepdims=True)
                         + jnp.sum(dvb * vc, axis=1, keepdims=True)
                         + f["e_col"] * kb_rows)
                # the row's share laid down the sublanes (one term a sum)
                dcols_ref[rows, hv:hv + 1] = dg_col + jnp.sum(
                    jnp.where(ci == ei, dg_row, 0.0), axis=1, keepdims=True)
                dcols_ref[rows, hvb + hv:hvb + hv + 1] = dbeta
                dv_ref[rows, hv, :] = (b_col * dvb).astype(dv_ref.dtype)
                dq = dq + dq_in * f["e_col"] + _dot(dqk, kc, _NN, pd)
                dkey = (dkey + dk_out * f["to_last"] + f["be_col"] * dkb
                        + _dot(dqk, qc, _TN, pd) + _dot(dkk, kc, _NN, pd)
                        + _dot(dkk, kc, _TN, pd))
            dq_ref[rows, i, :] = dq.astype(dq_ref.dtype)
            dk_ref[rows, i, :] = dkey.astype(dk_ref.dtype)


def _blocking(hk: int, n: int):
    """(key heads, chunks) a grid step. The heads are the second-minor
    dimension of q's, k's and v's blocks: a whole sublane tile of them, or
    all."""
    return (_KEY_HEADS_A_STEP if hk % _KEY_HEADS_A_STEP == 0 else hk,
            2 if n % 2 == 0 else 1)


def _group_vectors(run, beta, hvb):
    """G (B, T, Hv) float32 and β -> ``cols`` (the section's head): a
    shuffle of each token's own row, no transpose."""
    b, t, hv = run.shape
    cols = jnp.concatenate([x.astype(jnp.float32).reshape(b, t, -1, hvb)
                            for x in (run, beta)], axis=-1)
    return jnp.pad(cols, ((0, 0),) * 3 + ((0, _LANE - 2 * hvb),)).reshape(
        b, t, -1)


def _ungroup_vectors(dcols, hvb):
    """``cols``' cotangent -> (dG, dβ), (B, T, Hv)."""
    b, t, _ = dcols.shape
    dcols = dcols.reshape(b, t, -1, _LANE)
    return (dcols[..., :hvb].reshape(b, t, -1),
            dcols[..., hvb:2 * hvb].reshape(b, t, -1))


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, name=kernel.func.__name__.strip("_"), grid=grid,
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret)


class _Shapes:
    """The block specs of one call of the rule, from q's and v's shapes.
    One object a (shapes, interpret) (``_shapes``): the jitted calls below
    take it as their static argument, so the three DeltaNet layers of a
    step trace and lower each kernel ONCE (a kernel's body is some
    thousand operations written out head by head: 5 s a layer to trace)."""

    def __init__(self, q_shape, v_shape, interpret):
        self.b, self.t, hk, self.dk = q_shape
        hv, self.dv = v_shape[2], v_shape[3]
        self.r = hv // hk
        n = self.t // CHUNK
        self.hkb, self.cb = _blocking(hk, n)
        self.hvb = self.hkb * self.r
        self.grid = (self.b, hk // self.hkb, n // self.cb)
        self.interpret = interpret
        self.pd = jnp.float32 if interpret else jnp.bfloat16
        self.hk, self.hv, self.n = hk, hv, n
        span, hkb, hvb, dk, dv = (self.cb * CHUNK, self.hkb, self.hvb,
                                  self.dk, self.dv)
        last = self.grid[2] - 1

        def both(shape, index):
            """The spec first to last, and last to first (the backward)."""
            return (pl.BlockSpec(shape, index), pl.BlockSpec(
                shape, lambda b, g, n: index(b, g, last - n)))

        self.key = both((None, span, hkb, dk), lambda b, g, n: (b, n, g, 0))
        self.value = both((None, span, hvb, dv),
                          lambda b, g, n: (b, n, g, 0))
        self.cols = both((None, span, _LANE), lambda b, g, n: (b, n, g))
        self.solve = both((None, hkb, span, self.r * CHUNK),
                          lambda b, g, n: (b, g, n, 0))
        self.starts = both((None, self.cb, hvb * dk, dv),
                           lambda b, g, n: (b, n, g, 0))
        self.state = pl.BlockSpec((None, hvb * dk, dv),
                                  lambda b, g, n: (b, g, 0))
        self.args = (self.r, hkb, self.cb, dk)


_shapes = functools.lru_cache(maxsize=None)(_Shapes)


@functools.partial(jax.jit, static_argnums=0)
def _solve(s, k, cols):
    return _call(
        functools.partial(_solve_kernel, *s.args[:3], s.pd), s.grid,
        [s.key[0], s.cols[0]], s.solve[0],
        jax.ShapeDtypeStruct((s.b, s.hk, s.t, s.r * CHUNK), jnp.float32),
        [], s.interpret)(k, cols)


@functools.partial(jax.jit, static_argnums=0)
def _pass(s, q, k, v, cols, solve):
    """-> (o, the chunk-start states, the last state)."""
    return _call(
        functools.partial(_pass_kernel, *s.args, s.dv, s.pd), s.grid,
        [s.key[0], s.key[0], s.value[0], s.cols[0], s.solve[0]],
        [s.value[0], s.starts[0], s.state],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((s.b, s.n, s.hv * s.dk, s.dv), jnp.float32),
         jax.ShapeDtypeStruct((s.b, s.hv * s.dk, s.dv), jnp.float32)],
        [pltpu.VMEM((s.hvb, s.dk, s.dv), jnp.float32)],
        s.interpret)(q, k, v, cols, solve)


@functools.partial(jax.jit, static_argnums=0)
def _backward(s, q, k, v, cols, solve, starts, do, dlast):
    """-> (dq, dk, dv, ``cols``' cotangent)."""
    return _call(
        functools.partial(_backward_kernel, *s.args, s.dv, s.pd), s.grid,
        [s.key[1], s.key[1], s.value[1], s.cols[1], s.solve[1],
         s.starts[1], s.value[1], s.state],
        [s.key[1], s.key[1], s.value[1], s.cols[1]],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
        + [jax.ShapeDtypeStruct(cols.shape, jnp.float32)],
        [pltpu.VMEM((s.hvb, s.dk, s.dv), jnp.float32)],
        s.interpret)(q, k, v, cols, solve, starts, do, dlast)


def _rule_forward(q, k, v, run, beta, interpret):
    """-> (o, the last state, T, the chunk-start states)."""
    s = _shapes(q.shape, v.shape, interpret)
    cols = _group_vectors(run, beta, s.hvb)
    # named where it becomes a residual: a checkpoint that saves the name
    # runs the pass again in its backward pass, not the solve
    solve = checkpoint_name(_solve(s, k, cols), SOLVE_NAME)
    o, starts, last = _pass(s, q, k, v, cols, solve)
    return o, last.reshape(s.b, s.hv, s.dk, s.dv), solve, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, run, beta, interpret):
    return _rule_forward(q, k, v, run, beta, interpret)[:2]


def _rule_fwd(q, k, v, run, beta, interpret):
    o, last, solve, starts = _rule_forward(q, k, v, run, beta, interpret)
    return (o, last), (q, k, v, run, beta, solve, starts)


def _rule_bwd(interpret, residuals, cotangents):
    q, k, v, run, beta, solve, starts = residuals
    do, dlast = cotangents
    s = _shapes(q.shape, v.shape, interpret)
    dq, dk, dv, dcols = _backward(
        s, q, k, v, _group_vectors(run, beta, s.hvb), solve, starts, do,
        dlast.reshape(s.b, -1, s.dv))
    d_run, d_beta = _ungroup_vectors(dcols, s.hvb)
    return dq, dk, dv, d_run, d_beta.astype(beta.dtype)


_rule.defvjp(_rule_fwd, _rule_bwd)


def rule_runs_in_kernels(q_shape, v_shape, chunk: int = CHUNK, *,
                         force=None, interpret: bool = False) -> bool:
    """Whether ``chunked_gated_delta_rule`` takes the kernels for these
    shapes: a TPU (or ``interpret`` / ``force``, the tests'), the family's
    chunk, whole chunks, head sizes of whole lane tiles, value heads a
    multiple of the key heads. Anything else is the ``jax.numpy`` path."""
    use = force if force is not None else (use_pallas() or interpret)
    _, t, hk, dk = q_shape
    hv, dv = v_shape[2], v_shape[3]
    return bool(use and chunk == CHUNK and t % CHUNK == 0 and t > 0
                and dk % _LANE == 0 and dv % _LANE == 0 and hv % hk == 0
                # G and β of a grid step's value heads share a lane tile
                and 2 * (hv // hk) * _blocking(hk, 2)[0] <= _LANE)


# ---- a decay per key channel ------------------------------------------------
# (below every line the standing model's step is traced from: its lowered
# program names source lines, and the compile cache keys on them)

def _per_channel(q, k, v, g, beta, chunk, force=None, interpret=False):
    """``chunked_gated_delta_rule`` for g (B, T, H, Dk) — every key channel
    of a head decays by its own factor (Kimi Delta Attention), Hk = Hv: the
    decay then sits inside the k·k and q·k products and the state's decay
    between chunks is a row scale (ops/kda_rule.py, which builds on this
    file's solve and has kernels of its own: ``kda_runs_in_kernels``)."""
    from draco_tpu.ops.kda_rule import chunked_kda_rule

    if q.shape != k.shape or q.shape != g.shape or v.shape[2] != q.shape[2]:
        raise ValueError(
            f"a per-channel decay g {g.shape} wants q, k of its shape and as "
            f"many value heads: q {q.shape}, k {k.shape}, v {v.shape}")
    return chunked_kda_rule(q, k, v, g, beta, chunk, force=force,
                            interpret=interpret)
