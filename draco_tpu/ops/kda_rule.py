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

**On the chip the rule is three Pallas kernels** (the last section of this
file; ``kda_runs_in_kernels`` says when: a TPU, chunks of 64, T whole
chunks, Dk and Dv whole lane tiles, as many value heads as key heads —
anything else is the ``jax.numpy`` form above, which every CPU test runs and
the kernels are held to). The same algebra, the same sub-blocks, the same
roundings at the same places (products on bfloat16 operands with float32
sums where the form's einsums run at the chip's default precision; S, G,
every exponential and the pairwise blocks' sums in float32; the solve's
products at ``highest``); what changes is where the arrays live. Every array
that exists only inside a chunk — the sub-blocks' scaled copies of q and k,
the pairwise decays, A, W, L, the squarings, u, w, F, and in the backward
pass their cotangents — lives and dies in VMEM, and the state S (and its
cotangent) is carried in VMEM scratch along the chunk axis of the grid. The
pairwise blocks are formed a DIAGONAL at a time: row c against row c − j is
the (C, Dk) tile against itself moved down j rows, masked where c − j leaves
c's sub-block — fifteen tiles on the vector unit where the form holds a
(SUB, SUB, Dk) array a block. Main memory sees q, k, v, o and the five
gradients; G (the running sum of g inside each chunk, in g's own (B, T, H,
Dk) layout: one ``cumsum`` before the kernels, whose transpose autodiff
supplies) and β; and what crosses a kernel's edge:

* ``solve_kernel`` (chunk-parallel) writes T, 16.8 MB a layer at the cell's
  shapes — named ``SOLVE_NAME`` like the form's, so a checkpoint that saves
  the name keeps it;
* ``pass_kernel`` reads T and writes o, the last state, and the state each
  chunk STARTS from (N x (Dk, Dv) a head, 67 MB: the backward's residual,
  the one a ``lax.scan`` would keep too);
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
from draco_tpu.ops.delta_rule import (
    _LANE, _NN, _NT, _TN, CHUNK, SOLVE_NAME, _call, _dot, _dot6, _dot32,
    _iotas, _split, _unit_lower_inverse)

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


def chunked_kda_rule(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB, *,
                     force=None, interpret: bool = False):
    """q, k (B, T, H, Dk), already normalised and scaled as the layer wants
    them; v (B, T, H, Dv); g (B, T, H, Dk) <= 0; beta (B, T, H). Returns (o
    (B, T, H, Dv), the state after the last token (B, H, Dk, Dv) float32).
    Any T: a last chunk is closed with tokens that neither decay nor write
    (g = 0, β = 0, k = 0). ``force`` / ``interpret``: the tests' way to the
    kernels (``kda_runs_in_kernels``: which path)."""
    if sub == SUB and kda_runs_in_kernels(q.shape, v.shape, chunk,
                                          force=force, interpret=interpret):
        # G: the running sum inside each chunk, in g's own layout — outside
        # the kernels, so autodiff transposes it
        run = jnp.cumsum(g.astype(jnp.float32).reshape(
            g.shape[0], -1, chunk, *g.shape[2:]), axis=2).reshape(g.shape)
        return _rule(q, k, v, run, beta, interpret)
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


# ---- the kernels ------------------------------------------------------------
# One grid step holds ``hb`` heads of ONE chunk — all sixteen where there are
# sixteen: a block of (C, hb, D) is then one contiguous piece of the caller's
# array, where half the heads would be C pieces of a tile each — worked
# through in groups of ``hg``, a group's heads written out in line so that
# their products overlap; the chunk axis is the last, sequential grid axis
# and the states stay in VMEM scratch along it.
#
# Operands as main memory holds them: q, k, G (B, T, H, Dk), v, o (B, T, H,
# Dv) — the caller's arrays as they are; a head's rows are every hb-th row of
# the block read as (C·hb, D), one strided load a tile; β as ``cols`` (B,
# H / hg, T, 128), a group's β on the first ``hg`` lanes (a head's (C, 1)
# column is what scales rows); T (the solve) (B, H / pr, T, pr·C), ``pr`` = 2
# heads side by side on the lanes (a 64-wide minor dimension would be padded
# to 128 in main memory, and side by side two heads' solves are one product,
# ``delta_rule._solve_kernel``'s way); the states TRANSPOSED, (Dv, Dk) a head
# — the decay between chunks is then a (1, Dk) row against the lanes, as G
# holds it, and never a column — the chunk-start ones (B, N, H, Dv, Dk).

_HEADS_A_STEP = 16
_HEADS_A_GROUP = 8


def _head(ref, h, strided):
    """Head ``h``'s (C, D) rows of a (1, C, hb, D) block, as a view."""
    _, c, hb, d = ref.shape
    if strided:
        return ref.reshape(c * hb, d).at[pl.ds(h, c, stride=hb), :]
    return ref.at[0, :, h, :]


def _shifted(x):
    """x (C, D) -> [x moved down j rows, j < SUB] (the first j rows wrap:
    whoever reads them masks them). A move by eight rows renames whole
    tiles, so each amount below eight is rotated once."""
    by = [x] + [pltpu.roll(x, b, 0) for b in range(1, 8)]
    return [by[j % 8] if j < 8 else pltpu.roll(by[j % 8], j - j % 8, 0)
            for j in range(SUB)]


def _pairs(kc, g):
    """For j < SUB: (k_{c−j} e^{G_c − G_{c−j}} on row c where c − j is in
    c's sub-block and 0 elsewhere, that decay) — the exponent is <= 0: G
    falls along the rows. j = 0: (k, None)."""
    row = lax.broadcasted_iota(jnp.int32, kc.shape, 0)
    in_block = jnp.bitwise_and(row, SUB - 1)
    out = [(kc, None)]
    for j, (k_up, g_up) in enumerate(zip(_shifted(kc), _shifted(g))):
        if j:
            decay = jnp.exp(jnp.where(in_block >= j, g - g_up, -jnp.inf))
            out.append((k_up * decay, decay))
    return out


def _within(lefts, kc, g, pd, width=CHUNK, offset=0, pairs=None):
    """A head-chunk's decayed products, the module docstring's sub-blocks:
    for each (x (C, Dk), strict) of ``lefts`` the (C, ``width``) matrix M[c,
    offset + e] = Σ_d x_cd k_ed e^{G_cd − G_ed} for e < c (strict) or e <= c,
    zero elsewhere — A from (k, True), W from (q, False). kc, g (C, Dk)
    float32."""
    c, dk = kc.shape
    row = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    # a block of rows against every earlier column: both sides relative to
    # the block's first row, one product on the matrix unit
    tops = [[jnp.zeros((SUB, width), jnp.float32)] for _ in lefts]
    for i in range(1, c // SUB):
        blk = slice(i * SUB, (i + 1) * SUB)
        first = g[i * SUB:i * SUB + 1, :]
        down = jnp.exp(g[blk, :] - first)
        earlier = kc * jnp.exp(jnp.where(row < i * SUB, first - g, -jnp.inf))
        earlier = _at_columns(earlier, width, offset)
        for top, (x, _) in zip(tops, lefts):
            top.append(_dot(x[blk, :] * down, earlier, _NT, pd))
    mats = [jnp.concatenate(top, axis=0) for top in tops]
    # a block against itself, pairwise, a diagonal at a time: row c against
    # row c − j, (C, Dk) tiles on the vector unit, float32 throughout
    out_row = lax.broadcasted_iota(jnp.int32, (c, width), 0)
    out_lane = lax.broadcasted_iota(jnp.int32, (c, width), 1)
    for j, (pair, _) in enumerate(pairs or _pairs(kc, g)):
        at = out_lane - out_row == offset - j
        for m, (x, strict) in enumerate(lefts):
            if j or not strict:
                mats[m] = mats[m] + jnp.where(
                    at, jnp.sum(x * pair, axis=1, keepdims=True), 0.0)
    return mats


def _at_columns(x, width, offset):
    """x (C, Dk) as the rows [offset, offset + C) of (width, Dk) zeros: the
    right operand whose product lands on those columns."""
    parts = [x if rows is None else jnp.zeros((rows, x.shape[1]), x.dtype)
             for rows in (offset, None, width - offset - x.shape[0])
             if rows != 0]
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else x


def _within_transposed(qc, kc, g, da, dw, pd, pairs):
    """``_within``'s transpose for A (cotangent ``da``, zero on and above
    the diagonal) and W (``dw``, zero above it), both (C, C): -> (dq, dk, dG)
    (C, Dk). A generator (``_in_turn``)."""
    c, dk = kc.shape
    row = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    dq_blocks = [jnp.zeros((SUB, dk), jnp.float32)]
    dk_blocks = [jnp.zeros((SUB, dk), jnp.float32)]
    dg_blocks = [jnp.zeros((SUB, dk), jnp.float32)]
    dkey = jnp.zeros((c, dk), jnp.float32)
    dg = jnp.zeros((c, dk), jnp.float32)
    for i in range(1, c // SUB):
        blk = slice(i * SUB, (i + 1) * SUB)
        first = g[i * SUB:i * SUB + 1, :]
        down = jnp.exp(g[blk, :] - first)
        up = jnp.exp(jnp.where(row < i * SUB, first - g, -jnp.inf))
        earlier = kc * up
        lefts = jnp.concatenate([kc[blk, :] * down, qc[blk, :] * down],
                                axis=0)  # (2·SUB, Dk)
        reached = jnp.concatenate([da[blk, :], dw[blk, :]], axis=0)
        d_lefts = _dot(reached, earlier, _NN, pd)
        # (the block's own columns of da, dw meet rows of ``earlier`` that
        # are zero, and their rows of d_earlier meet zeros of ``up``)
        d_earlier = _dot(reached, lefts, _TN, pd)
        dk_blocks.append(d_lefts[:SUB] * down)
        dq_blocks.append(d_lefts[SUB:] * down)
        dkey = dkey + d_earlier * up
        through_down = d_lefts * lefts
        through_down = through_down[:SUB] + through_down[SUB:]
        through_up = d_earlier * earlier
        dg_blocks.append(through_down)
        # the reference row: + what went up, − what came down
        at_first = jnp.sum(through_up, axis=0, keepdims=True) - jnp.sum(
            through_down, axis=0, keepdims=True)
        dg = dg - through_up + jnp.where(row == i * SUB, at_first, 0.0)
    yield
    dq = jnp.concatenate(dq_blocks, axis=0)
    dkey = dkey + jnp.concatenate(dk_blocks, axis=0)
    dg = dg + jnp.concatenate(dg_blocks, axis=0)
    ci, ei = _iotas(c)
    # what row c − j is owed comes back up j rows; by j mod 8, so that each
    # amount below eight is rotated once (rows whose partner lies outside
    # their block hold zeros: nothing wraps)
    back_k, back_g = [None] * 8, [None] * 8
    for j, (pair, decay) in enumerate(pairs):
        on = ci - ei == j
        dw_j = jnp.sum(jnp.where(on, dw, 0.0), axis=1, keepdims=True)
        if not j:  # W's diagonal: q_c · k_c, no decay
            dq = dq + dw_j * kc
            dkey = dkey + dw_j * qc
            continue
        da_j = jnp.sum(jnp.where(on, da, 0.0), axis=1, keepdims=True)
        dq = dq + dw_j * pair
        dkey = dkey + da_j * pair
        reached = da_j * kc + dw_j * qc  # the pair's cotangent
        through = reached * pair
        dg = dg + through
        for back, x in ((back_k, reached * decay), (back_g, through)):
            if j >= 8:
                x = pltpu.roll(x, c - (j - j % 8), 0)
            back[j % 8] = x if back[j % 8] is None else back[j % 8] + x
    for b in range(8):
        up_k, up_g = back_k[b], back_g[b]
        if b:
            up_k, up_g = (pltpu.roll(x, c - b, 0) for x in (up_k, up_g))
        dkey, dg = dkey + up_k, dg - up_g
    return dq, dkey, dg


def _chunk_forward(qc, kc, vc, g, b_col, solve, state, pd, outputs=True):
    """A head-chunk's forward from the (transposed) state it starts from:
    everything the backward pass wants again and, with ``outputs``, the
    output and the state it leaves. A generator (``_in_turn``): it yields
    where a product waits for the one before it."""
    c = kc.shape[0]
    grown = jnp.exp(g)
    vb, kb = b_col * vc, b_col * kc * grown
    u, w = _dot(solve, vb, _NN, pd), _dot(solve, kb, _NN, pd)
    q_in = qc * grown
    if outputs:
        (within,) = _within([(qc, False)], kc, g, pd)
    yield
    # w S and q_in S: one product against the state
    read = _dot(jnp.concatenate([w, q_in], axis=0), state, _NT, pd)
    fresh = u - read[:c]
    yield
    last = g[c - 1:c, :]  # G_C, (1, Dk)
    keep = jnp.exp(last)
    to_last = jnp.exp(last - g)
    k_out = kc * to_last
    f = dict(grown=grown, vb=vb, kb=kb, w=w, fresh=fresh, q_in=q_in,
             keep=keep, to_last=to_last, k_out=k_out)
    if outputs:
        f["o"] = read[c:] + _dot(within, fresh, _NN, pd)
        f["new"] = state * keep + _dot(fresh, k_out, _TN, pd)
    return f


def _in_turn(heads):
    """Run the heads' generators a step each in turn, to the end: -> their
    results. The matrix unit takes its products in program order, so a
    head written out whole would hold the next head's first product behind
    its own last; in turn, one head's product runs while another's result
    is awaited."""
    results, live = [None] * len(heads), list(enumerate(heads))
    while live:
        still = []
        for i, head in live:
            try:
                next(head)
                still.append((i, head))
            except StopIteration as stop:
                results[i] = stop.value
        live = still
    return results


def _groups(hb, hg, body):
    """``body(first head of a group)`` for each group of ``hg`` heads."""
    if hb == hg:
        body(0)
    else:
        lax.fori_loop(0, hb // hg, lambda i, c: (body(i * hg), c)[1], 0)


def _solve_kernel(pr, hb, hg, strided, pd, k_ref, g_ref, cols_ref, t_ref):
    """T for ``pr`` heads side by side on the lanes, (C, pr·C): a product of
    two such matrices, head by head, is ONE product with the right operand
    laid out block-diagonally (``delta_rule._solve_kernel``)."""
    width = pr * CHUNK
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, width), 0)
    lane = lax.broadcasted_iota(jnp.int32, (CHUNK, width), 1)
    of_head = [(lane >= j * CHUNK) & (lane < (j + 1) * CHUNK)
               for j in range(pr)]
    eye = (row == jnp.bitwise_and(lane, CHUNK - 1)).astype(jnp.float32)

    def blocks(pieces):
        if pr == 1:
            return pieces
        return tuple(jnp.concatenate(
            [jnp.where(mask, x, 0.0) for mask in of_head], axis=0)
            for x in pieces)

    def pair(first, p):
        low = jnp.zeros((CHUNK, width), jnp.float32)
        for j in range(pr):
            i = p * pr + j
            kc = _head(k_ref, first + i, strided)[...].astype(jnp.float32)
            (a,) = _within([(kc, True)], kc,
                           _head(g_ref, first + i, strided)[...], pd, width,
                           j * CHUNK)
            low = low + cols_ref[first // hg, :, i:i + 1] * a
        # _solve_by_squaring: inv (I + P) = inv + inv P
        power = -low
        inv = eye + power
        pieces = _split(power)
        for _ in range(max(CHUNK - 1, 1).bit_length() - 1):
            yield
            power = _dot6(pieces, blocks(pieces))
            pieces = _split(power)
            yield
            inv = inv + _dot6(_split(inv), blocks(pieces))
        return inv

    def group(first):
        solved = _in_turn([pair(first, p) for p in range(hg // pr)])
        for p, inv in enumerate(solved):
            t_ref[first // pr + p] = inv

    _groups(hb, hg, group)


def _head_solve(t_ref, pr, first, i):
    return t_ref[first // pr + i // pr, :,
                 (i % pr) * CHUNK:(i % pr + 1) * CHUNK]


def _pass_kernel(pr, hb, hg, strided, pd, q_ref, k_ref, v_ref, g_ref,
                 cols_ref, t_ref, o_ref, starts_ref, last_ref, state_ref):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    def group(first):
        # every state of the group read before, and written after, the
        # heads' work: a store to ``state_ref[h]`` (h the loop's) would
        # otherwise order the next head's read behind this head's last
        # product, and the heads' products could not overlap
        states = [state_ref[first + i] for i in range(hg)]
        done = _in_turn([_chunk_forward(
            *(_head(ref, first + i, strided)[...].astype(jnp.float32)
              for ref in (q_ref, k_ref, v_ref, g_ref)),
            cols_ref[first // hg, :, i:i + 1],
            _head_solve(t_ref, pr, first, i), states[i], pd)
            for i in range(hg)])
        for i, (state, f) in enumerate(zip(states, done)):
            starts_ref[first + i] = state
            _head(o_ref, first + i, strided)[...] = f["o"].astype(
                o_ref.dtype)
            state_ref[first + i] = f["new"]

    _groups(hb, hg, group)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = state_ref[...]


def _backward_kernel(pr, hb, hg, strided, pd, q_ref, k_ref, v_ref, g_ref,
                     cols_ref, t_ref, starts_ref, do_ref, dlast_ref, dq_ref,
                     dk_ref, dv_ref, dg_ref, dcols_ref, dstate_ref):
    """The chunks last to first, the state's cotangent carried in VMEM."""
    ci, ei = _iotas(CHUNK)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dlast_ref[...].astype(jnp.float32)

    dcols_ref[...] = jnp.zeros(dcols_ref.shape, jnp.float32)

    def group(first):
        # the group's cotangent states read before, and everything written
        # after, the heads' work (``_pass_kernel``'s note)
        dstates = [dstate_ref[first + i] for i in range(hg)]
        done = _in_turn([head(first, i, dstates[i]) for i in range(hg)])
        for i, (dstate, dg, dcol, dv, dq, dkey) in enumerate(done):
            h = first + i
            dstate_ref[h] = dstate
            _head(dg_ref, h, strided)[...] = dg
            dcols_ref[first // hg, :, i:i + 1] = dcol
            _head(dv_ref, h, strided)[...] = dv.astype(dv_ref.dtype)
            _head(dq_ref, h, strided)[...] = dq.astype(dq_ref.dtype)
            _head(dk_ref, h, strided)[...] = dkey.astype(dk_ref.dtype)

    def head(first, i, dnew):
        h = first + i
        qc, kc, vc, g, do = (
            _head(ref, h, strided)[...].astype(jnp.float32)
            for ref in (q_ref, k_ref, v_ref, g_ref, do_ref))
        b_col = cols_ref[first // hg, :, i:i + 1]
        solve = _head_solve(t_ref, pr, first, i)
        state = starts_ref[h]
        pairs = _pairs(kc, g)
        a, within = _within([(kc, True), (qc, False)], kc, g, pd,
                            pairs=pairs)
        f = yield from _chunk_forward(qc, kc, vc, g, b_col, solve, state, pd,
                                      outputs=False)
        # o = q_in S + W F;  S' = diag(keep) S + k_outᵀ F
        dfresh = (_dot(within, do, _TN, pd)
                  + _dot(f["k_out"], dnew, _NT, pd))
        dwithin = jnp.where(ci >= ei, _dot(do, f["fresh"], _NT, pd), 0.0)
        dk_out = _dot(f["fresh"], dnew, _NN, pd)
        yield
        # F = u − w S: what reaches q_in and −w, one product against S
        reached = jnp.concatenate([do, dfresh], axis=0)
        from_state = _dot(reached, state, _NN, pd)
        dq_in, dw = from_state[:CHUNK], -from_state[CHUNK:]
        dstate = dnew * f["keep"] + _dot(
            jnp.concatenate([do, -dfresh], axis=0),
            jnp.concatenate([f["q_in"], f["w"]], axis=0), _TN, pd)
        dkeep = jnp.sum(state * dnew, axis=0, keepdims=True)  # (1, Dk)
        yield
        # u = T vb;  w = T kb;  T = (I + L)⁻¹: dL = −Tᵀ dT Tᵀ;  L = β A
        dsolve = (_dot(dfresh, f["vb"], _NT, pd)
                  + _dot(dw, f["kb"], _NT, pd))
        dvb = _dot(solve, dfresh, _TN, pd)
        dkb = _dot(solve, dw, _TN, pd)
        yield
        dlow = _dot32(solve, dsolve, _TN)
        yield
        dlow = jnp.where(ci > ei, -_dot32(dlow, solve, _NT), 0.0)
        yield
        dq, dkey, dg = yield from _within_transposed(
            qc, kc, g, b_col * dlow, dwithin, pd, pairs)
        pushed = dk_out * f["k_out"]
        # G_C: keep = e^{G_C}, k_out = k e^{G_C − G}
        at_last = f["keep"] * dkeep + jnp.sum(pushed, axis=0, keepdims=True)
        row = lax.broadcasted_iota(jnp.int32, g.shape, 0)
        return (
            dstate,
            dg + dq_in * f["q_in"] + f["kb"] * dkb - pushed
            + jnp.where(row == CHUNK - 1, at_last, 0.0),
            jnp.sum(dlow * a, axis=1, keepdims=True)
            + jnp.sum(dvb * vc, axis=1, keepdims=True)
            + jnp.sum(dkb * kc * f["grown"], axis=1, keepdims=True),
            b_col * dvb, dq + dq_in * f["grown"],
            dkey + dk_out * f["to_last"] + b_col * f["grown"] * dkb)

    _groups(hb, hg, group)


class _Shapes:
    """The block specs of one call of the rule, from q's and v's shapes. One
    object a (shapes, interpret) (``_shapes``): the jitted calls below take
    it as their static argument, so the KDA layers of a step trace and lower
    each kernel ONCE (``delta_rule._Shapes``)."""

    def __init__(self, q_shape, v_shape, interpret):
        self.b, self.t, self.h, self.dk = q_shape
        self.dv = v_shape[3]
        self.n = self.t // CHUNK
        self.hb = hb = _heads_a_step(self.h)
        self.hg = hg = _HEADS_A_GROUP if hb % _HEADS_A_GROUP == 0 else hb
        self.pr = pr = 2 if hg % 2 == 0 else 1
        self.grid = (self.b, self.h // hb, self.n)
        self.interpret = interpret
        self.pd = jnp.float32 if interpret else jnp.bfloat16
        dk, dv, last = self.dk, self.dv, self.n - 1

        def both(shape, index):
            """The spec first to last, and last to first (the backward)."""
            return (pl.BlockSpec(shape, index), pl.BlockSpec(
                shape, lambda b, g, n: index(b, g, last - n)))

        self.key = both((1, CHUNK, hb, dk), lambda b, g, n: (b, n, g, 0))
        self.value = both((1, CHUNK, hb, dv), lambda b, g, n: (b, n, g, 0))
        self.cols = both((None, hb // hg, CHUNK, _LANE),
                         lambda b, g, n: (b, g, n, 0))
        self.solve = both((None, hb // pr, CHUNK, pr * CHUNK),
                          lambda b, g, n: (b, g, n, 0))
        self.starts = both((None, None, hb, dv, dk),
                           lambda b, g, n: (b, n, g, 0, 0))
        self.state = pl.BlockSpec((None, hb, dv, dk),
                                  lambda b, g, n: (b, g, 0, 0))
        self.state_scratch = pltpu.VMEM((hb, dv, dk), jnp.float32)
        # whole sublane tiles of heads: a head's rows by one strided read
        self.args = (pr, hb, hg, not interpret and hb % 8 == 0, self.pd)


_shapes = functools.lru_cache(maxsize=None)(_Shapes)


def _heads_a_step(h: int) -> int:
    """The heads are the second-minor dimension of q's, k's, G's and v's
    blocks: whole sublane tiles of them — sixteen where they divide, the
    block then ``_HEADS_A_STEP``·D contiguous floats a token — or all."""
    for hb in (_HEADS_A_STEP, _HEADS_A_GROUP):
        if h % hb == 0:
            return hb
    return h


def _group_columns(beta, hg):
    """β (B, T, H) -> ``cols`` (the section's head)."""
    b, t, _ = beta.shape
    cols = beta.astype(jnp.float32).reshape(b, t, -1, hg)
    return jnp.moveaxis(
        jnp.pad(cols, ((0, 0),) * 3 + ((0, _LANE - hg),)), 2, 1)


@functools.partial(jax.jit, static_argnums=0)
def _solve(s, k, run, cols):
    return _call(
        functools.partial(_solve_kernel, *s.args), s.grid,
        [s.key[0], s.key[0], s.cols[0]], s.solve[0],
        jax.ShapeDtypeStruct((s.b, s.h // s.pr, s.t, s.pr * CHUNK),
                             jnp.float32),
        [], s.interpret)(k, run, cols)


@functools.partial(jax.jit, static_argnums=0)
def _pass(s, q, k, v, run, cols, solve):
    """-> (o, the chunk-start states, the last state; states transposed)."""
    return _call(
        functools.partial(_pass_kernel, *s.args), s.grid,
        [s.key[0], s.key[0], s.value[0], s.key[0], s.cols[0], s.solve[0]],
        [s.value[0], s.starts[0], s.state],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((s.b, s.n, s.h, s.dv, s.dk), jnp.float32),
         jax.ShapeDtypeStruct((s.b, s.h, s.dv, s.dk), jnp.float32)],
        [s.state_scratch], s.interpret)(q, k, v, run, cols, solve)


@functools.partial(jax.jit, static_argnums=0)
def _backward(s, q, k, v, run, cols, solve, starts, do, dlast):
    """-> (dq, dk, dv, dG, ``cols``' cotangent)."""
    return _call(
        functools.partial(_backward_kernel, *s.args), s.grid,
        [s.key[1], s.key[1], s.value[1], s.key[1], s.cols[1], s.solve[1],
         s.starts[1], s.value[1], s.state],
        [s.key[1], s.key[1], s.value[1], s.key[1], s.cols[1]],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
        + [jax.ShapeDtypeStruct(run.shape, jnp.float32),
           jax.ShapeDtypeStruct(cols.shape, jnp.float32)],
        [s.state_scratch], s.interpret)(
            q, k, v, run, cols, solve, starts, do, dlast)


def _rule_forward(q, k, v, run, beta, interpret):
    """-> (o, the last state, T, the chunk-start states)."""
    s = _shapes(q.shape, v.shape, interpret)
    cols = _group_columns(beta, s.hg)
    # named where it becomes a residual: a checkpoint that saves the name
    # runs the pass again in its backward pass, not the solve
    solve = checkpoint_name(_solve(s, k, run, cols), SOLVE_NAME)
    o, starts, last = _pass(s, q, k, v, run, cols, solve)
    return o, jnp.swapaxes(last, 2, 3), solve, starts


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
    dq, dk, dv, d_run, dcols = _backward(
        s, q, k, v, run, _group_columns(beta, s.hg), solve, starts, do,
        jnp.swapaxes(dlast, 2, 3))
    d_beta = jnp.moveaxis(dcols[..., :s.hg], 1, 2).reshape(beta.shape)
    return dq, dk, dv, d_run, d_beta.astype(beta.dtype)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_runs_in_kernels(q_shape, v_shape, chunk: int = CHUNK, *, force=None,
                        interpret: bool = False) -> bool:
    """Whether ``chunked_kda_rule`` takes the kernels for these shapes (q's,
    which are k's and g's, and v's): a TPU (or ``interpret`` / ``force``, the
    tests'), the family's chunk, whole chunks, head sizes of whole lane
    tiles, as many value heads. Anything else is the ``jax.numpy`` path."""
    use = force if force is not None else (use_pallas() or interpret)
    _, t, h, dk = q_shape
    return bool(use and chunk == CHUNK and t % CHUNK == 0 and t > 0
                and dk % _LANE == 0 and v_shape[3] % _LANE == 0
                and v_shape[2] == h
                # a group's β columns share a lane tile
                and (h % _HEADS_A_GROUP == 0 or h <= _LANE))
