"""Blockwise causal flash attention as a Pallas TPU kernel.

The LM paths' single-shard attention (parallel/ring_attention.dense_attention)
materialises the full (T, T) score matrix per head — O(T²) HBM traffic and
memory that caps sequence length on one chip. This kernel streams K/V blocks
through VMEM with the online-softmax accumulators (the same m/l/o algebra the
ring uses *across chips*, here applied *within* a chip's sequence), so peak
memory is O(T·Dh + block²) and the (T, T) matrix never exists.

Forward saves only the per-row log-sum-exp; backward is ONE kernel
(_bwd_kernel) that rebuilds each probability block once and takes all three
gradients from it: five products a block (s, dp, dv += pᵀ·do, dk += dsᵀ·q,
dq += ds·k), one pass of mask / exp / ds. Its grid sweeps the query blocks
under each key block, so dk / dv sum in block-sized scratch; dq, which that
sweep crosses, keeps its float32 sum whole for the head in VMEM ((T, Dh):
4 MB at T = 4096, Dh = 256) and no partial dq ever reaches main memory.
``vmem_limit_bytes`` is set from the shapes (_bwd_vmem_bytes); a head too
long for the chip's vector memory raises. Again (T, T) never exists.

Block-causal skipping, at two granularities. Whole grid steps: a (query
block, key block) pair whose keys all lie in the future computes nothing
(`pl.when`) and fetches nothing (the residency maps), so causal attention
does ~half the block work. Inside a computed grid step (PR 43): the pair's
offset d = i*bq - j*bk is one of a few values known at trace time (causal
1024 x 1024: 0, the diagonal pair, or "every entry seen"; 512 x 1024: 0,
512 or that), and for each the kernels hold one body (`_bodies`, selected
by `pl.when` on d) that walks the block in strips of ``SUB_TILE`` query
rows and multiplies each strip only against
the contiguous range of ``SUB_TILE``-wide key columns that holds a pair it
sees (`_strips`) — static slices of the blocks the grid step already holds,
so no more grid steps and no more fetches. The mask is applied only to a
strip the diagonal (or the window's edge) cuts, from static offsets; a pair
with every entry seen takes a body with no mask at all. What the rectangle
rule multiplied and threw away — the upper triangle of every diagonal pair,
half of each windowed pair — is not multiplied, down to the sub-tile:
the terms left out are exact zeros (p = exp(NEG_INF - lse) = 0), the sums
keep their order (dq over ascending keys, dk / dv over ascending queries),
and only the grouping of a shorter contraction can move a float32 sum's
last bit. `computed_pairs` counts the entries a head multiplies under the
rule. Where the rule does not fit — ``causal=False`` (the ring's fully
visible hops: nothing to mask), blocks or a window that are not whole
sub-tiles (`_fit_block`'s odd sizes, the small blocks of the tests), more
cut offsets than ``_MAX_CUT_BODIES`` — the kernels keep ONE body over the
whole rectangle, masked elementwise from the block indices (`_masked`).

Sliding window (``flash_attention(..., window=W)``): query t sees key s iff
0 <= t - s < W — itself and the W - 1 tokens before it. A (query block,
key block) pair is computed iff some pair of its positions satisfies BOTH
inequalities: the block's earliest key is no later than its latest query
(causality, as above) and its latest key is less than W before its earliest
query. Every other block is skipped from both sides, in the forward kernel
and in the backward kernel, and the residency maps clamp the block index
from both sides so that a skipped block is not fetched either. Inside a
computed pair the strips above apply to both inequalities: at 1024 x 1024
and W = 1024 the two pairs a query block computes (d = 0: the lower
triangle, d = 1024: the strict upper one) multiply 5/8 of their entries at
a sub-tile of 256 where the rectangle rule multiplied all (2 048 keys a
query for the 1 024 it sees, past the row's start; now 1 280).
``window=None`` is the causal program, unchanged.

No reference counterpart (the reference is CNN-only, SURVEY.md §5.7); this
is a hot-op kernel of the TPU build's long-context axis, complementing ring
attention (which shards T across chips; this kernel serves each shard or the
single-chip case). Dispatch: the kernel on a TPU backend — a shape that
does not tile RAISES there, it never becomes the O(T²) dense path behind the
caller's back; off-TPU the dense jnp path is the lowering (the CPU tests'
reference), and interpret mode covers the kernel body in CI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from draco_tpu.ops.coded import use_pallas

NEG_INF = -1e30
_LANE = 128
# the query block's default limit. 512 until PR 43: a taller block then
# multiplied more of the future (the rectangle rule), and now multiplies the
# same sub-tiles in half the grid steps — a layer-lane's forward / backward
# on the chip at SUB_TILE = 256, 512 -> 1024: kanana2 (32, 4096, 256 | 128)
# 2.21 / 3.71 -> 2.04 / 3.51 ms, qwen3next (16, 4096, 256) 1.00 / 2.25 ->
# 0.98 / 2.18, mellum2's full layer (32, 8192, 128) 5.29 / 9.26 -> 4.71 /
# 8.78 (the parent's rectangles: 2.40 / 4.14, 1.13 / 2.53, 5.55 / 9.71;
# PERF.md section 6, PR 43)
BLOCK_Q = 1024
# under a window a query block computes the key blocks that its
# window + block_q - 1 keys touch: at W = 1024 two key blocks of 1024
# whether it holds 512 queries or 1024, so the taller block halves the grid
# steps a query pays (a layer-lane of 32 heads at T = 8192 on the chip:
# forward 3.31 -> 2.75 ms, forward + backward 12.79 -> 11.15; PERF.md
# section 6, PR 35)
WINDOW_BLOCK_Q = 1024
# the edge of the sub-tiles a computed block pair is walked in (module
# docstring): whole sub-tiles with no seen pair are not multiplied. Smaller
# skips more and feeds the matrix unit shorter products; from the same
# readings, 512 / 256 / 128 at a 512-row causal block: kanana2 forward +
# backward 7.42 / 7.22 / 7.26 ms (parent 7.81), mellum2's sliding layer
# (W = 1024, 1024 x 1024) 7.67 / 7.44 / 7.70 (parent 9.05) — 256, where
# that layer multiplies 1 280 keys a query for the 1 024 it sees (2 048
# as rectangles). Strips of key columns in place of query rows lost in the
# backward at every point (+ 2 to + 7 %).
SUB_TILE = 256
# cut offsets beyond this keep the one rectangle body: each is a body the
# chip's compiler builds
_MAX_CUT_BODIES = 4


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fit_block(limit: int, t: int, lane_rule: bool) -> int:
    """Largest legal block size <= limit for a length-t axis: must divide t,
    be a multiple of the 8-row sublane tile, and (key blocks only,
    lane_rule=True) be a whole number of 128-wide lane tiles when wider
    than one. A plain min(limit, t) would demote every t not divisible by
    the default (e.g. t=1536 with bk=1024) out of the kernel — the
    shrink keeps every t%8==0 length kernel-eligible at the biggest block
    the shape allows (t=768 -> 256 under a 1024 limit). Returns 0 when no
    legal block exists (t%8 != 0); _kernel_eligible then raises."""
    b = min(limit, t)
    b -= b % 8
    while b >= 8:
        if t % b == 0 and (not lane_rule or b <= _LANE or b % _LANE == 0):
            return b
        b -= 8
    return 0


def _first_k_block(i, bq: int, bk: int, window: int):
    """The first key block a windowed query block i computes: the one that
    holds its earliest query's earliest key, i*bq - (window - 1)."""
    return jnp.maximum(i * bq - (window - 1), 0) // bk


def _last_q_block(j, bq: int, bk: int, window: int, nq: int):
    """The last query block a windowed key block j computes: the one that
    holds its latest key's latest query, j*bk + bk - 1 + (window - 1)."""
    return jnp.minimum((j * bk + bk + window - 2) // bq, nq - 1)


def _kv_residency_map(bq: int, bk: int, causal: bool, window=None):
    """Index map for K/V-row input blocks on a (g, <q-block>, <k-block>)
    grid. Causal: clamp at the diagonal — the kernels' pl.when already
    skips compute for j > (i*bq + bq - 1)//bk (the largest k-block with any
    q_pos >= k_pos entry), but without the clamp Mosaic still DMAs those
    future blocks from HBM every step (~2x the causal pass's traffic).
    Repeating the boundary index instead makes consecutive skipped steps
    fetch nothing (Mosaic elides copies when the block index is unchanged).
    The clamp is the identity on every computed block, so outputs are
    untouched; keep this formula in lockstep with the kernels' guards.
    ``window``: clamped from below too, at the first block the window
    reaches (_first_k_block)."""
    if not causal:
        return lambda g, i, j: (g, j, 0)
    if window is None:
        return lambda g, i, j: (g, jnp.minimum(j, (i * bq + bq - 1) // bk), 0)
    return lambda g, i, j: (g, jnp.clip(
        j, _first_k_block(i, bq, bk, window), (i * bq + bq - 1) // bk), 0)


def _q_residency_map(bq: int, bk: int, causal: bool, window=None, nq=0):
    """Index map for Q-row input blocks (q, do, per-row stats) on the
    backward grid (g, <k-block>, <q-block>). Causal: the sweep only computes
    from the first diagonal-touching q block, i_min = (j*bk)//bq — which equals
    ceil((j*bk - bq + 1)/bq), the smallest i with i*bq + bq - 1 >= j*bk —
    so clamp residency there (same elision mechanics as _kv_residency_map).
    ``window``: clamped from above too, at the last query block that still
    sees the key block (_last_q_block)."""
    if not causal:
        return lambda g, j, i: (g, i, 0)
    if window is None:
        return lambda g, j, i: (g, jnp.maximum(i, (j * bk) // bq), 0)
    return lambda g, j, i: (g, jnp.clip(
        i, (j * bk) // bq, _last_q_block(j, bq, bk, window, nq)), 0)


def _cols(stat, ncols):
    """Widen a lane-broadcast (bq, _LANE) row statistic to ncols columns.

    Mosaic requires the last dim of every block to be _LANE-aligned, so the
    per-row softmax stats live broadcast across all 128 lanes (every lane of a
    row holds the same value — the layout jax's own TPU flash kernel uses);
    to combine a stat with a (bq, ncols) score block, slice when ncols fits
    inside one lane tile, tile when it spans several.
    """
    if ncols <= _LANE:
        return stat[:, :ncols]
    return jnp.tile(stat, (1, ncols // _LANE))


def _computed(i, j, bq: int, bk: int, causal: bool, window):
    """Whether the (query block i, key block j) pair holds an entry the
    mask lets through — comparing raw block indices (j <= i) is only
    correct when bq == bk. Non-causal (the ring's fully-visible past-owner
    hops) computes every pair."""
    if not causal:
        return j >= 0
    seen = j * bk <= i * bq + bq - 1
    if window is not None:
        seen &= i * bq < j * bk + bk + window - 1
    return seen


def _masked(s, i, j, causal: bool, window):
    """Scores ``s`` (bq, bk) of block pair (i, j) with the entries the mask
    hides at NEG_INF: a key after its query, and under a window a key
    ``window`` or more before it."""
    if not causal:
        return s
    bq, bk = s.shape
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, s, NEG_INF)


def _strips(d: int, bq: int, bk: int, window, sub: int):
    """The work of a computed block pair whose offset i*bq - j*bk is ``d``,
    as strips of ``sub`` query rows: row r of the block sees column c iff
    0 <= r + d - c (< window), so a strip sees ONE contiguous range of
    columns, widened here to whole sub-tiles. ``(r0, rows, lo, hi,
    causal_cut, window_cut)`` a strip; a strip that sees no column is left
    out, and the two flags say which inequality can fail inside it (neither:
    no mask is applied)."""
    out = []
    for r0 in range(0, bq, sub):
        last = min(r0 + sub - 1 + d, bk - 1)  # the last row's last column
        first = 0 if window is None else max(r0 + d - window + 1, 0)
        if last < first:
            continue
        lo, hi = first // sub * sub, (last // sub + 1) * sub
        out.append((r0, sub, lo, hi, r0 + d < hi - 1,
                    window is not None and r0 + sub - 1 + d - lo >= window))
    return out


def _interior(d, bq: int, bk: int, window):
    """Whether every entry of a block pair at offset ``d`` is seen."""
    seen = d >= bk - 1
    if window is not None:
        seen &= d <= window - bq
    return seen


def _computed_offsets(t: int, bq: int, bk: int, causal: bool, window):
    """The offset i*bq - j*bk of every computed block pair of a length-t
    head, pair by pair."""
    return [i * bq - j * bk
            for i in range(t // bq) for j in range(t // bk)
            if _computed(i, j, bq, bk, causal, window)]


@functools.lru_cache(maxsize=None)
def _bodies(t: int, bq: int, bk: int, causal: bool, window, sub: int):
    """What the kernels' grid steps multiply, keyed by the pair's offset
    d = i*bq - j*bk: ``{None: [whole block, unmasked]}`` for the pairs with
    every entry seen, ``{d: _strips(d)}`` for each offset the mask cuts —
    only those that occur among the (t // bq) x (t // bk) pairs, so no body
    is compiled that no step takes. None where the rule does not apply and
    the kernels keep ONE body over the whole rectangle, masked elementwise
    from the block indices: no mask at all (``causal=False``), blocks or a
    window that are not whole sub-tiles, or more cut offsets than
    ``_MAX_CUT_BODIES`` (odd blocks out of ``_fit_block``)."""
    if (not causal or bq % sub or bk % sub
            or (window is not None and window % sub)):
        return None
    bodies = {}
    for d in _computed_offsets(t, bq, bk, causal, window):
        if _interior(d, bq, bk, window):
            bodies[None] = [(0, bq, 0, bk, False, False)]
        elif d not in bodies:
            bodies[d] = _strips(d, bq, bk, window, sub)
    if len(bodies) - (None in bodies) > _MAX_CUT_BODIES:
        return None
    return bodies


def _strip_masked(s, strip, d: int, window):
    """Scores ``s`` of a strip of a pair at the static offset ``d``, masked
    by the inequalities that can fail in it: c - r <= e and
    c - r > e - window with e = r0 + d - lo."""
    r0, _, lo, _, causal_cut, window_cut = strip
    if not (causal_cut or window_cut):
        return s
    e = r0 + d - lo
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    seen = ahead <= e if causal_cut else ahead > e - window
    if causal_cut and window_cut:
        seen &= ahead > e - window
    return jnp.where(seen, s, NEG_INF)


def _per_body(i, j, t, bq, bk, causal, window, compute):
    """Run ``compute(strips, mask)`` for the grid step's pair (i, j), under
    the ``pl.when`` of the body its offset selects (_bodies): ``strips`` as
    _strips gives them, ``mask(s, strip)`` the strip's scores masked."""
    bodies = _bodies(t, bq, bk, causal, window, SUB_TILE)
    if bodies is None:
        pl.when(_computed(i, j, bq, bk, causal, window))(functools.partial(
            compute, [(0, bq, 0, bk, causal, window is not None)],
            lambda s, strip: _masked(s, i, j, causal, window)))
        return
    d = i * bq - j * bk
    for d0, strips in bodies.items():
        pl.when(_interior(d, bq, bk, window) if d0 is None else d == d0)(
            functools.partial(compute, strips, functools.partial(
                _strip_masked, d=d0, window=window)))


def computed_pairs(t: int, bq: int, bk: int, window=None,
                   sub_tile: int | None = None) -> int:
    """The (query, key) entries one head's causal kernel multiplies at
    length ``t`` under blocks (bq, bk) — forward and backward alike —, from
    the rule the bodies are generated from (_bodies). ``sub_tile`` None:
    the kernels' own ``SUB_TILE``; 0: the rectangle rule, every computed
    pair whole. Seen pairs (what the rooflines count) are fewer."""
    sub = SUB_TILE if sub_tile is None else sub_tile
    bodies = _bodies(t, bq, bk, True, window, sub) if sub else None
    offsets = _computed_offsets(t, bq, bk, True, window)
    if bodies is None:
        return bq * bk * len(offsets)
    return sum(rows * (hi - lo)
               for d in offsets
               for _, rows, lo, hi, _, _ in bodies[
                   None if _interior(d, bq, bk, window) else d])


def seen_pairs(t: int, window=None) -> int:
    """The (query, key) pairs of one head with 0 <= t - s (< window)."""
    w = t if window is None else min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(scale, nk, bq, bk, causal, window, q_ref, k_ref, v_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def _compute(strips, mask):
        # matmuls take the input dtype (bf16 inputs ride the fast MXU pass)
        # and accumulate f32 via preferred_element_type — the flash standard;
        # all softmax/accumulator algebra stays f32
        for strip in strips:
            r0, nrows, lo, hi = strip[:4]
            rows = slice(r0, r0 + nrows)
            q = q_ref[0, rows]
            k = k_ref[0, lo:hi]
            v = v_ref[0, lo:hi]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (nrows, hi - lo) f32
            # (a row whose window has not reached these columns yet reads
            # all NEG_INF here: p = 1 against m = NEG_INF, and the first
            # strip with a key it sees — its own diagonal at the latest —
            # scales that away by corr = exp(NEG_INF - m) = 0)
            s = mask(s, strip)
            m_prev = m_ref[rows]  # (nrows, _LANE), lane-broadcast
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            p = jnp.exp(s - _cols(m_cur, hi - lo))
            corr = jnp.exp(m_prev - m_cur)  # (nrows, _LANE)
            l_ref[rows] = l_ref[rows] * corr + jnp.sum(p, axis=1)[:, None]
            acc_ref[rows] = acc_ref[rows] * _cols(corr, acc_ref.shape[1]) + \
                jax.lax.dot(p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
            m_ref[rows] = m_cur

    _per_body(i, j, nk * bk, bq, bk, causal, window, _compute)

    @pl.when(j == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / _cols(l, o_ref.shape[2])).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bq", "bk", "causal", "window",
                                    "interpret"))
def _flash_fwd(q, k, v, scale, bq, bk, causal, window, interpret):
    """q, k: (G, T, Dh_padded), v: (G, T, Dv_padded) (G = B·H folded; v's
    head size may differ from q/k's — latent attention scores at 192 and
    mixes values of 128). ``scale`` comes from the TRUE q/k head dim (the
    lane padding must not change the softmax temperature). Returns
    (o (G, T, Dv_padded), lse); lse is (G, T) — the kernel emits it
    lane-broadcast (G, T, _LANE) to satisfy Mosaic block tiling and the
    wrapper keeps lane 0."""
    g, t, dh = q.shape
    dv = v.shape[-1]
    nq, nk = t // bq, t // bk
    grid = (g, nq, nk)
    kern = functools.partial(_fwd_kernel, scale, nk, bq, bk, causal, window)
    kv_row = _kv_residency_map(bq, bk, causal, window)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, dh), kv_row),
            pl.BlockSpec((1, bk, dv), kv_row),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq, _LANE), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, dv), q.dtype),
            jax.ShapeDtypeStruct((g, t, _LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(scale, nq, nk, bq, bk, causal, window, has_dlse, *refs):
    """One (key block j, query block i) pair of the backward: the masked
    probability block P = exp(S - lse) and dS are built once, and all three
    gradients take their term from them. Grid (g, j, i), the query blocks
    innermost: dk and dv of block j sum over i in block-sized scratch and
    leave at the sweep's end; dq is crossed by the sweep, so its float32 sum
    is held whole for the head (``dq_acc``, (T, Dh)) and each slab leaves at
    the head's last key block. Each sum adds its blocks in ascending order
    (dq over j, dk / dv over i), as a pass of its own would."""
    if has_dlse:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dlse_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
    else:  # hot path (lse output unused): no dlse stream, no dead add
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
        dlse_ref = None
    j = pl.program_id(1)
    i = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(i * bq, bq), bq)  # block i of the head's dq

    @pl.when(j == 0)
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((bq, dq_acc.shape[1]), jnp.float32)

    @pl.when(i == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(strips, mask):
        for strip in strips:
            r0, nrows, lo, hi = strip[:4]
            rows = slice(r0, r0 + nrows)
            cols = slice(lo, hi)
            q = q_ref[0, rows]
            do = do_ref[0, rows]
            k = k_ref[0, cols]
            v = v_ref[0, cols]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32
            ) * scale
            s = mask(s, strip)
            p = jnp.exp(s - _cols(lse_ref[0, rows], hi - lo))  # f32
            dv_acc[cols] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
            )  # pᵀ · do -> (hi - lo, dv)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32
            )  # (nrows, hi - lo) f32
            # d lse_i / d s_ij = p_ij, so an lse cotangent adds p * dlse_i
            dsum = dp - _cols(dcap_ref[0, rows], hi - lo)
            if dlse_ref is not None:
                dsum = dsum + _cols(dlse_ref[0, rows], hi - lo)
            ds = (p * dsum).astype(q.dtype)
            dk_acc[cols] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # dsᵀ · q -> (hi - lo, dh)
            head_rows = pl.ds(pl.multiple_of(i * bq + r0, nrows), nrows)
            dq_acc[head_rows, :] += jax.lax.dot(
                ds, k, preferred_element_type=jnp.float32) * scale

    _per_body(i, j, nk * bk, bq, bk, causal, window, _compute)

    @pl.when(i == nq - 1)
    def _flush_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(j == nk - 1)
    def _flush_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


_VMEM_BYTES = 128 * 2 ** 20  # a v5e core's vector memory


def _bwd_vmem_bytes(t, dh, dv, bq, bk, itemsize, n_stats):
    """What one step of _bwd_kernel holds in VMEM: the head's dq (its
    float32 sum and the output block, double-buffered by the pipeline), the
    double-buffered blocks in (q, do and the row statistics; k, v) and out
    (dk, dv), the dk / dv sums, and the (bq, bk) float32 intermediates (s,
    p, dp, ds, the mask's two iotas, the operands' copies)."""
    dq = t * dh * (4 + 2 * itemsize)
    blocks = 2 * itemsize * (bq * (dh + dv) + 2 * bk * (dh + dv))
    stats = 2 * 4 * n_stats * bq * _LANE
    sums = 4 * bk * (dh + dv)
    return dq + blocks + stats + sums + 8 * 4 * bq * bk


@functools.partial(jax.jit,
                   static_argnames=("scale", "bq", "bk", "causal", "window",
                                    "interpret"))
def _flash_bwd(q, k, v, o, lse, do, dlse, scale, bq, bk, causal, window,
               interpret):
    """dq, dk, dv in one kernel (_bwd_kernel). dlse=None is the hot path
    (lse output unused): the kernel takes one fewer input stream and skips
    the dead add."""
    g, t, dh = q.shape
    dv = v.shape[-1]
    nq, nk = t // bq, t // bk
    dcap = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # lane-broadcast the per-row stats so their blocks tile (bq, _LANE)
    lse = jnp.broadcast_to(lse[..., None], (g, t, _LANE))
    dcap = jnp.broadcast_to(dcap[..., None], (g, t, _LANE))
    has_dlse = dlse is not None
    stats = [lse, dcap]
    if has_dlse:
        stats.append(jnp.broadcast_to(dlse.astype(jnp.float32)[..., None],
                                      (g, t, _LANE)))
    vmem = _bwd_vmem_bytes(t, dh, dv, bq, bk, q.dtype.itemsize, len(stats))
    if vmem > _VMEM_BYTES:
        raise ValueError(
            f"flash_attention backward: a head's dq (t={t}, dh={dh}) and the "
            f"blocks (bq={bq}, bk={bk}) want {vmem >> 20} MiB of the chip's "
            f"{_VMEM_BYTES >> 20} MiB vector memory — shard the sequence "
            f"(sp_attn=ring) or use attn_impl=dense for this shape")

    q_row = _q_residency_map(bq, bk, causal, window, nq)

    def k_row(g, j, i):
        return (g, j, 0)

    def head(g, j, i):
        return (g, 0, 0)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale, nq, nk, bq, bk, causal, window,
                          has_dlse),
        grid=(g, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_row),
            pl.BlockSpec((1, bk, dh), k_row),
            pl.BlockSpec((1, bk, dv), k_row),
            pl.BlockSpec((1, bq, dv), q_row),
            *[pl.BlockSpec((1, bq, _LANE), q_row)] * len(stats),
        ],
        out_specs=[
            pl.BlockSpec((1, t, dh), head),
            pl.BlockSpec((1, bk, dh), k_row),
            pl.BlockSpec((1, bk, dv), k_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, dh), q.dtype),
            jax.ShapeDtypeStruct((g, t, dh), k.dtype),
            jax.ShapeDtypeStruct((g, t, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, dh), jnp.float32),
            pltpu.VMEM((bk, dh), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem,
        ),
        interpret=interpret,
    )(q, k, v, do, *stats)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp cores on (G, T, Dh). Two variants sharing fwd/bwd kernels:
# _flash_core returns o only (the hot path — its backward has no dlse
# stream); _flash_core_lse returns (o, lse) with lse differentiable
# (d lse/d s = softmax), which is what lets the ring composition weight
# and merge per-hop outputs under grad.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, scale, bq, bk, causal, window, interpret):
    return _flash_fwd(q, k, v, scale, bq, bk, causal, window, interpret)[0]


def _flash_core_fwd(q, k, v, scale, bq, bk, causal, window, interpret):
    o, lse = _flash_fwd(q, k, v, scale, bq, bk, causal, window, interpret)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(scale, bq, bk, causal, window, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, None, scale, bq, bk, causal,
                      window, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core_lse(q, k, v, scale, bq, bk, causal, interpret):
    return _flash_fwd(q, k, v, scale, bq, bk, causal, None, interpret)


def _flash_core_lse_fwd(q, k, v, scale, bq, bk, causal, interpret):
    o, lse = _flash_fwd(q, k, v, scale, bq, bk, causal, None, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_core_lse_bwd(scale, bq, bk, causal, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _flash_bwd(q, k, v, o, lse, do, dlse, scale, bq, bk, causal,
                      None, interpret)


_flash_core_lse.defvjp(_flash_core_lse_fwd, _flash_core_lse_bwd)


# ---------------------------------------------------------------------------
# public entry — AttnFn contract of models/transformer.Block
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, window=None, block_q: int | None = None,
                    block_k: int = 1024, force=None,
                    interpret: bool = False):
    """Causal self-attention. q, k: (B, T, H, Dh), v: (B, T, H, Dv) — the
    Block contract with Dv == Dh, latent attention with Dh 192 against Dv
    128 (attention math upstream is f32; the kernel accumulates f32
    regardless); k and v may have fewer heads than q (grouped-query
    attention, ``spread_kv_heads``). Returns (B, T, H, Dv). ``window``: a
    query sees itself and the ``window - 1`` tokens before it (module
    docstring); None sees every earlier token. ``block_q`` None: ``BLOCK_Q``,
    under a window ``WINDOW_BLOCK_Q``.

    The causal mask is offset-invariant for self-attention (q and k share
    positions), so no offset argument is needed. Off-TPU (and not
    interpret/force) this is the dense streaming-softmax path; where the
    kernel is selected, a T that does not tile raises (_kernel_eligible).
    """
    from draco_tpu.parallel.ring_attention import dense_attention

    b, t, h, dh = q.shape
    k, v = spread_kv_heads(h, k, v)
    if block_q is None:
        block_q = BLOCK_Q if window is None else WINDOW_BLOCK_Q
    bq = _fit_block(block_q, t, lane_rule=False)
    bk = _fit_block(block_k, t, lane_rule=True)
    if window is not None and window < 1:
        raise ValueError(f"flash_attention: window={window} must be >= 1")
    if not _kernel_eligible(t, bq, bk, dh, force, interpret):
        return dense_attention(q, k, v, causal=True, window=window)
    return _run_folded(q, k, v, bq, bk, True, interpret, want_lse=False,
                       window=window)


def spread_kv_heads(heads: int, k, v):
    """Grouped-query heads: k, v (B, T, Hkv, D) with Hkv dividing ``heads``
    -> (B, T, heads, D), key/value head j serving query heads j·r ..
    j·r + r − 1. The kernels take one key/value head a query head; the
    copies are made here and autodiff sums their gradients back. Equal
    head counts pass through untouched."""
    r = heads // k.shape[2]
    if r == 1:
        return k, v
    return jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)


def runs_in_kernels(force=None, interpret: bool = False) -> bool:
    """Whether a call with these arguments takes the Pallas kernels (a
    shape that does not tile then raises) or the dense lowering."""
    return force if force is not None else (use_pallas() or interpret)


def _kernel_eligible(t, bq, bk, dh, force, interpret) -> bool:
    """Shared kernel-vs-dense dispatch for both public wrappers. Blocks
    (including T itself when it becomes the single block) must honour the
    8-sublane f32 tile, and key blocks wider than a lane tile must be whole
    lane tiles so the lane-broadcast row stats can tile across them (_cols).
    Where the kernel is selected — a TPU backend, ``force=True`` or
    interpret mode — a non-tiling shape raises: a caller who asked for the
    O(T·Dh)-memory kernel must not silently get the O(T²) dense path.
    False (the dense path) only when the kernel is not selected at all:
    off-TPU, or ``force=False``."""
    if not runs_in_kernels(force, interpret):
        return False
    if (bq < 8 or bk < 8  # _fit_block found no legal block (t % 8 != 0)
            or t % 8 or bq % 8 or bk % 8 or t % bq or t % bk
            or dh > 2 * _LANE or (bk > _LANE and bk % _LANE)):
        raise ValueError(
            f"flash_attention: shape does not tile for the kernel "
            f"(t={t}, bq={bq}, bk={bk}, dh={dh}; need t%8==0, t%bq==0, "
            f"t%bk==0, blocks%8==0, dh<={2 * _LANE}, and bk a multiple of "
            f"{_LANE} when bk>{_LANE}) — use attn_impl=dense for this shape")
    return True


def _run_folded(q, k, v, bq, bk, causal, interpret, want_lse, window=None):
    """(B,T,H,Dh) q, k and (B,T,H,Dv) v -> folded kernel call -> o
    (B,T,H,Dv), or (o, lse (B,T,H)) with a differentiable lse when
    want_lse. Each head size is padded to whole lane tiles on its own."""
    b, t, h, dh = q.shape
    dv = v.shape[-1]

    def fold(x):
        d = x.shape[-1]
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)  # (B,T,H,D)->(BH,T,D)
        if d % _LANE:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, _ceil_to(d, _LANE) - d)))
        return x

    args = (fold(q), fold(k), fold(v), 1.0 / (dh ** 0.5), bq, bk, causal)

    def unfold(o):
        return jnp.moveaxis(o[..., :dv].reshape(b, h, t, dv), 1, 2)

    if not want_lse:
        return unfold(_flash_core(*args, window, interpret))
    o, lse = _flash_core_lse(*args, interpret)
    return unfold(o), jnp.moveaxis(lse.reshape(b, h, t), 1, 2)  # (B, T, H)


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             block_q: int = 512, block_k: int = 1024,
                             force=None, interpret: bool = False):
    """(o, lse) pair for the ring composition (parallel/ring_attention.
    ring_flash_attention): lse is the per-row log-sum-exp in (B, T, H), and
    is differentiable (the kernels' VJP carries d lse/d s = softmax), which
    is what lets normalized per-hop outputs merge under grad. The dense
    streaming path (with lse) off-TPU; raises for a non-tiling shape where
    the kernel is selected."""
    from draco_tpu.parallel.ring_attention import dense_attention_lse

    b, t, h, dh = q.shape
    bq = _fit_block(block_q, t, lane_rule=False)
    bk = _fit_block(block_k, t, lane_rule=True)
    if not _kernel_eligible(t, bq, bk, dh, force, interpret):
        return dense_attention_lse(q, k, v, causal=causal)
    return _run_folded(q, k, v, bq, bk, causal, interpret, want_lse=True)


def attn_impl_fn(cfg):
    """cfg.attn_impl -> AttnFn for the single-shard LM paths (None = Block's
    dense default). One dispatch point shared by sp_step / pp_step. A model
    whose layers differ in their window hands each call its own
    (``window=``)."""
    return flash_attention if cfg.attn_impl == "flash" else None
