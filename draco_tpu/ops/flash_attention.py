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

Block-causal skipping: grid steps with j > i (keys entirely in the future)
compute nothing (`pl.when`), so causal attention does ~half the block work.

Sliding window (``flash_attention(..., window=W)``): query t sees key s iff
0 <= t - s < W — itself and the W - 1 tokens before it. A (query block,
key block) pair is computed iff some pair of its positions satisfies BOTH
inequalities: the block's earliest key is no later than its latest query
(causality, as above) and its latest key is less than W before its earliest
query. Every other block is skipped from both sides, in the forward kernel
and in the backward kernel, and the residency maps clamp the block index
from both sides so that a skipped block is not fetched either; the two
inequalities are applied elementwise inside every computed block (only the
blocks at the two edges hold masked entries). ``window=None`` is the causal
program, unchanged.

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
BLOCK_Q = 512  # the query block's default limit
# under a window a query block computes the key blocks that its
# window + block_q - 1 keys touch: at W = 1024 two key blocks of 1024
# whether it holds 512 queries or 1024, so the taller block halves the grid
# steps a query pays (a layer-lane of 32 heads at T = 8192 on the chip:
# forward 3.31 -> 2.75 ms, forward + backward 12.79 -> 11.15; PERF.md
# section 6, PR 35)
WINDOW_BLOCK_Q = 1024


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

    @pl.when(_computed(i, j, bq, bk, causal, window))
    def _compute():
        # matmuls take the input dtype (bf16 inputs ride the fast MXU pass)
        # and accumulate f32 via preferred_element_type — the flash standard;
        # all softmax/accumulator algebra stays f32
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk) f32
        # (a row whose window has not reached this block yet reads all
        # NEG_INF here: p = 1 against m = NEG_INF, and the first block with
        # a key it sees — its own diagonal at the latest — scales that away
        # by corr = exp(NEG_INF - m) = 0)
        s = _masked(s, i, j, causal, window)
        m_prev = m_ref[...]  # (bq, _LANE), lane-broadcast
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _cols(m_cur, bk))
        corr = jnp.exp(m_prev - m_cur)  # (bq, _LANE)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * _cols(corr, acc_ref.shape[1]) + \
            jax.lax.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

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

    @pl.when(_computed(i, j, bq, bk, causal, window))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = _masked(s, i, j, causal, window)
        p = jnp.exp(s - _cols(lse_ref[0], bk))  # (bq, bk) f32
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )  # pᵀ · do -> (bk, dv)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk) f32
        # d lse_i / d s_ij = p_ij, so an lse cotangent adds p * dlse_i
        dsum = dp - _cols(dcap_ref[0], bk)
        if dlse_ref is not None:
            dsum = dsum + _cols(dlse_ref[0], bk)
        ds = (p * dsum).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # dsᵀ · q -> (bk, dh)
        dq_acc[rows, :] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

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
