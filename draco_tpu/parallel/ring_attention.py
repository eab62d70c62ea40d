"""Ring attention — sequence-parallel exact attention over a device ring.

Each ``sp``-shard holds a contiguous block of the sequence. Q stays put; K/V
blocks travel the ring via ``lax.ppermute`` (one ICI hop per step), and every
shard folds each visiting block into a numerically-stable streaming softmax
(flash-attention accumulators m/l/o). After ``sp`` steps every query has seen
every key exactly once — exact attention, O(T/sp) memory per chip, comm
overlapped by XLA with the block einsums.

Written with ``lax.scan`` so the whole ring is reverse-differentiable
(``ppermute`` is linear; its transpose is the inverted ring), which is what
lets per-shard gradients psum over ``sp`` into exact per-worker gradients for
the coded-DP layer above (draco_tpu/parallel/sp_step.py).

No reference counterpart: the reference is CNN-only (SURVEY.md §5.7); this
axis is the TPU build's long-context capability.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, k_pos, scale, causal, o, m, l, window=None):
    """Fold one K/V block into the streaming-softmax accumulators.

    q: (B, Tq, H, Dh); k, v: (B, Tk, H, Dh); q_pos: (Tq,), k_pos: (Tk,)
    o: (B, Tq, H, Dh) accumulator, m, l: (B, Tq, H) running max / normaliser.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # (Tq, Tk)
        if window is not None:  # a key `window` or more before its query
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)  # (B, H, Tq)
    m_blk = jnp.moveaxis(m_blk, 1, 2)  # (B, Tq, H)
    m_new = jnp.maximum(m, m_blk)
    # exp of masked-everything rows stays 0 through the NEG_INF offset
    p = jnp.exp(s - jnp.moveaxis(m_new, 1, 2)[:, :, :, None])  # (B, H, Tq, Tk)
    corr = jnp.exp(m - m_new)  # (B, Tq, H)
    l_new = l * corr + jnp.moveaxis(jnp.sum(p, axis=-1), 1, 2)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v, preferred_element_type=jnp.float32)
    o_new = o * corr[..., None] + pv
    return o_new, m_new, l_new


def dense_attention(q, k, v, q_offset=0, k_offset=0, causal: bool = True,
                    window=None):
    """Single-shard exact attention with the same streaming accumulators.
    ``window`` (causal only): a query sees itself and the ``window - 1``
    positions before it.

    Used as the sp=1 fallback and as the oracle in tests.
    """
    return dense_attention_lse(q, k, v, q_offset, k_offset, causal,
                               window)[0]


def dense_attention_lse(q, k, v, q_offset=0, k_offset=0, causal: bool = True,
                        window=None):
    """dense_attention that also returns the per-row log-sum-exp (B, T, H)
    f32 — the dense counterpart of ops/flash_attention.flash_attention_with_lse
    (its off-TPU / non-tiling fallback, and the small-shape oracle)."""
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    scale = 1.0 / (dh**0.5)
    q_pos = q_offset + jnp.arange(tq)
    k_pos = k_offset + jnp.arange(tk)
    # v's head size may differ from q/k's (latent attention: 192 vs 128)
    o = jnp.zeros((b, tq, h, v.shape[-1]), jnp.float32)
    m = jnp.full((b, tq, h), NEG_INF, jnp.float32)
    l = jnp.zeros((b, tq, h), jnp.float32)
    if window is not None and not causal:
        raise ValueError("dense_attention: a window needs causal=True")
    o, m, l = _block_attn(q, k, v, q_pos, k_pos, scale, causal, o, m, l,
                          window)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype), lse


def ring_flash_attention(
    q,
    k,
    v,
    axis_name: Optional[str],
    causal: bool = True,
    attn_with_lse=None,
):
    """Ring attention with a blockwise-kernel inner: O(T_local·Dh) memory at
    BOTH levels. The plain ring (ring_attention) streams K/V blocks across
    chips but each hop still materialises the (T_local, T_local) score block
    on-chip; here every hop runs the flash kernel (ops/flash_attention) —
    causal for the self hop, non-causal for fully-visible past-owner hops,
    skipped entirely (lax.cond) for future owners — and the normalized
    per-hop (o, lse) pairs merge by log-sum-exp weights. The kernel's lse
    output is differentiable, so the merge backpropagates exactly.

    Same contract as ring_attention: (B, T_local, H, Dh) per shard, called
    inside shard_map; axis_name=None degrades to the single-shard kernel.
    """
    if attn_with_lse is None:
        from draco_tpu.ops.flash_attention import flash_attention_with_lse

        attn_with_lse = flash_attention_with_lse
    if axis_name is None:
        o, _ = attn_with_lse(q, k, v, causal=causal)
        return o

    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # hop 0: this shard's own block (the only hop needing the causal mask)
    o0, lse0 = attn_with_lse(q, k, v, causal=causal)

    def hop(carry, r):
        o_acc, lse_acc, k_prev, v_prev = carry
        # permute at hop START: after r hops this shard holds the block
        # owned by (idx - r) mod sp, and the final hop's blocks are used
        # (a trailing permute would be sp-th = wasted ICI traffic)
        k_blk = lax.ppermute(k_prev, axis_name, perm)
        v_blk = lax.ppermute(v_prev, axis_name, perm)
        owner = (idx - r) % sp
        # causal ring: a visiting block is visible iff its owner precedes
        # this shard (then it is FULLY visible — no mask needed); the
        # non-causal ring sees every block
        visible = (owner < idx) | jnp.asarray(not causal)

        def seen(_):
            o_h, lse_h = attn_with_lse(q, k_blk, v_blk, causal=False)
            return o_h.astype(jnp.float32), lse_h

        def skipped(_):
            return (jnp.zeros(q.shape, jnp.float32),
                    jnp.full(q.shape[:2] + (q.shape[2],), NEG_INF,
                             jnp.float32))

        o_h, lse_h = lax.cond(visible, seen, skipped, None)
        lse_new = jnp.logaddexp(lse_acc, lse_h)
        w1 = jnp.exp(lse_acc - lse_new)
        w2 = jnp.exp(lse_h - lse_new)
        o_new = o_acc * w1[..., None] + o_h * w2[..., None]
        return (o_new, lse_new, k_blk, v_blk), None

    carry = (o0.astype(jnp.float32), lse0, k, v)
    (o, _, _, _), _ = lax.scan(hop, carry, jnp.arange(1, sp))
    return o.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    axis_name: Optional[str],
    causal: bool = True,
):
    """Exact attention over sequence shards laid out on mesh axis ``axis_name``.

    q, k, v: (B, T_local, H, Dh) — this shard's block of the sequence. Must be
    called inside ``shard_map`` (or any context where ``axis_name`` is bound).
    With ``axis_name=None`` it degrades to single-shard dense attention.
    """
    if axis_name is None:
        return dense_attention(q, k, v, causal=causal)

    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, t, h, dh = q.shape
    scale = 1.0 / (dh**0.5)
    q_pos = idx * t + jnp.arange(t)

    o0 = jnp.zeros((b, t, h, dh), jnp.float32)
    m0 = jnp.full((b, t, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, t, h), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # hop 0 (own block) outside the scan so every scan iteration permutes
    # FIRST and the final hop's blocks are used — no trailing wasted permute
    o0, m0, l0 = _block_attn(q, k, v, q_pos, q_pos, scale, causal, o0, m0, l0)

    def ring_step(carry, r):
        o, m, l, k_prev, v_prev = carry
        k_blk = lax.ppermute(k_prev, axis_name, perm)
        v_blk = lax.ppermute(v_prev, axis_name, perm)
        # after r hops this shard holds the block owned by (idx - r) mod sp
        owner = (idx - r) % sp
        k_pos = owner * t + jnp.arange(t)
        o, m, l = _block_attn(q, k_blk, v_blk, q_pos, k_pos, scale, causal, o, m, l)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = lax.scan(ring_step, (o0, m0, l0, k, v),
                                  jnp.arange(1, sp))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
