"""All-to-all (Ulysses-style) sequence-parallel exact attention.

The second of the two standard sequence-parallelism strategies (ring
attention being the first — draco_tpu/parallel/ring_attention.py): instead
of streaming K/V blocks around a ring, one ``lax.all_to_all`` trades the
sequence shard for a head shard — every device then holds the FULL sequence
for ``H/sp`` heads, runs ordinary dense attention locally (heads are
embarrassingly parallel), and a second all_to_all restores the sequence
layout. Two collectives total, independent of sequence length, vs the
ring's ``sp`` ppermute hops — the better trade when heads are plentiful and
the per-device full-sequence score block fits memory; ring wins at extreme
T where O(T·T/sp) scores must never materialise.

Both strategies are exact (bitwise-comparable to dense attention up to f32
reduction order) and reverse-differentiable: all_to_all is linear and its
transpose is the inverse all_to_all, so per-shard gradients psum into exact
per-worker gradients for the coded-DP layer above (sp_step.py), same as the
ring.

No reference counterpart: the reference is CNN-only (SURVEY.md §5.7); this
axis is the TPU build's long-context capability.
"""

from __future__ import annotations

from typing import Optional

from jax import lax

from draco_tpu.parallel.ring_attention import dense_attention



def a2a_attention(
    q,
    k,
    v,
    axis_name: Optional[str],
    causal: bool = True,
    inner=None,
):
    """Exact attention over sequence shards via head-scatter all_to_all.

    q, k, v: (B, T_local, H, Dh) — this shard's block of the sequence, all
    H heads. H must be divisible by the ``axis_name`` mesh-axis size. Must
    be called inside ``shard_map``; with ``axis_name=None`` it degrades to
    single-shard dense attention.

    ``inner``: the full-sequence attention run on each device's head group
    after the scatter — defaults to dense causal attention; pass the flash
    kernel (ops/flash_attention.py) to remove the (T, T) score block this
    strategy otherwise materialises (causal-only contract: (q, k, v) -> o).
    """
    if axis_name is None:
        return (inner(q, k, v) if inner is not None
                else dense_attention(q, k, v, causal=causal))

    sp = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % sp:
        raise ValueError(f"a2a_attention: heads {h} not divisible by sp={sp}")

    # sequence-sharded, all heads  ->  full sequence, H/sp heads.
    # tiled all_to_all splits axis 2 (heads) into sp chunks, one per peer,
    # and concatenates the received chunks along axis 1 (sequence); peers
    # arrive in axis order, so concatenation restores sequence order.
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)

    # full-sequence attention on this device's head group. With the dense
    # default the whole (T, T) score block materialises per head group —
    # the strategy's known memory trade; the flash inner removes it.
    if inner is not None:
        oh = inner(qh, kh, vh)
    else:
        oh = dense_attention(qh, kh, vh, causal=causal)

    # full sequence, H/sp heads  ->  sequence-sharded, all heads
    return lax.all_to_all(oh, axis_name, split_axis=1, concat_axis=2, tiled=True)
