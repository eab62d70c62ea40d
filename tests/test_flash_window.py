"""The sliding window of ops/flash_attention.py: query t sees key s iff
0 <= t - s < W.

* the edge, exactly: a key W - 1 back moves the output, a key W back does
  not — in the kernel (interpret mode), in the dense lowering and in the
  models' plain lowering — for T below, equal to and no multiple of W, and
  W no multiple of a block;
* the kernel against ``dense_attention(window=W)``: output and all three
  gradients, grouped-query heads included, over block shapes that put the
  window's edge inside a block, on a block's border and across several;
* the blocks the kernels compute and the blocks the residency maps fetch
  are the same, and are the ones that hold a visible pair — counted against
  a brute-force mask;
* ``window=None`` is the causal program: the same jaxpr as a call that
  never heard of the argument, and the same bits out.

Tolerances: kernel and dense path are float32 sums of the same terms in
another order (blocks of keys against all keys at once): 2e-6 absolute on
outputs of order one, 2e-5 on gradients of order ten.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu.models.latent_moe import dense_causal_attention
from draco_tpu.ops import flash_attention as fa
from draco_tpu.ops.flash_attention import flash_attention
from draco_tpu.parallel.ring_attention import dense_attention


def _qkv(t, heads=2, kv=None, dh=16, seed=0):
    key = jax.random.key(seed)
    q = jax.random.normal(key, (1, t, heads, dh))
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (1, t, kv or heads, dh)) for i in (1, 2))
    return q, k, v


def _by_the_two_inequalities(q, k, v, window):
    """softmax over the keys s with 0 <= t - s < window, written out."""
    t = q.shape[1]
    k, v = fa.spread_kv_heads(q.shape[2], k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (0 <= back) & (back < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


IMPLS = {
    "kernel": lambda q, k, v, w: flash_attention(
        q, k, v, window=w, block_q=16, block_k=16, interpret=True),
    "dense": lambda q, k, v, w: dense_attention(q, k, v, window=w),
    "model_plain": lambda q, k, v, w: dense_causal_attention(q, k, v,
                                                             window=w),
    "off_tpu_fallback": lambda q, k, v, w: flash_attention(q, k, v,
                                                           window=w),
}


# T below W, equal to W, a multiple of W, no multiple of W; W = 24 is no
# multiple of the 16-wide blocks
@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("t,window", [(16, 24), (32, 32), (64, 32),
                                      (48, 24), (80, 24), (64, 1)])
def test_the_windows_edge_is_exact(impl, t, window):
    """The last query's output moves when the key W - 1 before it moves
    and does not when the key W before it does (nor when any earlier one
    does); it is the two inequalities' softmax everywhere."""
    q, k, v = _qkv(t)
    fn = IMPLS[impl]
    if impl == "kernel":  # one compiled program, four calls
        fn = jax.jit(fn, static_argnums=3)
    out = fn(q, k, v, window)
    np.testing.assert_allclose(
        out, _by_the_two_inequalities(q, k, v, window), atol=2e-6)
    last = t - 1

    def moved(s):
        bumped = v.at[0, s].add(10.0)
        return float(jnp.max(jnp.abs(fn(q, k, bumped, window)[0, last]
                                     - out[0, last])))

    inside = last - (window - 1)
    if inside >= 0:
        assert moved(inside) > 1e-3  # t - s = W - 1: seen
    for outside in (last - window, 0):
        if 0 <= outside < inside:
            assert moved(outside) == 0.0  # t - s >= W: not seen


@pytest.mark.parametrize("t,window,bq,bk,heads,kv", [
    (64, 16, 16, 16, 2, 2),   # the edge on a block's border
    (64, 24, 16, 32, 4, 2),   # inside a block, bq != bk, grouped-query
    (96, 40, 32, 16, 2, 1),   # across several key blocks
    (64, 100, 16, 16, 2, 2),  # a window longer than the row: plain causal
    (256, 100, 32, 128, 2, 2),  # a key block of a whole lane tile
    # bq != bk, a window that is a multiple of neither, grouped-query: three
    # to four query blocks cross a key block's sweep and two to three key
    # blocks add into a query block's dq, in the one backward kernel
    (256, 72, 32, 64, 4, 2),
    (256, 72, 64, 32, 4, 1),
])
def test_kernel_matches_dense_forward_and_all_three_gradients(
        t, window, bq, bk, heads, kv):
    q, k, v = _qkv(t, heads, kv, seed=t + window)

    def kernel(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=bq,
                               block_k=bk, interpret=True)

    def dense(q, k, v):
        return dense_attention(q, *fa.spread_kv_heads(heads, k, v),
                               window=window)

    def square(out):
        return jnp.sum(out ** 2)

    out, got = parity.with_gradients(kernel, square, (0, 1, 2))(q, k, v)
    want_out, want = parity.with_gradients(dense, square, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(out, want_out, atol=2e-6)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("t,window,bq,bk", [
    (8192, 1024, fa.WINDOW_BLOCK_Q, 1024),  # the benchmark's cell
    (256, 100, 32, 64), (256, 64, 64, 32), (128, 1, 16, 16),
    (192, 500, 64, 32),
])
def test_blocks_computed_and_fetched_are_those_with_a_visible_pair(
        t, window, bq, bk):
    """The kernels' guard against a brute-force mask, block by block; the
    two residency maps fetch exactly the computed blocks (outside them the
    index repeats a computed block's, which costs no copy)."""
    nq, nk = t // bq, t // bk
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (0 <= back) & (back < window)
    want = seen.reshape(nq, bq, nk, bk).any(axis=(1, 3))
    got = np.array([[bool(fa._computed(i, j, bq, bk, True, window))
                     for j in range(nk)] for i in range(nq)])
    np.testing.assert_array_equal(got, want)
    kv_row = fa._kv_residency_map(bq, bk, True, window)
    q_row = fa._q_residency_map(bq, bk, True, window, nq)
    for i, j in itertools.product(range(nq), range(nk)):
        fetched_k = int(kv_row(0, i, j)[1])
        fetched_q = int(q_row(0, j, i)[1])
        assert want[i, fetched_k] and want[fetched_q, j]
        if want[i, j]:
            assert fetched_k == j and fetched_q == i
    if (t, window) == (8192, 1024):
        # two key blocks a query block where causal alone has up to eight
        assert want.sum(axis=1).max() == 2
        causal = np.array([[bool(fa._computed(i, j, bq, bk, True, None))
                            for j in range(nk)] for i in range(nq)])
        assert causal.sum(axis=1).max() == 8
        assert want.sum() / causal.sum() < 0.45


# (T, bq, bk, window) of the cells' kernels: causal at 4 096 (kanana2, ouro,
# lfm2, qwen3next) and at 8 192 (mellum2's full layer), mellum2's window
CELL_GEOMETRIES = {
    "causal_t4096": (4096, fa.BLOCK_Q, 1024, None),
    "causal_t8192": (8192, fa.BLOCK_Q, 1024, None),
    "window_1024_t8192": (8192, fa.WINDOW_BLOCK_Q, 1024, 1024),
}
# the rectangle rule's entries a head (every computed block pair whole) and
# the pairs the mask lets through
RECTANGLES = {"causal_t4096": (10_485_760, 8_390_656),
              "causal_t8192": (37_748_736, 33_558_528),
              "window_1024_t8192": (15_728_640, 7_864_832)}


@pytest.mark.parametrize("name", sorted(CELL_GEOMETRIES))
def test_computed_pairs_fall_below_the_rectangles_and_not_below_the_seen(
        name):
    t, bq, bk, window = CELL_GEOMETRIES[name]
    rectangle, seen = RECTANGLES[name]
    # (causal 1 024 x 1 024 and 512 x 1 024 rectangles cover the same area)
    assert fa.computed_pairs(t, bq, bk, window, sub_tile=0) == rectangle
    assert fa.computed_pairs(t, 512, bk, window, sub_tile=0) == rectangle
    assert fa.seen_pairs(t, window) == seen
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    assert seen == ((0 <= back) & (back < (window or t))).sum()
    assert seen <= fa.computed_pairs(t, bq, bk, window) < rectangle
    # a finer sub-tile never computes more
    counts = [fa.computed_pairs(t, bq, bk, window, sub_tile=s)
              for s in (0, 512, 256, 128)]
    assert counts == sorted(counts, reverse=True) and counts[-1] >= seen


@pytest.mark.parametrize("t,bq,bk,window", [
    *CELL_GEOMETRIES.values(),
    (4096, 512, 1024, None),  # the ring's own-shard hop, the blocks to PR 42
    (2048, 512, 1024, 512), (1024, 256, 512, 256),
    (2048, 512, 1024, 1536),  # interior pairs under a window
])
def test_no_seen_pair_lies_outside_a_computed_sub_tile(t, bq, bk, window):
    """The generated bodies' strips, laid over the (T, T) plane by the
    offset that selects each, against the dense mask: every seen pair is in
    a computed strip; a strip without a mask holds seen pairs only; a strip
    holds a seen pair in its first and in its last sub-tile column (the
    range is not wider than the mask makes it); and the entries counted are
    ``computed_pairs``."""
    sub = fa.SUB_TILE
    bodies = fa._bodies(t, bq, bk, True, window, sub)
    assert bodies is not None and len(bodies) <= fa._MAX_CUT_BODIES + 1
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (0 <= back) & (back < (window or t))
    computed = np.zeros((t, t), bool)
    taken = set()
    for i, j in itertools.product(range(t // bq), range(t // bk)):
        if not fa._computed(i, j, bq, bk, True, window):
            continue
        d = i * bq - j * bk
        key = None if fa._interior(d, bq, bk, window) else d
        taken.add(key)
        for r0, rows, lo, hi, causal_cut, window_cut in bodies[key]:
            assert lo % sub == 0 and hi % sub == 0 and lo < hi <= bk
            tile = (slice(i * bq + r0, i * bq + r0 + rows),
                    slice(j * bk + lo, j * bk + hi))
            assert not computed[tile].any()  # no entry multiplied twice
            computed[tile] = True
            if not (causal_cut or window_cut):
                assert seen[tile].all()
            assert seen[tile][:, :sub].any() and seen[tile][:, -sub:].any()
            # each inequality is applied where it can fail, and only there
            assert causal_cut == (back[tile] < 0).any()
            assert window_cut == (back[tile] >= (window or t + 1)).any()
    assert not (seen & ~computed).any()
    assert taken == set(bodies)  # no body that no grid step takes
    assert computed.sum() == fa.computed_pairs(t, bq, bk, window)


@pytest.mark.parametrize("t,bq,bk,causal,window", [
    (2048, 1024, 1024, True, 1000),  # a window of no whole sub-tiles
    (1024, 512, 1024, False, None),  # the ring's fully visible hops
    (256, 64, 128, True, None), (768, 384, 768, True, None),  # odd blocks
    (4096, 256, 4096, True, None),  # more cut offsets than bodies allowed
])
def test_the_rectangle_rule_stays_where_sub_tiles_do_not_fit(
        t, bq, bk, causal, window):
    assert fa._bodies(t, bq, bk, causal, window, fa.SUB_TILE) is None
    if causal:
        assert fa.computed_pairs(t, bq, bk, window) == fa.computed_pairs(
            t, bq, bk, window, sub_tile=0)


def test_window_none_is_the_causal_program_bit_for_bit():
    """No window: the jaxpr of today's call, kernel bodies included, and
    the same bits as a window too long to hide a key."""
    q, k, v = _qkv(64, 4, 2)

    def causal(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=32,
                               interpret=True)

    def none(q, k, v):
        return flash_attention(q, k, v, window=None, block_q=16, block_k=32,
                               interpret=True)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                        argnums=(0, 1, 2))

    assert str(jax.make_jaxpr(grads(none))(q, k, v)) == str(
        jax.make_jaxpr(grads(causal))(q, k, v))
    # the causal kernel carries no trace of the window's second inequality
    text = str(jax.make_jaxpr(grads(causal))(q, k, v))
    windowed = str(jax.make_jaxpr(grads(lambda q, k, v: flash_attention(
        q, k, v, window=24, block_q=16, block_k=32, interpret=True)))(
            q, k, v))
    assert text.count(" lt ") < windowed.count(" lt ")
    np.testing.assert_array_equal(none(q, k, v), causal(q, k, v))
    np.testing.assert_array_equal(
        flash_attention(q, k, v, window=64, block_q=16, block_k=32,
                        interpret=True), causal(q, k, v))
    for a, b in zip(grads(none)(q, k, v), grads(causal)(q, k, v)):
        np.testing.assert_array_equal(a, b)
    # off the chip too: the dense lowering's text without the argument
    assert str(jax.make_jaxpr(lambda *a: dense_attention(*a, window=None))(
        q, q, q)) == str(jax.make_jaxpr(dense_attention)(q, q, q))


def test_a_window_below_one_and_a_window_without_causality_are_refused():
    q, k, v = _qkv(16)
    with pytest.raises(ValueError, match="window=0"):
        flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, k, v, causal=False, window=4)
