"""models/hybrid_moe.HybridMoeLM at a tiny size (hidden 64, 4 query heads on
2 key/value heads, 2 key and 4 value DeltaNet heads, 8 routed experts of
which 2 are held, one period of 3 DeltaNet layers and 1 gated-attention
layer) against the plain reference the benchmark compares it with on the
chip (benchmark/reference/nets/qwen3_next.py, which imports nothing of
draco_tpu and runs the delta rule token by token). What every
published-config block is held to alike — loss, logits, every leaf's
gradient, the 32 shares adding up, the shared expert layer, the refusals —
is tests/test_spec_lm_parity.py's; here is what is this block's own:

* the DeltaNet state counter, and a DeltaNet layer with the rule in the
  Pallas kernels (interpret mode) against the reference token by token;
* grouped-query heads through the flash kernel (interpret mode);
* every leaf of 128 elements or more of the PUBLISHED configuration starts
  and ends on a 128-wide line of the vote's stack (its sub-line leaves come
  last in ravel order), and an unravel of a row in lines is the flat one.

Tolerances: program and reference are float32 sums of the same terms in
another order (chunked against token by token, a dispatch buffer against a
dense mask, the flash-style softmax against the plain one): 2e-6 relative on
the loss, 2e-4 of a leaf's largest gradient entry (1e-3 through the rule's
kernels). Every compared value is one compiled program (tests/parity.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import qwen3_next as ref
from draco_tpu.models import hybrid_moe, latent_moe
from draco_tpu.models.hybrid_moe import HybridMoeLM
from draco_tpu.training.step import _make_unravel

SPEC = parity.tiny("hybrid-moe-tiny")
T = 80  # a chunk of 64 and a closing chunk of 16: the state crosses chunks
MOVED = ("centred_scale", "scale", "dt_bias")


def _tokens(seed=0, batch=2):
    return parity.tokens(SPEC["vocab_rows"], batch, T, seed)


@pytest.fixture(scope="module")
def model():
    lm = HybridMoeLM(SPEC)
    return lm, parity.moved(lm.init(jax.random.key(3)), jax.random.key(4),
                            MOVED)


def test_layers_are_of_two_kinds_and_the_shapes_say_so(model):
    lm, params = model
    assert lm.layer_types == ["linear_attention"] * 3 + ["full_attention"]
    shapes = jax.tree.leaves(lm.param_shapes(),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert [tuple(x.shape) for x in jax.tree.leaves(params)] == shapes
    assert "qkvz" in params["layer0"] and "q" not in params["layer0"]
    assert "q" in params["layer3"] and "qkvz" not in params["layer3"]
    # the router keeps its published width, no selection bias
    assert set(params["layer0"]["router"]) == {"kernel"}
    assert params["layer0"]["router"]["kernel"].shape[1] == \
        SPEC["num_experts"]
    assert params["linear_heads"]["A_log"].shape == (
        3, SPEC["linear_num_value_heads"])


def test_the_state_counter_grows_when_the_heads_forget_less(model):
    """``linattn_state_absmax`` reads the states the row leaves behind:
    with every head's decay rate cut by e^6 the states keep what they were
    written, and the counter rises."""
    lm, params = model
    toks = _tokens(3, batch=1)
    counters = jax.jit(lambda p: parity.mean_nll(lm, p, toks)[1])
    stats = counters(params)
    slow = dict(params, linear_heads=dict(
        params["linear_heads"],
        A_log=params["linear_heads"]["A_log"] - 6.0))
    held = counters(slow)
    assert float(held["linattn_state_absmax"]) > \
        float(stats["linattn_state_absmax"])


# the tiny block with DeltaNet heads of a whole lane tile and a row of two
# chunks: the shapes the rule's kernels take
LANE_SPEC = dict(SPEC, linear_key_head_dim=128, linear_value_head_dim=128)
LANE_T = 128


@pytest.fixture
def rule_in_kernels(monkeypatch):
    """The model's two names for the rule — the call and the question which
    path it takes — told ``interpret=True``: the kernels, off the chip."""
    for name in ("chunked_gated_delta_rule", "rule_runs_in_kernels"):
        monkeypatch.setattr(hybrid_moe, name, functools.partial(
            getattr(hybrid_moe, name), interpret=True))


def test_deltanet_layer_in_the_kernels_matches_the_reference(
        rule_in_kernels):
    """The layer's output and the gradient of its input, of every leaf and
    of the per-head vectors, the rule in the Pallas kernels (interpret
    mode), against ``qwen3_next.gated_deltanet`` token by token."""
    lm = HybridMoeLM(LANE_SPEC)
    params = parity.moved(lm.init(jax.random.key(11)), jax.random.key(12),
                          MOVED)
    p = params["layer1"]
    heads = (params["linear_heads"]["A_log"][1],
             params["linear_heads"]["dt_bias"][1])
    h = jax.random.normal(jax.random.key(13),
                          (LANE_T, SPEC["hidden_size"]))
    probe = jax.random.normal(jax.random.key(14), h.shape)

    def program(h, p, heads):
        out, (absmax, kernels) = lm._linear_attention(h[None], p, *heads)
        return jnp.sum(out[0] * probe), (out[0], absmax, kernels)

    def reference(h, p, heads):
        out = ref.gated_deltanet(h, p, heads, LANE_SPEC, lambda t: t)
        return jnp.sum(out * probe), out

    (_, (got, absmax, kernels)), g_got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1, 2), has_aux=True))(h, p, heads)
    (_, want), g_want = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True))(h, p, heads)
    assert float(kernels) == 1.0 and float(absmax) > 0.0
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))
    flat_want = jax.tree.leaves(g_want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            flat_want):
        # (the layer's leaves the mixer does not read take none, in both)
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-3 * scale + 1e-9, path
    mixer = ("qkvz", "ba", "conv", "out_norm", "out")
    assert all(float(jnp.max(jnp.abs(x))) > 0.0
               for key in mixer for x in jax.tree.leaves(g_got[1][key]))


def test_the_counter_counts_the_layers_whose_rule_ran_in_the_kernels(
        rule_in_kernels):
    lm = HybridMoeLM(LANE_SPEC)
    params = lm.init(jax.random.key(15))
    toks = _tokens(5, batch=1)[:, :64]
    _, stats = jax.jit(lm.hidden)(params, toks)
    assert float(stats["linattn_kernel_layers"]) == \
        lm.layer_types.count("linear_attention") == 3
    # the tiny block's own heads (16 wide) stay on the jax.numpy path
    small = HybridMoeLM(SPEC)
    _, stats = jax.jit(small.hidden)(small.init(jax.random.key(15)), toks)
    assert float(stats["linattn_kernel_layers"]) == 0.0


def test_flash_kernel_serves_grouped_query_heads():
    """Four query heads on two key/value heads through the kernel
    (interpret mode) against the plain lowering, outputs and gradients:
    a key/value head's gradient is the sum over the query heads it serves."""
    from draco_tpu.ops.flash_attention import flash_attention

    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 32, 4, 16))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 32, 2, 16))
            for i in (1, 2))

    def kernel(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               interpret=True)

    def plain(q, k, v):
        # query head j reads key/value head j // 2
        s = jnp.einsum("bqjid,bkjd->bjiqk", q.reshape(1, 32, 2, 2, 16),
                       k) * 16 ** -0.5
        mask = jnp.arange(32)[:, None] >= jnp.arange(32)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bjiqk,bkjd->bqjid", p, v).reshape(1, 32, 4, 16)

    def square(out):
        return jnp.sum(out ** 2)

    out, got = parity.with_gradients(kernel, square, (0, 1, 2))(q, k, v)
    want_out, want = parity.with_gradients(plain, square, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(out, want_out, atol=2e-6)
    np.testing.assert_allclose(latent_moe.dense_causal_attention(q, k, v),
                               want_out, atol=2e-6)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


# ---- the stack's lines --------------------------------------------------

def test_every_leaf_of_the_published_configuration_lies_on_the_lines():
    """The real leaf table through ``_make_unravel``: every leaf of 128
    elements or more starts and ends on a 128-wide line, so the winner's
    row is cut where it lies (PR 29); the two sub-line leaves (a row of 32
    a DeltaNet layer) come last and shift nothing."""
    from draco_tpu.parallel.sp_step import STACK_LANES

    lm = HybridMoeLM(parity.published("qwen3-next-80b-a3b-ep32"))
    shapes = jax.eval_shape(lm.init, jax.random.key(0))
    _, dim, offsets = _make_unravel(shapes)
    assert dim == 424_340_544
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(shapes)]
    off_lines = []
    for name, lo, hi in zip(paths, offsets[:-1], offsets[1:]):
        if lo % STACK_LANES or hi % STACK_LANES:
            off_lines.append(name)
            assert hi - lo < STACK_LANES, name
    assert off_lines == ["['linear_heads']['A_log']",
                         "['linear_heads']['dt_bias']"]
    assert paths[-2:] == off_lines


def test_unravel_of_a_row_in_lines_is_the_flat_unravel():
    """Leaves on the lines, a leaf under a line, and a leaf of several
    lines that starts off them: cut from a (d / 128, 128) row with zeros
    closing the last tile, each is what the flat vector gives."""
    shapes = {"a": (2, 128), "b": (32,), "c": (3, 100), "d": (128,)}
    params = jax.tree.map(lambda s: jnp.zeros(s), shapes,
                          is_leaf=lambda x: isinstance(x, tuple))
    unravel, dim, _ = _make_unravel(params)
    flat = jnp.arange(dim, dtype=jnp.float32)
    want = unravel(flat)
    rows = jnp.pad(flat, (0, -dim % 1024)).reshape(-1, 128)
    got = unravel(rows)
    for key in shapes:
        assert got[key].shape == shapes[key]
        np.testing.assert_array_equal(got[key], want[key])
    # no view of the whole row, flat: each off-line leaf reads its own lines
    text = jax.jit(unravel).lower(rows).as_text()
    assert f"tensor<{rows.size}xf32>" not in text
