"""models/conv_moe.ShortConvMoeLM at a tiny size (hidden 64, 4 query heads
on 2 key/value heads of 16, 3-tap gated convolutions, five of six published
layers kept — the dense conv layer 0, then attention and three conv layers
with 8 routed experts top-2 of which 2 are held —, a 64-row embedding that
is the head too) against the plain reference the benchmark compares it with
on the chip (benchmark/reference/nets/lfm2.py, which imports nothing of
draco_tpu, writes the convolution as its shifted sums and masks every key
explicitly). What every published-config block is held to alike — loss,
logits, every leaf's gradient and three plain SGD steps' parameters (for a
conv-only, an attention-only and the kept five-layer pattern), the 4 shares
adding up, the shared expert layer, the refusals — is
tests/test_spec_lm_parity.py's; here is what is this block's own:

* the convolution is causal (changing token t changes no row before t) and
  its taps' gradient matches finite differences;
* the tied leaf's gradient is the sum of its two uses: an untied twin's
  embedding gradient plus its head's, transposed;
* the published share runs over every token, the attention layer through
  the flash kernel (interpret mode) is the reference's, and every leaf of
  the published configuration lies on the stack's lines.

Tolerances: program and reference are float32 sums of the same terms in
another order: 2e-6 relative on the loss, 2e-5 absolute on logits of order
one, 2e-4 of a leaf's largest gradient entry.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import lfm2 as ref
from draco_tpu.models import conv_moe, latent_moe
from draco_tpu.models.conv_moe import ShortConvMoeLM
from draco_tpu.models.hybrid_moe import causal_depthwise_conv

SPEC = parity.tiny("conv-moe-tiny")
T = 48
# the dense conv layer and a sparse conv layer: no attention at all
CONV_ONLY = dict(SPEC, layers=2, layers_held=[0, 3])


def _published():
    return parity.published("lfm2-8b-a1b-ep4")


def _tokens(seed=0, batch=2, t=T):
    return parity.tokens(SPEC["vocab_rows"], batch, t, seed)


def _model(spec, seed=3, **kw):
    lm = ShortConvMoeLM(spec, **kw)
    return lm, parity.moved(lm.init(jax.random.key(seed)),
                            jax.random.key(seed + 1), ("scale",))


@pytest.fixture(scope="module")
def model():
    return _model(SPEC)


def test_layer_kinds_are_read_by_index_and_the_tree_has_no_head(model):
    lm, params = model
    assert lm.layer_types == ["conv", "full_attention", "conv", "conv",
                              "conv"]
    assert lm.dense_layers == [True, False, False, False, False]
    assert lm.tied_head and "head" not in params
    shapes = jax.tree.leaves(lm.param_shapes(),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert [tuple(x.shape) for x in jax.tree.leaves(params)] == shapes
    d = SPEC["hidden_size"]
    assert set(params["layer0"]) == {"in_proj", "conv", "out_proj",
                                     "operator_norm", "ffn_norm", "mlp"}
    assert set(params["layer1"]) == {"q", "k", "v", "o", "operator_norm",
                                     "ffn_norm", "router", "experts"}
    assert params["layer0"]["in_proj"]["kernel"].shape == (d, 3 * d)
    assert params["layer0"]["conv"]["taps"].shape == (3, d)
    # the router keeps its published width; no shared expert anywhere
    assert params["layer1"]["router"]["kernel"].shape == (d, 8)
    assert lm.moe.shared is None and lm.moe.scoring == "sigmoid"
    assert not any("shared" in p for p in params.values())
    # what is under a line wide stands after every layer in ravel order
    assert list(params)[-2:] == ["qk_norm", "router_bias"]
    assert params["qk_norm"]["q"]["scale"].shape == (1, 16)
    assert params["router_bias"]["expert_bias"].shape == (4, 8)
    # seeded: taps at variance 1/3, the tied matrix at the matrices' std
    fresh = lm.init(jax.random.key(0))
    assert float(jnp.std(fresh["embed"]["embedding"])) == pytest.approx(
        0.02, rel=0.1)
    assert float(jnp.std(fresh["layer0"]["conv"]["taps"])) == pytest.approx(
        3 ** -0.5, rel=0.2)


# ---- the convolution ------------------------------------------------------

def test_the_convolution_is_causal(model):
    """Changing token t changes no row of the stream before t — through the
    whole conv-only model, taps and gates included."""
    lm, params = _model(CONV_ONLY)
    toks = _tokens(5, batch=1)
    hidden = jax.jit(lm.hidden)
    base, _ = hidden(params, toks)
    at = 20
    other = toks.at[0, at].set((toks[0, at] + 1) % SPEC["vocab_rows"])
    moved, _ = hidden(params, other)
    np.testing.assert_array_equal(base[0, :at], moved[0, :at])
    assert float(jnp.max(jnp.abs(base[0, at] - moved[0, at]))) > 0.0
    # three taps: the change reaches rows t .. t + 2 a conv layer, and the
    # first row it cannot reach by the taps alone is moved by nothing else
    u = jax.random.normal(jax.random.key(0), (1, T, 8))
    taps = jax.random.normal(jax.random.key(1), (3, 8))
    v = causal_depthwise_conv(u, taps)
    v2 = causal_depthwise_conv(u.at[0, at].add(1.0), taps)
    changed = np.flatnonzero(np.asarray(jnp.any(v != v2, axis=-1)[0]))
    assert changed.tolist() == [at, at + 1, at + 2]
    # v_t = taps[2] u_t + taps[1] u_{t-1} + taps[0] u_{t-2}, zeros before 0
    np.testing.assert_allclose(v[0, 0], taps[2] * u[0, 0], rtol=1e-6)
    np.testing.assert_allclose(
        v[0, 5], taps[2] * u[0, 5] + taps[1] * u[0, 4] + taps[0] * u[0, 3],
        rtol=1e-5)


def test_the_taps_gradient_matches_finite_differences(model):
    lm, params = model
    toks = _tokens(6, batch=1)
    layer, eps = "layer2", 1e-2

    @jax.jit
    def loss_at(taps):
        p = dict(params, **{layer: dict(params[layer], conv={"taps": taps})})
        return parity.mean_nll(lm, p, toks)[0]

    taps = params[layer]["conv"]["taps"]
    grad = jax.jit(jax.grad(loss_at))(taps)
    # the entries the loss leans on most, one of each tap
    for j in range(3):
        c = int(jnp.argmax(jnp.abs(grad[j])))
        step = jnp.zeros_like(taps).at[j, c].set(eps)
        fd = (float(loss_at(taps + step)) - float(loss_at(taps - step))) / (
            2 * eps)
        assert fd == pytest.approx(float(grad[j, c]), rel=0.05, abs=1e-6)


def test_the_operator_is_two_products_and_no_activation(model):
    """[B | C | X] are contiguous thirds in this order: y = (C . conv(B . X))
    W_out. Doubling B's columns doubles y; so does doubling C's or X's."""
    lm, params = model
    p = params["layer0"]
    d = SPEC["hidden_size"]
    h = jax.random.normal(jax.random.key(2), (1, T, d))
    base, peak = lm._short_conv(h, p)
    w = p["in_proj"]["kernel"]
    for lo in (0, d, 2 * d):
        doubled = dict(p, in_proj={"kernel": w.at[:, lo:lo + d].multiply(2)})
        np.testing.assert_allclose(lm._short_conv(h, doubled)[0], 2 * base,
                                   rtol=2e-5, atol=1e-7)
    # the counter's value: max |C . v| before the output projection
    bcx = h @ w
    gated = bcx[..., d:2 * d] * causal_depthwise_conv(
        bcx[..., :d] * bcx[..., 2 * d:], p["conv"]["taps"])
    assert float(peak) == pytest.approx(float(jnp.max(jnp.abs(gated))),
                                        rel=1e-6)
    np.testing.assert_allclose(base, gated @ p["out_proj"]["kernel"],
                               rtol=1e-5, atol=1e-7)


# ---- the tied head --------------------------------------------------------

def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(model):
    """An untied twin (``tie_word_embeddings`` false: a ``head`` leaf) whose
    head is the embedding transposed computes the same loss; the tied
    leaf's gradient is the twin's embedding gradient plus its head
    gradient, transposed."""
    lm, params = model
    twin = ShortConvMoeLM(dict(SPEC, tie_word_embeddings=False))
    assert not twin.tied_head
    twin_params = dict(params, head={
        "kernel": params["embed"]["embedding"].T})
    assert jax.tree.structure(twin_params) == jax.tree.structure(
        jax.eval_shape(twin.init, jax.random.key(0)))
    toks = _tokens(7)
    (loss, stats), tied = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(lm, p, toks), has_aux=True))(params)
    (twin_loss, twin_stats), untied = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(twin, p, toks), has_aux=True))(twin_params)
    assert float(loss) == pytest.approx(float(twin_loss), rel=1e-6)
    assert float(stats["tied_head"]) == 1.0
    assert float(twin_stats["tied_head"]) == 0.0
    both = untied["embed"]["embedding"] + untied["head"]["kernel"].T
    scale = float(jnp.max(jnp.abs(both)))
    np.testing.assert_allclose(tied["embed"]["embedding"], both,
                               atol=1e-5 * scale)
    # both uses are really there: neither part alone is the gradient
    for part in (untied["embed"]["embedding"], untied["head"]["kernel"].T):
        assert float(jnp.max(jnp.abs(
            tied["embed"]["embedding"] - part))) > 1e-2 * scale
    # every other leaf's gradient is the twin's
    for name in ("layer0", "layer1", "final_norm", "qk_norm"):
        for a, b in zip(jax.tree.leaves(tied[name]),
                        jax.tree.leaves(untied[name])):
            np.testing.assert_allclose(a, b, atol=1e-5 * float(
                jnp.max(jnp.abs(b))) + 1e-12)
    # the reference reads the same key the same way
    assert float(jax.jit(lambda p: ref.loss(p, toks, twin.spec))(
        twin_params)) == pytest.approx(float(twin_loss), rel=2e-6)


def test_the_standing_models_read_their_own_head_leaf():
    """``SpecLM.head_kernel`` hands the four standing blocks the ``head``
    leaf itself (the same object: their traced programs do not change)."""
    from draco_tpu.models.spec_lm import SpecLM

    assert SpecLM.tied_head is False
    kernel = jnp.ones((4, 8))
    params = {"head": {"kernel": kernel},
              "embed": {"embedding": jnp.zeros((8, 4))}}
    assert SpecLM({}).head_kernel(params) is kernel
    from draco_tpu.models.hybrid_moe import HybridMoeLM
    from draco_tpu.models.looped import LoopedLM
    from draco_tpu.models.windowed_moe import WindowedMoeLM

    for cls in (latent_moe.LatentMoeLM, HybridMoeLM, WindowedMoeLM,
                LoopedLM):
        assert cls.tied_head is False, cls.__name__


def test_the_published_share_runs_over_every_token():
    assert conv_moe.causal_depthwise_conv is causal_depthwise_conv
    # the published share: top-4 of 32 is an eighth, 8 of 32 held
    lm = ShortConvMoeLM(_published())
    assert lm.moe.dense and lm.moe.top_k == 4 and lm.moe.experts == 32
    assert (lm.moe.first, lm.moe.held) == (0, 8)
    assert lm.head_dim == 64 and lm.rope.shape == (32,)
    assert lm.layer_types == ["conv", "full_attention", "conv", "conv",
                              "conv"]
    assert lm.dense_layers == [True, False, False, False, False]
    # a sparser mapping of the family keeps the sorted path
    assert not ShortConvMoeLM(dict(_published(), num_experts=64)).moe.dense


def test_a_rematerialised_layer_keeps_the_two_products(capsys):
    from jax.ad_checkpoint import print_saved_residuals

    net = ShortConvMoeLM(SPEC, remat=True)
    params = net.init(jax.random.key(0))
    toks = _tokens(3)
    print_saved_residuals(lambda p: parity.mean_nll(net, p, toks)[0], params)
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
            if "_every_token" in line]
    rows = toks.shape[0] * toks.shape[1]
    held, width = SPEC["experts_held"][1], SPEC["moe_intermediate_size"]
    assert kept == [f"f32[{rows},{held},{width}]"] * (2 * 4)


def test_every_leaf_of_the_published_configuration_lies_on_the_lines():
    """Every leaf but the per-head norm weights and the selection biases is
    whole 128-wide lines at a whole-line offset; those close the ravel: the
    two (1, 64) norm weights one line together, the (4, 32) biases one."""
    from draco_tpu.parallel.sp_step import row_layout

    lm = ShortConvMoeLM(_published())
    shapes = jax.tree_util.tree_flatten_with_path(
        lm.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))[0]
    sizes = [int(np.prod(s)) for _, s in shapes]
    assert sum(sizes) == 507_820_288
    small = [jax.tree_util.keystr(p) for p, s in shapes
             if int(np.prod(s)) % 128]
    assert small == ["['qk_norm']['k']['scale']", "['qk_norm']['q']['scale']"]
    layout = row_layout(sizes)
    assert (layout.joined_leaves, layout.joined_size, layout.zeros) == (
        2, 128, 768)
    assert layout.lines * 128 == 507_820_288 + 768


def test_attention_through_the_kernel_matches_the_reference(model):
    """The attention layer's core in the flash kernel (interpret mode; 32
    tokens in blocks of 16, grouped-query heads of 16): loss and a gradient
    of each kind of leaf are the reference's."""
    from draco_tpu.ops.flash_attention import flash_attention

    _, params = model
    lm = ShortConvMoeLM(SPEC, attn_fn=functools.partial(
        flash_attention, block_q=16, block_k=16, interpret=True))
    toks = _tokens(3, batch=1, t=32)
    loss, got = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(lm, p, toks)[0]))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks, SPEC)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    for path in (("layer1", "q", "kernel"), ("layer1", "k", "kernel"),
                 ("layer1", "v", "kernel"), ("qk_norm", "q", "scale"),
                 ("qk_norm", "k", "scale"), ("embed", "embedding")):
        g, w = parity.leaf(got, path), parity.leaf(want, path)
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-9, path
