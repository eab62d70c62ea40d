"""models/windowed_moe.WindowedMoeLM at a tiny size (hidden 64, 4 query
heads on 2 key/value heads of 16, a window of 24 tokens in three layers of
four and YaRN over 32 original positions in the fourth, 8 routed experts of
which 2 are held, no shared expert) against the plain reference the
benchmark compares it with on the chip (benchmark/reference/nets/mellum.py,
which imports nothing of draco_tpu and masks every key by the two
inequalities). What every published-config block is held to alike — loss,
logits, every leaf's gradient, the 8 shares adding up, the shared expert
layer, the refusals — is tests/test_spec_lm_parity.py's; here is what is
this block's own:

* the window is really there: a longer window is another loss, and the
  sliding layers through the block-skipping kernel (interpret mode) are the
  reference's too, with the counter at 3;
* YaRN's pins at the PUBLISHED numbers: low 18, high 35, f_i = e_i below
  18, e_i / 16 from 35 on, between them strictly between; the factor
  1.2772588722239782 on cos and sin, so the logits carry its square;
* the held experts over every token equal the sorted buffers, run no
  sort and no scatter, and keep their two products through a
  rematerialised layer; no ``shared`` leaf in the tree.

Tolerances: program and reference are float32 sums of the same terms in
another order (a dispatch buffer against a dense mask, the flash-style
softmax against the plain one): 2e-6 relative on the loss, 2e-5 absolute on
logits of order one, 2e-4 of a leaf's largest gradient entry. Every
compared value is one compiled program (tests/parity.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import mellum as ref
from draco_tpu.models import latent_moe, windowed_moe
from draco_tpu.models.windowed_moe import WindowedMoeLM, rope_frequencies
from draco_tpu.ops.flash_attention import flash_attention

SPEC = parity.tiny("windowed-moe-tiny")
T = 80  # three windows of 24 and a rest: no multiple of the window


def _published():
    return parity.published("mellum2-12b-a2.5b-ep8")


def _tokens(seed=0, batch=2, t=T):
    return parity.tokens(SPEC["vocab_rows"], batch, t, seed)


@pytest.fixture(scope="module")
def model():
    lm = WindowedMoeLM(SPEC)
    return lm, parity.moved(lm.init(jax.random.key(3)), jax.random.key(4),
                            ("scale",))


def test_layer_kinds_are_read_verbatim_and_there_is_no_shared_leaf(model):
    lm, params = model
    assert lm.layer_types == ["sliding_attention"] * 3 + ["full_attention"]
    # not from an interval: another order is another model
    other = WindowedMoeLM(dict(SPEC, layer_types=[
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention"]))
    assert other.layer_types[0] == "full_attention"
    shapes = jax.tree.leaves(lm.param_shapes(),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert [tuple(x.shape) for x in jax.tree.leaves(params)] == shapes
    assert set(params["layer0"]) == {"attn_norm", "q", "k", "v", "o",
                                     "mlp_norm", "router", "experts"}
    assert lm.moe.shared is None
    # the router keeps its published width, no selection bias
    assert set(params["layer0"]["router"]) == {"kernel"}
    assert params["layer0"]["router"]["kernel"].shape[1] == \
        SPEC["num_experts"]
    assert params["layer0"]["k"]["kernel"].shape == (
        SPEC["hidden_size"], SPEC["num_key_value_heads"] * SPEC["head_dim"])


def test_the_window_and_the_full_layers_rotary_are_really_there(model):
    """A window that covers the row, and the full layer read with the
    sliding layers' rotary entry, are each another loss — in the program
    as in the reference."""
    lm, params = model
    # q and k thirty times their seeded size: scores that tell keys apart
    params = {name: ({**leaves, "q": {"kernel": 30 * leaves["q"]["kernel"]},
                      "k": {"kernel": 30 * leaves["k"]["kernel"]}}
                     if name.startswith("layer") else leaves)
              for name, leaves in params.items()}
    toks = _tokens(2, batch=1)
    def loss_of(net):
        return float(jax.jit(lambda p: parity.mean_nll(net, p, toks)[0])(
            params))

    base = loss_of(lm)
    wide = dict(SPEC, sliding_window=T)
    rope = dict(SPEC["rope_parameters"])
    rope["full_attention"] = rope["sliding_attention"]
    plain = dict(SPEC, rope_parameters=rope)
    for other in (wide, plain):
        moved = loss_of(WindowedMoeLM(other))
        # ten times what program and reference may differ by
        assert abs(moved - base) > 2e-5 * base
        assert moved == pytest.approx(float(jax.jit(
            lambda p: ref.loss(p, toks, other))(params)), rel=2e-6)


# ---- the sliding layers through the kernel ------------------------------

@pytest.fixture
def in_kernels(monkeypatch):
    """The model's name for the question which path the attention takes,
    told ``interpret=True``; the kernel itself comes as ``attn_fn``."""
    monkeypatch.setattr(windowed_moe, "runs_in_kernels", functools.partial(
        windowed_moe.runs_in_kernels, interpret=True))
    return functools.partial(flash_attention, block_q=16, block_k=16,
                             interpret=True)


def test_sliding_layers_in_the_kernel_match_the_reference(model, in_kernels):
    """T = 64: four key blocks of 16 under a window of 24 — the windowed
    kernel skips blocks on both sides and masks the edge inside a block —
    and grouped-query heads, loss and a gradient of each kind of leaf."""
    _, params = model
    lm = WindowedMoeLM(SPEC, attn_fn=in_kernels)
    toks = _tokens(3, batch=1, t=64)
    (loss, stats), got = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(lm, p, toks), has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks, SPEC)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert float(stats["window_kernel_layers"]) == \
        lm.layer_types.count("sliding_attention") == 3
    for path in (("layer0", "q", "kernel"), ("layer1", "k", "kernel"),
                 ("layer2", "v", "kernel"), ("layer3", "q", "kernel"),
                 ("layer0", "attn_norm", "scale"),
                 ("embed", "embedding")):
        g, w = parity.leaf(got, path), parity.leaf(want, path)
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-9, path


def test_the_counter_is_zero_on_the_plain_lowering(model, in_kernels):
    """The kernel path is selected, the model was handed no kernel: 0."""
    lm, params = model
    _, stats = jax.jit(lm.hidden)(params, _tokens(4, batch=1, t=64))
    assert float(stats["window_kernel_layers"]) == 0.0


# ---- YaRN at the published numbers --------------------------------------

def test_yarn_pins_at_the_published_numbers():
    rope = _published()["rope_parameters"]
    theta, dim = 500000.0, 128
    e = theta ** (-2.0 * np.arange(64) / dim)
    plain, one = rope_frequencies(rope["sliding_attention"], dim)
    np.testing.assert_allclose(plain, e, rtol=1e-6)
    assert one == 1.0

    def c(n):  # the pair that turns n times over the original positions
        return dim * math.log(8192 / (2 * math.pi * n)) / (
            2 * math.log(theta))

    assert math.floor(c(32)) == 18 and math.ceil(c(1)) == 35
    f, factor = rope_frequencies(rope["full_attention"], dim)
    assert f.dtype == np.float32 and f.shape == (64,)
    np.testing.assert_allclose(f[:19], e[:19], rtol=1e-6)  # ramp 0 at 18
    np.testing.assert_allclose(f[35:], e[35:] / 16, rtol=1e-6)
    assert np.all(f[19:35] < e[19:35]) and np.all(f[19:35] > e[19:35] / 16)
    # i = 26 lies 8 / 17 of the way: the blend, written out
    np.testing.assert_allclose(
        f[26], e[26] / 16 * (8 / 17) + e[26] * (9 / 17), rtol=1e-6)
    assert factor == 1.2772588722239782 == 0.1 * math.log(16) + 1
    # an entry without the factor is refused by the key's name
    bare = {k: v for k, v in rope["full_attention"].items()
            if k != "attention_factor"}
    with pytest.raises(ValueError, match="attention_factor"):
        rope_frequencies(bare, dim)
    # the reference computes the same table from its own formula
    rf, rfactor = ref.frequencies(rope["full_attention"], dim)
    np.testing.assert_array_equal(rf, f)
    assert rfactor == factor


def test_the_factor_is_on_cos_and_sin_so_the_logits_carry_its_square():
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 16))
    freqs, _ = rope_frequencies(SPEC["rope_parameters"]["full_attention"],
                                16)
    pos = jnp.arange(5)
    rope_half = latent_moe.rope_half
    one = rope_half(x, pos, freqs, 1.0)
    scaled = rope_half(x, pos, freqs, 1.25)
    # the one helper both half-rotation models call: fewer frequencies
    # than pairs rotate the leading dims and pass the rest (hybrid_moe)
    part = rope_half(x, pos, freqs[:2], 1.0)
    np.testing.assert_array_equal(part[..., 4:], x[..., 4:])
    np.testing.assert_allclose(np.hypot(part[..., :2], part[..., 2:4]),
                               np.hypot(x[..., :2], x[..., 2:4]), rtol=1e-5)
    np.testing.assert_allclose(scaled, 1.25 * one, rtol=1e-6)
    # position 0: angle 0, the vector itself times the factor
    np.testing.assert_allclose(scaled[:, 0], 1.25 * x[:, 0], rtol=1e-6)
    # a rotation: pairs (i, i + 8) keep their length
    half = np.hypot(one[..., :8], one[..., 8:])
    np.testing.assert_allclose(half, np.hypot(x[..., :8], x[..., 8:]),
                               rtol=1e-5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", scaled, scaled)
    np.testing.assert_allclose(
        logits, 1.25 ** 2 * jnp.einsum("bqhd,bkhd->bhqk", one, one),
        rtol=1e-5)


# ---- the held experts over every token ----------------------------------

def _sorted(lm):
    """The same model on the sorted pairs' buffers."""
    other = WindowedMoeLM(lm.spec, remat=lm.remat)
    other.moe = lm.moe._replace(dense=False)
    return other


def test_which_models_run_their_held_experts_over_every_token():
    """top_k / experts is the share of the tokens a held expert expects:
    an eighth here (top-8 of 64), 1/21 and 1/51 on the other two LMs,
    which keep the sorted path; so does a chip that holds every expert."""
    from draco_tpu.models.hybrid_moe import HybridMoeLM

    assert WindowedMoeLM(_published()).moe.dense
    # uniform routing sends the chip T·k·held/experts pairs; C is 4 x that
    assert WindowedMoeLM(_published()).dispatch_rows(8192) == 32768
    assert WindowedMoeLM(SPEC).moe.dense  # top-3 of 8
    assert not WindowedMoeLM(dict(_published(), num_experts=128)).moe.dense
    assert not WindowedMoeLM(
        dict(SPEC, experts_held=[0, SPEC["num_experts"]])).moe.dense
    for name, cls in (("kanana-2-30b-a3b-ep16", latent_moe.LatentMoeLM),
                      ("qwen3-next-80b-a3b-ep32", HybridMoeLM)):
        assert not cls(dict(parity.published(name), layers=1)).moe.dense, \
            name


@pytest.mark.parametrize("first", [0, 3, 6])
def test_every_token_equals_the_sorted_buffers(model, first):
    """One layer's experts both ways on the same weights: output, counters
    and every gradient (the router's through the combine weights, the
    input's, the three expert matrices'). float32 sums in another order:
    1e-5 of the largest entry."""
    lm, params = model
    spec = dict(SPEC, experts_held=[first, 2])
    dense = WindowedMoeLM(spec)
    assert dense.moe.dense
    p = dict(params["layer2"])
    x = jax.random.normal(jax.random.key(11), (T, SPEC["hidden_size"]))

    def run(net, x, p):
        y, stats = net._experts(x, p)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), (
            y, stats)

    (_, (y_d, s_d)), g_d = jax.jit(jax.value_and_grad(
        functools.partial(run, dense), argnums=(0, 1), has_aux=True))(x, p)
    (_, (y_s, s_s)), g_s = jax.jit(jax.value_and_grad(
        functools.partial(run, _sorted(dense)), argnums=(0, 1),
        has_aux=True))(x, p)
    np.testing.assert_allclose(y_d, y_s, atol=1e-5 * float(
        jnp.max(jnp.abs(y_s))))
    np.testing.assert_array_equal(s_d["load"], s_s["load"])
    assert float(s_d["dropped"]) == float(s_d["further"]) == 0.0
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_s)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(
            jnp.max(jnp.abs(b))) + 1e-12)


def test_every_token_runs_no_sort_and_no_scatter(model):
    """The point of the path: work that no routing can move. Its program
    holds no sort, no gather of rows and no scatter-add; the sorted path's
    holds all three."""
    lm, params = model
    x = jnp.zeros((T, SPEC["hidden_size"]))

    def ops(net):
        text = str(jax.make_jaxpr(
            lambda x, p: net._experts(x, p)[0])(x, params["layer0"]))
        return {op for op in ("sort", "scatter-add", "scatter_add",
                              "while") if op in text}

    assert ops(lm) == set()
    assert {"sort", "while"} <= ops(_sorted(lm))


def test_a_rematerialised_layer_keeps_the_two_products(model, capsys):
    """Under the per-layer rematerialisation the gate and up products'
    results are kept (``KEEP_DENSE``): the backward pass reads them and
    the recomputed layer does not run them again."""
    from jax.ad_checkpoint import print_saved_residuals

    lm, params = model
    net = WindowedMoeLM(SPEC, remat=True)
    toks = _tokens(3)
    print_saved_residuals(lambda p: parity.mean_nll(net, p, toks)[0], params)
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
            if "_every_token" in line]  # the named values, nothing else
    rows = toks.shape[0] * toks.shape[1]
    held, width = SPEC["experts_held"][1], SPEC["moe_intermediate_size"]
    assert kept == [f"f32[{rows},{held},{width}]"] * (2 * SPEC["layers"])
