"""models/conv_moe.ShortConvMoeLM at a tiny size (hidden 64, 4 query heads
on 2 key/value heads of 16, 3-tap gated convolutions, five of six published
layers kept — the dense conv layer 0, then attention and three conv layers
with 8 routed experts top-2 of which 2 are held —, a 64-row embedding that
is the head too) against the plain reference the benchmark compares it with
on the chip (benchmark/reference/nets/lfm2.py, which imports nothing of
draco_tpu, writes the convolution as its shifted sums and masks every key
explicitly):

* loss, logits, every leaf's gradient and three plain SGD steps'
  parameters on seeded weights, the norms' weights moved off their initial
  ones, for a conv-only, an attention-only and the kept five-layer pattern;
* the convolution is causal (changing token t changes no row before t) and
  its taps' gradient matches finite differences;
* the tied leaf's gradient is the sum of its two uses: an untied twin's
  embedding gradient plus its head's, transposed;
* the shares add up: over all 4 shares of a layer of 8 experts the routed
  parts summed are the uncut reference layer's expert part — nothing is
  counted twice, the model has no shared expert;
* the expert layer is latent_moe's, not a copy, told sigmoid scoring with
  the selection bias and ``shared=None``;
* a mapping the block cannot state is refused by the key's name.

Tolerances: program and reference are float32 sums of the same terms in
another order: 2e-6 relative on the loss, 2e-5 absolute on logits of order
one, 2e-4 of a leaf's largest gradient entry.
"""

import functools
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference.nets import lfm2 as ref  # noqa: E402
from draco_tpu.config import TrainConfig  # noqa: E402
from draco_tpu.models import build_lm, conv_moe, latent_moe  # noqa: E402
from draco_tpu.models.conv_moe import ShortConvMoeLM  # noqa: E402
from draco_tpu.models.hybrid_moe import causal_depthwise_conv  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "testdata",
                       "conv-moe-tiny.json")) as fh:
    TINY = json.load(fh)
SPEC = TINY["train_config"]["model_spec"]
T = 48
# the kept pattern, and two single-kind models cut from the same mapping
PATTERNS = {
    "kept_five": SPEC,
    # the dense conv layer and a sparse conv layer: no attention at all
    "conv_only": dict(SPEC, layers=2, layers_held=[0, 3]),
    # two attention layers, one of them made dense by the published count
    "attention_only": dict(
        SPEC, layers=2, layers_held=[0, 1], num_dense_layers=1,
        layer_types=["full_attention"] * 6),
}


def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-ep4.json")) as fh:
        return json.load(fh)["train_config"]["model_spec"]


def _tokens(seed=0, batch=2, t=T):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, SPEC["vocab_rows"], (batch, t)),
                       jnp.int32)


def _loss(lm, params, toks):
    nll, stats = lm.token_nll(params, toks, jnp.roll(toks, -1, axis=1))
    return jnp.mean(nll[:, :-1]), stats


def _moved(params, key):
    """Norm weights off their initial ones, so that a norm left out or
    applied twice shows."""
    def move(path, x):
        if path[-1].key == "scale":
            k = jax.random.fold_in(key, zlib.crc32(
                jax.tree_util.keystr(path).encode()) % 2**31)
            return x + 0.1 * jax.random.normal(k, x.shape)
        return x

    return jax.tree_util.tree_map_with_path(move, params)


def _model(spec, seed=3, **kw):
    lm = ShortConvMoeLM(spec, **kw)
    return lm, _moved(lm.init(jax.random.key(seed)), jax.random.key(seed + 1))


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


@functools.lru_cache(maxsize=None)
def _compiled(pattern):
    """One pattern's model (rematerialised, as the cell runs it), weights,
    and the two compiled functions every test of the pattern reads:
    (params, tokens) -> ((loss, counters), gradient) of the program, and
    (loss, gradient) of the reference."""
    spec = PATTERNS[pattern]
    lm, params = _model(spec, remat=True)
    program = jax.jit(jax.value_and_grad(
        lambda p, toks: _loss(lm, p, toks), has_aux=True))
    reference = jax.jit(jax.value_and_grad(
        lambda p, toks: ref.loss(p, toks, spec)))
    return spec, lm, params, program, reference


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_loss_and_logits_match_the_reference(pattern):
    spec, lm, params, program, reference = _compiled(pattern)
    toks = _tokens()
    (loss, stats), _ = program(params, toks)
    assert float(loss) == pytest.approx(float(reference(params, toks)[0]),
                                        rel=2e-6)
    got = jax.jit(lm.logits)(params, toks)
    want = jax.jit(jax.vmap(lambda p, t: ref.logits(p, t, spec),
                            in_axes=(None, 0)))(params, toks)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert set(stats) == set(lm.stat_names)
    assert lm.stat_names == latent_moe.STAT_NAMES + (
        "short_conv_layers", "short_conv_absmax", "tied_head")
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["short_conv_layers"]) == lm.layer_types.count("conv")
    assert float(stats["tied_head"]) == 1.0
    assert (float(stats["short_conv_absmax"]) > 0.0) == (
        "conv" in lm.layer_types)


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_every_leafs_gradient_matches_the_reference(pattern):
    _, _, params, program, reference = _compiled(pattern)
    toks = _tokens(1)
    _, got = program(params, toks)
    _, want = reference(params, toks)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w)))
        if "expert_bias" in name:  # held fixed: no gradient on either side
            assert scale == 0.0 and float(jnp.max(jnp.abs(g))) == 0.0
            continue
        assert scale > 0.0, f"{name} takes no gradient"
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale + 1e-9, name


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_three_steps_parameters_match_the_reference(pattern):
    """Plain SGD with torch-style momentum, three steps on three rows, the
    program's gradient on one side and the reference's on the other."""
    _, _, params, program, reference = _compiled(pattern)
    lr, mu = 0.05, 0.9

    def train(grad_fn):
        p, buf = params, None
        for step in range(3):
            g = grad_fn(p, _tokens(10 + step))[1]
            buf = g if buf is None else jax.tree.map(
                lambda b, x: mu * b + x, buf, g)
            p = jax.tree.map(lambda a, b: a - lr * b, p, buf)
        return p

    got, want = train(program), train(reference)
    for (path, a), b, p0 in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want),
                                jax.tree.leaves(params)):
        moved = float(jnp.max(jnp.abs(b - p0)))
        # 2e-4 of what the steps moved, or two float32 roundings of the
        # parameter itself (taps of order one move by 4e-5)
        ulps = 2.4e-7 * float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * moved + ulps + 1e-9, \
            jax.tree_util.keystr(path)


# ---- the convolution ------------------------------------------------------

def test_the_convolution_is_causal(model):
    """Changing token t changes no row of the stream before t — through the
    whole conv-only model, taps and gates included."""
    spec = PATTERNS["conv_only"]
    lm, params = _model(spec)
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
        return _loss(lm, p, toks)[0]

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
    (loss, stats), tied = _compiled("kept_five")[3](params, toks)
    (twin_loss, twin_stats), untied = jax.jit(jax.value_and_grad(
        lambda p: _loss(twin, p, toks), has_aux=True))(twin_params)
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
    assert float(ref.loss(twin_params, toks, twin.spec)) == pytest.approx(
        float(twin_loss), rel=2e-6)


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


# ---- the chip's share -----------------------------------------------------

def test_the_4_shares_add_up_to_the_uncut_layer():
    """4 chips hold two of 8 experts each. Every share's routed part, on
    top of what every chip computes alike (the mixer, once), is what the
    reference gives for the whole layer with all 8 experts held. Nothing is
    counted twice: there is no shared expert."""
    n_exp = SPEC["num_experts"]
    whole = dict(SPEC, experts_held=[0, n_exp])
    lm_whole, params = _model(whole, seed=5)
    assert not lm_whole.moe.dense  # a chip that holds every expert sorts
    p = params["layer2"]  # a sparse conv layer
    bias = params["router_bias"]["expert_bias"][1]
    x = jax.random.normal(jax.random.key(7), (T, SPEC["hidden_size"]))
    same = lambda t: t  # noqa: E731
    want = ref.layer(x, p, (None, None, bias), whole, same, "conv", False)
    eps = SPEC["norm_eps"]
    once = x + ref.short_conv(ref.rms(x, p["operator_norm"]["scale"], eps),
                              p, whole, same)
    total, landed = once, 0.0
    for first in range(0, n_exp, 2):
        lm = ShortConvMoeLM(dict(SPEC, experts_held=[first, 2]))
        assert lm.moe.dense  # top-2 of 8: over every token, as the cell
        part = dict(p, mlp_norm=p["ffn_norm"],
                    router=dict(p["router"], e_score_correction_bias=bias),
                    experts=jax.tree.map(lambda a: a[first:first + 2],
                                         p["experts"]))
        after, stats = lm._experts(once, part)  # once + routed
        total = total + (after - once)
        landed += float(jnp.sum(stats["load"]))
        assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every (token, choice) pair landed on exactly one share
    assert landed == T * SPEC["num_experts_per_tok"]
    # the bias moves the choice and not the weights: without it the
    # reference's layer is another one
    no_bias = ref.layer(x, p, (None, None, 0 * bias), whole, same, "conv",
                        False)
    assert float(jnp.max(jnp.abs(no_bias - want))) > 1e-4


def test_the_expert_layer_is_shared_not_copied():
    for name in ("_choose", "_route", "_buffer", "_every_token", "_experts",
                 "dispatch_rows", "token_nll", "weighted_nll", "init"):
        assert getattr(ShortConvMoeLM, name) is getattr(
            latent_moe.LatentMoeLM, name), name
    assert conv_moe.fold_stats is latent_moe.fold_stats
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
    print_saved_residuals(lambda p: _loss(net, p, toks)[0], params)
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


# ---- the mapping and the configuration ------------------------------------

@pytest.mark.parametrize("edit,names", [
    (lambda s: s.pop("conv_L_cache"), "conv_L_cache"),
    (lambda s: s.update(conv_bias=True), "conv_bias"),
    (lambda s: s.update(use_expert_bias=False), "use_expert_bias"),
    (lambda s: s.update(norm_topk_prob=False), "norm_topk_prob"),
    (lambda s: s.update(tie_word_embeddings="yes"), "tie_word_embeddings"),
    (lambda s: s.update(layer_types=["conv", "conv", "sliding_attention",
                                     "conv", "conv", "conv"]),
     r"layer_types'\]\[2\]"),
    # a dense layer after a sparse one: the kept indices out of order
    (lambda s: s.update(layers_held=[2, 0, 3, 4, 5]),
     "dense layer after a sparse one"),
    (lambda s: s.update(layers_held=[0, 2, 2, 4, 5]), "layers_held"),
    (lambda s: s.update(layers_held=[0, 2, 3, 4, 6]), "layers_held"),
    (lambda s: s.update(layers=4), "layers_held"),
    (lambda s: s.update(experts_held=[7, 2]), "experts_held"),
    (lambda s: s.update(num_key_value_heads=3), "num_key_value_heads"),
    (lambda s: s.update(num_attention_heads=64), "hidden_size"),
    (lambda s: s.update(conv_L_cache=0), "conv_L_cache"),
    (lambda s: s.update(vocab_rows=1), "vocab_rows"),
])
def test_a_mapping_the_block_cannot_state_is_refused_by_name(edit, names):
    spec = json.loads(json.dumps(SPEC))
    edit(spec)
    with pytest.raises(ValueError, match=names):
        ShortConvMoeLM(spec)


def _cfg(**kw):
    base = dict(network="ShortConvMoeLM", dataset="synthetic-text",
                model_spec=SPEC, vocab=SPEC["vocab_rows"], seq_len=T,
                batch_size=2, num_workers=3, approach="maj_vote",
                group_size=3, worker_fail=1, train_dir="")
    base.update(kw)
    return TrainConfig(**base)


def test_the_network_is_built_on_the_normal_path():
    from draco_tpu.config import SPEC_NETWORKS
    from draco_tpu.ops.flash_attention import flash_attention

    assert SPEC_NETWORKS["ShortConvMoeLM"] == "draco_tpu.models.conv_moe"
    cfg = _cfg().validate()
    lm = build_lm(cfg)
    assert isinstance(lm, ShortConvMoeLM) and lm.remat == cfg.remat
    assert lm.stat_names[-3:] == ("short_conv_layers", "short_conv_absmax",
                                  "tied_head")
    # the route's bare kernel reaches the model
    lm = build_lm(cfg, kernel_fn=flash_attention)
    assert lm.attn_fn is flash_attention


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
        lambda p: _loss(lm, p, toks)[0]))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks, SPEC)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    for path in (("layer1", "q", "kernel"), ("layer1", "k", "kernel"),
                 ("layer1", "v", "kernel"), ("qk_norm", "q", "scale"),
                 ("qk_norm", "k", "scale"), ("embed", "embedding")):
        g, w = got, want
        for key in path:
            g, w = g[key], w[key]
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-9, path


@pytest.mark.parametrize("kw,names", [
    (dict(tensor_shards=2), "tensor_shards"),
    (dict(seq_shards=2), "seq_shards"),
    (dict(vocab=SPEC["vocab_rows"] + 1), "vocab_rows"),
    (dict(model_spec=None), "model_spec"),
    # another family's mapping under this network's name, and back
    (dict(model_spec={"hidden_size": 64}), "model_spec lacks"),
    (dict(network="WindowedMoeLM"), "model_spec lacks"),
    (dict(network="LeNet"), "ShortConvMoeLM"),
])
def test_what_stays_unsupported_is_refused_by_name(kw, names):
    with pytest.raises(ValueError, match=names):
        _cfg(**kw).validate()
