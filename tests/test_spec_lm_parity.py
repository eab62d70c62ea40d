"""What every published-config language model (models/spec_lm.SpecLM's
blocks) is held to alike, one parametrised test each over ONE table — a
new block is a row in ``BLOCKS`` and, in its own file, what is its own:

* loss and logits, every leaf's gradient and three plain SGD steps'
  parameters against the plain reference the benchmark compares the block
  with on the chip (benchmark/reference/nets/*, which import nothing of
  draco_tpu), on seeded weights moved off their initial zeros and ones;
* a rematerialised block gives the same gradient;
* ``weighted_nll`` — the surface the route trains through — is
  Σ weights · ``token_nll`` / denom, value and gradient;
* the shares add up: every chip's share of a layer's routed experts, plus
  what every chip computes alike ONCE, is the uncut reference layer;
* the expert layer is latent_moe's, not a copy;
* a mapping the block cannot state is refused by the key's name, the block
  is built on the normal path, and every TrainConfig it does not support is
  refused by name.

Every compared value is one compiled program a block (tests/parity.py):
``block_programs`` holds a block's model, weights, and the jitted (loss, gradient)
of program and reference for every case of the file.

Tolerances, each block's as they stood in its own file: program and
reference are float32 sums of the same terms in another order (chunked
against token by token, a dispatch buffer against a dense mask, the
flash-style softmax against the plain one, a scan against a Python loop):
2e-6 relative on the loss, 2e-5 absolute on logits of order one, 2e-4 of a
leaf's largest gradient entry (1e-3 for HybridMoeLM's two per-head leaves,
whose gradients are sums over every token of terms that cancel).
"""

import copy
import dataclasses
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import latent_moe as ref_latent
from benchmark.reference.nets import kimi_linear, lfm2, mellum, ouro
from benchmark.reference.nets import qwen3_next
from draco_tpu.config import SPEC_NETWORKS, TrainConfig
from draco_tpu.models import build_lm, conv_moe, hybrid_moe, kda_moe
from draco_tpu.models import latent_moe, looped, windowed_moe
from draco_tpu.models.conv_moe import ShortConvMoeLM
from draco_tpu.models.hybrid_moe import HybridMoeLM
from draco_tpu.models.kda_moe import KdaMoeLM
from draco_tpu.models.latent_moe import LatentMoeLM
from draco_tpu.models.looped import LoopedLM
from draco_tpu.models.windowed_moe import WindowedMoeLM
from draco_tpu.ops.flash_attention import flash_attention
from draco_tpu.ops.kda_rule import kda_runs_in_kernels


def _hybrid_counters(lm, stats):
    assert lm.stat_names[:4] == latent_moe.STAT_NAMES
    assert float(stats["linattn_state_absmax"]) > 0.0
    # off the chip the rule takes the jax.numpy path in every layer
    assert float(stats["linattn_kernel_layers"]) == 0.0


def _windowed_counters(lm, stats):
    assert lm.stat_names == latent_moe.STAT_NAMES + (
        "window_kernel_layers",)
    # the model hands them over in that order (a compiled program's
    # result forgets a mapping's order: read it from an abstract trace)
    order = []
    toks = jnp.zeros((1, 8), jnp.int32)
    jax.eval_shape(lambda p: order.append(tuple(
        lm.token_nll(p, toks, toks)[1])), lm.init(jax.random.key(0)))
    assert order == [lm.stat_names]
    # off the chip every sliding layer takes the plain lowering
    assert float(stats["window_kernel_layers"]) == 0.0


def _short_conv_counters(lm, stats):
    assert lm.stat_names == latent_moe.STAT_NAMES + (
        "short_conv_layers", "short_conv_absmax", "tied_head")
    assert float(stats["short_conv_layers"]) == lm.layer_types.count("conv")
    assert float(stats["tied_head"]) == 1.0
    assert (float(stats["short_conv_absmax"]) > 0.0) == (
        "conv" in lm.layer_types)


KDA_TAIL = ("kda_layers", "kda_kernel_layers", "kda_state_absmax",
            "kda_decay_min", "heads_held")


def _kda_counters(lm, stats):
    assert lm.stat_names == latent_moe.STAT_NAMES + KDA_TAIL
    kda = sum(kind == "kda" for kind, _ in lm.kept)
    assert float(stats["kda_layers"]) == kda
    # the layers whose rule took the Pallas kernels: the backend and the
    # shapes decide (a whole chunk here: the row's length is the case's) —
    # none off the chip, none at a head size that is no lane tile
    shape = (1, 64, lm.heads, lm.spec["linear_attn_config"]["head_dim"])
    assert float(stats["kda_kernel_layers"]) == kda * kda_runs_in_kernels(
        shape, shape)
    assert not kda_runs_in_kernels((1, 64, 16, 128), (1, 64, 16, 128))
    assert float(stats["heads_held"]) == lm.spec["heads_held"][1]
    assert (float(stats["kda_state_absmax"]) > 0.0) == bool(kda)
    assert (float(stats["kda_decay_min"]) < 0.0) == bool(kda)


@dataclasses.dataclass(frozen=True)
class Block:
    """One row: the model, its tiny mapping and reference, how its cases
    run, and what the comparison allows."""
    cls: type
    file: str            # benchmark/testdata/<file>.json
    ref: types.ModuleType
    t: int               # the rows' length: what the block's mixer must cross
    moves: tuple | None = ("scale",)  # parity.moved's leaves; None: every
    remat: bool = False  # the model the cases compile (the other: one case)
    edit: dict = dataclasses.field(default_factory=dict)  # of the mapping
    rel_by_suffix: dict = dataclasses.field(default_factory=dict)
    zero: tuple = ()     # leaves that take no gradient, on either side
    counters: object = None  # (lm, stats): the block's own counters' values
    stat_tail: tuple = ()    # the counters the block adds, last in the row


CONV = dict(cls=ShortConvMoeLM, file="conv-moe-tiny", ref=lfm2, t=48,
            remat=True, zero=("expert_bias",), counters=_short_conv_counters,
            stat_tail=("short_conv_layers", "short_conv_absmax", "tied_head"))
BLOCKS = {
    "LatentMoeLM": Block(
        LatentMoeLM, "latent-moe-tiny", ref_latent, 32, moves=(),
        zero=("e_score_correction_bias",),
        stat_tail=latent_moe.STAT_NAMES),
    # T = 80: a chunk of 64 and a closing chunk of 16, the state crosses
    "HybridMoeLM": Block(
        HybridMoeLM, "hybrid-moe-tiny", qwen3_next, 80,
        moves=("centred_scale", "scale", "dt_bias"),
        rel_by_suffix={"['A_log']": 1e-3, "['dt_bias']": 1e-3},
        counters=_hybrid_counters,
        stat_tail=("linattn_state_absmax", "linattn_kernel_layers")),
    # T = 80: three windows of 24 and a rest, no multiple of the window
    "WindowedMoeLM": Block(
        WindowedMoeLM, "windowed-moe-tiny", mellum, 80,
        counters=_windowed_counters, stat_tail=("window_kernel_layers",)),
    # ShortConvMoeLM: the kept pattern (rematerialised, as the cell runs
    # it), and two single-kind models cut from the same mapping
    "kept_five": Block(**CONV),
    # the dense conv layer and a sparse conv layer: no attention at all
    "conv_only": Block(**CONV, edit=dict(layers=2, layers_held=[0, 3])),
    # two attention layers, one of them made dense by the published count
    "attention_only": Block(**CONV, edit=dict(
        layers=2, layers_held=[0, 1], num_dense_layers=1,
        layer_types=["full_attention"] * 6)),
    "LoopedLM": Block(LoopedLM, "looped-tiny", ouro, 40, moves=None,
                      stat_tail=looped.STAT_NAMES),
    # T = 80: a chunk of 64 and a closing chunk of 16, the state crosses;
    # heads 2-3 of 4 held in both mixers (rematerialised, as the cell runs)
    "KdaMoeLM": Block(
        KdaMoeLM, "kda-moe-tiny", kimi_linear, 80,
        moves=("scale", "dt_bias"), remat=True,
        rel_by_suffix={"['A_log']": 1e-3, "['dt_bias']": 1e-3},
        zero=("e_score_correction_bias",), counters=_kda_counters,
        stat_tail=KDA_TAIL),
    # the dense KDA layer and a sparse one: no latent attention at all
    "kda_only": Block(
        KdaMoeLM, "kda-moe-tiny", kimi_linear, 80,
        moves=("scale", "dt_bias"), remat=True,
        rel_by_suffix={"['A_log']": 1e-3, "['dt_bias']": 1e-3},
        zero=("e_score_correction_bias",), counters=_kda_counters,
        stat_tail=KDA_TAIL, edit=dict(layers=2, layers_held=[1, 6])),
    # latent attention alone, the first of the two made dense
    "nope_latent_only": Block(
        KdaMoeLM, "kda-moe-tiny", kimi_linear, 80, remat=True,
        zero=("e_score_correction_bias",), counters=_kda_counters,
        stat_tail=KDA_TAIL, edit=dict(
            layers=2, layers_held=[1, 2], linear_attn_config=dict(
                parity.tiny("kda-moe-tiny")["linear_attn_config"],
                kda_layers=[], full_attn_layers=[1, 2, 3, 4, 5, 6]))),
}
NETWORKS = ["LatentMoeLM", "HybridMoeLM", "WindowedMoeLM", "kept_five",
            "LoopedLM", "KdaMoeLM"]  # one row a network
SPARSE = [name for name in BLOCKS if name != "LoopedLM"]


@functools.lru_cache(maxsize=None)
def spec_of(name):
    """The block's mapping (one object a block: copy it before an edit)."""
    block = BLOCKS[name]
    return dict(parity.tiny(block.file), **block.edit)


@functools.lru_cache(maxsize=None)
def block_programs(name):
    """One block's model, weights, and the compiled functions every case
    of the block reads: (params, tokens) -> ((loss, counters), gradient) of
    the program, (loss, gradient) of the reference, and both logits."""
    block, spec = BLOCKS[name], spec_of(name)
    lm = block.cls(spec, remat=block.remat)
    params = parity.moved(lm.init(jax.random.key(3)), jax.random.key(4),
                          block.moves)
    return types.SimpleNamespace(
        block=block, spec=spec, lm=lm, params=params,
        program=jax.jit(jax.value_and_grad(
            lambda p, toks: parity.mean_nll(lm, p, toks), has_aux=True)),
        reference=jax.jit(jax.value_and_grad(
            lambda p, toks: block.ref.loss(p, toks, spec))),
        logits=jax.jit(lm.logits),
        ref_logits=jax.jit(lambda p, row: block.ref.logits(p, row, spec)))


def _tokens(name, seed, batch=2):
    return parity.tokens(spec_of(name)["vocab_rows"], batch, BLOCKS[name].t,
                         seed)


# ---- program against reference ------------------------------------------

@pytest.mark.parametrize("name", SPARSE)
def test_loss_and_logits_match_the_reference(name):
    c = block_programs(name)
    toks = _tokens(name, 0)
    (loss, stats), _ = c.program(c.params, toks)
    assert float(loss) == pytest.approx(
        float(c.reference(c.params, toks)[0]), rel=2e-6)
    got = c.logits(c.params, toks)
    for b in range(toks.shape[0]):
        np.testing.assert_allclose(got[b], c.ref_logits(c.params, toks[b]),
                                   atol=2e-5)
    assert set(stats) == set(c.lm.stat_names)
    assert float(stats["moe_dropped"]) == 0.0
    if c.block.counters:
        c.block.counters(c.lm, stats)


@pytest.mark.parametrize("name", SPARSE)
def test_every_leafs_gradient_matches_the_reference(name):
    c = block_programs(name)
    toks = _tokens(name, 1)
    _, got = c.program(c.params, toks)
    _, want = c.reference(c.params, toks)
    parity.assert_leaves_close(got, want, 2e-4, c.block.rel_by_suffix,
                               c.block.zero)


@pytest.mark.parametrize("name", list(BLOCKS))
def test_three_steps_parameters_match_the_reference(name):
    """Plain SGD with torch-style momentum, three steps on three rows, the
    program's gradient on one side and the reference's on the other."""
    c = block_programs(name)
    lr, mu = 0.05, 0.9

    def train(grad_fn):
        p, buf = c.params, None
        for step in range(3):
            g = grad_fn(p, _tokens(name, 10 + step))[1]
            buf = g if buf is None else jax.tree.map(
                lambda b, x: mu * b + x, buf, g)
            p = jax.tree.map(lambda a, b: a - lr * b, p, buf)
        return p

    got, want = train(c.program), train(c.reference)
    for (path, a), b, p0 in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want),
                                jax.tree.leaves(c.params)):
        moved = float(jnp.max(jnp.abs(b - p0)))
        # 2e-4 of what the steps moved, or two float32 roundings of the
        # parameter itself (taps of order one move by 4e-5)
        ulps = 2.4e-7 * float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * moved + ulps + 1e-9, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", ["LatentMoeLM", "HybridMoeLM",
                                  "WindowedMoeLM", "kept_five", "KdaMoeLM"])
def test_rematerialised_block_gives_the_same_gradient(name):
    c = block_programs(name)
    toks = _tokens(name, 2)
    other = c.block.cls(c.spec, remat=not c.block.remat)
    _, got = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(other, p, toks), has_aux=True))(c.params)
    _, want = c.program(c.params, toks)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("name", ["LatentMoeLM", "HybridMoeLM",
                                  "WindowedMoeLM", "kept_five", "KdaMoeLM"])
def test_the_weighted_surface_is_the_weighted_sum_of_token_nll(name):
    """``weighted_nll(params, tokens, targets, weights, denom)``, which the
    route trains through, against Σ weights · ``token_nll`` / denom: value,
    every leaf's gradient and the counters, at weights that differ by
    position and row and a denominator that is not their sum (LoopedLM's
    own: tests/test_looped_lm.py, the exits' objective)."""
    c = block_programs(name)
    lm, toks = c.lm, _tokens(name, 5)
    targets = jnp.roll(toks, -1, axis=1)
    weights = jax.random.uniform(jax.random.key(6), toks.shape, minval=0.2)
    weights = weights.at[:, -1].set(0.0)
    denom = 73.0

    def per_position(p):
        nll, stats = lm.token_nll(p, toks, targets)
        return jnp.sum(nll * weights) / denom, stats

    def weighted(p):
        return lm.weighted_nll(p, toks, targets, weights, denom)

    (want, want_stats), want_grad = jax.jit(jax.value_and_grad(
        per_position, has_aux=True))(c.params)
    (got, stats), grad = jax.jit(jax.value_and_grad(
        weighted, has_aux=True))(c.params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    parity.assert_leaves_close(grad, want_grad, 1e-5, zero=c.block.zero,
                               floor=1e-12)
    assert set(stats) == set(lm.stat_names)
    for key in stats:
        np.testing.assert_allclose(stats[key], want_stats[key], rtol=1e-6,
                                   err_msg=key)


# ---- the chip's share ---------------------------------------------------
# One uncut layer a block: (the share's mapping but for ``experts_held``,
# the layer's leaves, x, the reference's whole layer, what every chip
# computes alike — mixer ``once``, shared expert —, a share's leaves).

def _latent_uncut(t):
    """Four chips hold two experts each: the shared experts and attention
    are counted ONCE."""
    spec, ref = parity.tiny("latent-moe-tiny"), ref_latent
    whole = dict(spec, experts_held=[0, spec["n_routed_experts"]])
    p = LatentMoeLM(whole).init(jax.random.key(5))["layer1"]
    x = jax.random.normal(jax.random.key(6), (t, spec["hidden_size"]))
    same = lambda a: a  # noqa: E731

    @jax.jit
    def reference(x, p):
        eps = spec["rms_norm_eps"]
        once = x + ref.attention(ref.rms(x, p["attn_norm"]["scale"], eps), p,
                                 whole, same)
        h = ref.rms(once, p["mlp_norm"]["scale"], eps)
        return (ref.layer(x, p, whole, same, dense=False), once,
                latent_moe.swiglu(h, p["shared"]))

    return spec, spec["n_routed_experts"], p, x, reference(x, p), dict


def _hybrid_uncut(t):
    """32 chips hold two of 64 experts each: the DeltaNet mixer and the
    gated shared expert are counted ONCE."""
    spec, ref = parity.tiny("hybrid-moe-tiny"), qwen3_next
    small = dict(spec, num_experts=64, num_experts_per_tok=5)
    whole = dict(small, experts_held=[0, 64])
    params = parity.moved(HybridMoeLM(whole).init(jax.random.key(5)),
                          jax.random.key(6),
                          BLOCKS["HybridMoeLM"].moves)
    p = params["layer1"]
    heads = (params["linear_heads"]["A_log"][1],
             params["linear_heads"]["dt_bias"][1])
    x = jax.random.normal(jax.random.key(7), (t, spec["hidden_size"]))
    same = lambda a: a  # noqa: E731

    @jax.jit
    def reference(x, p, heads):
        eps = spec["rms_norm_eps"]
        once = x + ref.gated_deltanet(
            ref.rms(x, p["attn_norm"]["centred_scale"], eps), p, heads,
            whole, same)
        h = ref.rms(once, p["mlp_norm"]["centred_scale"], eps)
        shared = (jax.nn.sigmoid(h @ p["shared_gate"]["kernel"])
                  * ref.swiglu(h, p["shared"], same))
        return (ref.layer(x, p, heads, whole, same, full=False), once,
                shared)

    return small, 64, p, x, reference(x, p, heads), dict


def _windowed_uncut(t):
    """8 chips hold two of 16 experts each: the attention is counted ONCE,
    nothing else — there is no shared expert."""
    spec, ref = parity.tiny("windowed-moe-tiny"), mellum
    small = dict(spec, num_experts=16, num_experts_per_tok=5)
    whole = dict(small, experts_held=[0, 16])
    params = parity.moved(WindowedMoeLM(whole).init(jax.random.key(5)),
                          jax.random.key(6))
    p = params["layer1"]
    x = jax.random.normal(jax.random.key(7), (t, spec["hidden_size"]))
    same = lambda a: a  # noqa: E731

    @jax.jit
    def reference(x, p):
        once = x + ref.attention(
            ref.rms(x, p["attn_norm"]["scale"], spec["rms_norm_eps"]), p,
            whole, same, "sliding_attention")
        return (ref.layer(x, p, whole, same, "sliding_attention"), once,
                jnp.zeros_like(x))

    return small, 16, p, x, reference(x, p), dict


def _short_conv_uncut(t):
    """4 chips hold two of 8 experts each: the mixer is counted ONCE,
    nothing twice — there is no shared expert. The shares run over every
    token, as the cell does (top-2 of 8); a chip that holds every expert
    sorts."""
    spec, ref = parity.tiny("conv-moe-tiny"), lfm2
    n_exp = spec["num_experts"]
    whole = dict(spec, experts_held=[0, n_exp])
    lm_whole = ShortConvMoeLM(whole)
    assert not lm_whole.moe.dense
    assert ShortConvMoeLM(spec).moe.dense
    params = parity.moved(lm_whole.init(jax.random.key(5)),
                          jax.random.key(6))
    p = params["layer2"]  # a sparse conv layer
    bias = params["router_bias"]["expert_bias"][1]
    x = jax.random.normal(jax.random.key(7), (t, spec["hidden_size"]))
    same = lambda a: a  # noqa: E731

    @jax.jit
    def reference(x, p, bias):
        once = x + ref.short_conv(
            ref.rms(x, p["operator_norm"]["scale"], spec["norm_eps"]), p,
            whole, same)
        return (ref.layer(x, p, (None, None, bias), whole, same, "conv",
                          False), once, jnp.zeros_like(x))

    want = reference(x, p, bias)
    # the bias moves the choice and not the weights: without it the
    # reference's layer is another one
    assert float(jnp.max(jnp.abs(reference(x, p, 0 * bias)[0]
                                 - want[0]))) > 1e-4

    def part(p, **own):
        return dict(p, mlp_norm=p["ffn_norm"], router=dict(
            p["router"], e_score_correction_bias=bias), **own)

    return spec, n_exp, p, x, want, part


UNCUT = {"LatentMoeLM": _latent_uncut, "HybridMoeLM": _hybrid_uncut,
         "WindowedMoeLM": _windowed_uncut, "kept_five": _short_conv_uncut}


@pytest.mark.parametrize("name", sorted(UNCUT))
def test_the_shares_add_up_to_the_uncut_layer(name):
    """Every share's routed part, summed over the chips, plus what every
    chip computes alike ONCE, is what the reference gives for the whole
    layer with every expert held. The shares' models, each built from its
    own mapping, differ in the first expert held and in nothing else the
    layer reads: ONE compiled program, that expert an argument, is every
    share's (HybridMoeLM's 32 shares were 32 compiles)."""
    block = BLOCKS[name]
    spec, n_exp, p, x, (want, once, shared), part = UNCUT[name](block.t)
    lms = [block.cls(dict(spec, experts_held=[first, 2]))
           for first in range(0, n_exp, 2)]

    @jax.jit
    def run_share(first, once, share):
        lm = copy.copy(lms[0])
        lm.moe = lm.moe._replace(first=first)
        return lm._experts(once, share)

    total, landed = once + shared, 0.0
    for lm in lms:
        first = lm.moe.first
        assert lm.moe == lms[0].moe._replace(first=first)
        assert lm.spec == dict(lms[0].spec, experts_held=[first, 2])
        share = part(p, experts=jax.tree.map(lambda a: a[first:first + 2],
                                             p["experts"]))
        after, stats = run_share(jnp.int32(first), once, share)
        total = total + (after - once - shared)  # the routed part
        landed += float(jnp.sum(stats["load"]))
        assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every (token, choice) pair landed on exactly one share
    assert landed == block.t * spec["num_experts_per_tok"]


@pytest.mark.parametrize("name,module,names", [
    ("HybridMoeLM", hybrid_moe, ("_route", "_buffer", "_experts",
                                 "dispatch_rows", "token_nll")),
    ("WindowedMoeLM", windowed_moe, ("_route", "_buffer", "_experts",
                                     "dispatch_rows", "token_nll", "init")),
    ("kept_five", conv_moe, ("_choose", "_route", "_buffer", "_every_token",
                             "_experts", "dispatch_rows", "token_nll",
                             "weighted_nll", "init")),
    ("KdaMoeLM", kda_moe, ("_choose", "_route", "_buffer", "_experts",
                           "dispatch_rows", "token_nll", "weighted_nll",
                           "init")),
])
def test_the_expert_layer_is_shared_not_copied(name, module, names):
    for method in names:
        assert getattr(BLOCKS[name].cls, method) is getattr(
            LatentMoeLM, method), method
    assert module.fold_stats is latent_moe.fold_stats


def test_softmax_routing_runs_further_buffers_exactly(monkeypatch):
    """A dispatch buffer of 16 rows: the chip's share of the 480 pairs
    overflows it, the layer runs further buffers as under sigmoid routing
    (tests/test_latent_moe.py), nothing is dropped and loss and gradient
    are the reference's."""
    c = block_programs("HybridMoeLM")
    toks = _tokens("HybridMoeLM", 4)
    monkeypatch.setattr(HybridMoeLM, "dispatch_rows",
                        lambda self, tokens: 16)
    (loss, stats), got = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(c.lm, p, toks), has_aux=True))(c.params)
    assert float(stats["moe_full_dispatch"]) > 0.0
    assert float(stats["moe_dropped"]) == 0.0
    want_loss, want = c.reference(c.params, toks)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    for path in (("layer0", "experts", "down", "kernel"),
                 ("layer3", "router", "kernel"),
                 ("layer2", "shared_gate", "kernel")):
        g, w = parity.leaf(got, path), parity.leaf(want, path)
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-9, path


# ---- the mapping and the configuration ----------------------------------

def _rope(kind, **kw):
    def edit(s):
        s["rope_parameters"][kind].update(kw)
    return edit


def _set(**kw):
    return lambda s: s.update(kw)


def _pop(key):
    return lambda s: s.pop(key)


def _linear(**kw):
    return lambda s: s["linear_attn_config"].update(kw)


REFUSED_MAPPINGS = {
    "LatentMoeLM": [
        (_pop("kv_lora_rank"), "kv_lora_rank"),
        (_set(q_lora_rank=1536), "q_lora_rank"),
        (_set(scoring_func="softmax"), "scoring_func"),
        (_set(experts_held=[7, 2]), "experts_held"),
        (_set(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (_set(tie_word_embeddings=True), "tie_word_embeddings"),
    ],
    "HybridMoeLM": [
        (_pop("linear_key_head_dim"), "linear_key_head_dim"),
        (_set(experts_held=[7, 2]), "experts_held"),
        (_set(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (_set(tie_word_embeddings=True), "tie_word_embeddings"),
        (_set(mlp_only_layers=[0]), "mlp_only_layers"),
        (_set(use_sliding_window=True), "use_sliding_window"),
        (_set(num_key_value_heads=3), "num_key_value_heads"),
        (_set(partial_rotary_factor=0.3), "partial_rotary_factor"),
    ],
    "WindowedMoeLM": [
        (_pop("sliding_window"), "sliding_window"),
        (_set(experts_held=[7, 2]), "experts_held"),
        (_set(attention_bias=True), "attention_bias"),
        (_set(use_sliding_window=False), "use_sliding_window"),
        (_set(tie_word_embeddings=True), "tie_word_embeddings"),
        (_set(norm_topk_prob=False), "norm_topk_prob"),
        (_set(hidden_act="gelu"), "hidden_act"),
        (_set(mlp_layer_types=["sparse", "dense", "sparse", "sparse"]),
         r"mlp_layer_types'\]\[1\]"),
        (_set(layer_types=["sliding_attention", "chunked_attention",
                           "sliding_attention", "full_attention"]),
         r"layer_types'\]\[1\]"),
        (_set(layers=5), "layers"),
        (_set(num_key_value_heads=3), "num_key_value_heads"),
        (_set(sliding_window=0), "sliding_window"),
        (_rope("full_attention", rope_type="llama3"), "rope_type"),
        (_rope("full_attention", mscale=0.7), "mscale"),
        (_rope("sliding_attention", factor=2.0), "factor"),
        (lambda s: s["rope_parameters"].pop("full_attention"),
         "full_attention"),
    ],
    "kept_five": [
        (_pop("conv_L_cache"), "conv_L_cache"),
        (_set(conv_bias=True), "conv_bias"),
        (_set(use_expert_bias=False), "use_expert_bias"),
        (_set(norm_topk_prob=False), "norm_topk_prob"),
        (_set(tie_word_embeddings="yes"), "tie_word_embeddings"),
        (_set(layer_types=["conv", "conv", "sliding_attention", "conv",
                           "conv", "conv"]), r"layer_types'\]\[2\]"),
        # a dense layer after a sparse one: the kept indices out of order
        (_set(layers_held=[2, 0, 3, 4, 5]), "dense layer after a sparse one"),
        (_set(layers_held=[0, 2, 2, 4, 5]), "layers_held"),
        (_set(layers_held=[0, 2, 3, 4, 6]), "layers_held"),
        (_set(layers=4), "layers_held"),
        (_set(experts_held=[7, 2]), "experts_held"),
        (_set(num_key_value_heads=3), "num_key_value_heads"),
        (_set(num_attention_heads=64), "hidden_size"),
        (_set(conv_L_cache=0), "conv_L_cache"),
        (_set(vocab_rows=1), "vocab_rows"),
    ],
    "KdaMoeLM": [
        (_pop("kv_lora_rank"), "kv_lora_rank"),
        (_set(q_lora_rank=1536), "q_lora_rank"),
        (_set(mla_use_nope=False), "mla_use_nope"),
        (_set(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (_set(num_expert_group=2), "num_expert_group"),
        (_set(topk_group=2), "topk_group"),
        (_set(moe_layer_freq=2), "moe_layer_freq"),
        (_set(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
        (_set(tie_word_embeddings=True), "tie_word_embeddings"),
        (_set(hidden_act="gelu"), "hidden_act"),
        (_set(moe_router_activation_func="softmax"),
         "moe_router_activation_func"),
        (_set(moe_renormalize=False), "moe_renormalize"),
        (_linear(short_conv_kernel_size=3), "short_conv_kernel_size"),
        (_linear(num_heads=8), r"linear_attn_config'\]\['num_heads"),
        (lambda s: s["linear_attn_config"].pop("kda_layers"), "kda_layers"),
        # a kept layer in both of the config's lists, and in neither
        (_linear(full_attn_layers=[3, 4]), "layer 3"),
        (_linear(kda_layers=[1, 2, 3, 6]), "layer 5"),
        (_set(layers_held=[1, 3, 2, 4, 5]), "layers_held"),
        (_set(layers=4), "layers_held"),
        (_set(heads_held=[3, 2]), "heads_held"),
        (_set(heads_held=[0, 0]), "heads_held"),
        (_set(experts_held=[7, 2]), "experts_held"),
        (_set(vocab_rows=1), "vocab_rows"),
    ],
    "LoopedLM": [
        (_set(layer_types=["full_attention", "sliding_attention"]),
         "layer_types"),
        (_set(use_sliding_window=True), "use_sliding_window"),
        (_set(rope_scaling={"rope_type": "yarn", "factor": 4}),
         "rope_scaling"),
        (_set(total_ut_steps=0), "total_ut_steps"),
        (_set(total_ut_steps=2.5), "total_ut_steps"),
        (_set(num_key_value_heads=2), "num_key_value_heads"),
        (_set(tie_word_embeddings=True), "tie_word_embeddings"),
        (_set(hidden_act="gelu"), "hidden_act"),
        (_set(layers=0), "layers"),
        (_set(layers=7), "layers"),
        (_set(head_dim=15), "head_dim"),
        (_set(vocab_rows=1), "vocab_rows"),
    ],
}


def _cases(table):
    """[(name, *row)] of {name: rows}, each with an id that says both."""
    return [pytest.param(name, *row, id=f"{name}-{i}-{row[-1]}")
            for name, rows in table.items() for i, row in enumerate(rows)]


@pytest.mark.parametrize("name,edit,names", _cases(REFUSED_MAPPINGS))
def test_a_mapping_the_block_cannot_state_is_refused_by_name(name, edit,
                                                             names):
    spec = json.loads(json.dumps(spec_of(name)))
    edit(spec)
    with pytest.raises(ValueError, match=names):
        BLOCKS[name].cls(spec)


def _cfg(name, **kw):
    spec = spec_of(name)
    base = dict(network=BLOCKS[name].cls.__name__, dataset="synthetic-text",
                model_spec=spec, vocab=spec["vocab_rows"],
                seq_len=BLOCKS[name].t, batch_size=2, num_workers=3,
                approach="maj_vote", group_size=3, worker_fail=1,
                train_dir="")
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.parametrize("name", NETWORKS)
def test_the_network_is_built_on_the_normal_path(name):
    block = BLOCKS[name]
    assert SPEC_NETWORKS[block.cls.__name__] == block.cls.__module__
    cfg = _cfg(name).validate()
    lm = build_lm(cfg)
    assert type(lm) is block.cls and lm.remat == cfg.remat
    assert lm.stat_names[-len(block.stat_tail):] == block.stat_tail
    # the route's bare kernel reaches the model
    lm = build_lm(cfg, kernel_fn=flash_attention)
    assert lm.attn_fn is flash_attention


_LM = dict(network="TransformerLM", model_spec=None, vocab=64)
VOCAB_ROWS = 64  # every tiny mapping's
UNSUPPORTED = {
    "LatentMoeLM": [
        (dict(tensor_shards=2), "tensor_shards"),
        (dict(seq_shards=2), "seq_shards"),
        (dict(expert_shards=2), "expert_shards"),
        (dict(pipeline_shards=2), "pipeline_shards"),
        (dict(vocab=VOCAB_ROWS + 1), "vocab_rows"),
        (dict(moe_experts=4), "moe_experts"),
        (dict(model_spec=None), "model_spec"),
        (dict(network="LeNet", dataset="synthetic-mnist"), "model_spec"),
        (dict(wire_dtype="bf16"), "wire_dtype"),
        (dict(numerics_watch="on"), "numerics_watch"),
        (dict(_LM, seq_shards=2), "seq_shards"),
        (dict(_LM, tensor_shards=2), "tensor_shards"),
    ],
    "HybridMoeLM": [
        (dict(tensor_shards=2), "tensor_shards"),
        (dict(seq_shards=2), "seq_shards"),
        (dict(vocab=VOCAB_ROWS + 1), "vocab_rows"),
        (dict(moe_experts=4), "moe_experts"),
        (dict(model_spec=None), "model_spec"),
        # the other family's mapping under this network's name, and back
        (dict(model_spec={"hidden_size": 64}), "model_spec lacks"),
        (dict(network="LatentMoeLM"), "kv_lora_rank"),
    ],
    "WindowedMoeLM": [
        (dict(tensor_shards=2), "tensor_shards"),
        (dict(seq_shards=2), "seq_shards"),
        (dict(vocab=VOCAB_ROWS + 1), "vocab_rows"),
        (dict(model_spec=None), "model_spec"),
        (dict(model_spec={"hidden_size": 64}), "model_spec lacks"),
        (dict(network="HybridMoeLM"), "full_attention_interval"),
        (dict(network="LeNet"), "WindowedMoeLM"),
    ],
    "kept_five": [
        (dict(tensor_shards=2), "tensor_shards"),
        (dict(seq_shards=2), "seq_shards"),
        (dict(vocab=VOCAB_ROWS + 1), "vocab_rows"),
        (dict(model_spec=None), "model_spec"),
        (dict(model_spec={"hidden_size": 64}), "model_spec lacks"),
        (dict(network="WindowedMoeLM"), "model_spec lacks"),
        (dict(network="LeNet"), "ShortConvMoeLM"),
    ],
    "KdaMoeLM": [
        (dict(tensor_shards=2), "tensor_shards"),
        (dict(seq_shards=2), "seq_shards"),
        (dict(vocab=VOCAB_ROWS + 1), "vocab_rows"),
        (dict(model_spec=None), "model_spec"),
        (dict(model_spec={"hidden_size": 64}), "model_spec lacks"),
        (dict(network="LatentMoeLM"), "model_spec lacks"),
        (dict(network="LeNet"), "KdaMoeLM"),
    ],
}


@pytest.mark.parametrize("name,kw,names", _cases(UNSUPPORTED))
def test_what_stays_unsupported_is_refused_by_name(name, kw, names):
    assert spec_of(name)["vocab_rows"] == VOCAB_ROWS
    with pytest.raises(ValueError, match=names):
        _cfg(name, **kw).validate()
