"""models/hybrid_moe.HybridMoeLM at a tiny size (hidden 64, 4 query heads on
2 key/value heads, 2 key and 4 value DeltaNet heads, 8 routed experts of
which 2 are held, one period of 3 DeltaNet layers and 1 gated-attention
layer) against the plain reference the benchmark compares it with on the
chip (benchmark/reference/nets/qwen3_next.py, which imports nothing of
draco_tpu and runs the delta rule token by token):

* loss, logits and every leaf's gradient on seeded weights, the norms' and
  the per-head leaves moved off their initial zeros and ones;
* the shares add up: over all 32 shares of a layer of 64 experts, the routed
  parts summed plus the gated shared expert and the mixer ONCE are the uncut
  reference layer;
* the expert layer is latent_moe's, not a copy: one ``_route`` / ``_buffer``
  under both models, and softmax routing runs further dispatch buffers
  exactly as sigmoid routing does;
* grouped-query heads through the flash kernel (interpret mode);
* every leaf of 128 elements or more of the PUBLISHED configuration starts
  and ends on a 128-wide line of the vote's stack (its sub-line leaves come
  last in ravel order), and an unravel of a row in lines is the flat one;
* a mapping the block cannot state is refused by the key's name.

Tolerances: program and reference are float32 sums of the same terms in
another order (chunked against token by token, a dispatch buffer against a
dense mask, the flash-style softmax against the plain one): 2e-6 relative on
the loss, 2e-5 absolute on logits of order one, 2e-4 of a leaf's largest
gradient entry (1e-3 for the two per-head leaves, whose gradients are sums
over every token of terms that cancel).
"""

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

from benchmark.reference.nets import qwen3_next as ref  # noqa: E402
from draco_tpu.config import TrainConfig  # noqa: E402
from draco_tpu.models import build_lm, hybrid_moe, latent_moe  # noqa: E402
from draco_tpu.models.hybrid_moe import HybridMoeLM  # noqa: E402
from draco_tpu.training.step import _make_unravel  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "testdata",
                       "hybrid-moe-tiny.json")) as fh:
    TINY = json.load(fh)
SPEC = TINY["train_config"]["model_spec"]
T = 80  # a chunk of 64 and a closing chunk of 16: the state crosses chunks
PER_HEAD = ("['A_log']", "['dt_bias']")


def _tokens(seed=0, batch=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, SPEC["vocab_rows"], (batch, T)),
                       jnp.int32)


def _loss(lm, params, toks):
    nll, stats = lm.token_nll(params, toks, jnp.roll(toks, -1, axis=1))
    return jnp.mean(nll[:, :-1]), stats


def _moved(params, key):
    """Norm weights and per-head vectors off their initial zeros / ones,
    so that a (1 + w) read as w, or a head's row read as another's, shows."""
    def move(path, x):
        if path[-1].key in ("centred_scale", "scale", "dt_bias"):
            k = jax.random.fold_in(key, zlib.crc32(
                jax.tree_util.keystr(path).encode()) % 2**31)
            return x + 0.1 * jax.random.normal(k, x.shape)
        return x

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def model():
    lm = HybridMoeLM(SPEC)
    return lm, _moved(lm.init(jax.random.key(3)), jax.random.key(4))


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


def test_loss_and_logits_match_the_reference(model):
    lm, params = model
    toks = _tokens()
    loss, stats = _loss(lm, params, toks)
    assert float(loss) == pytest.approx(
        float(ref.loss(params, toks, SPEC)), rel=2e-6)
    got = lm.logits(params, toks)
    for b in range(toks.shape[0]):
        np.testing.assert_allclose(got[b], ref.logits(params, toks[b], SPEC),
                                   atol=2e-5)
    assert set(stats) == set(lm.stat_names)
    assert lm.stat_names[:4] == latent_moe.STAT_NAMES
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["linattn_state_absmax"]) > 0.0
    # off the chip the rule takes the jax.numpy path in every layer
    assert float(stats["linattn_kernel_layers"]) == 0.0


def test_every_leafs_gradient_matches_the_reference(model):
    lm, params = model
    toks = _tokens(1)
    got = jax.grad(lambda p: _loss(lm, p, toks)[0])(params)
    want = jax.grad(ref.loss)(params, toks, SPEC)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0.0, f"{name} takes no gradient"
        rel = 1e-3 if name.endswith(PER_HEAD) else 2e-4
        assert float(jnp.max(jnp.abs(g - w))) <= rel * scale + 1e-9, name


def test_rematerialised_block_gives_the_same_gradient(model):
    lm, params = model
    toks = _tokens(2)
    plain = jax.grad(lambda p: _loss(lm, p, toks)[0])(params)
    remat = jax.grad(lambda p: _loss(HybridMoeLM(SPEC, remat=True), p,
                                     toks)[0])(params)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


def test_the_state_counter_grows_when_the_heads_forget_less(model):
    """``linattn_state_absmax`` reads the states the row leaves behind:
    with every head's decay rate cut by e^6 the states keep what they were
    written, and the counter rises."""
    lm, params = model
    toks = _tokens(3, batch=1)
    _, stats = _loss(lm, params, toks)
    slow = dict(params, linear_heads=dict(
        params["linear_heads"],
        A_log=params["linear_heads"]["A_log"] - 6.0))
    _, held = _loss(lm, slow, toks)
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
    import functools

    for name in ("chunked_gated_delta_rule", "rule_runs_in_kernels"):
        monkeypatch.setattr(hybrid_moe, name, functools.partial(
            getattr(hybrid_moe, name), interpret=True))


def test_deltanet_layer_in_the_kernels_matches_the_reference(
        rule_in_kernels):
    """The layer's output and the gradient of its input, of every leaf and
    of the per-head vectors, the rule in the Pallas kernels (interpret
    mode), against ``qwen3_next.gated_deltanet`` token by token."""
    lm = HybridMoeLM(LANE_SPEC)
    params = _moved(lm.init(jax.random.key(11)), jax.random.key(12))
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

    (_, (got, absmax, kernels)), g_got = jax.value_and_grad(
        program, argnums=(0, 1, 2), has_aux=True)(h, p, heads)
    (_, want), g_want = jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True)(h, p, heads)
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
    _, stats = lm.hidden(params, toks)
    assert float(stats["linattn_kernel_layers"]) == \
        lm.layer_types.count("linear_attention") == 3
    # the tiny block's own heads (16 wide) stay on the jax.numpy path
    small = HybridMoeLM(SPEC)
    _, stats = small.hidden(small.init(jax.random.key(15)), toks)
    assert float(stats["linattn_kernel_layers"]) == 0.0


def test_the_32_shares_add_up_to_the_uncut_layer():
    """32 chips hold two of 64 experts each. Every share's routed part,
    plus the DeltaNet mixer and the gated shared expert ONCE, is what the
    reference gives for the whole layer with all 64 experts held."""
    n_exp = 64
    small = dict(SPEC, num_experts=n_exp, num_experts_per_tok=5)
    whole = dict(small, experts_held=[0, n_exp])
    lm_whole = HybridMoeLM(whole)
    params = _moved(lm_whole.init(jax.random.key(5)), jax.random.key(6))
    p = params["layer1"]
    heads = (params["linear_heads"]["A_log"][1],
             params["linear_heads"]["dt_bias"][1])
    x = jax.random.normal(jax.random.key(7), (T, SPEC["hidden_size"]))
    same = lambda t: t  # noqa: E731
    want = ref.layer(x, p, heads, whole, same, full=False)

    eps = SPEC["rms_norm_eps"]
    once = x + ref.gated_deltanet(
        ref.rms(x, p["attn_norm"]["centred_scale"], eps), p, heads, whole,
        same)
    h = ref.rms(once, p["mlp_norm"]["centred_scale"], eps)
    shared = (jax.nn.sigmoid(h @ p["shared_gate"]["kernel"])
              * ref.swiglu(h, p["shared"], same))
    total = once + shared
    landed = 0.0
    for first in range(0, n_exp, 2):
        lm = HybridMoeLM(dict(small, experts_held=[first, 2]))
        part = dict(p, experts=jax.tree.map(lambda a: a[first:first + 2],
                                            p["experts"]))
        after, stats = lm._experts(once, part)  # once + shared + routed
        total = total + (after - once - shared)
        landed += float(jnp.sum(stats["load"]))
        assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every (token, choice) pair landed on exactly one share
    assert landed == T * small["num_experts_per_tok"]


def test_the_expert_layer_is_shared_not_copied():
    for name in ("_route", "_buffer", "_experts", "dispatch_rows",
                 "token_nll"):
        assert getattr(HybridMoeLM, name) is getattr(
            latent_moe.LatentMoeLM, name), name
    assert hybrid_moe.fold_stats is latent_moe.fold_stats


def test_softmax_routing_runs_further_buffers_exactly(model, monkeypatch):
    """A dispatch buffer of 16 rows: the chip's share of the 480 pairs
    overflows it, the layer runs further buffers, nothing is dropped
    and loss and gradient are the reference's."""
    lm, params = model
    toks = _tokens(4)
    monkeypatch.setattr(HybridMoeLM, "dispatch_rows",
                        lambda self, tokens: 16)
    (loss, stats), got = jax.value_and_grad(
        lambda p: _loss(lm, p, toks), has_aux=True)(params)
    assert float(stats["moe_full_dispatch"]) > 0.0
    assert float(stats["moe_dropped"]) == 0.0
    want_loss, want = jax.value_and_grad(ref.loss)(params, toks, SPEC)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    for path in (("layer0", "experts", "down", "kernel"),
                 ("layer3", "router", "kernel"),
                 ("layer2", "shared_gate", "kernel")):
        g, w = got, want
        for key in path:
            g, w = g[key], w[key]
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-9, path


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

    np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v), atol=2e-6)
    np.testing.assert_allclose(latent_moe.dense_causal_attention(q, k, v),
                               plain(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


# ---- the stack's lines --------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep32.json")) as fh:
        return json.load(fh)["train_config"]["model_spec"]


def test_every_leaf_of_the_published_configuration_lies_on_the_lines():
    """The real leaf table through ``_make_unravel``: every leaf of 128
    elements or more starts and ends on a 128-wide line, so the winner's
    row is cut where it lies (PR 29); the two sub-line leaves (a row of 32
    a DeltaNet layer) come last and shift nothing."""
    from draco_tpu.parallel.sp_step import STACK_LANES

    lm = HybridMoeLM(_published())
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


# ---- the mapping and the configuration ----------------------------------

@pytest.mark.parametrize("edit,names", [
    (lambda s: s.pop("linear_key_head_dim"), "linear_key_head_dim"),
    (lambda s: s.update(experts_held=[7, 2]), "experts_held"),
    (lambda s: s.update(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (lambda s: s.update(tie_word_embeddings=True), "tie_word_embeddings"),
    (lambda s: s.update(mlp_only_layers=[0]), "mlp_only_layers"),
    (lambda s: s.update(use_sliding_window=True), "use_sliding_window"),
    (lambda s: s.update(num_key_value_heads=3), "num_key_value_heads"),
    (lambda s: s.update(partial_rotary_factor=0.3), "partial_rotary_factor"),
])
def test_a_mapping_the_block_cannot_state_is_refused_by_name(edit, names):
    spec = dict(SPEC)
    edit(spec)
    with pytest.raises(ValueError, match=names):
        HybridMoeLM(spec)


def _cfg(**kw):
    base = dict(network="HybridMoeLM", dataset="synthetic-text",
                model_spec=SPEC, vocab=SPEC["vocab_rows"], seq_len=T,
                batch_size=2, num_workers=3, approach="maj_vote",
                group_size=3, worker_fail=1, train_dir="")
    base.update(kw)
    return TrainConfig(**base)


def test_the_network_is_built_on_the_normal_path():
    cfg = _cfg().validate()
    lm = build_lm(cfg)
    assert isinstance(lm, HybridMoeLM) and lm.remat == cfg.remat
    assert lm.stat_names[-2:] == ("linattn_state_absmax",
                                  "linattn_kernel_layers")


@pytest.mark.parametrize("kw,names", [
    (dict(tensor_shards=2), "tensor_shards"),
    (dict(seq_shards=2), "seq_shards"),
    (dict(vocab=SPEC["vocab_rows"] + 1), "vocab_rows"),
    (dict(moe_experts=4), "moe_experts"),
    (dict(model_spec=None), "model_spec"),
    # the other family's mapping under this network's name, and back
    (dict(model_spec={"hidden_size": 64}), "model_spec lacks"),
    (dict(network="LatentMoeLM"), "kv_lora_rank"),
])
def test_what_stays_unsupported_is_refused_by_name(kw, names):
    with pytest.raises(ValueError, match=names):
        _cfg(**kw).validate()
