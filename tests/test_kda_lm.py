"""models/kda_moe.KdaMoeLM and ops/kda_rule.py at a tiny size — what is the
block's own (loss, logits, every leaf's gradient, three steps, the
rematerialised block, the weighted surface, the refused mappings and
configurations, the counters: tests/test_spec_lm_parity.py's rows
``KdaMoeLM`` / ``kda_only`` / ``nope_latent_only``):

* the chunked per-channel rule against the recurrence token by token —
  outputs, last state and all five gradients — at mild decays and at decays
  so strong that a chunk's summed g passes −80 (and −500): a form that
  formed e^{−G} would overflow there, one that dropped the small terms
  would miss the gradients;
* the three Pallas kernels (interpret mode) against the ``jax.numpy`` form at
  the same three strengths — o, the last state, the five gradients —, the
  shapes they do not take, the checkpoint that keeps the solve;
* g of three dimensions never enters the new code, still matches the
  scalar-decay recurrence, and is what the per-channel rule gives when every
  channel of a head is handed the same decay (bit-equality with the parent
  is the lowered program's, compared on the build that runs the cell:
  CHANGES.md, PR 45);
* the share tied to the model: over both head shares x all expert shares of
  the tiny mapping, the mixers' partial sums and the experts' parts, with
  router, shared expert and whole-width pieces counted once, add up to the
  uncut reference's layer — the KDA layer and the latent-attention layer;
* latent attention is ``latent_moe``'s own, its rotation switched off, and
  ``rope_interleaved`` still rotates where it is handed a base.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import parity  # noqa: E402
from benchmark.reference.nets import kimi_linear as ref  # noqa: E402
from benchmark.reference.nets import qwen3_next  # noqa: E402
from draco_tpu.models import latent_moe  # noqa: E402
from draco_tpu.models.kda_moe import KdaMoeLM  # noqa: E402
from draco_tpu.ops import delta_rule, kda_rule  # noqa: E402

T, H, D = 150, 2, 16  # two whole chunks and a closing one of 22 tokens


def _inputs(strength, seed=0, t=T, h=H, d=D):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (1, t, h, d))
    k = jax.random.normal(ks[1], (1, t, h, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, t, h, d))
    g = -strength * jax.nn.softplus(jax.random.normal(ks[3], (1, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h)))
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    """The recurrence as written: -> (o (1, T, H, Dv), the last state)."""
    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   b_t[:, None] * (v_t - read))
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state, o = jax.lax.scan(token, jnp.zeros((H, D, D)),
                            (q[0], k[0], v[0], g[0], beta[0]))
    return o[None], state[None]


_W = jax.random.normal(jax.random.key(9), (1, T, H, D))
_WS = jax.random.normal(jax.random.key(10), (1, H, D, D))


def _loss(out):
    return jnp.sum(out[0] * _W) + jnp.sum(out[1] * _WS)


with jax.default_matmul_precision("highest"):
    CHUNKED = parity.with_gradients(
        lambda *a: delta_rule.chunked_gated_delta_rule(*a), _loss,
        argnums=(0, 1, 2, 3, 4))
    SCANNED = parity.with_gradients(_token_by_token, _loss,
                                    argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("strength,least", [(0.05, -5.0), (1.5, -80.0),
                                            (8.0, -500.0)])
def test_chunked_per_channel_rule_is_the_recurrence(strength, least):
    args = _inputs(strength)
    # the strong cases are strong: a chunk's summed g passes ``least``
    reach = float(kda_rule.chunk_decay_min(args[3]))
    assert reach < least if least < -5.0 else reach > least
    (o, state), grads = CHUNKED(*args)
    (want_o, want_state), want = SCANNED(*args)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(state, want_state, atol=2e-6)
    for name, a, b in zip("qkvgb", grads, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0.0, name
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * scale, name


def test_a_sub_block_of_the_whole_chunk_gives_the_same():
    """``sub`` = the chunk: every pair of a chunk's tokens pairwise, no
    reference row at all — the form the blocks' products must agree with."""
    args = _inputs(1.5, seed=3)
    o, state = jax.jit(kda_rule.chunked_kda_rule)(*args)
    want_o, want_state = jax.jit(functools.partial(
        kda_rule.chunked_kda_rule, sub=64))(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(state, want_state, atol=2e-6)


def test_a_decay_a_head_never_enters_the_new_code(monkeypatch):
    q, k, v, g, beta = _inputs(0.3, seed=1)
    g3 = g[..., 0]

    def refuse(*a, **kw):
        raise AssertionError("g (B, T, H) reached the per-channel rule")

    monkeypatch.setattr(delta_rule, "_per_channel", refuse)
    o, state = jax.jit(delta_rule.chunked_gated_delta_rule)(q, k, v, g3,
                                                            beta)
    monkeypatch.undo()
    want = qwen3_next.delta_rule(q[0], k[0], v[0], g3[0], beta[0],
                                 lambda x: x)
    np.testing.assert_allclose(o[0], want, atol=2e-6)
    # every channel of a head handed the head's decay: the same rule
    o4, state4 = jax.jit(delta_rule.chunked_gated_delta_rule)(
        q, k, v, jnp.broadcast_to(g3[..., None], g.shape), beta)
    np.testing.assert_allclose(o4, o, atol=2e-6)
    np.testing.assert_allclose(state4, state, atol=2e-6)


@pytest.mark.parametrize("which", ["k_of_one_head", "v_of_twice_the_heads"])
def test_shapes_the_per_channel_rule_cannot_take_are_refused(which):
    q, k, v, g, beta = _inputs(0.3)
    if which == "k_of_one_head":
        k = k[:, :, :1]
    else:
        v = jnp.concatenate([v, v], axis=2)
    with pytest.raises(ValueError, match="per-channel decay"):
        delta_rule.chunked_gated_delta_rule(q, k, v, g, beta)


# ---- the kernels (interpret mode) against the jax.numpy form ----------------

LANES = 128  # the kernels take head sizes of whole lane tiles


def _lane_inputs(t, heads, strength, d=LANES, seed=0):
    """(``_inputs`` at T = ``t``, ``heads`` heads of size ``d``; what the
    two outputs are weighed by)."""
    ks = jax.random.split(jax.random.key(seed + 100), 2)
    return _inputs(strength, seed, t, heads, d), (
        jax.random.normal(ks[0], (1, t, heads, d)),
        jax.random.normal(ks[1], (1, heads, d, d)))


def _both_forms(args, probes, **kernel_kw):
    """(o, state, five gradients) of the ``jax.numpy`` form and of the call
    with ``kernel_kw``, each ONE compiled program; both outputs take a
    cotangent."""
    def run(**kw):
        (o, state), grads = parity.with_gradients(
            functools.partial(delta_rule.chunked_gated_delta_rule, **kw),
            lambda out: (jnp.sum(out[0] * probes[0])
                         + jnp.sum(out[1] * probes[1])),
            argnums=(0, 1, 2, 3, 4))(*args)
        return (o, state) + tuple(grads)

    return run(), run(**kernel_kw)


def _assert_same(got, want, rel=2e-5):
    for name, a, b in zip("o state dq dk dv dg dbeta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0.0, name  # no term lost
        assert float(jnp.max(jnp.abs(a - b))) <= rel * scale, name


@pytest.mark.parametrize("strength,least", [(0.05, -5.0), (1.5, -80.0),
                                            (8.0, -500.0)])
def test_kernels_are_the_jnp_form(strength, least):
    """Two heads side by side in the solve, T of two grid steps: the state
    and its cotangent cross a grid step in VMEM."""
    args, probes = _lane_inputs(128, 2, strength)
    reach = float(kda_rule.chunk_decay_min(args[3]))
    assert reach < least if least < -5.0 else reach > least
    assert kda_rule.kda_runs_in_kernels(args[0].shape, args[2].shape,
                                        interpret=True)
    want, got = _both_forms(args, probes, interpret=True)
    _assert_same(got, want)


def test_kernels_work_through_a_step_s_heads_in_groups(monkeypatch):
    """Four heads a grid step in two groups of two (sixteen in two of eight
    on the chip): the loop over the groups, a head's index the loop's."""
    monkeypatch.setattr(kda_rule, "_HEADS_A_STEP", 4)
    monkeypatch.setattr(kda_rule, "_HEADS_A_GROUP", 2)
    kda_rule._shapes.cache_clear()
    try:
        args, probes = _lane_inputs(128, 4, 1.5, seed=2)
        shapes = kda_rule._shapes(args[0].shape, args[2].shape, True)
        assert (shapes.hb, shapes.hg, shapes.pr) == (4, 2, 2)
        want, got = _both_forms(args, probes, interpret=True)
        _assert_same(got, want)
    finally:
        kda_rule._shapes.cache_clear()


@pytest.mark.parametrize("t,d,chunk", [
    (100, LANES, 64),  # T not whole chunks
    (128, 64, 64),     # head size under a lane tile
    (128, LANES, 32),  # not the family's chunk
])
def test_shapes_the_kernels_do_not_take_are_the_jnp_form(t, d, chunk,
                                                         monkeypatch):
    """The kernels are not chosen — a call to them would raise here — and
    the result is today's, bit for bit, whatever ``interpret`` says."""
    args, _ = _lane_inputs(t, 1, 0.2, d=d)
    assert not kda_rule.kda_runs_in_kernels(args[0].shape, args[2].shape,
                                            chunk, force=True)

    def refuse(*a, **kw):
        raise AssertionError("the kernels were chosen")

    monkeypatch.setattr(kda_rule, "_rule", refuse)
    rule = functools.partial(delta_rule.chunked_gated_delta_rule, chunk=chunk)
    want = jax.jit(rule)(*args)
    got = jax.jit(functools.partial(rule, interpret=True))(*args)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_off_the_chip_the_kernels_are_not_chosen():
    shape = (1, 128, 2, LANES)
    assert not kda_rule.kda_runs_in_kernels(shape, shape)
    assert not kda_rule.kda_runs_in_kernels(shape, shape, interpret=True,
                                            force=False)
    assert kda_rule.kda_runs_in_kernels(shape, shape, force=True)
    # as many value heads as key heads, or the jax.numpy form
    assert not kda_rule.kda_runs_in_kernels(shape, (1, 128, 4, LANES),
                                            force=True)


def test_a_checkpoint_that_keeps_the_solve_runs_the_pass_again_only():
    """The solve is its own kernel and T carries ``SOLVE_NAME``: the
    gradient's program is three kernels (solve, pass, backward); under a
    checkpoint five (solve and pass again); with the name saved — the
    mixer's policy, ``KEEP_SOLVE`` — four: the rematerialised forward is the
    pass alone."""
    from draco_tpu.models.hybrid_moe import KEEP_SOLVE

    args, _ = _lane_inputs(64, 1, 0.2)

    def rule(*a):
        return delta_rule.chunked_gated_delta_rule(*a, interpret=True)[0]

    def kernels(fn):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))
        text = str(jax.make_jaxpr(grad)(*args))
        return [text.count(f"name={name}_kernel")
                for name in ("solve", "pass", "backward")]

    assert kernels(rule) == [1, 1, 1]
    assert kernels(jax.checkpoint(rule)) == [2, 2, 1]
    assert kernels(jax.checkpoint(rule, policy=KEEP_SOLVE)) == [1, 2, 1]


# ---- the share tied to the model ---------------------------------------

SPEC = parity.tiny("kda-moe-tiny")
HEADS, EXPERTS = SPEC["num_attention_heads"], SPEC["num_experts"]
WHOLE = dict(SPEC, heads_held=[0, HEADS], experts_held=[0, EXPERTS])
SAME = lambda a: a  # noqa: E731


def _columns(x, first, count, width):
    """Heads [first, first + count) of a (..., heads·width) leaf."""
    return x[..., first * width:(first + count) * width]


def _mixer_share(p, a_log, first, count, kind):
    """What a chip that holds heads [first, first + count) holds of an
    uncut layer's mixer: its heads' columns of the input projections, rows
    of the output projection; the whole-width pieces as they are."""
    s = SPEC
    if kind == "kda":
        dk = s["linear_attn_config"]["head_dim"]
        cut = functools.partial(_columns, first=first, count=count, width=dk)
        out = dict(p)
        for name in ("q", "k", "v", "g_b"):
            out[name] = {"kernel": cut(p[name]["kernel"])}
        for name in ("q_conv", "k_conv", "v_conv"):
            out[name] = {"taps": cut(p[name]["taps"])}
        out["f_b"] = {"kernel": cut(p["f_b"]["kernel"]),
                      "dt_bias": cut(p["f_b"]["dt_bias"])}
        out["b"] = {"kernel": p["b"]["kernel"][:, first:first + count]}
        out["o"] = {"kernel": p["o"]["kernel"][
            first * dk:(first + count) * dk]}
        return out, a_log[first:first + count]
    nope, rp, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                    s["v_head_dim"])
    return dict(
        p, q={"kernel": _columns(p["q"]["kernel"], first, count, nope + rp)},
        kv_b={"kernel": _columns(p["kv_b"]["kernel"], first, count,
                                 nope + vd)},
        o={"kernel": p["o"]["kernel"][first * vd:(first + count) * vd]}), None


@pytest.mark.parametrize("kind,layer", [("kda", "layer1"), ("mla", "layer3")])
def test_head_and_expert_shares_add_up_to_the_uncut_layer(kind, layer):
    """Two head shares x four expert shares: the mixer's partial sums over
    the head shares (the same on every expert share: the mixer never reads
    ``experts_held``), then every expert share's routed part, with the
    shared expert ONCE, is the reference's whole layer at every head and
    every expert."""
    t = 80
    lm_whole = KdaMoeLM(WHOLE)
    params = parity.moved(lm_whole.init(jax.random.key(5)),
                          jax.random.key(6), ("scale", "dt_bias"))
    p = params[layer]
    a_log = params["linear_heads"]["A_log"][1] if kind == "kda" else None
    x = jax.random.normal(jax.random.key(7), (t, SPEC["hidden_size"]))
    eps = SPEC["rms_norm_eps"]

    @jax.jit
    def reference(x, p, a_log):
        h = ref.rms(x, p["attn_norm"]["scale"], eps)
        mixed = (ref.kda(h, p, a_log, WHOLE, SAME) if kind == "kda"
                 else ref.latent_attention(h, p, WHOLE, SAME))
        m = ref.rms(x + mixed, p["mlp_norm"]["scale"], eps)
        return (ref.layer(x, p, a_log, WHOLE, SAME, kind == "kda", False),
                mixed, ref.swiglu(m, p["shared"], SAME))

    want, want_mixed, shared = reference(x, p, a_log)
    half = HEADS // 2
    once = x
    for first in (0, half):
        parts = []
        for e_first in (0, EXPERTS // 2):
            lm = KdaMoeLM(dict(SPEC, heads_held=[first, half],
                               experts_held=[e_first, 2]))
            share, heads = _mixer_share(p, a_log, first, half, kind)

            @jax.jit
            def mixer(x, share, heads, lm=lm):
                h = lm.norm(x[None], share["attn_norm"])
                if kind == "kda":
                    return lm._kda(h, share, heads)[0][0]
                return latent_moe.LatentMoeLM._attention(
                    lm._latent, h, share, jnp.arange(t))[0]

            parts.append(mixer(x, share, heads))
        np.testing.assert_array_equal(parts[0], parts[1])
        once = once + parts[0]
    np.testing.assert_allclose(once - x, want_mixed, atol=2e-5)
    total, landed = once + shared, 0.0
    for e_first in range(0, EXPERTS, 2):
        lm = KdaMoeLM(dict(SPEC, experts_held=[e_first, 2]))
        share = dict(p, experts=jax.tree.map(
            lambda a: a[e_first:e_first + 2], p["experts"]))
        after, stats = jax.jit(lm._experts)(once, share)
        total = total + (after - once - shared)  # the routed part
        landed += float(jnp.sum(stats["load"]))
        assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every (token, choice) pair landed on exactly one share
    assert landed == t * SPEC["num_experts_per_token"]


def test_latent_attention_is_latent_moes_with_no_rotation():
    """The block holds no attention of its own; what it hands
    ``LatentMoeLM._attention`` has no rotary base, and ``rope_interleaved``
    without one passes its input — with one it still rotates."""
    assert not hasattr(KdaMoeLM, "_attention")
    lm = KdaMoeLM(SPEC)
    assert lm._latent.spec["rope_theta"] is None
    assert lm._latent.spec["num_attention_heads"] == SPEC["heads_held"][1]
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 8))
    pos = jnp.arange(6)
    assert latent_moe.rope_interleaved(x, pos, None) is x
    turned = latent_moe.rope_interleaved(x, pos, 10000.0)
    np.testing.assert_array_equal(turned[:, 0], x[:, 0])  # position 0
    assert float(jnp.max(jnp.abs(turned[:, 1:] - x[:, 1:]))) > 0.1
    # no position enters the model anywhere: a row reads the same wherever
    # it is said to start
    toks = parity.tokens(SPEC["vocab_rows"], 1, 8, 0)
    only = KdaMoeLM(dict(
        SPEC, layers=1, layers_held=[2], linear_attn_config=dict(
            SPEC["linear_attn_config"], kda_layers=[],
            full_attn_layers=[1, 2, 3, 4, 5, 6])))
    p = only.init(jax.random.key(2))
    a, _ = jax.jit(only.hidden)(p, toks, 0)
    b, _ = jax.jit(only.hidden)(p, toks, 1000)
    np.testing.assert_array_equal(a, b)
