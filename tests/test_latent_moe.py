"""models/latent_moe.LatentMoeLM at a tiny size (hidden 64, 4 heads, 8 routed
experts of which 2 are held) against the plain reference the benchmark
compares it with on the chip (benchmark/reference/nets/latent_moe.py, which
imports nothing of draco_tpu). What every published-config block is held
to alike — loss, logits, every leaf's gradient, the shares adding up, the
refusals — is tests/test_spec_lm_parity.py's; here is what is this
block's own:

* the top-k never drops a token, at any imbalance (all tokens to one held
  expert);
* the dispatch buffer holds C rows, fewer than T·k where the chip holds a
  small share of the experts: a routing that lands 0, 1, C − 1, C, C + 1 or
  T·min(k, held) pairs here gives the reference's output and gradients, with
  the further buffers it took counted;
* the flash kernel at q/k and v of different head sizes (interpret mode);
* the network validates with the vote.

Every compared value is one compiled program (tests/parity.py).
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import latent_moe as ref
from draco_tpu.config import TrainConfig
from draco_tpu.models import latent_moe
from draco_tpu.models.latent_moe import LatentMoeLM

SPEC = parity.tiny("latent-moe-tiny")
T = 32


def _tokens(seed=0, batch=2):
    return parity.tokens(SPEC["vocab_rows"], batch, T, seed)


@pytest.fixture(scope="module")
def model():
    lm = LatentMoeLM(SPEC)
    return lm, lm.init(jax.random.key(3))


def test_parameter_count_is_the_shapes(model):
    lm, params = model
    shapes = jax.tree.leaves(lm.param_shapes(),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert [tuple(x.shape) for x in jax.tree.leaves(params)] == shapes
    held = SPEC["experts_held"][1]
    assert params["layer1"]["experts"]["gate"]["kernel"].shape == (
        held, SPEC["hidden_size"], SPEC["moe_intermediate_size"])
    assert params["layer1"]["router"]["kernel"].shape[1] == \
        SPEC["n_routed_experts"]
    assert "router" not in params["layer0"]


def test_no_token_is_dropped_when_all_choose_one_held_expert(model):
    lm, params = model
    first, held = SPEC["experts_held"]
    k = SPEC["num_experts_per_tok"]
    skew = copy.deepcopy(jax.tree.map(np.asarray, params))
    bias = np.zeros(SPEC["n_routed_experts"], np.float32)
    # every token's top-k: both held experts, then experts held elsewhere
    bias[[first, first + 1]] = 50.0
    bias[0] = 40.0
    for i in range(SPEC["first_k_dense_replace"], SPEC["layers"]):
        skew[f"layer{i}"]["router"]["e_score_correction_bias"] = bias
    toks = _tokens(4)
    loss, stats = jax.jit(lambda p: parity.mean_nll(lm, p, toks))(skew)
    moe_layers = SPEC["layers"] - SPEC["first_k_dense_replace"]
    assert k >= held
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["moe_assignments_held"]) == \
        toks.size * held * moe_layers
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(1.0)
    want = jax.jit(lambda p: ref.loss(p, toks, SPEC))(skew)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)


# ---- the dispatch buffer: C rows, and every routing still exact ---------
# 2 of 64 experts held, k = 3, 1 024 tokens: C = 512 of T·k = 3 072 rows,
# and up to T·min(k, held) = 2 048 pairs can land here (four buffers)
SMALL_SHARE = dict(SPEC, n_routed_experts=64, experts_held=[4, 2])
N_TOK = 1024
C_ROWS = 512


def _routing_that_lands(m: int):
    """(x (N_TOK, hidden), an expert layer's parameters) under which
    exactly ``m`` (token, choice) pairs choose an expert held here: the
    router reads coordinate 0 for the first held expert and coordinate 1
    for the second, the selection bias puts both between a token's default
    three (held elsewhere) and the rest."""
    spec = SMALL_SHARE
    first, held = spec["experts_held"]
    k, n_exp = spec["num_experts_per_tok"], spec["n_routed_experts"]
    assert held == 2 and 0 <= m <= 2 * N_TOK
    rng = np.random.default_rng(m)
    p = LatentMoeLM(spec).init(jax.random.key(11))["layer1"]
    p = jax.tree.map(np.array, p)
    kernel = p["router"]["kernel"]
    kernel[:2, first:first + held] = np.eye(2)
    bias = np.full(n_exp, -10.0, np.float32)
    bias[first:first + held] = 0.3
    bias[first + held:first + held + k] = 0.5
    p["router"]["e_score_correction_bias"] = bias
    x = rng.standard_normal((N_TOK, spec["hidden_size"])).astype(np.float32)
    x[:, :2] = -8.0
    shuffled = rng.permutation(N_TOK)
    x[shuffled[:min(m, N_TOK)], 0] = 8.0
    x[shuffled[:max(m - N_TOK, 0)], 1] = 8.0
    return jnp.asarray(x), jax.tree.map(jnp.asarray, p)


def _experts_both_ways(spec):
    """(x, p, cotangent) -> (y, counters, the gradients for x and p) of
    ``_experts``, and (y, the gradients) of the dense reference: two
    compiled programs that serve every routing of the same shapes."""
    lm = LatentMoeLM(spec)

    def ours(x, p):
        y, stats = lm._experts(x, p)
        return y, latent_moe.fold_stats([stats])

    def reference(x, p):
        h = ref.rms(x, p["mlp_norm"]["scale"], spec["rms_norm_eps"])
        return x + ref.experts(h, p, spec, lambda t: t), {}

    def probed(fn):
        def run(x, p, cot):
            def loss(x, p):
                y, stats = fn(x, p)
                return jnp.sum(y * cot), (y, stats)

            grads, out = jax.grad(loss, argnums=(0, 1), has_aux=True)(x, p)
            return out, grads

        return jax.jit(run)

    return probed(ours), probed(reference)


@pytest.fixture(scope="module")
def small_share():
    return _experts_both_ways(SMALL_SHARE)


def _assert_experts_match_the_reference(both_ways, x, p, landed, further):
    """``_experts`` of (x, p): output and every leaf's gradient against the
    dense reference, and the counters."""
    ours, reference = both_ways
    cot = jax.random.normal(jax.random.key(landed), x.shape)
    (y, stats), got = ours(x, p, cot)
    assert float(stats["moe_assignments_held"]) == landed
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["moe_full_dispatch"]) == further
    (want_y, _), want = reference(x, p, cot)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale + 1e-9, name


def test_dispatch_buffer_is_sized_by_the_chips_share():
    assert LatentMoeLM(SMALL_SHARE).dispatch_rows(N_TOK) == C_ROWS
    # the published widths: 8 of 128 held, top-6, 4 096 tokens a lane
    assert LatentMoeLM(dict(SPEC, n_routed_experts=128, experts_held=[0, 8],
                            num_experts_per_tok=6)).dispatch_rows(4096) \
        == 6144
    # every expert held: the buffer is every pair, one path
    k, n_exp = SPEC["num_experts_per_tok"], SPEC["n_routed_experts"]
    assert LatentMoeLM(dict(SPEC, experts_held=[0, n_exp])).dispatch_rows(
        T) == T * k
    # a quarter held at the tiny size: 4 × the share is every pair too
    assert LatentMoeLM(SPEC).dispatch_rows(2 * T) == 2 * T * k


@pytest.mark.parametrize("landed,further", [
    (0, 0), (1, 0), (C_ROWS - 1, 0), (C_ROWS, 0), (C_ROWS + 1, 1),
    (2 * N_TOK, 3)])
def test_any_routing_is_computed_exactly(small_share, landed, further):
    """Output and every leaf's gradient against the dense reference, for
    routings that fill the buffer to its last row, pass it by one, and
    send every token to both held experts."""
    x, p = _routing_that_lands(landed)
    _assert_experts_match_the_reference(small_share, x, p, landed, further)


def test_a_last_buffer_that_reaches_past_the_pairs_is_exact():
    """8 of 64 held, 1 000 tokens: C = 1 536 of 3 000 pairs, so the second
    buffer reaches past the last pair — and is needed when every token
    takes three held experts."""
    spec = dict(SPEC, n_routed_experts=64, experts_held=[8, 8])
    tokens, k = 1000, spec["num_experts_per_tok"]
    assert LatentMoeLM(spec).dispatch_rows(tokens) == 1536
    p = LatentMoeLM(spec).init(jax.random.key(13))["layer1"]
    bias = np.zeros(spec["n_routed_experts"], np.float32)
    bias[[9, 12, 15]] = 50.0
    p["router"]["e_score_correction_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.key(14), (tokens, spec["hidden_size"]))
    _assert_experts_match_the_reference(_experts_both_ways(spec), x, p,
                                        tokens * k, 1)


def test_lanes_side_by_side_each_take_the_buffers_they_need():
    """Under ``vmap`` (the tests' lanes) the loop over further buffers runs
    as long as any lane needs it, and each lane still gets its own sum."""
    lm = LatentMoeLM(SMALL_SHARE)
    x0, p = _routing_that_lands(7)
    x1 = x0.at[:, 0].set(8.0)  # every token to the first held expert

    def loss(x, p):
        y, stats = lm._experts(x, p)
        return jnp.sum(y ** 2), stats["further"]

    alone = jax.jit(jax.value_and_grad(loss, argnums=1, has_aux=True))
    each = [alone(x, p) for x in (x0, x1)]
    (_, further), grads = jax.jit(jax.vmap(alone, in_axes=(0, None)))(
        jnp.stack([x0, x1]), p)
    assert further.tolist() == [0.0, 1.0]
    for lane, ((_, f), g) in enumerate(each):
        assert float(f) == float(further[lane])
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(grads)):
            np.testing.assert_allclose(a, b[lane], rtol=1e-5, atol=1e-6)


def test_flash_kernel_takes_q_k_and_v_of_different_head_sizes():
    from draco_tpu.ops.flash_attention import flash_attention

    key = jax.random.key(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 32, 2, d))
               for i, d in enumerate((24, 24, 16)))

    def kernel(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               interpret=True)

    def square(out):
        return jnp.sum(out ** 2)

    out, got = parity.with_gradients(kernel, square, (0, 1, 2))(q, k, v)
    want_out, want = parity.with_gradients(
        latent_moe.dense_causal_attention, square, (0, 1, 2))(q, k, v)
    assert out.shape == (1, 32, 2, 16)
    np.testing.assert_allclose(out, want_out, atol=2e-6)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


def _cfg(**kw):
    base = dict(network="LatentMoeLM", dataset="synthetic-text",
                model_spec=SPEC, vocab=SPEC["vocab_rows"], seq_len=T,
                batch_size=2, num_workers=3, approach="maj_vote",
                group_size=3, worker_fail=1, train_dir="")
    base.update(kw)
    return TrainConfig(**base)


def test_the_new_network_validates_with_the_vote():
    assert _cfg().validate().network == "LatentMoeLM"
    assert _cfg(network="TransformerLM", model_spec=None, vocab=64,
                approach="maj_vote").validate().approach == "maj_vote"


