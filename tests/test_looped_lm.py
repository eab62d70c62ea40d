"""models/looped.LoopedLM at a tiny size (hidden 64, 4 heads of 16, SwiGLU
of 96, 2 layers kept, a whole vocabulary of 64) against the plain reference
the benchmark compares it with on the chip (benchmark/reference/nets/
ouro.py, which imports nothing of draco_tpu and loops in Python):

* the objective, every exit's cross-entropy, the exit distribution and
  every leaf's gradient on seeded weights, the norms' weights and the gate
  moved off their initial values, at ``total_ut_steps`` 1, 2 and 4, each
  layer application rematerialised and not;
* the loop is over the SAME leaves: a shared leaf's gradient is the sum of
  the passes' taken apart;
* the exit distribution sums to one and the last pass takes the rest; the
  entropy term's gradient reaches the gate;
* the head and loss a block of rows at a time (spec_lm.blocked_nll) are the
  whole-array form, values and gradients, for one exit and four and on all
  four published-config models;
* the head that takes its gradients in the forward pass (spec_lm.
  weighted_nll, the training objective's surface): value and the gradients
  for the state, the weight and the rows' weights against plain autodiff of
  the whole-array form, for row counts that fill their blocks and do not,
  rows of weight zero, an upstream cotangent and a denominator other than
  one; four exits' rows through ``LoopedLM.weighted_nll`` against Σ w ·
  ``token_nll``, every leaf's gradient and the counters; rows that fit one
  block stay plain autodiff, bit for bit what the route computed before;
* a one-element leaf goes through the vote stack's row layout, wherever it
  lies, and the published leaf table keeps it last;
* a missing key of the mapping is named (what the block refuses by the
  key's name, and three plain SGD steps against the reference:
  tests/test_spec_lm_parity.py).

Tolerances: program and reference are float32 sums of the same terms in
another order (a scan against a Python loop, log-sigmoids against
products): 2e-6 relative on the objective, 2e-5 absolute on cross-entropies
of order one, 2e-4 of a leaf's largest gradient entry.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import ouro as ref
from draco_tpu.config import SPEC_NETWORKS, TrainConfig
from draco_tpu.models import build_lm, looped, spec_lm
from draco_tpu.models.looped import LoopedLM, exit_log_probs

SPEC = parity.tiny("looped-tiny")
T = 40


def _tokens(seed=0, batch=2, t=T, vocab=SPEC["vocab_rows"]):
    return parity.tokens(vocab, batch, t, seed)


def _moved(params, seed=1):
    """Every leaf off its initial value, so that a norm left out or applied
    twice, a gate without its bias, shows."""
    return parity.moved(params, jax.random.key(seed))


def _close(got, want, what):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-12,
            err_msg=f"{what}: {jax.tree_util.keystr(path)}")


@functools.lru_cache(maxsize=None)
def _reference_programs(steps):
    """(params, tokens) -> (objective, gradient), (params, row) -> the
    exits' (cross-entropies, probabilities) and -> the logits of the plain
    reference at ``steps`` passes: it knows no rematerialisation, so both
    cases of a pass count read the same three programs."""
    spec = dict(SPEC, total_ut_steps=steps)
    return (jax.jit(jax.value_and_grad(lambda p, toks: ref.loss(p, toks,
                                                                spec))),
            jax.jit(lambda p, row: ref.exits(p, row, spec)),
            jax.jit(lambda p, row: ref.logits(p, row, spec)))


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_objective_exits_and_gradients_are_the_references(steps, remat):
    spec = dict(SPEC, total_ut_steps=steps)
    lm = LoopedLM(spec, remat=remat)
    params = _moved(lm.init(jax.random.key(0)))
    toks = _tokens()
    ref_loss, ref_exits, ref_logits = _reference_programs(steps)
    (loss, stats), grad = jax.jit(jax.value_and_grad(
        lambda p: parity.mean_nll(lm, p, toks), has_aux=True))(params)
    want, want_grad = ref_loss(params, toks)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    _close(grad, want_grad, f"steps {steps}")
    ce, logp = jax.jit(lm.exit_terms)(params, toks,
                                      jnp.roll(toks, -1, axis=1))
    assert ce.shape == logp.shape == (steps,) + toks.shape
    for b in range(toks.shape[0]):
        ref_ce, ref_p = ref_exits(params, toks[b])
        np.testing.assert_allclose(ce[:, b, :-1], ref_ce, atol=2e-5)
        np.testing.assert_allclose(jnp.exp(logp[:, b, :-1]), ref_p,
                                   atol=2e-6)
    assert set(stats) == set(lm.stat_names)
    assert lm.stat_names == looped.STAT_NAMES
    assert float(stats["loop_passes"]) == steps
    # the counters are means over every position of the row
    p = jnp.exp(logp)
    np.testing.assert_allclose(stats["exit_ce_first"], jnp.mean(ce[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(stats["exit_ce_last"], jnp.mean(ce[-1]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        stats["exit_pass_mean"],
        jnp.mean(sum((t + 1) * p[t] for t in range(steps))), rtol=1e-6)
    np.testing.assert_allclose(stats["exit_entropy"],
                               jnp.mean(-jnp.sum(p * logp, axis=0)),
                               rtol=1e-5, atol=1e-7)
    # the last exit's logits are what ``logits`` hands back
    np.testing.assert_allclose(jax.jit(lm.logits)(params, toks)[0],
                               ref_logits(params, toks[0]), atol=2e-5)


def test_a_shared_leafs_gradient_is_the_sum_of_the_four_passes():
    lm = LoopedLM(SPEC)
    params = _moved(lm.init(jax.random.key(3)))
    toks = _tokens(3)
    steps, layers = SPEC["total_ut_steps"], SPEC["layers"]
    names = [f"layer{i}" for i in range(layers)]

    def apart(copies):
        """The same model, pass t reading its own copy of the layers."""
        twin = LoopedLM(SPEC)

        def passes(p, tokens, pos_offset=0):
            positions = pos_offset + jnp.arange(tokens.shape[1])
            x, out = p["embed"]["embedding"][tokens], []
            for copy in copies:
                for name in names:
                    x = twin._layer(x, copy[name], positions)
                x = twin.norm(x, p["final_norm"])
                out.append(x)
            return jnp.stack(out)

        twin.passes = passes
        return parity.mean_nll(twin, params, toks)[0]

    shared = jax.jit(jax.grad(
        lambda p: parity.mean_nll(lm, p, toks)[0]))(params)
    each = jax.jit(jax.grad(apart))([{n: params[n] for n in names}] * steps)
    assert len(each) == steps
    summed = jax.tree.map(lambda *g: sum(g), *each)
    _close({n: shared[n] for n in names}, summed, "sum of the passes")
    # and no pass is idle: each one's share of a leaf is not zero
    for g in each:
        assert float(jnp.max(jnp.abs(g["layer0"]["q"]["kernel"]))) > 0


@pytest.mark.parametrize("steps", [1, 2, 4, 7])
def test_the_exits_share_one_and_the_last_pass_takes_the_rest(steps):
    z = 3.0 * jax.random.normal(jax.random.key(steps), (steps, 5, 11))
    logp = exit_log_probs(z)
    p, lam = jnp.exp(logp), jax.nn.sigmoid(z)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    left = jnp.ones(z.shape[1:])
    for t in range(steps - 1):
        np.testing.assert_allclose(p[t], lam[t] * left, atol=1e-6)
        left = left * (1.0 - lam[t])
    np.testing.assert_allclose(p[-1], left, atol=1e-6)
    # the last gate is not read: the last pass takes what is left
    moved = exit_log_probs(z.at[-1].add(5.0))
    assert np.array_equal(np.asarray(moved), np.asarray(logp))


def test_the_entropy_terms_gradient_reaches_the_gate(monkeypatch):
    """With a head of zeros every exit's cross-entropy is log V whatever
    the gate says, so Σ pᵗ·CEᵗ is a constant of the gate: what reaches
    ``loop_exit`` is the entropy term alone, −β·∇H."""
    lm = LoopedLM(SPEC)
    params = _moved(lm.init(jax.random.key(5)))
    params["head"]["kernel"] = jnp.zeros_like(params["head"]["kernel"])
    toks = _tokens(5)
    targets = jnp.roll(toks, -1, axis=1)
    grad = jax.jit(jax.grad(lambda p: jnp.mean(
        lm.token_nll(p, toks, targets)[0])))(params)["loop_exit"]
    entropy = jax.jit(jax.grad(lambda p: lm.token_nll(p, toks, targets)[1][
        "exit_entropy"]))(params)["loop_exit"]
    assert float(jnp.max(jnp.abs(grad["kernel"]))) > 1e-6
    _close(grad, jax.tree.map(lambda g: -looped.ENTROPY_WEIGHT * g, entropy),
           "entropy term")
    monkeypatch.setattr(looped, "ENTROPY_WEIGHT", 0.0)
    none = jax.jit(jax.grad(lambda p: jnp.mean(
        lm.token_nll(p, toks, targets)[0])))(params)["loop_exit"]
    assert float(jnp.max(jnp.abs(none["kernel"]))) < 1e-7
    assert float(jnp.abs(none["bias"][0])) < 1e-7


# ---- the head and loss a block of rows at a time ----------------------

@pytest.mark.parametrize("lead", [(1, 37), (4, 2, 37), (2, 64)])
def test_the_blocked_head_is_the_whole_array_form(lead, monkeypatch):
    """One exit's rows ((B, T)) and four exits' ((R, B, T)), a row count
    that is and is not a multiple of the block: values and all three
    gradients."""
    hidden, vocab = 32, 50
    h = jax.random.normal(jax.random.key(0), lead + (hidden,))
    kernel = 0.3 * jax.random.normal(jax.random.key(1), (hidden, vocab))
    targets = jax.random.randint(jax.random.key(2), lead, 0, vocab)
    weight = jax.random.normal(jax.random.key(3), lead)

    def total():
        # (a function of its own a call: jax keeps a function's trace)
        return lambda h, kernel: jnp.sum(
            weight * spec_lm.blocked_nll(h, kernel, targets))

    def nll_and_grads():
        # (a jit of its own a call too: the trace reads the patched budget)
        return (jax.jit(lambda h, kernel: spec_lm.blocked_nll(
                    h, kernel, targets))(h, kernel),
                jax.jit(jax.grad(total(), argnums=(0, 1)))(h, kernel))

    whole, whole_grad = nll_and_grads()
    assert "scan" not in str(jax.make_jaxpr(total())(h, kernel))
    # 16 rows a block: 37 rows are three blocks, the last one padded
    monkeypatch.setattr(spec_lm, "HEAD_BLOCK_BYTES", 16 * 4 * vocab)
    assert spec_lm.head_block_rows(vocab) == 16
    assert "scan" in str(jax.make_jaxpr(total())(h, kernel))
    blocked, blocked_grad = nll_and_grads()
    assert blocked.shape == lead and blocked.dtype == jnp.float32
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)
    for got, want in zip(blocked_grad, whole_grad):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_blocks_rows_are_a_power_of_two_inside_the_budget():
    # the cells: an eighth of a vocabulary is one block of every row a
    # lane has (4 096, mellum2 8 192); four exits against the whole
    # vocabulary are eight blocks of 2 048
    assert spec_lm.head_block_rows(12288) == 8192
    assert spec_lm.head_block_rows(12800) == 8192
    assert spec_lm.head_block_rows(49152) == 2048
    for vocab in (2, 64, 12288, 49152, 151936, 2**31):
        rows = spec_lm.head_block_rows(vocab)
        assert rows >= 1 and rows & (rows - 1) == 0
        assert rows == 1 or 4 * vocab * rows <= spec_lm.HEAD_BLOCK_BYTES


@pytest.mark.parametrize("network,file,seq_len", [
    ("LatentMoeLM", "latent-moe-tiny.json", 40),
    ("HybridMoeLM", "hybrid-moe-tiny.json", 80),
    ("WindowedMoeLM", "windowed-moe-tiny.json", 40),
    ("LoopedLM", "looped-tiny.json", 40),
    ("ShortConvMoeLM", "conv-moe-tiny.json", 48)])
def test_every_spec_models_loss_is_the_same_a_block_at_a_time(
        network, file, seq_len, monkeypatch):
    spec = parity.tiny(file.removesuffix(".json"))
    lm = build_lm(TrainConfig(
        network=network, dataset="synthetic-text", model_spec=spec,
        vocab=spec["vocab_rows"], seq_len=seq_len, remat=True).validate())
    assert isinstance(lm, spec_lm.SpecLM) and network in SPEC_NETWORKS
    params = lm.init(jax.random.key(7))
    toks = _tokens(7, t=seq_len, vocab=spec["vocab_rows"])

    def run():
        return jax.jit(jax.value_and_grad(
            lambda p: parity.mean_nll(lm, p, toks)[0]))(params)

    whole, whole_grad = run()
    monkeypatch.setattr(spec_lm, "HEAD_BLOCK_BYTES",
                        32 * 4 * spec["vocab_rows"])
    blocked, blocked_grad = run()
    np.testing.assert_allclose(blocked, whole, rtol=1e-6)
    _close(blocked_grad, whole_grad, network)


# ---- the head takes its gradients in the forward pass -----------------

def _plain_weighted(h, kernel, targets, weights, denom):
    return jnp.sum(spec_lm._block_nll(h, kernel, targets) * weights) / denom


@pytest.mark.parametrize("lead,masked,cotangent,denom", [
    ((4, 1, 32), False, 1.0, 1.0),    # eight whole blocks
    ((4, 2, 37), False, 1.0, 1.0),    # the last block padded
    ((1, 37), True, 1.0, 1.0),        # rows of weight zero
    ((2, 64), False, -2.5, 1.0),      # an upstream cotangent
    ((4, 2, 37), True, 0.7, 73.0),    # all of it, and a denominator
])
def test_the_fused_head_is_plain_autodiff_of_the_whole_array_form(
        lead, masked, cotangent, denom, monkeypatch):
    hidden, vocab = 32, 50
    h = jax.random.normal(jax.random.key(0), lead + (hidden,))
    kernel = 0.3 * jax.random.normal(jax.random.key(1), (hidden, vocab))
    targets = jax.random.randint(jax.random.key(2), lead, 0, vocab)
    weights = jax.random.uniform(jax.random.key(3), lead, minval=0.2)
    if masked:
        weights = weights * (jax.random.uniform(jax.random.key(4), lead)
                             < 0.6)

    def fused():
        # (a function of its own a call: jax keeps a function's trace)
        return lambda h, kernel, weights: cotangent * spec_lm.weighted_nll(
            h, kernel, targets, weights, denom)[0]

    want, want_grad = jax.jit(jax.value_and_grad(
        lambda *a: cotangent * _plain_weighted(a[0], a[1], targets, a[2],
                                               denom),
        argnums=(0, 1, 2)))(h, kernel, weights)
    assert "_fused_nll" not in str(jax.make_jaxpr(fused())(h, kernel,
                                                           weights))
    monkeypatch.setattr(spec_lm, "HEAD_BLOCK_BYTES", 16 * 4 * vocab)
    rows = int(np.prod(lead))
    assert spec_lm.head_blocks_fused(rows, vocab) == -(-rows // 16)
    assert "_fused_nll" in str(jax.make_jaxpr(fused())(h, kernel, weights))
    got, got_grad = jax.jit(jax.value_and_grad(
        fused(), argnums=(0, 1, 2)))(h, kernel, weights)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w, what in zip(got_grad, want_grad, ("h", "kernel", "weights")):
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=what)
    # the rows' values come back too, and carry no gradient
    total, nll = jax.jit(lambda h: spec_lm.weighted_nll(
        h, kernel, targets, weights, denom))(h)
    np.testing.assert_allclose(
        nll, jax.jit(spec_lm._block_nll)(h, kernel, targets), rtol=1e-6,
        atol=1e-6)
    assert nll.shape == lead and nll.dtype == jnp.float32
    assert not np.any(jax.jit(jax.grad(lambda h: jnp.sum(
        spec_lm.weighted_nll(h, kernel, targets, weights, denom)[1])))(h))
    # what a forward-only call runs is the blocked form as it stands
    np.testing.assert_allclose(
        total, jax.jit(_plain_weighted)(h, kernel, targets, weights, denom),
        rtol=1e-5)


@pytest.mark.parametrize("fused", [False, True])
def test_the_exits_objective_through_the_weighted_surface(fused,
                                                          monkeypatch):
    """Σ w·ℓ / denom from ``LoopedLM.weighted_nll`` — the exits' rows at
    weights w·pᵗ through the head, the gate's gradient arriving as the
    weights' cotangent — against the same sum over ``token_nll``: value,
    every leaf's gradient (the gate's among them), the counters."""
    lm = LoopedLM(SPEC, remat=True)
    params = _moved(lm.init(jax.random.key(3)))
    toks = _tokens(3)
    targets = jnp.roll(toks, -1, axis=1)
    w = (jnp.arange(T) < T - 1).astype(jnp.float32)[None, :]
    denom = toks.shape[0] * (T - 1)

    def per_position(p):
        nll, stats = lm.token_nll(p, toks, targets)
        return jnp.sum(nll * w) / denom, stats

    def weighted(p):
        return lm.weighted_nll(p, toks, targets, weights=w, denom=denom)

    (want, want_stats), want_grad = jax.jit(jax.value_and_grad(
        per_position, has_aux=True))(params)
    blocks = 0
    if fused:
        # 32 rows a block: four exits of 2 x 40 rows are ten blocks
        monkeypatch.setattr(spec_lm, "HEAD_BLOCK_BYTES",
                            32 * 4 * SPEC["vocab_rows"])
        blocks = 10
    assert ("_fused_nll" in str(jax.make_jaxpr(weighted)(params))) == fused
    (got, stats), grad = jax.jit(jax.value_and_grad(
        weighted, has_aux=True))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    _close(grad, want_grad, "weighted_nll")
    assert float(jnp.max(jnp.abs(grad["loop_exit"]["kernel"]))) > 0
    assert set(stats) == set(lm.stat_names) == set(looped.STAT_NAMES)
    assert float(stats["head_blocks_fused"]) == blocks
    assert float(want_stats["head_blocks_fused"]) == 0
    for name in looped.STAT_NAMES[:5]:
        np.testing.assert_allclose(stats[name], want_stats[name], rtol=1e-5,
                                   err_msg=name)


def test_rows_that_fit_one_block_stay_plain_autodiff():
    """The three sparse cells' bypass: their rows are one block, so the
    weighted surface is the whole-array form under plain autodiff — the
    head's ``custom_vjp`` not in the jaxpr, the gradient bit for bit that of
    Σ w · ``token_nll`` / denom, which the route computed before."""
    spec = parity.tiny("latent-moe-tiny")
    lm = build_lm(TrainConfig(
        network="LatentMoeLM", dataset="synthetic-text", model_spec=spec,
        vocab=spec["vocab_rows"], seq_len=T, remat=True).validate())
    params = lm.init(jax.random.key(11))
    toks = _tokens(11, vocab=spec["vocab_rows"])
    targets = jnp.roll(toks, -1, axis=1)
    w = (jnp.arange(T) < T - 1).astype(jnp.float32)
    denom = toks.shape[0] * (T - 1)

    def before(p):
        nll, stats = lm.token_nll(p, toks, targets)
        return jnp.sum(nll * w[None, :]) / denom, stats

    def now(p):
        return lm.weighted_nll(p, toks, targets, weights=w[None, :],
                               denom=denom)

    assert "_fused_nll" not in str(jax.make_jaxpr(now)(params))
    (want, _), want_grad = jax.jit(jax.value_and_grad(
        before, has_aux=True))(params)
    (got, stats), grad = jax.jit(jax.value_and_grad(
        now, has_aux=True))(params)
    assert set(stats) == set(lm.stat_names)
    assert np.array_equal(got, want)
    for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(want_grad)):
        assert np.array_equal(a, b)


# ---- the vote stack's rows --------------------------------------------

@pytest.mark.parametrize("where", ["last", "middle", "first"])
def test_a_one_element_leaf_goes_through_the_row_layout(where):
    """``sp_step.row_layout`` / ``_write_row`` with a one-element leaf among
    whole-line ones: last (where LoopedLM keeps its gate's bias: one small
    joined piece with the closing zeros), and in the middle or first (every
    later leaf then reaches a line's end only with the zeros: one long
    joined piece — slower on the chip, the same bytes)."""
    from draco_tpu.parallel.sp_step import (
        STACK_LANES, _write_row, row_layout,
    )
    from draco_tpu.training.step import _flatten_tree, _make_unravel

    shapes = [(4, 128), (256,), (2, 3, 128), (128,)]
    shapes.insert({"last": 4, "middle": 2, "first": 0}[where], (1,))
    tree = {f"leaf{i}": jax.random.normal(jax.random.key(i), shape)
            for i, shape in enumerate(shapes)}
    unravel, dim, offsets = _make_unravel(tree)
    layout = row_layout(np.diff(offsets))
    assert dim == 1665 and layout.zeros == 383
    assert layout.lines * STACK_LANES == dim + layout.zeros
    if where == "last":
        assert (layout.joined_leaves, layout.joined_size) == (1, 1)
        assert len(layout.pieces) == 5
    else:
        assert layout.joined_leaves == {"middle": 3, "first": 5}[where]
    got = jax.jit(lambda s, t: _write_row(s, 1, t, layout))(
        jnp.full((2, layout.lines, STACK_LANES), jnp.nan), tree)
    want = jnp.pad(_flatten_tree(tree), (0, layout.zeros)).reshape(
        -1, STACK_LANES)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want))
    assert np.isnan(np.asarray(got[0])).all()
    for a, b in zip(jax.tree.leaves(unravel(got[1])), jax.tree.leaves(tree)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def test_the_published_leaf_table_keeps_the_gate_last():
    """Every leaf of the configuration the benchmark runs lies on the
    stack's 128-wide lines but the gate's (2 048 + 1 elements), which sort
    last in ravel order and close the row with its zeros; d is the
    configuration's count."""
    from draco_tpu.parallel.sp_step import row_layout
    from draco_tpu.training.step import _make_unravel

    with open(os.path.join(parity.ROOT, "benchmark", "configs",
                           "ouro-2.6b-l4.json")) as fh:
        config = json.load(fh)
    spec = config["train_config"]["model_spec"]
    tree = jax.eval_shape(LoopedLM(spec).init, jax.random.key(0))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    assert paths[-2:] == ["['loop_exit']['bias']", "['loop_exit']['kernel']"]
    _, dim, offsets = _make_unravel(tree)
    assert dim == 406_884_353 and "406 884 353" in config["size"]
    layout = row_layout(np.diff(offsets))
    assert (layout.joined_leaves, layout.joined_size, layout.zeros) == (
        2, 2049, 1023)
    assert all(off % 128 == 0 for off in offsets[:-2])
    # the init the configuration states: the gate's bias zeros (all four
    # exits start near 1/2, 1/4, 1/8, 1/8), norms ones
    assert LoopedLM.init_rules == {"scale": "ones", "embedding": 1.0,
                                   "bias": "zeros"}
    assert config["weights"]["bias"] == "zeros"


# ---- what the block refuses -------------------------------------------

@pytest.mark.parametrize("key", looped.SPEC_KEYS)
def test_a_missing_key_is_named(key):
    spec = {k: v for k, v in SPEC.items() if k != key}
    with pytest.raises(ValueError, match=key):
        looped.check_spec(spec)
    with pytest.raises(ValueError, match="model_spec"):
        TrainConfig(network="LoopedLM", dataset="synthetic-text",
                    model_spec=spec, vocab=SPEC["vocab_rows"]).validate()


def test_the_config_validates_the_mapping_and_builds_the_block():
    looped.check_spec(SPEC)
    with pytest.raises(ValueError, match="mapping"):
        looped.check_spec(None)
    cfg = TrainConfig(network="LoopedLM", dataset="synthetic-text",
                      model_spec=SPEC, vocab=SPEC["vocab_rows"],
                      seq_len=T).validate()
    assert isinstance(build_lm(cfg), LoopedLM)
    with pytest.raises(ValueError, match="vocab_rows"):
        TrainConfig(network="LoopedLM", dataset="synthetic-text",
                    model_spec=SPEC, vocab=32).validate()
    with pytest.raises(ValueError, match="seq_shards"):
        TrainConfig(network="LoopedLM", dataset="synthetic-text",
                    model_spec=SPEC, vocab=SPEC["vocab_rows"],
                    seq_shards=2, num_workers=8).validate()
