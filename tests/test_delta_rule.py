"""ops/delta_rule.chunked_gated_delta_rule against the recurrence itself,
token by token (benchmark/reference/nets/qwen3_next.delta_rule, which
imports nothing of draco_tpu): outputs, the state a row leaves behind, and
the gradient of every input — at T a multiple of the chunk and not, at one
chunk and many, at key heads serving one value head and two, and at log
decays so negative that exp(−Σg) over a chunk overflows float32: the
chunked form takes exponentials of differences G_c − G_e <= 0 only, so it
must stay finite and right there.

Tolerance: both sides are float32 sums of the same terms in another order
(a chunk's writes solved at once against one token at a time): 2e-5 of the
largest entry forward, 1e-4 for a gradient (at strongly negative g the
gradient of g is what is left of terms that cancel: its largest entry is
1e-3 of the other gradients' and carries their rounding)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from benchmark.reference.nets import qwen3_next as ref
from draco_tpu.ops.delta_rule import (
    SOLVE_NAME, _solve_by_squaring, _unit_lower_inverse,
    chunked_gated_delta_rule, rule_runs_in_kernels,
)

pytestmark = pytest.mark.core
DK, DV = 16, 8


def _inputs(t, hk, hv, g_scale, seed=0, dk=DK, dv=DV):
    keys = jax.random.split(jax.random.key(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (t, hk, dk)))
    v = jax.random.normal(keys[2], (t, hv, dv))
    g = -g_scale * jax.nn.softplus(jax.random.normal(keys[3], (t, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (t, hv)))
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    r = v.shape[1] // q.shape[1]
    return ref.delta_rule(jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1),
                          v, g, beta, lambda x: x)


def _chunked(q, k, v, g, beta, chunk):
    o, state = chunked_gated_delta_rule(q[None], k[None], v[None], g[None],
                                        beta[None], chunk)
    return o[0], state[0]


ALL_FIVE = (0, 1, 2, 3, 4)


def _probed(out):
    """Σ output · a seeded probe, over every output."""
    return sum(jnp.sum(o * jax.random.normal(jax.random.key(9 + i), o.shape))
               for i, o in enumerate(jax.tree.leaves(out)))


# (outputs, the five inputs' gradients) of the recurrence token by token and
# of the chunked form: one compiled program a side and a shape, whatever the
# decay's scale
_token_by_token_with_gradients = parity.with_gradients(
    _token_by_token, _probed, ALL_FIVE)


@functools.lru_cache(maxsize=None)
def _chunked_with_gradients(chunk):
    return parity.with_gradients(
        lambda *a: _chunked(*a, chunk)[0], _probed, ALL_FIVE)


def _close(got, want, what, rel=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    assert np.all(np.isfinite(np.asarray(got))), what
    assert float(jnp.max(jnp.abs(got - want))) <= rel * scale + 1e-9, what


@pytest.mark.parametrize("t,chunk,hk,hv,g_scale", [
    (64, 16, 2, 2, 0.1),   # whole chunks, one value head a key head
    (50, 16, 2, 4, 0.1),   # a last chunk of 2 tokens, two value heads a key
    (7, 16, 1, 2, 1.0),    # under one chunk
    (130, 64, 2, 4, 0.05),  # the family's chunk, slow decay: a long memory
    (50, 16, 2, 4, 40.0),  # strongly negative g: exp(640) would overflow
])
def test_chunked_rule_is_the_recurrence(t, chunk, hk, hv, g_scale):
    args = _inputs(t, hk, hv, g_scale)
    want, g_want = _token_by_token_with_gradients(*args)
    got, g_got = _chunked_with_gradients(chunk)(*args)
    _close(got, want, "outputs")
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        _close(a, b, f"gradient of {name}", rel=1e-4)


def test_the_state_handed_back_is_the_rows_last():
    """The state after the last REAL token: the closing tokens of a padded
    chunk neither decay it nor write to it."""
    q, k, v, g, beta = _inputs(21, 1, 1, 0.3, seed=3)
    _, state = jax.jit(functools.partial(_chunked, chunk=8))(q, k, v, g, beta)

    @jax.jit
    def written_out(k, v, g, beta):
        s = jnp.zeros((DK, DV))
        for t in range(21):
            s = jnp.exp(g[t, 0]) * s
            s = s + jnp.outer(k[t, 0],
                              beta[t, 0] * (v[t, 0] - s.T @ k[t, 0]))
        return s

    _close(state[0], written_out(k, v, g, beta), "state")


def test_the_solves_stated_cotangent_is_autodiffs():
    """dL = −Tᵀ dT Tᵀ against the transpose of the squarings themselves."""
    low = jnp.tril(0.2 * jax.random.normal(jax.random.key(1), (3, 16, 16)),
                   -1)
    probe = jax.random.normal(jax.random.key(2), low.shape)
    got = jax.jit(jax.grad(
        lambda x: jnp.sum(_unit_lower_inverse(x) * probe)))(low)
    want = jax.jit(jax.grad(lambda x: jnp.sum(
        _solve_by_squaring(x) * probe)))(low)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_checkpoint_that_keeps_the_solve_does_not_solve_again():
    """Under ``jax.checkpoint`` the gradient's program holds the solve's
    ten products at ``highest`` twice (forward, rematerialised forward)
    plus the cotangent's two; with ``SOLVE_NAME`` saved, once plus two — as
    without any checkpoint."""
    args = [x[None] for x in _inputs(128, 2, 4, 0.3)]

    def products(fn):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))
        return str(jax.make_jaxpr(grad)(*args)).count("HIGHEST") // 2

    def rule(*a):
        return chunked_gated_delta_rule(*a, 64)[0]

    keep = jax.checkpoint_policies.save_only_these_names(SOLVE_NAME)
    assert products(rule) == 12
    assert products(jax.checkpoint(rule)) == 22
    assert products(jax.checkpoint(rule, policy=keep)) == 12


@pytest.mark.parametrize("c", [1, 2, 5, 16, 64])
def test_unit_lower_inverse_is_the_inverse(c):
    # entries as the rule has them: β·(k_c·k_e)·decay of unit keys, under
    # one in size (a matrix of unit normals here has an inverse of 2^c)
    low = jnp.tril(0.2 * jax.random.normal(jax.random.key(c), (3, c, c)), -1)
    got = _unit_lower_inverse(low)
    want = jnp.linalg.inv(jnp.eye(c) + low)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


# ---- the kernels (interpret mode) against the jax.numpy path ---------------

def _lane_inputs(t, hk, hv, g_scale, d=128):
    """``_inputs`` at head sizes of ``d`` (whole lane tiles at 128), a
    batch of one."""
    return [x[None] for x in _inputs(t, hk, hv, g_scale, dk=d, dv=d)]


def _both_paths(args, **kernel_kw):
    """(o, state, five gradients) of the ``jax.numpy`` path and of the call
    with ``kernel_kw``, each ONE compiled program (the forward pass run
    once); both outputs take a cotangent."""
    def run(**kw):
        (o, state), grads = parity.with_gradients(
            functools.partial(chunked_gated_delta_rule, **kw), _probed,
            ALL_FIVE)(*args)
        return (o, state) + grads

    return run(), run(**kernel_kw)


@pytest.mark.parametrize("t,hk,hv,g_scale", [
    (64, 1, 1, 0.3),    # one chunk, one value head a key head
    (128, 1, 2, 0.1),   # two chunks in one grid step, Hv = 2 Hk
    (192, 2, 2, 1.0),   # three chunks, one a grid step: the state crosses
    (256, 2, 4, 0.05),  # two grid steps of two chunks, slow decay
    (128, 2, 4, 40.0),  # strongly negative g: exp(+2 560) would overflow
    (64, 8, 8, 0.5),    # a whole sublane tile of key heads a grid step
    (64, 16, 16, 0.5),  # two grid steps of eight key heads
])
def test_kernels_are_the_jnp_path(t, hk, hv, g_scale):
    args = _lane_inputs(t, hk, hv, g_scale)
    assert rule_runs_in_kernels(args[0].shape, args[2].shape, interpret=True)
    want, got = _both_paths(args, interpret=True)
    names = "o state dq dk dv dg dbeta".split()
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, name, rel=2e-5)


@pytest.mark.parametrize("t,d,chunk", [
    (100, 128, 64),  # T not whole chunks
    (128, 64, 64),   # head size under a lane tile
    (128, 128, 32),  # not the family's chunk
])
def test_shapes_the_kernels_do_not_take_are_the_jnp_path(t, d, chunk,
                                                         monkeypatch):
    """The kernel is not chosen — a call to it would raise here — and the
    result is today's, bit for bit, whatever ``interpret`` says."""
    from draco_tpu.ops import delta_rule

    args = _lane_inputs(t, 1, 2, 0.2, d=d)
    assert not rule_runs_in_kernels(args[0].shape, args[2].shape, chunk,
                                    force=True)

    def refuse(*a, **kw):
        raise AssertionError("the kernels were chosen")

    monkeypatch.setattr(delta_rule, "_rule", refuse)
    want = chunked_gated_delta_rule(*args, chunk)
    got = chunked_gated_delta_rule(*args, chunk, interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_off_the_chip_the_kernels_are_not_chosen():
    args = _lane_inputs(128, 1, 2, 0.2)
    assert not rule_runs_in_kernels(args[0].shape, args[2].shape)
    assert not rule_runs_in_kernels(args[0].shape, args[2].shape,
                                    interpret=True, force=False)


def test_a_checkpoint_that_keeps_the_solve_runs_the_pass_again_only():
    """On the kernel path the solve is its own kernel and T carries
    ``SOLVE_NAME``: the gradient's program is three kernels (solve, pass,
    backward); under a checkpoint five (solve and pass again); with the
    name saved four — the rematerialised forward is the pass alone."""
    args = _lane_inputs(128, 1, 2, 0.2)

    def rule(*a):
        return chunked_gated_delta_rule(*a, interpret=True)[0]

    def kernels(fn):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))
        return str(jax.make_jaxpr(grad)(*args)).count("pallas_call[")

    keep = jax.checkpoint_policies.save_only_these_names(SOLVE_NAME)
    assert kernels(rule) == 3
    assert kernels(jax.checkpoint(rule)) == 5
    assert kernels(jax.checkpoint(rule, policy=keep)) == 4
    plain = jax.grad(lambda *a: jnp.sum(jnp.sin(rule(*a))),
                     argnums=(0, 1, 2, 3, 4))(*args)
    kept = jax.grad(jax.checkpoint(
        lambda *a: jnp.sum(jnp.sin(rule(*a))), policy=keep),
        argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(plain, kept):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
