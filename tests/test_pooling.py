"""``models.pooling.max_pool_2x2`` against ``nn.max_pool``: same maxima, same
gradient routing (the first maximum of a window in row-major order takes the
whole cotangent), bit for bit on the CPU — on random input, on input with
ties inside windows, and under the step builder's two ``vmap``s."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from draco_tpu.models import build_model
from draco_tpu.models.pooling import max_pool_2x2

pytestmark = pytest.mark.core

# pools 1 and 3 of VGG on 32×32 and LeNet's two pools on 28×28 take the
# fused form; VGG's last two (sides under 8) and odd sides fall back
FUSED_SHAPES = [(3, 32, 32, 64), (3, 8, 8, 256), (3, 24, 24, 20),
                (3, 8, 8, 50)]
FALLBACK_SHAPES = [(3, 4, 4, 512), (3, 2, 2, 512), (2, 5, 7, 3), (2, 10, 9, 3),
                   (2, 9, 10, 3)]


def _reference(x):
    return nn.max_pool(x, (2, 2), strides=(2, 2))


def _input(kind, shape, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "random":
        x = rng.standard_normal(shape)
    elif kind == "relu_zeros":  # what VGG's pools see: runs of exact zeros
        x = np.maximum(rng.standard_normal(shape), 0.0)
    else:  # repeated positive values: most windows hold a tie at the max
        x = rng.integers(1, 4, shape)
    return jnp.asarray(x, jnp.float32)


def _weighted(pool, w):
    return lambda x: jnp.sum(pool(x) * w)


def _cotangent(shape, seed=1):
    out = (*shape[:-3], shape[-3] // 2, shape[-2] // 2, shape[-1])
    return jnp.asarray(np.random.default_rng(seed).standard_normal(out),
                       jnp.float32)


@pytest.mark.parametrize("kind", ["random", "relu_zeros", "ties"])
@pytest.mark.parametrize("shape", FUSED_SHAPES + FALLBACK_SHAPES, ids=str)
def test_forward_and_gradient_equal_nn_max_pool(shape, kind):
    x, w = _input(kind, shape), _cotangent(shape)
    assert np.array_equal(max_pool_2x2(x), _reference(x))
    got = jax.jit(jax.grad(_weighted(max_pool_2x2, w)))(x)
    want = jax.grad(_weighted(_reference, w))(x)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("window,want", [
    ([[2., 2.], [2., 1.]], [[3., 0.], [0., 0.]]),
    ([[0., 1.], [1., 1.]], [[0., 3.], [0., 0.]]),
    ([[0., 0.], [5., 5.]], [[0., 0.], [3., 0.]]),
    ([[0., 0.], [0., 0.]], [[3., 0.], [0., 0.]]),
])
def test_first_in_window_takes_the_whole_cotangent(window, want):
    """Every window of an 8×8 plane holds the same tie."""
    x = jnp.tile(jnp.asarray(window), (4, 4)).reshape(1, 8, 8, 1)
    g = jax.grad(lambda x: 3.0 * jnp.sum(max_pool_2x2(x)))(x)
    assert np.array_equal(g.reshape(8, 8), np.tile(np.asarray(want), (4, 4)))


@pytest.mark.parametrize("kind", ["random", "ties"])
@pytest.mark.parametrize("shape", FUSED_SHAPES, ids=str)
def test_under_two_vmaps_with_shared_parameters(shape, kind):
    """As the step builder applies it: vmap(vmap(grad)) over (n, r) lanes of
    a loss whose parameter (here the cotangent weights) is shared."""
    n, r = 3, 2
    xs = jnp.stack([jnp.stack([_input(kind, shape, seed=10 * i + j)
                               for j in range(r)]) for i in range(n)])
    w = _cotangent(shape)

    def lanes(pool):
        def loss(w, x):
            return jnp.sum(pool(x) * w)

        both = jax.grad(loss, argnums=(0, 1))
        return jax.jit(jax.vmap(jax.vmap(both, in_axes=(None, 0)),
                                in_axes=(None, 0)))(w, xs)

    for got, want in zip(lanes(max_pool_2x2), lanes(_reference)):
        assert np.array_equal(got, want)


def _window_ops(fn, *args):
    """(reduce_window, select_and_scatter) operations in ``fn`` and in its
    gradient, as handed to the compiler."""
    forward = jax.jit(fn).lower(*args).as_text()
    backward = jax.jit(jax.grad(fn)).lower(*args).as_text()
    return (forward.count("reduce_window"),
            backward.count("select_and_scatter"))


@pytest.mark.parametrize("shape", FUSED_SHAPES + FALLBACK_SHAPES, ids=str)
def test_shape_alone_decides_the_form(shape):
    """Even sides of 8 and more lower to no window operation at all; the
    rest is nn.max_pool's pair."""
    x, w = _input("random", shape), _cotangent(shape)
    got = _window_ops(_weighted(max_pool_2x2, w), x)
    assert got == ((0, 0) if shape in FUSED_SHAPES else (1, 1))


@pytest.mark.parametrize("name,shape,pools_left", [
    ("VGG11", (2, 32, 32, 3), 2), ("VGG16_bn", (2, 32, 32, 3), 2),
    ("LeNet", (2, 28, 28, 1), 0)])
def test_models_take_the_fused_pool(name, shape, pools_left):
    """What the models hand to the compiler: VGG on 32×32 keeps only its
    last two pools (4×4 and 2×2 planes) as window operations, LeNet none."""
    model = build_model(name)
    x = jnp.ones(shape, jnp.float32)
    variables = model.init({"params": jax.random.key(0),
                            "dropout": jax.random.key(1)}, x, train=False)

    def loss(p):
        return jnp.sum(model.apply({**variables, "params": p}, x,
                                   train=False))

    assert _window_ops(loss, variables["params"]) == (pools_left,
                                                      pools_left)
