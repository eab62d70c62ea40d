"""Layer equations shared by the reference nets. NHWC, HWIO, plain jnp/lax."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5  # flax.linen.BatchNorm default


def _same(t):
    return t


def operands(dtype):
    """(cast, q) for a compute type: ``cast`` puts a tensor in the type the
    activations are held in, ``q`` rounds the operands of a product. For
    float32 and bfloat16 the tensors are simply held in that type. An 8-bit
    float (the control below bfloat16) keeps float32 tensors and rounds each
    product's operands through the 8-bit type, gradients passing straight
    through the rounding."""
    dt = jnp.dtype(dtype)
    if dt.itemsize >= 2:
        return (lambda t: t.astype(dt)), _same

    def q(t):
        return t + lax.stop_gradient(t.astype(dt).astype(t.dtype) - t)

    return (lambda t: t.astype(jnp.float32)), q


def conv(x, kernel, stride=1, pad=1, bias=None, q=_same):
    y = lax.conv_general_dilated(
        q(x), q(kernel.astype(x.dtype)), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y if bias is None else y + bias.astype(x.dtype)


def batch_norm(x, p):
    """Training-mode normalisation over the batch row's own statistics
    (each logical worker normalises over its own B examples)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x32 - mean), axis=(0, 1, 2))
    y = (x32 - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def dense(x, p, q=_same):
    return q(x) @ q(p["kernel"].astype(x.dtype)) + p["bias"].astype(x.dtype)


def max_pool2(x):
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def avg_pool4(x):
    b, h, w, c = x.shape
    return jnp.mean(x.reshape(b, h // 4, 4, w // 4, 4, c), axis=(2, 4))


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
