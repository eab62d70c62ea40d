"""CIFAR VGG-11 (Simonyan & Zisserman configuration A, no BatchNorm): eight
3x3 convs with bias at 64,128,256,256,512,512,512,512 and 2x2 max-pools
after convs 1, 2, 4, 6, 8; classifier dropout-512-relu-dropout-512-relu-10.

Dropout: the program's flax module draws its two masks from the step's
dropout key through flax's own per-module rng path. The masks here come from
flax's public ``nn.Dropout`` under the same two module names, so the same
key gives the same masks; the arithmetic on them is plain."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmark.reference.nets.common import (
    conv, cross_entropy, dense, max_pool2, operands)

LAYOUT = (64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M")


class _Masks(nn.Module):
    """Dropout_0 and Dropout_1 at the top level of a module, as in the
    program's VGG: applied to ones they return mask / keep_prob."""

    @nn.compact
    def __call__(self, ones):
        return (nn.Dropout(0.5, deterministic=False)(ones),
                nn.Dropout(0.5, deterministic=False)(ones))


def loss(params, x, y, dropout_key, dtype):
    cast, q = operands(dtype)
    x = cast(x)
    i = 0
    for v in LAYOUT:
        if v == "M":
            x = max_pool2(x)
        else:
            p = params[f"Conv_{i}"]
            x = jax.nn.relu(conv(x, p["kernel"], bias=p["bias"], q=q))
            i += 1
    x = x.reshape(x.shape[0], -1)
    m0, m1 = _Masks().apply({}, jnp.ones((x.shape[0], 512), jnp.float32),
                            rngs={"dropout": dropout_key})
    x = jax.nn.relu(dense(x * m0.astype(x.dtype), params["Dense_0"], q))
    x = jax.nn.relu(dense(x * m1.astype(x.dtype), params["Dense_1"], q))
    return cross_entropy(dense(x.astype("float32"), params["Dense_2"], q), y)
