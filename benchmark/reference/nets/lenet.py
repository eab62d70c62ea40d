"""LeNet for MNIST shapes, relu after the pool as the Draco reference has
it. The small net of the CPU tests; no cell of BENCHMARK.json uses it."""

from __future__ import annotations

import jax

from benchmark.reference.nets.common import (
    conv, cross_entropy, dense, max_pool2, operands)


def loss(params, x, y, dropout_key, dtype):
    del dropout_key
    cast, q = operands(dtype)
    x = cast(x)
    for name in ("Conv_0", "Conv_1"):
        p = params[name]
        x = jax.nn.relu(max_pool2(conv(x, p["kernel"], pad=0, bias=p["bias"],
                                   q=q)))
    x = x.reshape(x.shape[0], -1)
    x = dense(x, params["Dense_0"], q)
    return cross_entropy(dense(x.astype("float32"), params["Dense_1"], q), y)
