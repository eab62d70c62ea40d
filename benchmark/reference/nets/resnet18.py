"""CIFAR ResNet-18 (He et al. 2016, the 3x3-stem variant Draco trains):
stem conv-BN-relu, four stages of two BasicBlocks at 64/128/256/512, stride
2 at the first block of stages 2-4 with a 1x1 projection shortcut, 4x4
average pool, linear classifier. No dropout."""

from __future__ import annotations

import jax

from benchmark.reference.nets.common import (
    avg_pool4, batch_norm, conv, cross_entropy, dense, operands)

STAGES = ((64, 1), (64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
          (512, 2), (512, 1))


def _block(p, x, stride, q):
    out = jax.nn.relu(batch_norm(conv(x, p["Conv_0"]["kernel"], stride, q=q),
                                 p["BatchNorm_0"]))
    out = batch_norm(conv(out, p["Conv_1"]["kernel"], q=q), p["BatchNorm_1"])
    if "Conv_2" in p:
        x = batch_norm(conv(x, p["Conv_2"]["kernel"], stride, pad=0, q=q),
                       p["BatchNorm_2"])
    return jax.nn.relu(out + x)


def loss(params, x, y, dropout_key, dtype):
    del dropout_key
    cast, q = operands(dtype)
    x = cast(x)
    x = jax.nn.relu(batch_norm(conv(x, params["Conv_0"]["kernel"], q=q),
                               params["BatchNorm_0"]))
    for i, (_, stride) in enumerate(STAGES):
        x = _block(params[f"BasicBlock_{i}"], x, stride, q)
    x = avg_pool4(x).reshape(x.shape[0], -1)
    return cross_entropy(dense(x.astype("float32"), params["Dense_0"], q), y)
