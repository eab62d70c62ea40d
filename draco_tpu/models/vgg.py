"""CIFAR VGG-11/13/16/19 with optional BatchNorm (reference: src/model_ops/vgg.py).

Feature configs A/B/D/E with 2×2 max-pools; classifier
dropout → 512 → relu → dropout → 512 → relu → 10.

Dropout determinism (TPU-native design decision): the reference seeds torch's
global RNG per group/epoch, which makes dropout *group*-deterministic for the
repetition code but leaves the cyclic path's per-batch gradients
worker-dependent (two workers computing the same batch draw different dropout
masks — decode there was only approximate). Here the dropout rng key is folded
from (step, batch-id) by the trainer, so any worker computing batch k draws
the same mask and both codes stay exactly decodable.

The pool is ``pooling.max_pool_2x2``, not ``nn.max_pool``: under the step
builder's two ``vmap``s ``nn.max_pool`` is a 6-D ``reduce-window`` and its
backward a ``select-and-scatter`` behind two relayout copies — 46.4 of the
145.8 device ms a step of ``vgg11.cyclic_s2`` (ledger, PR 24; 157 → 113 ms a
step with the fused pool: PERF.md §6, PR 25). Do not "simplify" it back.
"""

from __future__ import annotations

from typing import Any, Sequence, Union

import flax.linen as nn
import jax.numpy as jnp

from draco_tpu.models.pooling import max_pool_2x2

_CFG = {
    "A": (64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"),
    "B": (64, 64, "M", 128, 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"),
    "D": (64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M",
          512, 512, 512, "M"),
    "E": (64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M", 512, 512, 512, 512,
          "M", 512, 512, 512, 512, "M"),
}


class VGG(nn.Module):
    cfg: Sequence[Union[int, str]]
    batch_norm: bool = False
    num_classes: int = 10
    dtype: Any = jnp.float32  # MXU compute dtype; params/stats stay float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        for v in self.cfg:
            if v == "M":
                x = max_pool_2x2(x)
            else:
                x = nn.Conv(int(v), (3, 3), padding=((1, 1), (1, 1)),
                            dtype=self.dtype)(x)
                if self.batch_norm:
                    x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                     dtype=self.dtype)(x)
                x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))  # (B, 512)
        x = nn.Dropout(0.5, deterministic=not train)(x)
        x = nn.relu(nn.Dense(512, dtype=self.dtype)(x))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        x = nn.relu(nn.Dense(512, dtype=self.dtype)(x))
        # logits in float32 (loss numerics)
        return nn.Dense(self.num_classes)(x.astype(jnp.float32))


def VGG11(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["A"], False, num_classes, dtype)


def VGG11_bn(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["A"], True, num_classes, dtype)


def VGG13(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["B"], False, num_classes, dtype)


def VGG13_bn(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["B"], True, num_classes, dtype)


def VGG16(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["D"], False, num_classes, dtype)


def VGG16_bn(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["D"], True, num_classes, dtype)


def VGG19(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["E"], False, num_classes, dtype)


def VGG19_bn(num_classes: int = 10, dtype: Any = jnp.float32):
    return VGG(_CFG["E"], True, num_classes, dtype)
