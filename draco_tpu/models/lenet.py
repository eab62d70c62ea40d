"""LeNet for MNIST (reference: src/model_ops/lenet.py:20-41).

conv(1→20, 5×5, VALID) → maxpool2 → relu → conv(20→50) → maxpool2 → relu →
fc(800→500) → fc(500→10). Note the reference applies relu *after* the pool;
kept as-is.

The pools are ``pooling.max_pool_2x2``, not ``nn.max_pool``, whose
``reduce-window`` / ``select-and-scatter`` pair under the step builder's two
``vmap``s ran 50–90 × off its roofline on the chip (46.4 of 145.8 device ms a
step in ``vgg11.cyclic_s2``: ledger, PR 24; PERF.md §6, PR 25)."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from draco_tpu.models.pooling import max_pool_2x2


class LeNet(nn.Module):
    num_classes: int = 10
    dtype: Any = jnp.float32  # MXU compute dtype; params stay float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        x = nn.Conv(20, (5, 5), padding="VALID", dtype=self.dtype)(x)
        x = max_pool_2x2(x)
        x = nn.relu(x)
        x = nn.Conv(50, (5, 5), padding="VALID", dtype=self.dtype)(x)
        x = max_pool_2x2(x)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))  # (B, 4*4*50)
        x = nn.Dense(500, dtype=self.dtype)(x)
        x = nn.Dense(self.num_classes)(x.astype(jnp.float32))
        return x
