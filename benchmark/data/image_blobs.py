"""Labelled images from ``--seed``: class-conditional Gaussian blobs at the
shapes the configuration's ``data`` block states (there is no network, so no
CIFAR-10 files), made on the host in bulk because the program's input
pipeline gathers its batches from host memory."""

from __future__ import annotations

import numpy as np


def make(spec: dict, seed: int):
    """``spec``: ``sample_shape`` [H, W, C], ``classes``, ``train_examples``.
    Returns (train_x float32 (N, H, W, C), train_y int32 (N,))."""
    h, w, c = spec["sample_shape"]
    classes, n_train = spec["classes"], spec["train_examples"]
    rng = np.random.default_rng([seed, 0x64617461])
    protos = rng.standard_normal((classes, h, w, c), dtype=np.float32)
    y = rng.integers(0, classes, size=n_train).astype(np.int32)
    x = rng.standard_normal((n_train, h, w, c), dtype=np.float32)
    x *= np.float32(0.8)
    x += np.float32(0.6) * protos[y]
    return x, y
