"""Which rows each logical worker trains on at each step, and how they are
augmented: the reference's own copy of the job's input rules, so that it
needs nothing from the program to follow a run.

The rules (Draco, ICML 2018, and its reference implementation):

* ``cyclic``: every step addresses one global batch of n*B consecutive
  samples of an epoch shuffle all workers agree on; row k of the (n, B)
  reshape is batch k.
* ``baseline``: every worker walks its own epoch shuffle.
* CIFAR augmentation: reflect-pad 4, random 32x32 crop, random horizontal
  flip, keyed per (step, batch row); dropout keyed the same way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _epoch_permutation(seed: int, epoch: int, n: int) -> np.ndarray:
    rng = np.random.RandomState((seed * 100003 + epoch * 23) % (2**31 - 1))
    return rng.permutation(n)


def _wrapped(perm: np.ndarray, start: int, width: int) -> np.ndarray:
    idx = perm[start:start + width]
    if len(idx) < width:
        idx = np.concatenate([idx, perm[:width - len(idx)]])
    return idx


def step_indices(policy: str, n_samples: int, step: int, n: int, b: int,
                 seed: int) -> np.ndarray:
    """(n, B) sample indices of 1-based training ``step``."""
    t = step - 1
    if policy == "cyclic":
        bpe = max(n_samples // (n * b), 1)
        perm = _epoch_permutation(seed, t // bpe, n_samples)
        return _wrapped(perm, (t % bpe) * n * b, n * b).reshape(n, b)
    if policy == "baseline":
        bpe = max(n_samples // b, 1)
        return np.stack([
            _wrapped(_epoch_permutation(seed + 31 * (w + 1), t // bpe,
                                        n_samples),
                     ((t % bpe) * b) % n_samples, b)
            for w in range(n)])
    raise ValueError(f"no index policy {policy!r}")


def fold(key, *data):
    for d in data:
        key = jax.random.fold_in(key, d)
    return key


def row_keys(seed: int, step: int, row: int):
    """(augmentation key, dropout key) of batch ``row`` at ``step``."""
    return (fold(jax.random.key(seed + 2), step, row),
            fold(jax.random.key(seed + 3), step, row))


def _augment_one(x, key, pad=4):
    h, w, c = x.shape
    kh, kw, kf = jax.random.split(key, 3)
    xp = jnp.pad(x, ((pad, pad), (pad, pad), (0, 0)), mode="reflect")
    top = jax.random.randint(kh, (), 0, 2 * pad + 1)
    left = jax.random.randint(kw, (), 0, 2 * pad + 1)
    x = jax.lax.dynamic_slice(xp, (top, left, 0), (h, w, c))
    return jnp.where(jax.random.bernoulli(kf), x[:, ::-1, :], x)


def augment(x, key):
    """x: (B, H, W, C); one independent draw per sample."""
    return jax.vmap(_augment_one)(x, jax.random.split(key, x.shape[0]))
