"""Weights from ``--seed``: the same seed gives the same run. One jitted
call on the device, float32 as trained, over the parameter tree's shapes.
Which leaf gets which initialiser is the configuration's: its ``weights``
block maps a leaf's name in the tree (its last path segment) to one of the
few initialisers below. (Inputs from the seed: benchmark/data/.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A jax key from any whole number up to and beyond 2**31."""
    return jax.random.fold_in(jax.random.key(seed >> 31), seed & 0x7FFFFFFF)


def _initialiser(rule: str):
    """``normal_fan_in`` (variance 1 / product of all but the last axis),
    ``normal:<std>``, ``ones``, ``zeros``."""
    if rule == "normal_fan_in":
        return lambda key, shape: (
            jax.random.normal(key, shape, jnp.float32)
            * np.float32(int(np.prod(shape[:-1])) ** -0.5))
    if rule.startswith("normal:"):
        std = np.float32(float(rule.split(":", 1)[1]))
        return lambda key, shape: (
            jax.random.normal(key, shape, jnp.float32) * std)
    if rule == "ones":
        return lambda key, shape: jnp.ones(shape, jnp.float32)
    if rule == "zeros":
        return lambda key, shape: jnp.zeros(shape, jnp.float32)
    raise ValueError(f"seeded weights: no initialiser {rule!r}")


def make_weights(shapes_tree, rules: dict, seed: int, sharding=None):
    """A tree like ``shapes_tree`` (leaves with .shape), seeded, on device.
    ``rules``: leaf name -> initialiser, from the configuration's file."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes_tree)
    leaves = []
    for p, x in paths:
        name = str(getattr(p[-1], "key", p[-1]))
        if name not in rules:
            raise ValueError(f"seeded weights: the configuration's "
                             f"'weights' block has no rule for a leaf "
                             f"named {name!r}")
        leaves.append((_initialiser(rules[name]), tuple(x.shape)))

    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            init(jax.random.fold_in(key, i), shape)
            for i, (init, shape) in enumerate(leaves)])

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))
