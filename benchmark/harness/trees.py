"""Norms and distances over parameter-shaped trees, each one device
program: what the check reads on both sides, the program's and the
reference's."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _norms(tree, minus=None):
    if minus is not None:
        tree = jax.tree.map(jnp.subtract, tree, minus)
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def leaf_norms(tree, minus=None) -> list:
    """Per-leaf L2 norms of ``tree`` (of ``tree - minus`` if given), as
    floats."""
    return [float(v) for v in jax.device_get(_norms(tree, minus))]


@jax.jit
def _rel_diff(a, b):
    num = sum(jnp.sum(jnp.square(x.astype(jnp.float32) - y))
              for x, y in zip(a, b))
    den = sum(jnp.sum(jnp.square(y)) for y in b)
    return jnp.sqrt(num / den)


def rel_diff(leaves, reference_leaves) -> float:
    """Norm of the difference over the reference's norm, all leaves taken
    as one vector."""
    return float(_rel_diff(list(leaves), list(reference_leaves)))
