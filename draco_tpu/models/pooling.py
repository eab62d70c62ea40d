"""2×2 / stride-2 max-pool as an elementwise max the compiler can fuse.

``nn.max_pool`` is ``lax.reduce_window``; under the step builder's two
``vmap``s (n workers × r redundant rows) that is a 6-D ``reduce-window`` the
TPU compiler lays out with W on the sublanes, and its transpose is a
``select-and-scatter`` fed by two relayout copies: 46 of 146 device ms a step
in ``vgg11.cyclic_s2`` (PERF.md §6, PR 25). Here the four phases of the window
are split off by a reshape and reduced elementwise, so the forward fuses into
the convolution's bias + ReLU epilogue and the backward into compare/select
fusions. Same maxima, same gradient routing as ``nn.max_pool``. Which pools
take this form is decided by their shape alone (``max_pool_2x2``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def _windows(x):
    """(..., H, W, C) → (..., H/2, 2, W/2, 2, C): axes -4 and -2 hold the
    window's row and column phase."""
    *lead, h, w, c = x.shape
    return x.reshape(*lead, h // 2, 2, w // 2, 2, c)


def _per_window(v):
    """(..., H/2, W/2, C) broadcastable against ``_windows``' result."""
    return v[..., :, None, :, None, :]


@jax.custom_vjp
def _max_pool_even(x):
    return _windows(x).max(axis=(-4, -2))


def _max_pool_even_fwd(x):
    y = _max_pool_even(x)
    # both are live anyway (the ReLU's mask, the next convolution's operand):
    # no mask and no tie count is saved, as plain autodiff of jnp.max would
    return y, (x, y)


def _max_pool_even_bwd(res, g):
    x, y = res
    hit = _windows(x) == _per_window(y)
    # the first maximum of a window in row-major order takes the whole
    # cotangent: the rule of nn.max_pool's select-and-scatter (GE select)
    order = (2 * lax.broadcasted_iota(jnp.int32, (2, 1, 2, 1), 0)
             + lax.broadcasted_iota(jnp.int32, (2, 1, 2, 1), 2))
    first = jnp.where(hit, order, 4).min(axis=(-4, -2), keepdims=True)
    dx = jnp.where(order == first, _per_window(g), jnp.zeros((), g.dtype))
    # the barrier keeps the select in the windowed shape: without it XLA
    # hoists the reshape below over the select and materialises the
    # upsampled cotangent instead of broadcasting it inside the fusion
    # (+24 ms a call at the vgg11.cyclic_s2 shapes: PERF.md §6, PR 25)
    return (lax.optimization_barrier(dx).reshape(x.shape),)


_max_pool_even.defvjp(_max_pool_even_fwd, _max_pool_even_bwd)


# below this many rows or columns the pool keeps nn.max_pool (see max_pool_2x2)
_MIN_SIDE = 8


def max_pool_2x2(x):
    """Max over non-overlapping 2×2 windows of (..., H, W, C).

    The fused form is taken when H and W are even and at least 8. Odd sides
    keep ``nn.max_pool``'s floor semantics by falling back to it; so do
    sides under 8 (VGG's last two pools, 8 % of its pooled elements, each
    under the trace's 4 ms cut): after the fused form there the chip's
    compiler sums the next convolution in another order, a ReLU flips here
    and there at its boundary, and the step's first gradient leaves the
    reference's by 0.4 % (PERF.md §6, PR 25)."""
    h, w = x.shape[-3], x.shape[-2]
    if h % 2 or w % 2 or min(h, w) < _MIN_SIDE:
        return nn.max_pool(x, (2, 2), strides=(2, 2))
    return _max_pool_even(x)
