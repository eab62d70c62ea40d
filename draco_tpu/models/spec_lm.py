"""What every decoder LM built from a published config mapping shares
(``config.SPEC_NETWORKS``): the tree of leaf shapes and its seeded
``init``, the helpers the blocks are written in (RMS norm, half-rotation
rotary, the products' operand rule, SwiGLU, the plain attention lowering),
and the head and loss — ``SpecLM``, the base of ``latent_moe.
RoutedExpertLM`` (the four sparse-expert models) and of ``looped.LoopedLM``
(dense, its depth a loop over the same leaves).

The head and the loss (under ``draco_head``): logits, log-softmax and the
target's log-probability a block of rows at a time (``head_block_rows``:
what ``HEAD_BLOCK_BYTES`` of float32 logits hold), so that no (rows, V)
array outlives its block — one exit's rows or four exits' alike. Rows that
fit one block are that block, with no loop: the whole-array form, under
plain autodiff. ``blocked_nll`` hands back each row's value (its loop
rematerialises each block where something differentiates it: four products
a block); ``weighted_nll``, which the training objective goes through, is
given each row's weight and so knows a block's cotangent in the forward
pass — softmax − one-hot, times the weight: the block's logits product, the
state's gradient and the weight gradient (added into one float32
accumulator the loop carries) are taken there, three products a block, and
the backward pass scales them by the scalar cotangent and runs none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from draco_tpu.ops.coded import use_pallas
from draco_tpu.ops.flash_attention import spread_kv_heads

INIT_STD = 0.02  # initializer_range is not in the published configs
# The embedding alone is seeded at unit scale. Every block reads its input
# through an RMS norm, so what a block adds does not shrink with its input:
# beside rows of std 0.02 the stream after layer 0 is the attention's running
# mean of v — the same vector at every position — and every token of a
# sequence takes the same six experts (measured at the published widths:
# single experts of the eight held got 0 to 2 982 of 4 096 tokens, the chip's
# share 3 096 to 7 891 pairs by seed, and the step time followed it). A
# trained model's stream is the token's own, as it is here at unit scale.
EMBED_STD = 1.0
# Float32 logits a block of the head may hold. Every row of a 4 096-token
# lane against an eighth of a vocabulary (12 288 to 12 800 rows: 0.2 GB),
# and 8 192 against the same (0.4 GB), are one block; four exits of 4 096
# rows against a whole vocabulary of 49 152 (3.2 GB) are eight blocks of
# 2 048. A block's weight gradient is added into the whole (hidden, V) one,
# read and written once a block: below about a thousand rows that traffic,
# not the products, is the block's time (rows / 962 at the v5e's peaks).
HEAD_BLOCK_BYTES = 2**29


def _operand(x):
    """What a kernel's product is handed. On the TPU a float32 product at
    default precision rounds its operands to bfloat16 and accumulates in
    float32; the Pallas kernels take the type they are given, so they are
    given what XLA's own products get. Elsewhere products are float32."""
    return x.astype(jnp.bfloat16) if use_pallas() else x


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * scale).astype(x.dtype)


def rope_half(x, positions, freqs, factor: float = 1.0):
    """Rotate the pairs (x[i], x[i + n]) of the first 2n dims of the last
    axis by positions·freqs[i], n = len(freqs), cos and sin times
    ``factor``; the dims past 2n pass. x: (B, T, H, dim), positions: (T,)."""
    half = len(freqs)
    ang = positions.astype(jnp.float32)[:, None] * freqs  # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    a, b = x[..., :half], x[..., half:2 * half]
    parts = [a * cos - b * sin, b * cos + a * sin]
    if 2 * half < x.shape[-1]:
        parts.append(x[..., 2 * half:])
    return jnp.concatenate(parts, axis=-1)


def dense_causal_attention(q, k, v, window=None):
    """(B, T, H, Dh) q, k and (B, T, H, Dv) v -> (B, T, H, Dv): the plain
    lowering where no kernel is selected (parallel/ring_attention.
    dense_attention, the one the kernels fall back to). k and v may have
    fewer heads (grouped-query attention). ``window``: a query sees itself
    and the ``window - 1`` tokens before it; None sees every earlier
    token."""
    from draco_tpu.parallel.ring_attention import dense_attention

    k, v = spread_kv_heads(q.shape[2], k, v)
    return dense_attention(q, k, v, window=window)


def _dot(x, kernel):
    return x @ kernel.astype(x.dtype)


def swiglu(h, p):
    return _dot(jax.nn.silu(_dot(h, p["gate"]["kernel"]))
                * _dot(h, p["up"]["kernel"]), p["down"]["kernel"])


def head_block_rows(vocab_rows: int) -> int:
    """Rows of a head block: the largest power of two whose float32 logits
    against ``vocab_rows`` fit ``HEAD_BLOCK_BYTES``."""
    return 1 << max((HEAD_BLOCK_BYTES // (4 * vocab_rows)).bit_length() - 1,
                    0)


def _block_nll(h, kernel, targets):
    logp = jax.nn.log_softmax(_dot(h, kernel).astype(jnp.float32))
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _in_blocks(rows, *arrays):
    """Arrays of n leading elements -> a tuple of (blocks, rows, ...)
    arrays, zeros closing the last block."""
    n = arrays[0].shape[0]
    blocks = -(-n // rows)
    pad = blocks * rows - n
    return tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            blocks, rows, *a.shape[1:]) for a in arrays)


def blocked_nll(h, kernel, targets):
    """h (..., hidden) the head's input rows, ``kernel`` (hidden, V),
    ``targets`` (...) -> each row's negative log-probability of its target,
    float32, a block of rows at a time (module docstring)."""
    lead, n = targets.shape, targets.size
    rows = head_block_rows(kernel.shape[-1])
    if n <= rows:
        return _block_nll(h, kernel, targets)
    nll = lax.map(
        jax.checkpoint(lambda ht: _block_nll(ht[0], kernel, ht[1])),
        _in_blocks(rows, h.reshape(n, -1), targets.reshape(n)))
    return nll.reshape(-1)[:n].reshape(lead)


def head_blocks_fused(n_rows: int, vocab_rows: int) -> int:
    """Blocks of ``n_rows`` head rows whose gradients ``weighted_nll``'s
    forward pass takes: every one where the rows outgrow one block, none
    where they are that block."""
    rows = head_block_rows(vocab_rows)
    return 0 if n_rows <= rows else -(-n_rows // rows)


@jax.custom_vjp
def _fused_nll(h, kernel, targets, weights):
    """h (n, hidden), targets and weights (n,) -> (Σ weights·nll, each
    row's nll (n,)): the primal is the blocked form as it stands (what a
    forward-only call runs)."""
    nll = blocked_nll(h, kernel, targets)
    return jnp.sum(nll * weights), nll


def _fused_nll_fwd(h, kernel, targets, weights):
    n = targets.shape[0]
    w = kernel.astype(h.dtype)
    hs, ts, ws = _in_blocks(head_block_rows(kernel.shape[-1]), h, targets,
                            weights)

    def block(carry, xs):
        gw, gh = carry
        i, hb, tb, wb = xs
        logits = (hb @ w).astype(jnp.float32)
        # log-softmax as jax.nn.log_softmax writes it
        shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
        log_sum = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
        nll = (log_sum - jnp.take_along_axis(shifted, tb[:, None], axis=-1)
               )[:, 0]
        hit = tb[:, None] == lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        d = (wb[:, None] * (jnp.exp(shifted - log_sum) - hit)).astype(h.dtype)
        return (gw + (hb.T @ d).astype(jnp.float32),
                gh.at[i].set(d @ w.T)), nll

    # the state's gradient rides in the carry, zeros first: as a result
    # stacked by the loop its buffer is allocated where the lane begins and
    # held through the whole forward pass (134 MB of the cell's peak)
    (gw, gh), nll = lax.scan(
        block, (jnp.zeros(kernel.shape, jnp.float32), jnp.zeros_like(hs)),
        (jnp.arange(hs.shape[0]), hs, ts, ws))
    gh, nll = gh.reshape(-1, h.shape[-1])[:n], nll.reshape(-1)[:n]
    return (jnp.sum(nll * weights), nll), (gh, gw.astype(kernel.dtype), nll)


def _fused_nll_bwd(res, cotangents):
    # exact for every cotangent of the sum, which is a scalar; the rows'
    # nll carry none (``weighted_nll`` stops it)
    c, _ = cotangents
    gh, gw, nll = res
    return c.astype(gh.dtype) * gh, c.astype(gw.dtype) * gw, None, c * nll


_fused_nll.defvjp(_fused_nll_fwd, _fused_nll_bwd)


def weighted_nll(h, kernel, targets, weights, denom=1.0):
    """h (..., hidden), ``kernel`` (hidden, V), ``targets`` (...) and
    ``weights`` (broadcastable against them) -> (Σ weights·nll / denom, a
    scalar; each row's nll, float32, carrying no gradient). Rows that
    outgrow one block take their gradients in the forward pass (module
    docstring), at weights / denom: a loss that is this sum hands back the
    cotangent 1, and the backward pass's scaling — a pass over the
    (hidden, V) weight gradient and one over the state's — folds away."""
    if not head_blocks_fused(targets.size, kernel.shape[-1]):
        nll = _block_nll(h, kernel, targets)
        return jnp.sum(nll * weights) / denom, lax.stop_gradient(nll)
    total, nll = _fused_nll(
        h.reshape(targets.size, -1), kernel, targets.reshape(-1),
        jnp.broadcast_to(weights / denom, targets.shape).reshape(-1))
    return total, lax.stop_gradient(nll).reshape(targets.shape)


class SpecLM:
    """``init(key) -> params`` seeded over ``param_shapes()``; ``token_nll(
    params, tokens, targets, pos_offset, train) -> (per-position objective
    (B, T) float32, the ``stat_names`` counters)``. A model adds
    ``param_shapes``, ``norm(x, p)`` (its RMS norm over a norm's leaves
    ``p``), ``hidden`` (or ``head_rows`` itself) and ``init_rules`` (leaf
    name -> ``"ones"`` | ``"zeros"`` | a normal's std; ``INIT_STD``
    otherwise).

    ``attn_fn``: (q, k, v) -> o with v's own head size (ops/
    flash_attention.flash_attention on the TPU); None is the plain lowering.
    ``remat``: rematerialise each layer in the backward pass.
    ``stat_names``: the counters' names, in the order a step's metric row
    carries them."""

    stat_names: tuple = ()
    init_rules: dict = {}

    def __init__(self, spec: dict, attn_fn=None, dtype=jnp.float32,
                 remat: bool = False):
        self.spec = dict(spec)
        self.attn_fn = attn_fn or dense_causal_attention
        self.dtype = jnp.dtype(dtype)
        self.remat = remat

    # ---- parameters ---------------------------------------------------
    def mlp_shapes(self, width: int, lead=()) -> dict:
        d = self.spec["hidden_size"]
        return {"gate": {"kernel": lead + (d, width)},
                "up": {"kernel": lead + (d, width)},
                "down": {"kernel": lead + (width, d)}}

    def init(self, key):
        shapes = self.param_shapes()
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        leaves = []
        for i, (path, shape) in enumerate(paths):
            rule = self.init_rules.get(path[-1].key, INIT_STD)
            k = jax.random.fold_in(key, i)
            if rule in ("ones", "zeros"):
                leaves.append(getattr(jnp, rule)(shape, jnp.float32))
            else:
                leaves.append(rule * jax.random.normal(k, shape, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # ---- head and loss ------------------------------------------------
    def head_rows(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (the rows the head reads (..., B, T, hidden) —
        here the last layer's output under the final norm —, the
        ``stat_names`` counters)."""
        x, stats = self.hidden(params, tokens, pos_offset)
        with jax.named_scope("draco_head"):
            return self.norm(x, params["final_norm"]), stats

    def logits(self, params, tokens, pos_offset=0):
        """tokens (B, T) -> (B, T, vocab_rows) float32."""
        h, _ = self.head_rows(params, tokens, pos_offset)
        with jax.named_scope("draco_head"):
            return _dot(h, self.head_kernel(params)).astype(jnp.float32)

    def token_nll(self, params, tokens, targets, pos_offset=0,
                  train: bool = True):
        """tokens, targets (B, T) -> (per-position negative log-likelihood
        (B, T) float32 over the vocabulary slice, the ``stat_names``
        counters)."""
        del train  # no dropout in these blocks
        h, stats = self.head_rows(params, tokens, pos_offset)
        with jax.named_scope("draco_head"):
            return blocked_nll(h, self.head_kernel(params), targets), stats

    def weighted_nll(self, params, tokens, targets, weights, denom=1.0,
                     pos_offset=0, train: bool = True):
        """tokens, targets (B, T), ``weights`` broadcastable against them
        (the route's: one a position, (T,)) -> (Σ weights · ``token_nll`` /
        denom, a scalar, the ``stat_names`` counters): the training
        objective's surface (``weighted_nll``)."""
        del train  # no dropout in these blocks
        h, stats = self.head_rows(params, tokens, pos_offset)
        with jax.named_scope("draco_head"):
            return weighted_nll(h, self.head_kernel(params), targets,
                                weights, denom)[0], stats

    # ---- a head tied to the embedding ---------------------------------
    # (below every line the standing models' steps are traced from: their
    # lowered programs name source lines, and the compile cache keys on
    # them)
    tied_head: bool = False

    def head_kernel(self, params):
        """The head's (hidden, V) matrix: the ``head`` leaf, or — where the
        model says ``tied_head`` and so carries no such leaf — the
        embedding's, transposed: ONE leaf read twice (rows gathered at the
        bottom, this product at the top), its gradient the sum of both
        uses."""
        if self.tied_head:
            return params["embed"]["embedding"].T
        return params["head"]["kernel"]
