"""What the parity tests share (a plain module: no plugin, no marker).

A parity test holds a program to a plain reference of the same semantics:
the published-config language models against benchmark/reference/nets/*, a
Pallas kernel in interpret mode against its ``jax.numpy`` path. What it
compares is COMPILED — one program a (function, shapes): called eagerly, a
model's loss, its gradient and above all a reference's token-by-token Python
loop are one dispatch and one tiny compile a primitive, thousands a test,
and on a loaded host that, not the arithmetic, was a third of the suite's
clock. So: hand ``jax.jit`` a function of the ARRAYS that vary between
cases (weights, tokens) with everything else closed over, keep the jitted
object where every case of the same shapes finds it again (module level, a
module-scoped fixture, ``functools.lru_cache``), and take a forward value
and its gradients from one program (``with_gradients``). Where a test's
point is the eager path or a jaxpr's text it stays as it is.
"""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model_spec(directory: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", directory,
                           name + ".json")) as fh:
        return json.load(fh)["train_config"]["model_spec"]


def tiny(name: str) -> dict:
    """The ``model_spec`` of benchmark/testdata/<name>.json."""
    return _model_spec("testdata", name)


def published(name: str) -> dict:
    """The ``model_spec`` of benchmark/configs/<name>.json."""
    return _model_spec("configs", name)


def tokens(vocab: int, batch: int, t: int, seed: int):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, (batch, t)), jnp.int32)


def mean_nll(lm, params, toks):
    """(mean next-token loss over the row's first T - 1 positions, the
    model's counters)."""
    nll, stats = lm.token_nll(params, toks, jnp.roll(toks, -1, axis=1))
    return jnp.mean(nll[:, :-1]), stats


def moved(params, key, leaf_names=None):
    """The leaves called one of ``leaf_names`` (None: every leaf) off their
    initial zeros and ones by a seeded 0.1·normal, so that a (1 + w) read
    as w, a norm left out or applied twice, a head's row read as
    another's, shows."""
    def move(path, x):
        if leaf_names is None or path[-1].key in leaf_names:
            k = jax.random.fold_in(key, zlib.crc32(
                jax.tree_util.keystr(path).encode()) % 2**31)
            return x + 0.1 * jax.random.normal(k, x.shape)
        return x

    return jax.tree_util.tree_map_with_path(move, params)


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def assert_leaves_close(got, want, rel, rel_by_suffix=None, zero=(),
                        floor=1e-9):
    """Every leaf of ``got`` within ``rel`` of the largest entry of
    ``want``'s (``rel_by_suffix``: {a leaf name's ending: its own rel}), plus
    ``floor``. A leaf whose name holds one of ``zero`` takes no gradient on
    either side; every other leaf of ``want`` must be somewhere non-zero."""
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert g.shape == w.shape, name
        scale = float(jnp.max(jnp.abs(w)))
        if any(z in name for z in zero):
            assert scale == 0.0 and not np.any(np.asarray(g)), name
            continue
        assert scale > 0.0, f"{name} takes no gradient"
        bound = rel
        for suffix, own in (rel_by_suffix or {}).items():
            if name.endswith(suffix):
                bound = own
        assert float(jnp.max(jnp.abs(g - w))) <= bound * scale + floor, name


def jitted(fn, static, **kw):
    """arrays -> ``fn(static, *arrays, **kw)`` under ``jax.jit`` (``static``:
    what the function closes over — a code, a model). Each call of this
    helper is a NEW jit with a cache of its own: make it once and call it
    again where a loop, or cases of one shape, should share the compile."""
    return jax.jit(lambda *a: fn(static, *a, **kw))


def run_jitted(fn, static, *arrays, **kw):
    """``fn(static, *arrays, **kw)`` as one compiled program in place of a
    dispatch a primitive, for a test that calls it once: nothing is kept."""
    return jitted(fn, static, **kw)(*arrays)


def with_gradients(fn, loss_of, argnums=0):
    """jit of args -> (fn(*args), the gradients of loss_of(fn(*args)) for
    the arguments ``argnums``): ONE compiled program, the forward pass run
    once for the value compared and the gradients both."""
    def run(*args):
        def loss(*a):
            out = fn(*a)
            return loss_of(out), out

        grads, out = jax.grad(loss, argnums=argnums, has_aux=True)(*args)
        return out, grads

    return jax.jit(run)
