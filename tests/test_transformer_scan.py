"""scan_layers: the LM layer stack compiled as ONE nn.scan body.

Why this exists: the unrolled 12-layer remat program of the d≈159M LM perf
point is ~12× the size it needs to be, and its compile ran for ~27 minutes
without finishing (PERF_HISTORY.md §4). ``scan_layers``
compiles the stack as a single scanned block over stacked weights —
identical math, ~layers× smaller XLA program. These tests pin:

  1. output parity with the unrolled model (restacking per-block params
     along a leading layer axis reproduces the scanned model exactly);
  2. the coded train step (tp path) runs under scan_layers + remat and
     matches the unrolled step's loss;
  3. the Megatron partition specs shift right by one under the stacked
     "blocks" subtree (tp sharding stays on the correct dims).

No reference counterpart (reference is CNN-only); this is TPU-build
compile-scaling infrastructure.
"""

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from draco_tpu.models.transformer import TransformerLM

pytestmark = pytest.mark.core


def _toks(b=2, t=16, vocab=64):
    return jnp.asarray(np.random.RandomState(0).randint(0, vocab, (b, t)),
                       jnp.int32)


def _init(model, key, toks):
    """The model's parameters, the init ONE compiled program (called
    eagerly it is a dispatch and a tiny compile a primitive)."""
    return jax.jit(lambda k: model.init({"params": k}, toks, train=True))(
        jax.random.key(key))["params"]


def _restack(p_unroll, p_scan, layers):
    """Unrolled block0..N-1 params stacked into the scan layout."""
    stacked = jtu.tree_map(lambda *xs: jnp.stack(xs),
                           *[p_unroll[f"block{i}"] for i in range(layers)])
    out = dict(p_scan)
    out["blocks"] = stacked
    for k in p_unroll:
        if not k.startswith("block"):
            out[k] = p_unroll[k]
    return out


def test_scan_layers_output_parity():
    kw = dict(vocab=64, dim=32, heads=4, layers=3)
    toks = _toks()
    m_u = TransformerLM(**kw)
    m_s = TransformerLM(**kw, scan_layers=True)
    p_u, p_s = _init(m_u, 0, toks), _init(m_s, 0, toks)
    assert p_s["blocks"]["qkv"]["kernel"].shape == (3, 32, 96)
    p_mix = _restack(p_u, p_s, 3)
    o_u = jax.jit(lambda p: m_u.apply({"params": p}, toks, train=True))(p_u)
    o_s = jax.jit(lambda p: m_s.apply({"params": p}, toks, train=True))(p_mix)
    np.testing.assert_allclose(np.asarray(o_u), np.asarray(o_s),
                               rtol=0, atol=1e-5)


def test_scan_layers_remat_grad_parity():
    """remat inside the scan body (prevent_cse=False) must not change
    gradients vs the unrolled remat model."""
    kw = dict(vocab=64, dim=32, heads=4, layers=2)
    toks = _toks()
    m_u = TransformerLM(**kw, remat=True)
    m_s = TransformerLM(**kw, scan_layers=True, remat=True)
    p_u, p_s = _init(m_u, 1, toks), _init(m_s, 1, toks)
    p_mix = _restack(p_u, p_s, 2)

    def loss_u(p):
        return jnp.mean(m_u.apply({"params": p}, toks, train=True) ** 2)

    def loss_s(p):
        return jnp.mean(m_s.apply({"params": p}, toks, train=True) ** 2)

    g_u = jax.jit(jax.grad(loss_u))(p_u)
    g_s = jax.jit(jax.grad(loss_s))(p_mix)
    g_u_stacked = jtu.tree_map(lambda *xs: jnp.stack(xs),
                               *[g_u[f"block{i}"] for i in range(2)])
    flat_u = jnp.concatenate([x.ravel() for x in jtu.tree_leaves(g_u_stacked)])
    flat_s = jnp.concatenate([x.ravel() for x in
                              jtu.tree_leaves(g_s["blocks"])])
    np.testing.assert_allclose(np.asarray(flat_u), np.asarray(flat_s),
                               rtol=1e-4, atol=1e-5)


def test_tp_train_step_scan_layers_matches_unrolled():
    """The full coded LM train step (cyclic, folded mesh) under scan_layers
    produces the same loss trajectory as the unrolled program."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.mesh import make_folded_wtp_mesh
    from draco_tpu.parallel.tp_step import build_tp_train_setup
    from tools._lowering_common import make_scan_loop, stage_scan_inputs

    common = dict(
        network="TransformerLM", dataset="synthetic-text",
        approach="cyclic", redundancy="shared",
        batch_size=2, lr=0.01, momentum=0.9,
        num_workers=8, worker_fail=1, err_mode="rev_grad",
        seq_len=32, vocab=64, model_dim=32, model_heads=4, model_layers=2,
        max_steps=3, eval_freq=0, train_dir="", log_every=10**9,
        remat=True,
    )
    mesh = make_folded_wtp_mesh(8)
    cfg_u = TrainConfig(**common, scan_layers=False)
    cfg_s = TrainConfig(**common, scan_layers=True)
    setup_u = build_tp_train_setup(cfg_u, mesh)
    setup_s = build_tp_train_setup(cfg_s, mesh)
    # nn.scan's split_rngs draws different init streams than the unrolled
    # block0..N-1 modules, so equalise by restacking the unrolled params
    # into the scan layout (momentum slots are zeros at init either way)
    p_u = jax.device_get(setup_u.state.params)
    p_s = jax.device_get(setup_s.state.params)
    state_s = setup_s.state._replace(
        params=jtu.tree_map(jnp.asarray,
                            _restack(p_u, p_s, common["model_layers"])))
    xs, ms = stage_scan_inputs(cfg_u, 2)
    losses = {}
    with mesh:
        _, ls = jax.jit(make_scan_loop(setup_u))(setup_u.state, xs, ms)
        losses["unroll"] = np.asarray(jax.device_get(ls))
        _, ls = jax.jit(make_scan_loop(setup_s))(state_s, xs, ms)
        losses["scan"] = np.asarray(jax.device_get(ls))
    for v in losses.values():
        assert np.all(np.isfinite(v))
    # same params, same data, same math — trajectories agree to f32 noise
    np.testing.assert_allclose(losses["unroll"], losses["scan"],
                               rtol=2e-4, atol=2e-4)


def test_ep_partition_spec_shifts_under_blocks():
    """scan_layers stacks expert weights as (layers, E, ...) — the ep spec
    must shard E (now axis 1), not the new leading layer axis (review
    finding: P(EP_AXIS) on the stacked tree sharded layers over ep)."""
    from jax.sharding import PartitionSpec as P

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel import EP_AXIS, make_mesh_wep
    from draco_tpu.parallel.ep_step import (
        build_ep_train_setup, ep_partition_spec,
    )

    cfg = TrainConfig(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=4, moe_experts=4, expert_shards=2, seq_len=32, vocab=32,
        model_dim=32, model_heads=4, model_layers=2, approach="baseline",
        mode="normal", worker_fail=0, max_steps=3, lr=0.05, momentum=0.9,
        eval_freq=0, train_dir="", log_every=1000, scan_layers=True,
    )
    mesh = make_mesh_wep(4, 2)
    setup = build_ep_train_setup(cfg, mesh)
    seen = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            setup.state.params)[0]:
        names = [getattr(k, "key", str(k)) for k in path]
        seen["/".join(names)] = (ep_partition_spec(path),
                                 leaf.sharding.spec, leaf.shape)
    spec, placed, shape = seen["blocks/moe/w1"]
    assert spec == P(None, EP_AXIS)
    assert placed == spec
    assert shape[0] == 2 and shape[1] == 4  # (layers, E, ...)
    assert seen["blocks/moe/router/kernel"][0] == P()
    for key, (want, got, _) in seen.items():
        assert got == want, (key, want, got)


def test_partition_spec_shifts_under_blocks():
    from jax.sharding import PartitionSpec as P

    from draco_tpu.parallel.mesh import TP_AXIS
    from draco_tpu.parallel.tp_step import param_partition_spec

    class K:  # stand-in for jtu.DictKey
        def __init__(self, key):
            self.key = key

    unrolled = [K("block0"), K("qkv"), K("kernel")]
    scanned = [K("blocks"), K("qkv"), K("kernel")]
    assert param_partition_spec(unrolled) == P(None, TP_AXIS)
    assert param_partition_spec(scanned) == P(None, None, TP_AXIS)
    assert param_partition_spec([K("blocks"), K("proj"), K("kernel")]) == \
        P(None, TP_AXIS, None)
    assert param_partition_spec([K("blocks"), K("mlp_in"), K("bias")]) == \
        P(None, TP_AXIS)
    assert param_partition_spec([K("embed"), K("embedding")]) == P()
