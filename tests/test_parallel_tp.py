"""Tensor parallelism (GSPMD param-sharding path): exactness vs tp=1,
actual shard placement, and coded-DP composition on the (w, tp) mesh."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from draco_tpu.config import TrainConfig
from draco_tpu.parallel import TP_AXIS, make_mesh_wtp
from draco_tpu.parallel.tp_step import (
    build_tp_train_setup,
    param_partition_spec,
    train_tp,
)


def _tp_cfg(**kw):
    base = dict(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=4, tensor_shards=2, seq_len=32, vocab=32, model_dim=32,
        model_heads=4, model_layers=1, approach="baseline", mode="normal",
        worker_fail=0, max_steps=3, lr=0.05, momentum=0.9, eval_freq=0,
        train_dir="", log_every=1000,
    )
    base.update(kw)
    return TrainConfig(**base)


def _flat(params):
    return np.concatenate([np.ravel(x) for x in jax.tree.leaves(params)])


def test_partition_rules():
    """Megatron rules: column-parallel qkv/mlp_in, row-parallel proj/mlp_out,
    everything else replicated."""
    cfg = _tp_cfg()
    mesh = make_mesh_wtp(4, 2)
    setup = build_tp_train_setup(cfg, mesh)
    seen = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(setup.state.params)[0]:
        names = [getattr(k, "key", str(k)) for k in path]
        seen["/".join(names)] = (param_partition_spec(path), leaf.sharding.spec)
    assert seen["block0/qkv/kernel"][0] == P(None, TP_AXIS)
    assert seen["block0/proj/kernel"][0] == P(TP_AXIS, None)
    assert seen["block0/mlp_in/kernel"][0] == P(None, TP_AXIS)
    assert seen["block0/mlp_out/kernel"][0] == P(TP_AXIS, None)
    assert seen["embed/embedding"][0] == P()
    # the placement actually applied, not just computed — in the NORMALIZED
    # spelling (trailing Nones stripped, tp_step._norm_spec): the applied
    # shardings are pinned to the form XLA reports back, so the K-fused
    # carry cannot retrace against its own output layout (PERF_HISTORY.md §9)
    from draco_tpu.parallel.tp_step import _norm_spec

    for key, (want, got) in seen.items():
        assert got == _norm_spec(want), (key, want, got)


def test_tp_matches_single_shard():
    """(4 w × 2 tp) and (4 w × 1 tp) must produce the same trajectory —
    tensor parallelism is a layout choice, not a math change."""
    mesh_tp = make_mesh_wtp(4, 2)
    state_tp, m_tp = train_tp(_tp_cfg(), mesh_tp, steps=3, quiet=True)

    mesh_1 = make_mesh_wtp(4, 1, devices=jax.devices()[:4])
    state_1, m_1 = train_tp(_tp_cfg(tensor_shards=1), mesh_1, steps=3, quiet=True)

    np.testing.assert_allclose(float(m_tp["loss"]), float(m_1["loss"]), rtol=1e-4)
    np.testing.assert_allclose(
        _flat(jax.device_get(state_tp.params)),
        _flat(jax.device_get(state_1.params)),
        rtol=1e-3, atol=1e-5,
    )


def test_tp_params_stay_sharded_after_steps():
    cfg = _tp_cfg()
    mesh = make_mesh_wtp(4, 2)
    state, _ = train_tp(cfg, mesh, steps=2, quiet=True)
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    qkv = next(l for p, l in flat
               if [getattr(k, "key", "") for k in p][-2:] == ["qkv", "kernel"])
    assert qkv.sharding.spec == P(None, TP_AXIS)


def test_tp_geomedian_under_attack():
    """Robust aggregation composed with tensor parallelism: (4 w × 2 tp),
    one rev_grad adversary, geometric median — finite and progressing.
    (Cyclic × tp needs n > 4s mesh rows, i.e. ≥ 10 devices with tp=2 —
    exercised by dryrun_multichip(16) instead; the 8-device CI mesh only
    fits w=4 × tp=2.)"""
    cfg = _tp_cfg(mode="geometric_median", worker_fail=1, err_mode="rev_grad")
    mesh = make_mesh_wtp(4, 2)
    state, metrics = train_tp(cfg, mesh, steps=6, quiet=True)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 7


def test_tp_validation():
    with pytest.raises(ValueError, match="tensor_shards"):
        _tp_cfg(tensor_shards=3).validate()
    with pytest.raises(ValueError, match="separate paths"):
        _tp_cfg(tensor_shards=2, seq_shards=2).validate()


def test_tp_cyclic_simulate_matches_shared():
    """Reference-parity r× redundant compute (redundancy='simulate',
    cyclic_worker.py:122-146) and the one-copy 'shared' fast path must give
    the same trajectory — per-batch gradients are deterministic under XLA,
    so the encoded rows are algebraically identical. n=8 workers fold onto
    the (w=4 × tp=2) mesh, 2 lanes/device; one live rev_grad adversary is
    decoded away in both."""
    kw = dict(num_workers=8, approach="cyclic", worker_fail=1,
              err_mode="rev_grad")
    mesh = make_mesh_wtp(4, 2)
    st_sim, m_sim = train_tp(_tp_cfg(redundancy="simulate", **kw), mesh,
                             steps=3, quiet=True)
    st_sh, m_sh = train_tp(_tp_cfg(redundancy="shared", **kw), mesh,
                           steps=3, quiet=True)
    np.testing.assert_allclose(float(m_sim["loss"]), float(m_sh["loss"]),
                               rtol=1e-4)
    np.testing.assert_allclose(
        _flat(jax.device_get(st_sim.params)),
        _flat(jax.device_get(st_sh.params)),
        rtol=1e-3, atol=1e-5,
    )


def test_tp_folded_accepts_flash():
    """The folded (tp=1) LM regime — what the perf/convergence tools run —
    accepts attn_impl=flash; the kernel (dense fallback off-TPU) slots in
    as the Block attention with an unchanged training contract."""
    import numpy as np

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.mesh import make_folded_wtp_mesh
    from draco_tpu.parallel.sp_step import synthetic_text
    from draco_tpu.parallel.tp_step import build_tp_train_setup

    cfg = TrainConfig(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=4, approach="baseline", mode="normal", worker_fail=0,
        seq_len=16, vocab=32, model_dim=32, model_heads=2, model_layers=1,
        attn_impl="flash", max_steps=2, eval_freq=0,
        train_dir="", log_every=1000,
    )
    cfg.validate()
    mesh = make_folded_wtp_mesh(4)
    setup = build_tp_train_setup(cfg, mesh)
    toks = synthetic_text(cfg.seed, 1, 4, 2, 16, 32)
    import jax.numpy as jnp
    st, metrics = setup.train_step(setup.state, jnp.asarray(toks),
                                   jnp.zeros((4,), bool))
    assert np.isfinite(float(metrics["loss"]))
