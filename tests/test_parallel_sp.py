"""Sequence parallelism: ring attention exactness (fwd + grad) and the 2-D
mesh (w × sp) coded training step, on the 8-device virtual CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from draco_tpu.config import TrainConfig
from draco_tpu.parallel import make_mesh_2d, ring_attention
from draco_tpu.parallel.ring_attention import dense_attention
from draco_tpu.parallel.sp_step import build_sp_train_setup, synthetic_text, train_sp


def _qkv(rng, b=2, t=32, h=2, dh=8):
    return tuple(rng.normal(size=(b, t, h, dh)).astype(np.float32) for _ in range(3))


def _softmax_attn(q, k, v, causal):
    dh = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :]
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [True, False])
def test_dense_attention_matches_softmax(rng, causal):
    q, k, v = _qkv(rng)
    out = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), _softmax_attn(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sp,causal", [(4, True), (8, True), (4, False)])
def test_ring_attention_matches_dense(rng, sp, causal):
    q, k, v = _qkv(rng, t=32)
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    out = jax.jit(ring)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), _softmax_attn(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_gradient_matches_dense(rng):
    """d/dq,k,v of a scalar of ring attention == dense attention's — the
    ppermute transpose routing that the SP gradient psum relies on."""
    q, k, v = _qkv(rng, t=16)
    sp = 4
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))

    def ring_scalar(q, k, v):
        f = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
        return jnp.sum(jnp.sin(f(q, k, v)))

    def dense_scalar(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=True)))

    g_ring = jax.jit(jax.grad(ring_scalar, argnums=(0, 1, 2)))(
        *map(jnp.asarray, (q, k, v)))
    g_dense = jax.jit(jax.grad(dense_scalar, argnums=(0, 1, 2)))(
        *map(jnp.asarray, (q, k, v)))
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), rtol=1e-4, atol=1e-5)


def test_synthetic_text_deterministic():
    a = synthetic_text(428, 7, 2, 3, 16, 64)
    b = synthetic_text(428, 7, 2, 3, 16, 64)
    assert np.array_equal(a, b)
    assert a.shape == (2, 3, 16)
    # ramps: t_{i+1} - t_i constant per sequence
    d = np.diff(a, axis=-1) % 64
    assert np.all(d == d[..., :1])


def _sp_cfg(**kw):
    base = dict(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=2, seq_shards=4, seq_len=32, vocab=32, model_dim=32,
        model_heads=2, model_layers=1, approach="baseline", mode="normal",
        worker_fail=0, max_steps=3, lr=0.05, momentum=0.9, eval_freq=0,
        train_dir="", log_every=1000,
    )
    base.update(kw)
    return TrainConfig(**base)


def test_sp_step_runs_and_learns():
    cfg = _sp_cfg()
    mesh = make_mesh_2d(2, 4)
    state, metrics = train_sp(cfg, mesh, steps=8, quiet=True)
    assert int(state.step) == 9
    assert np.isfinite(float(metrics["loss"]))


def test_sp_matches_single_shard():
    """Same config on (2 w × 4 sp) and (2 w × 1 sp): ring attention must not
    change the training trajectory."""
    cfg = _sp_cfg()
    mesh_sp = make_mesh_2d(2, 4)
    state_sp, m_sp = train_sp(cfg, mesh_sp, steps=3, quiet=True)

    cfg1 = _sp_cfg(seq_shards=1)
    mesh_1 = make_mesh_2d(2, 1)
    state_1, m_1 = train_sp(cfg1, mesh_1, steps=3, quiet=True)

    np.testing.assert_allclose(float(m_sp["loss"]), float(m_1["loss"]), rtol=1e-4)
    flat_sp = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_sp.params)])
    flat_1 = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_1.params)])
    np.testing.assert_allclose(flat_sp, flat_1, rtol=1e-3, atol=1e-5)


def test_sp_cyclic_tolerates_adversary():
    """cyclic s=1 on a (8 w × 1 sp) mesh vs no-attack run: decode must null
    the Byzantine rows (exact recovery), trajectories must match."""
    cfg_atk = _sp_cfg(num_workers=8, seq_shards=1, approach="cyclic",
                      worker_fail=1, err_mode="rev_grad")
    mesh = make_mesh_2d(8, 1)
    state_a, m_a = train_sp(cfg_atk, mesh, steps=3, quiet=True)

    cfg_clean = _sp_cfg(num_workers=8, seq_shards=1, approach="baseline",
                        worker_fail=0)
    state_c, m_c = train_sp(cfg_clean, mesh, steps=3, quiet=True)

    flat_a = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_a.params)])
    flat_c = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_c.params)])
    np.testing.assert_allclose(flat_a, flat_c, rtol=2e-2, atol=2e-4)


def test_sp_geomedian_under_attack():
    """Robust aggregation composed with ring attention: (4 w × 2 sp) mesh,
    one rev_grad adversary, geometric-median aggregation — must stay finite
    and make progress. (Full cyclic × sp needs n > 4s mesh rows and runs in
    the driver's dryrun_multichip instead — 8 CPU devices only fit w=4×sp=2.)"""
    cfg = _sp_cfg(num_workers=4, seq_shards=2, mode="geometric_median",
                  worker_fail=1, err_mode="rev_grad")
    mesh = make_mesh_2d(4, 2)
    state, metrics = train_sp(cfg, mesh, steps=6, quiet=True)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 7


def test_sp_checkpoint_resume(tmp_path):
    """train_sp honours train_dir/eval_freq/checkpoint_step: checkpoints are
    written at cadence and a resumed run continues from the saved state."""
    d = str(tmp_path / "out")
    cfg = _sp_cfg(train_dir=d, eval_freq=2)
    mesh = make_mesh_2d(2, 4)
    state_full, _ = train_sp(cfg, mesh, steps=4, quiet=True)

    from draco_tpu.utils import checkpoint as ckpt

    assert ckpt.available_steps(d) == [2, 4]
    cfg_resume = _sp_cfg(train_dir=d, eval_freq=2, checkpoint_step=2)
    state_res, _ = train_sp(cfg_resume, mesh, steps=2, quiet=True)
    assert int(state_res.step) == int(state_full.step)
    a = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_res.params)])
    b = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_full.params)])
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_transformer_rejected_on_image_paths():
    from draco_tpu.models import build_model

    with pytest.raises(ValueError, match="token model"):
        build_model("TransformerLM")


def test_config_validates_transformer_knobs():
    with pytest.raises(ValueError, match="divisible"):
        _sp_cfg(model_dim=48, model_heads=5).validate()
    with pytest.raises(ValueError, match="rotary"):
        _sp_cfg(model_dim=6, model_heads=2).validate()
    with pytest.raises(ValueError, match="maj_vote"):
        _sp_cfg(approach="maj_vote").validate()
    with pytest.raises(ValueError, match="seq_shards"):
        TrainConfig(network="LeNet", seq_shards=2).validate()


def test_sp_bf16_matches_trajectory_loosely():
    """bf16 compute must train: loss decreases and stays finite on the
    2-D (w × sp) mesh with ring attention."""
    import numpy as np

    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import train_sp

    cfg = _sp_cfg(compute_dtype="bfloat16", max_steps=10)
    mesh = make_mesh_2d(cfg.num_workers, cfg.seq_shards)
    state, metrics = train_sp(cfg, mesh, steps=10, quiet=True)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("sp,causal", [(2, True), (4, True), (4, False)])
def test_a2a_attention_matches_dense(rng, sp, causal):
    from draco_tpu.parallel import a2a_attention

    q, k, v = _qkv(rng, t=32, h=4)
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    a2a = shard_map(
        functools.partial(a2a_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    out = jax.jit(a2a)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), _softmax_attn(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def test_a2a_attention_gradient_matches_dense(rng):
    """The all_to_all transpose routing: d/dq,k,v through the head-scatter
    layout swap must equal dense attention's gradients."""
    from draco_tpu.parallel import a2a_attention

    q, k, v = _qkv(rng, t=16, h=4)
    sp = 4
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))

    def a2a_scalar(q, k, v):
        f = shard_map(
            functools.partial(a2a_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
        return jnp.sum(jnp.sin(f(q, k, v)))

    def dense_scalar(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=True)))

    g_a2a = jax.jit(jax.grad(a2a_scalar, argnums=(0, 1, 2)))(
        *map(jnp.asarray, (q, k, v)))
    g_dense = jax.jit(jax.grad(dense_scalar, argnums=(0, 1, 2)))(
        *map(jnp.asarray, (q, k, v)))
    for ga, gd in zip(g_a2a, g_dense):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gd), rtol=1e-4, atol=1e-5)


def test_sp_a2a_matches_ring_trajectory():
    """sp_attn=a2a and sp_attn=ring compute the same exact attention, so the
    whole coded-SP training trajectory must agree (f32 tolerance)."""
    cfg_r = _sp_cfg(sp_attn="ring", model_heads=4)
    cfg_a = _sp_cfg(sp_attn="a2a", model_heads=4)
    mesh = make_mesh_2d(2, 4)
    state_r, m_r = train_sp(cfg_r, mesh, steps=3, quiet=True)
    state_a, m_a = train_sp(cfg_a, mesh, steps=3, quiet=True)
    np.testing.assert_allclose(float(m_a["loss"]), float(m_r["loss"]), rtol=1e-4)
    flat_r = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_r.params)])
    flat_a = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state_a.params)])
    np.testing.assert_allclose(flat_a, flat_r, rtol=1e-3, atol=1e-5)


def test_a2a_head_divisibility_validated():
    with pytest.raises(ValueError, match="model_heads"):
        _sp_cfg(sp_attn="a2a", seq_shards=4, model_heads=3,
                model_dim=36).validate()


def test_sp_worker_folding_matches_full_mesh():
    """num_workers=4 folded onto a (w=2 × sp=2) mesh (2 vmapped lanes per
    device) must reproduce the full (w=4 × sp=2) mesh trajectory — the
    worker-folding discipline tp_step already has, extended to sp so a
    single chip can run the n-lane coded SP step (advisor r2)."""
    cfg = _sp_cfg(num_workers=4, seq_shards=2)
    state_full, m_full = train_sp(cfg, make_mesh_2d(4, 2), steps=3, quiet=True)
    state_fold, m_fold = train_sp(cfg, make_mesh_2d(2, 2), steps=3, quiet=True)

    np.testing.assert_allclose(float(m_fold["loss"]), float(m_full["loss"]),
                               rtol=1e-4)
    flat_full = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(state_full.params)])
    flat_fold = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(state_fold.params)])
    np.testing.assert_allclose(flat_fold, flat_full, rtol=1e-3, atol=1e-5)


def test_sp_cyclic_simulate_matches_shared():
    """Reference-parity r× redundant compute under sequence parallelism:
    redundancy='simulate' (each worker evaluates its 2s+1 assigned rows,
    sequence-sharded) must match the 'shared' fast path trajectory; one
    live rev_grad adversary is decoded away in both. n=8 workers fold onto
    the (w=4 × sp=2) mesh."""
    kw = dict(num_workers=8, seq_shards=2, approach="cyclic", worker_fail=1,
              err_mode="rev_grad")
    mesh = make_mesh_2d(4, 2)
    st_sim, m_sim = train_sp(_sp_cfg(redundancy="simulate", **kw), mesh,
                             steps=3, quiet=True)
    st_sh, m_sh = train_sp(_sp_cfg(redundancy="shared", **kw), mesh,
                           steps=3, quiet=True)
    np.testing.assert_allclose(float(m_sim["loss"]), float(m_sh["loss"]),
                               rtol=1e-4)
    flat_sim = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(st_sim.params)])
    flat_sh = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(st_sh.params)])
    np.testing.assert_allclose(flat_sim, flat_sh, rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# ring + flash composition (ring_flash_attention)
# ---------------------------------------------------------------------------

def _flash_inner():
    from draco_tpu.ops.flash_attention import flash_attention_with_lse

    return functools.partial(flash_attention_with_lse, force=True,
                             interpret=True)


@pytest.mark.parametrize("sp,causal", [(4, True), (4, False), (8, True)])
def test_ring_flash_matches_dense(rng, sp, causal):
    """The blockwise kernel as the ring inner (causal self hop, unmasked
    past hops, cond-skipped future hops, lse-weighted merge) must equal
    full-sequence softmax attention."""
    from draco_tpu.parallel.ring_attention import ring_flash_attention

    q, k, v = _qkv(rng, t=8 * sp)  # T_local = 8: the kernel's sublane tile
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    ring = shard_map(
        functools.partial(ring_flash_attention, axis_name="sp", causal=causal,
                          attn_with_lse=_flash_inner()),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    out = jax.jit(ring)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), _softmax_attn(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def test_ring_flash_gradient_matches_dense(rng):
    """Grad flows through the lse merge (the kernels' dlse backward term)
    and the cond-skipped hops; must equal dense attention's gradient."""
    from draco_tpu.parallel.ring_attention import ring_flash_attention

    q, k, v = _qkv(rng, t=32)
    sp = 4
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))

    def ring_scalar(q, k, v):
        f = shard_map(
            functools.partial(ring_flash_attention, axis_name="sp",
                              causal=True, attn_with_lse=_flash_inner()),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
        return jnp.sum(jnp.sin(f(q, k, v)))

    def dense_scalar(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=True)))

    g_ring = jax.jit(jax.grad(ring_scalar, argnums=(0, 1, 2)))(
        *map(jnp.asarray, (q, k, v)))
    g_dense = jax.jit(jax.grad(dense_scalar, argnums=(0, 1, 2)))(
        *map(jnp.asarray, (q, k, v)))
    for name, gr, gd in zip("qkv", g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), rtol=1e-4,
                                   atol=1e-5, err_msg=f"d{name}")
