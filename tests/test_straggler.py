"""Straggler mitigation: erasure decoding, masked aggregation, present-aware
vote, and the end-to-end drop path.

The reference has no working straggler handling — its PS blocks until every
gradient arrives (baseline_master.py:112-116) and the tag-77 kill switch is
unreferenced (resnet_split.py:625-737, SURVEY.md §5.3). Here known-missing
workers are erasures: the cyclic code recovers the exact sum from any n-2s
present rows (one redundancy unit per erasure vs two per unknown error).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu import aggregation
from draco_tpu.coding import cyclic, repetition
from draco_tpu.config import TrainConfig


@pytest.fixture
def rng():
    return np.random.RandomState(11)


# --------------------------------------------------------------------------
# cyclic erasure decode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,s,missing", [
    (9, 2, (1,)), (9, 2, (0, 4)), (9, 2, (2, 5, 7)), (9, 2, (0, 3, 6, 8)),  # e <= 2s
    (7, 1, (6,)), (7, 1, (0, 3)),
])
def test_erasure_only_exact(n, s, missing, rng):
    code = cyclic.build_cyclic_code(n, s)
    d = 256
    batch_grads = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(batch_grads[code.batch_ids]))
    present = np.ones(n, dtype=bool)
    present[list(missing)] = False
    # missing rows arrive as zeros
    enc_re = jnp.asarray(np.asarray(enc_re) * present[:, None])
    enc_im = jnp.asarray(np.asarray(enc_im) * present[:, None])
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    dec, used = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf),
        present=jnp.asarray(present))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=2e-3, atol=2e-3)
    used = np.asarray(used)
    assert not used[list(missing)].any()
    assert used.sum() == n - 2 * s


@pytest.mark.parametrize("n,s,adv,missing", [(9, 2, (3,), (7,)), (11, 2, (0,), (5,))])
def test_joint_adversary_and_erasure(n, s, adv, missing, rng):
    """t adversaries + e erasures with t + e <= s: locator budget covers both."""
    from draco_tpu.attacks import inject_cyclic

    code = cyclic.build_cyclic_code(n, s)
    d = 256
    batch_grads = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(batch_grads[code.batch_ids]))
    adv_mask = np.zeros(n, dtype=bool)
    adv_mask[list(adv)] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv_mask), "rev_grad")
    present = np.ones(n, dtype=bool)
    present[list(missing)] = False
    enc_re = jnp.asarray(np.asarray(enc_re) * present[:, None])
    enc_im = jnp.asarray(np.asarray(enc_im) * present[:, None])
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    dec, used = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf),
        present=jnp.asarray(present))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=5e-3, atol=5e-3)
    used = np.asarray(used)
    assert not used[list(adv)].any()
    assert not used[list(missing)].any()


# --------------------------------------------------------------------------
# masked aggregation
# --------------------------------------------------------------------------

def test_masked_mean_matches_subset(rng):
    g = rng.randn(8, 33).astype(np.float32)
    present = np.array([1, 1, 0, 1, 1, 1, 0, 1], dtype=bool)
    out = aggregation.mean(jnp.asarray(g), present=jnp.asarray(present))
    np.testing.assert_allclose(np.asarray(out), g[present].mean(0), rtol=1e-5)


def test_masked_geomedian_matches_subset(rng):
    g = rng.randn(8, 17).astype(np.float32)
    present = np.array([1, 0, 1, 1, 1, 1, 1, 0], dtype=bool)
    out = aggregation.geometric_median(jnp.asarray(g), present=jnp.asarray(present))
    sub = aggregation.geometric_median(jnp.asarray(g[present]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(sub), atol=1e-4)


def test_masked_krum_never_picks_absent_or_adversary(rng):
    n, s = 8, 1
    g = rng.randn(n, 25).astype(np.float32)
    g[2] += 1000.0  # adversary
    present = np.ones(n, dtype=bool)
    present[5] = False
    g[5] = 7777.0  # garbage in an absent row must not matter
    out = aggregation.krum(jnp.asarray(g), s, present=jnp.asarray(present))
    picked = np.asarray(out)
    assert not np.allclose(picked, g[2])
    assert not np.allclose(picked, g[5])
    # picked row is one of the present honest rows
    assert any(np.allclose(picked, g[i]) for i in range(n) if present[i] and i != 2)


def test_masked_coord_median_under_colluding_attack(rng):
    """Stragglers AND colluders together: 2 absent rows + 2 strong-ipm
    colluders among 8 — coord-median over the present rows must stay with
    the honest cluster (the attack payload is a bitwise-shared outlier per
    coordinate once the fill rows are excluded)."""
    from draco_tpu import attacks

    g = (rng.randn(8, 33) * 0.01 + 1.0).astype(np.float32)
    adv = np.asarray(np.arange(8) < 2)
    present = np.array([1, 1, 1, 0, 1, 1, 0, 1], dtype=bool)
    attacked = attacks.inject_plain(jnp.asarray(g), jnp.asarray(adv), "ipm",
                                    magnitude=-800.0, n_mal=2)
    out = aggregation.coordinate_median(attacked,
                                        present=jnp.asarray(present))
    honest = g[present & ~adv]
    assert np.abs(np.asarray(out) - honest.mean(0)).max() < 0.05


def test_vote_with_absent_members(rng):
    code = repetition.build_repetition_code(6, 3)
    d = 19
    honest = rng.randn(2, d).astype(np.float32)
    rows = np.stack([honest[0]] * 3 + [honest[1]] * 3)
    rows[1] = -55.0  # adversary in group 0
    present = np.array([1, 1, 1, 1, 0, 1], dtype=bool)  # straggler in group 1
    out = repetition.majority_vote(code, jnp.asarray(rows),
                                   present=jnp.asarray(present))
    want = (honest[0] + honest[1]) / 2  # both groups still produce winners
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)


def test_vote_dead_group_renormalises(rng):
    code = repetition.build_repetition_code(6, 3)
    rows = np.stack([np.full(7, float(i // 3)) for i in range(6)]).astype(np.float32)
    present = np.array([0, 0, 0, 1, 1, 1], dtype=bool)  # group 0 fully absent
    out = repetition.majority_vote(code, jnp.asarray(rows),
                                   present=jnp.asarray(present))
    np.testing.assert_allclose(np.asarray(out), np.full(7, 1.0))


# --------------------------------------------------------------------------
# config budget validation
# --------------------------------------------------------------------------

def test_config_rejects_over_budget_cyclic():
    with pytest.raises(ValueError, match="straggler budget"):
        TrainConfig(approach="cyclic", num_workers=9, worker_fail=2,
                    straggle_mode="drop", straggle_count=5).validate()
    # e <= 2s erasure-only is fine when no adversaries are live
    TrainConfig(approach="cyclic", num_workers=9, worker_fail=2,
                adversary_count=0, straggle_mode="drop",
                straggle_count=4).validate()
    # joint regime t + e <= s
    TrainConfig(approach="cyclic", num_workers=9, worker_fail=2,
                adversary_count=1, straggle_mode="drop",
                straggle_count=1).validate()


def test_config_rejects_dead_group():
    with pytest.raises(ValueError, match="group_size"):
        TrainConfig(approach="maj_vote", num_workers=6, group_size=3,
                    straggle_mode="drop", straggle_count=3).validate()


# --------------------------------------------------------------------------
# end-to-end: training with stragglers
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_cyclic_trains_through_stragglers_and_attacks():
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    ds = load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)
    cfg = TrainConfig(
        network="LeNet", dataset="synthetic-mnist", batch_size=4,
        num_workers=9, approach="cyclic", worker_fail=2,
        adversary_count=1, err_mode="rev_grad",
        straggle_mode="drop", straggle_count=1,
        redundancy="shared", max_steps=25, eval_freq=0, train_dir="",
        log_every=1000,
    )
    tr = Trainer(cfg, mesh=make_mesh(9), dataset=ds, quiet=True)
    first = tr.run(max_steps=1)
    last = tr.run(max_steps=25)
    assert np.isfinite(last["loss"])
    assert last["loss"] < first["loss"]
    assert last["present"] == 8.0
    tr.close()


@pytest.mark.slow
def test_baseline_mean_with_stragglers():
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    ds = load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)
    cfg = TrainConfig(
        network="FC", dataset="synthetic-mnist", batch_size=4,
        num_workers=8, approach="baseline", mode="normal",
        straggle_mode="drop", straggle_count=2,
        max_steps=20, eval_freq=0, train_dir="", log_every=1000,
    )
    tr = Trainer(cfg, mesh=make_mesh(8), dataset=ds, quiet=True)
    first = tr.run(max_steps=1)
    last = tr.run(max_steps=20)
    assert last["loss"] < first["loss"]
    tr.close()


def test_config_rejects_maj_vote_joint_budget():
    # one straggler + one adversary can land in the same size-3 group:
    # 3 - 1 = 2 present members, no honest majority over 1 adversary
    with pytest.raises(ValueError, match="joint budget"):
        TrainConfig(approach="maj_vote", num_workers=9, group_size=3,
                    worker_fail=1, straggle_mode="drop",
                    straggle_count=1).validate()
    # group_size=5 leaves 4 present > 2*1 — within budget
    TrainConfig(approach="maj_vote", num_workers=10, group_size=5,
                worker_fail=1, straggle_mode="drop",
                straggle_count=1).validate()


def test_config_rejects_krum_with_too_many_stragglers():
    with pytest.raises(ValueError, match="krum"):
        TrainConfig(approach="baseline", mode="krum", num_workers=8,
                    worker_fail=2, straggle_mode="drop",
                    straggle_count=4).validate()
    TrainConfig(approach="baseline", mode="krum", num_workers=8,
                worker_fail=2, straggle_mode="drop",
                straggle_count=3).validate()
