"""End-to-end SPMD training-step tests on the 8-device virtual mesh — the
integration layer the reference verified only by running real clusters
(SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.data.datasets import load_dataset
from draco_tpu.runtime import make_mesh
from draco_tpu.training.trainer import Trainer


def make_cfg(**kw):
    base = dict(
        network="LeNet",
        dataset="synthetic-mnist",
        batch_size=8,
        lr=0.01,
        momentum=0.9,
        num_workers=8,
        max_steps=30,
        eval_freq=0,
        train_dir="",
        log_every=1000,
    )
    base.update(kw)
    return TrainConfig(**base)


@pytest.fixture(scope="module")
def ds():
    return load_dataset("synthetic-mnist", synthetic_train=1024, synthetic_test=256)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def run_steps(cfg, ds, mesh, n):
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    first = None
    for step in range(1, n + 1):
        x, y = tr._device_batch(step)
        mask = jnp.asarray(tr._adv_schedule[step])
        tr.state, m = tr.setup.train_step(tr.state, x, y, mask)
        if first is None:
            first = {k: float(v) for k, v in m.items()}
    # (its state stays readable; an open Trainer keeps its compile watch
    # receiving events into every later file of the worker)
    tr.close()
    return tr, first, {k: float(v) for k, v in m.items()}


class TestBaseline:
    def test_loss_decreases(self, ds, mesh):
        tr, first, last = run_steps(make_cfg(), ds, mesh, 25)
        assert last["loss"] < first["loss"]

    def test_geomedian_resists_attack(self, ds, mesh):
        cfg = make_cfg(mode="geometric_median", worker_fail=2, err_mode="rev_grad",
                       max_steps=40)
        tr, first, last = run_steps(cfg, ds, mesh, 30)
        assert last["loss"] < first["loss"]

    def test_mean_destroyed_by_attack(self, ds, mesh):
        cfg = make_cfg(mode="normal", worker_fail=2, err_mode="rev_grad", lr=0.05)
        tr, first, last = run_steps(cfg, ds, mesh, 15)
        assert not (last["loss"] < first["loss"])  # diverges or NaN

    def test_krum_resists_attack(self, ds, mesh):
        cfg = make_cfg(mode="krum", worker_fail=2, err_mode="constant", max_steps=40)
        tr, first, last = run_steps(cfg, ds, mesh, 30)
        assert last["loss"] < first["loss"]


class TestMajVote:
    def test_vote_resists_one_adversary_per_step(self, ds, mesh):
        # 8 workers in 2 groups of 4 (honest majority everywhere). With only
        # 2 distinct batches per step the voted gradient is noisy, so a calmer
        # lr than the baseline tests.
        cfg = make_cfg(approach="maj_vote", group_size=4, worker_fail=1,
                       err_mode="rev_grad", max_steps=40)
        tr, first, last = run_steps(cfg, ds, mesh, 30)
        assert last["loss"] < first["loss"]

    @pytest.mark.parametrize("err_mode,group_size,wf", [
        ("rev_grad", 4, 1),   # reference attack, single adversary per group
        ("ipm", 4, 1),        # single omniscient adversary
        # both colluders in ONE group (group_size = n), sending bitwise-
        # identical ipm payloads — a 2-vs-6 minority the vote must discard
        # (the case where identical malicious rows could out-count honest
        # rows if the honest-majority budget were mis-checked). alie is
        # inert at n=8 (z <= 0, attacks.py warns) so ipm is the colluding
        # payload with teeth here.
        ("ipm", 8, 2),
    ])
    def test_vote_attacked_equals_clean(self, ds, mesh, err_mode, group_size,
                                        wf):
        """The filtered update must be *identical* to a no-adversary run —
        the strongest statement of vote correctness — for the reference
        attack and for colluding payloads that evade approximate rules."""
        params = {}
        for fail in (0, wf):
            cfg = make_cfg(approach="maj_vote", group_size=group_size,
                           worker_fail=fail, err_mode=err_mode, max_steps=8)
            tr, _, _ = run_steps(cfg, ds, mesh, 8)
            params[fail] = np.concatenate(
                [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr.state.params))]
            )
        np.testing.assert_array_equal(params[0], params[wf])

    def test_vote_equals_clean_mean_of_groups(self, ds, mesh):
        # with no adversaries, vote = mean over groups of the shared batch
        # gradient; training must track the plain run on the same group batches
        cfg = make_cfg(approach="maj_vote", group_size=2, worker_fail=0, max_steps=10)
        tr, first, last = run_steps(cfg, ds, mesh, 10)
        assert last["loss"] < first["loss"]


class TestCyclic:
    @pytest.mark.parametrize("redundancy", ["simulate", "shared"])
    def test_decodes_and_learns_under_attack(self, ds, mesh, redundancy):
        cfg = make_cfg(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                       redundancy=redundancy, max_steps=40)
        tr, first, last = run_steps(cfg, ds, mesh, 25)
        assert last["loss"] < first["loss"]
        # decode uses exactly n - 2s rows every step (n=8, s=1)
        assert last["honest_located"] == 6.0

    def test_simulate_and_shared_agree(self, ds, mesh):
        """The r× redundant path and the compute-once path must produce the
        same parameters — they are algebraically identical programs."""
        out = {}
        for red in ("simulate", "shared"):
            cfg = make_cfg(approach="cyclic", worker_fail=1, err_mode="constant",
                           redundancy=red, max_steps=6)
            tr, _, _ = run_steps(cfg, ds, mesh, 6)
            out[red] = jax.device_get(tr.state.params)
        flat_a = np.concatenate([np.ravel(x) for x in jax.tree.leaves(out["simulate"])])
        flat_b = np.concatenate([np.ravel(x) for x in jax.tree.leaves(out["shared"])])
        np.testing.assert_allclose(flat_a, flat_b, rtol=2e-3, atol=2e-5)

    def test_layer_granularity_agrees_with_global(self, ds, mesh):
        """decode_granularity=layer runs one locator per parameter tensor
        (reference: cyclic_master.py:125-129); with per-worker corruption it
        must land on the same honest set, hence the same parameters."""
        out = {}
        for gran in ("global", "layer"):
            cfg = make_cfg(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                           redundancy="shared", decode_granularity=gran,
                           max_steps=6)
            tr, _, last = run_steps(cfg, ds, mesh, 6)
            assert last["honest_located"] == 6.0
            out[gran] = jax.device_get(tr.state.params)
        flat_g = np.concatenate([np.ravel(x) for x in jax.tree.leaves(out["global"])])
        flat_l = np.concatenate([np.ravel(x) for x in jax.tree.leaves(out["layer"])])
        np.testing.assert_allclose(flat_g, flat_l, rtol=2e-3, atol=2e-5)

    def test_cyclic_matches_plain_mean_without_adversary(self, ds, mesh):
        """Decode of honest encodings == plain averaging of the same batches:
        run cyclic s=0... not allowed (s>=0 ok) — use s=1 with no actual
        corruption by err_mode=random (passthrough)."""
        cfg = make_cfg(approach="cyclic", worker_fail=1, err_mode="random",
                       redundancy="shared", max_steps=6)
        tr, first, last = run_steps(cfg, ds, mesh, 6)
        assert last["loss"] < first["loss"]


class TestBatchNormModel:
    def test_resnet_cyclic_smoke(self, mesh):
        ds = load_dataset("synthetic-cifar10", synthetic_train=256, synthetic_test=64)
        cfg = make_cfg(network="ResNet18", dataset="synthetic-cifar10", batch_size=2,
                       approach="cyclic", worker_fail=1, err_mode="rev_grad",
                       redundancy="shared", max_steps=4, lr=0.01)
        tr, first, last = run_steps(cfg, ds, mesh, 3)
        assert np.isfinite(last["loss"])
        assert last["honest_located"] == 6.0


class TestEvalAndCheckpoint:
    def test_eval_and_checkpoint_roundtrip(self, ds, mesh, tmp_path):
        cfg = make_cfg(max_steps=60, eval_freq=30, train_dir=str(tmp_path), log_every=30)
        tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
        tr.run()
        from draco_tpu.utils import checkpoint as ckpt

        assert ckpt.available_steps(str(tmp_path)) == [30, 60]
        rec = tr.evaluate(60)
        assert rec["prec1_test"] > 0.8  # synthetic blobs are easy

        # the ragged tail (256 % 100 = 56) must be scored, not dropped:
        # full-split eval in one batch == eval in uneven batches, exactly
        full = tr.evaluate(60, batch_size=len(ds.test_x))
        ragged = tr.evaluate(60, batch_size=100)
        assert ragged["prec1_test"] == pytest.approx(full["prec1_test"], abs=1e-6)
        assert ragged["prec5_test"] == pytest.approx(full["prec5_test"], abs=1e-6)

        # resume from a checkpoint and confirm the step counter fast-forwards
        cfg2 = make_cfg(max_steps=60, eval_freq=0, train_dir=str(tmp_path),
                        checkpoint_step=30)
        tr2 = Trainer(cfg2, mesh=mesh, dataset=ds, quiet=True)
        assert tr2._start_step == 31
        assert int(tr2.state.step) == 31
        tr.close()
        tr2.close()


def _write_idx(path, arr, magic):
    payload = magic.to_bytes(4, "big")
    for d in arr.shape:
        payload += int(d).to_bytes(4, "big")
    with open(path, "wb") as f:
        f.write(payload + arr.tobytes())


def test_real_format_data_end_to_end(tmp_path, mesh):
    """The NON-synthetic branch, end to end: idx-ubyte fixture files on disk
    -> load_dataset("MNIST") -> Trainer (cyclic, under attack) -> full-split
    evaluate. This is the reference's real-data path
    (src/util.py:23-66 -> training -> distributed_evaluator.py:92-110) run in
    CI, not just loader unit tests — the data is class-conditional uint8
    blobs, so learning is observable."""
    r = np.random.RandomState(11)
    protos = r.randint(0, 256, size=(10, 28, 28)).astype(np.int16)

    def make(n, salt):
        rr = np.random.RandomState(11 + salt)
        y = rr.randint(0, 10, size=n).astype(np.uint8)
        noise = rr.randint(-20, 21, size=(n, 28, 28))
        x = np.clip(protos[y] + noise, 0, 255).astype(np.uint8)
        return x, y

    tr_x, tr_y = make(512, 1)
    te_x, te_y = make(96, 2)  # 96 % 64 != 0: the eval tail is exercised too
    _write_idx(str(tmp_path / "train-images-idx3-ubyte"), tr_x, 0x00000803)
    _write_idx(str(tmp_path / "train-labels-idx1-ubyte"), tr_y, 0x00000801)
    _write_idx(str(tmp_path / "t10k-images-idx3-ubyte"), te_x, 0x00000803)
    _write_idx(str(tmp_path / "t10k-labels-idx1-ubyte"), te_y, 0x00000801)

    real_ds = load_dataset("MNIST", data_dir=str(tmp_path))
    assert not real_ds.synthetic and real_ds.name == "MNIST"

    cfg = make_cfg(dataset="MNIST", data_dir=str(tmp_path), batch_size=4,
                   approach="cyclic", worker_fail=1, err_mode="rev_grad",
                   redundancy="shared", max_steps=30, test_batch_size=64)
    tr = Trainer(cfg, mesh=mesh, dataset=real_ds, quiet=True)
    first = tr.run(max_steps=1)
    last = tr.run(max_steps=30)
    assert np.isfinite(last["loss"]) and last["loss"] < first["loss"]
    rec = tr.evaluate(30)
    assert rec["prec1_test"] > 0.6  # blobs are easy; attack is being decoded out
    tr.close()


def test_elastic_resume_across_topology_and_approach(tmp_path, ds):
    """Beyond the reference (whose PS blocks forever on a topology change and
    resumes from a hardcoded path, baseline_master.py:54-57): a checkpoint
    written by a cyclic n=8 run restores into a geo-median n=6 run.
    Params/opt state are replicated and topology-independent, so an operator
    can shrink the fleet or swap the aggregation rule mid-training."""
    cfg8 = make_cfg(num_workers=8, approach="cyclic", worker_fail=1,
                    err_mode="rev_grad", batch_size=4, max_steps=6,
                    eval_freq=3, train_dir=str(tmp_path))
    tr8 = Trainer(cfg8, mesh=make_mesh(8), dataset=ds, quiet=True)
    tr8.run()
    tr8.close()
    from draco_tpu.utils import checkpoint as ckpt_mod

    assert 6 in ckpt_mod.available_steps(str(tmp_path))
    saved = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr8.state.params))])

    cfg6 = make_cfg(num_workers=6, approach="baseline",
                    mode="geometric_median", worker_fail=1,
                    err_mode="rev_grad", batch_size=4, max_steps=10,
                    eval_freq=0, train_dir=str(tmp_path), checkpoint_step=6)
    tr6 = Trainer(cfg6, mesh=make_mesh(6), dataset=ds, quiet=True)
    restored = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr6.state.params))])
    np.testing.assert_array_equal(restored, saved)  # exact handoff
    last = tr6.run()
    tr6.close()
    assert int(tr6.state.step) == 11  # resumed at 7, ran through 10
    assert np.isfinite(last["loss"])


def test_resume_across_schedule_family_switch(tmp_path, ds, mesh):
    """A checkpoint written under lr_schedule=constant restores into a cosine
    run (and keeps training): the opt-state pytree is schedule-invariant
    (optim.build_optimizer routes every family through the same
    chain(rule, scale_by_schedule)) — end-to-end pin of the r3 advisor
    finding that a family switch used to fail or misrestore."""
    cfg_const = make_cfg(max_steps=6, eval_freq=3, train_dir=str(tmp_path))
    tr1 = Trainer(cfg_const, mesh=mesh, dataset=ds, quiet=True)
    tr1.run()
    tr1.close()
    saved = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr1.state.params))])

    cfg_cos = make_cfg(max_steps=12, eval_freq=0, train_dir=str(tmp_path),
                       checkpoint_step=6, lr_schedule="cosine",
                       warmup_steps=2)
    tr2 = Trainer(cfg_cos, mesh=mesh, dataset=ds, quiet=True)
    restored = np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr2.state.params))])
    np.testing.assert_array_equal(restored, saved)
    last = tr2.run()
    tr2.close()
    assert int(tr2.state.step) == 13 and np.isfinite(last["loss"])


def test_same_seed_training_is_bitwise_deterministic(ds, mesh):
    """SURVEY §5.2: SPMD removes the reference's MPI tag-race surface
    entirely; what remains to guarantee is determinism — two Trainer runs
    from the same seed must produce bitwise-identical parameters after
    several coded steps (the property the repetition vote's bitwise
    equality also rests on)."""
    cfg = make_cfg(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                   batch_size=4, max_steps=4)
    leaves = []
    for _ in range(2):
        tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
        tr.run()
        leaves.append(jax.tree.leaves(jax.device_get(tr.state.params)))
        tr.close()
    for a, b in zip(*leaves, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# one aggregation seam (ISSUE 28): the CNN step has no coded tail of its
# own — cases of one test, so each still counts
SEAM_CASES = {
    "cyclic-simulate": dict(approach="cyclic", worker_fail=1,
                            err_mode="rev_grad", redundancy="simulate"),
    "cyclic-shared": dict(approach="cyclic", worker_fail=1,
                          err_mode="rev_grad", redundancy="shared"),
    "maj_vote": dict(approach="maj_vote", group_size=4, worker_fail=1,
                     err_mode="rev_grad"),
    "baseline-geomedian": dict(approach="baseline", mode="geometric_median",
                               worker_fail=2, err_mode="rev_grad"),
}


@pytest.mark.parametrize("case", sorted(SEAM_CASES))
def test_step_update_is_the_seams_aggregate(case, ds, mesh):
    """The gradient the CNN step hands its optimizer IS
    ``parallel/common.aggregate_flat_grads`` of the per-worker stack: the
    rows are computed here, plainly (LeNet: no BatchNorm, no dropout, no
    augmentation), the seam is called directly on them, and the step's own
    aggregate is read back from the momentum buffer, which after a first
    step from zero holds exactly the gradient the optimizer was given.
    Trivially true while the step calls the seam; false the day someone
    gives ``training/step.py`` a tail of its own again."""
    from draco_tpu import rng as drng
    from draco_tpu.parallel.common import aggregate_flat_grads

    cfg = make_cfg(**SEAM_CASES[case])
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    setup, params = tr.setup, tr.state.params
    x, y = tr._device_batch(1)
    mask = jnp.asarray(tr._adv_schedule[1])

    def row_grad(xk, yk):
        def loss(p):
            logp = jax.nn.log_softmax(setup.model.apply({"params": p}, xk))
            return -jnp.mean(jnp.take_along_axis(logp, yk[:, None], axis=1))
        return jnp.concatenate(
            [g.reshape(-1) for g in jax.tree.leaves(jax.grad(loss)(params))])

    # (the rows and the seam each ONE compiled program: called eagerly
    # they are a dispatch and a tiny compile a primitive)
    stack = jax.jit(jax.vmap(row_grad))(x, y)  # (n, d): one row a batch
    if cfg.redundancy == "simulate" and cfg.approach == "cyclic":
        stack = stack[np.asarray(setup.code.batch_ids)]  # (n, hat_s, d)

    @jax.jit
    def seam(stack, mask, step):
        rand_factor = (drng.random_projection_factors_in_graph(
            cfg.seed, setup.dim) if cfg.approach == "cyclic" else None)
        return aggregate_flat_grads(stack, mask, cfg, setup.code,
                                    rand_factor, step=step, mesh=mesh)

    want, health = seam(stack, mask, tr.state.step)

    state, metrics = setup.train_step(tr.state, x, y, mask)
    shapes = [p.shape for p in jax.tree.leaves(params)]
    leaves = jax.tree.leaves(state.opt_state)
    at = next(i for i in range(len(leaves))
              if [l.shape for l in leaves[i:i + len(shapes)]] == shapes)
    got = np.concatenate([np.asarray(l).reshape(-1)
                          for l in leaves[at:at + len(shapes)]])
    scale = float(np.max(np.abs(np.asarray(want))))
    np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                               atol=1e-5 * scale)
    # and the step's health columns are the seam's health
    if cfg.approach == "cyclic":
        assert int(metrics["located_errors"]) == int(
            np.sum(np.asarray(health["flagged"]))) == cfg.num_adversaries
        assert int(metrics["honest_located"]) == int(
            np.sum(np.asarray(health["honest"])))
    elif cfg.approach == "maj_vote":
        assert int(metrics["located_errors"]) == int(
            np.sum(np.asarray(health["flagged"]))) == cfg.num_adversaries
        assert float(metrics["vote_agree"]) == float(health["vote_agree"])
    else:
        assert health is None and "located_errors" not in metrics
    tr.close()
