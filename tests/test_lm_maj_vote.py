"""The repetition code on the token route (parallel/sp_step.py at
seq_shards == 1, parallel/token_loop.py): the loop feeds a group's members
the same rows, the lanes agree bitwise, ``aggregate_flat_grads`` injects on
the raw rows and votes. The attacked run reproduces the clean one exactly,
the vote names the adversary on every step, and the new network trains
through ``draco_tpu.cli`` coded and attacked."""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from draco_tpu.config import TrainConfig  # noqa: E402
from draco_tpu.parallel import make_mesh_2d  # noqa: E402
from draco_tpu.parallel.sp_step import build_sp_train_setup  # noqa: E402
from draco_tpu.parallel.token_loop import (  # noqa: E402
    run_token_loop, step_tokens,
)

TINY = os.path.join(ROOT, "benchmark", "testdata", "latent-moe-tiny.json")
with open(TINY) as fh:
    SPEC = json.load(fh)["train_config"]["model_spec"]
STEPS = 4


class _Rows:
    def __init__(self):
        self.rows = []

    def write(self, record):
        self.rows.append(dict(record))

    def flush(self):
        pass

    def close(self):
        pass


def _cfg(network, **kw):
    base = dict(network=network, dataset="synthetic-text", batch_size=2,
                num_workers=6, approach="maj_vote", group_size=3,
                worker_fail=1, err_mode="rev_grad", seq_len=32, vocab=64,
                lr=0.05, eval_freq=0, train_dir="", log_every=1,
                max_steps=STEPS)
    if network == "LatentMoeLM":
        base.update(model_spec=SPEC)
    else:
        base.update(model_dim=32, model_heads=2, model_layers=1)
    base.update(kw)
    return TrainConfig(**base).validate()


def _run(cfg):
    mesh = make_mesh_2d(cfg.num_workers, 1, jax.devices()[:1])
    rows = _Rows()
    state, _ = run_token_loop(build_sp_train_setup(cfg, mesh), cfg,
                              quiet=True, writer=rows)
    return jax.tree.map(np.asarray, state.params), rows.rows


@pytest.fixture(scope="module", params=["LatentMoeLM", "TransformerLM"])
def runs(request):
    attacked = _run(_cfg(request.param))
    clean = _run(_cfg(request.param, adversary_count=0))
    return attacked, clean


def test_a_groups_members_are_fed_the_same_rows():
    cfg = _cfg("TransformerLM")
    toks = step_tokens(cfg, 3)
    assert toks.shape == (6, 2, 32)
    for g in (0, 3):
        assert np.array_equal(toks[g], toks[g + 1])
        assert np.array_equal(toks[g], toks[g + 2])
    assert not np.array_equal(toks[0], toks[3])
    mine = step_tokens(cfg, 3, tokens=lambda step, rows: np.full(
        (rows, 2, 32), step, np.int32))
    assert mine.shape == (6, 2, 32) and (mine == 3).all()


def test_attacked_run_equals_the_clean_run(runs):
    (attacked, _), (clean, _) = runs
    for a, c in zip(jax.tree.leaves(attacked), jax.tree.leaves(clean)):
        assert np.array_equal(a, c)


def test_the_vote_names_the_adversary_on_every_step(runs):
    (_, rows), _ = runs
    assert [r["step"] for r in rows] == list(range(1, STEPS + 1))
    for r in rows:
        assert r["det_adv"] == r["det_tp"] == r["located_errors"] == 1.0
        assert r["flagged_groups"] == 1.0
        assert r["vote_agree"] == pytest.approx(5 / 6)
        assert np.isfinite(r["loss"])


def test_clean_lanes_are_bit_equal(runs):
    _, (_, rows) = runs
    for r in rows:
        assert r["vote_agree"] == 1.0
        assert r["det_adv"] == r["located_errors"] == 0.0


def test_every_logged_step_carries_the_loops_ledger(runs):
    (_, rows), _ = runs
    for r in rows:
        assert r["t_comp"] == pytest.approx(
            r["t_dispatch"] + r["t_wait"] + r["t_drain"], abs=2e-6)
        assert r["t_fetch"] > 0 and r["t_book"] >= 0
    assert rows[0]["t_book"] == 0.0


def test_the_new_network_reports_its_experts_counters():
    _, rows = _run(_cfg("LatentMoeLM", num_workers=3, max_steps=2))
    for r in rows:
        assert r["moe_dropped"] == 0.0
        assert r["moe_full_dispatch"] == 0.0
        assert 0 < r["moe_assignments_held"] <= 2 * 32 * 3 * 2
        assert r["moe_load_max_over_mean"] >= 1.0


def test_the_chunked_loop_runs_the_same_steps():
    """K = 2 through ``train_token_many``: the metric block's columns are
    the eager record's, the vote's and the experts' included."""
    cfg = _cfg("LatentMoeLM", num_workers=3)
    eager, rows1 = _run(cfg)
    chunked, rows2 = _run(_cfg("LatentMoeLM", num_workers=3,
                               steps_per_call=2))
    for a, c in zip(jax.tree.leaves(eager), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-7)
    names = {"loss", "vote_agree", "located_errors", "det_tp", "det_adv",
             "moe_assignments_held", "moe_dropped", "moe_full_dispatch"}
    assert names <= set(rows2[-1]) and names <= set(rows1[-1])
    assert rows2[-1]["det_tp"] == 1.0


@pytest.mark.parametrize("network", ["LatentMoeLM", "TransformerLM"])
def test_lanes_in_turn_train_the_same_as_side_by_side(network, monkeypatch):
    """The large side of ``sp_step.LANES_IN_TURN_BYTES`` at the tiny size:
    lanes in turn (``lax.map``), each layer rematerialised, the stack in
    tiles (a d that is no multiple of 1024, so the last tile is closed with
    zeros), the attack a row at a time, the fingerprints a block at a time
    — what the d = 425 M cell runs. Same verdicts, same training."""
    from draco_tpu.coding import repetition
    from draco_tpu.parallel import sp_step

    cfg = _cfg(network, num_workers=3, max_steps=3)
    side_by_side, rows1 = _run(cfg)
    monkeypatch.setattr(sp_step, "LANES_IN_TURN_BYTES", 0)
    monkeypatch.setattr(repetition, "FINGERPRINT_BLOCK", 2048)
    setup = build_sp_train_setup(cfg, make_mesh_2d(3, 1, jax.devices()[:1]))
    assert setup.dim % 1024  # the padded case
    in_turn, rows2 = _run(cfg)
    for a, b in zip(jax.tree.leaves(side_by_side), jax.tree.leaves(in_turn)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    for r1, r2 in zip(rows1, rows2):
        assert r2["det_adv"] == r2["det_tp"] == r2["located_errors"] == 1.0
        assert r2["vote_agree"] == r1["vote_agree"] == pytest.approx(2 / 3)
        assert r2["loss"] == pytest.approx(r1["loss"], rel=1e-5)


@pytest.mark.parametrize("layout", [(1, -1), (8, -1), (-1, 8, 128)])
def test_a_stack_with_rows_of_several_axes_votes_the_same(layout):
    """A large stack is kept (n, d / 1024, 8, 128) (sp_step.STACK_TILE): the
    tail attacks it a row at a time and the vote hashes it a block at a
    time; same bits, same verdict as the (n, d) stack."""
    import jax.numpy as jnp

    from draco_tpu.coding import repetition
    from draco_tpu.parallel.common import aggregate_flat_grads

    cfg = _cfg("TransformerLM")
    d = 8 * 640
    one = jax.random.normal(jax.random.key(1), (2, d))
    flat = jnp.repeat(one, 3, axis=0)  # two groups of three equal rows
    mask = jnp.asarray([False, True, False, False, False, True])
    step = jnp.asarray(5, jnp.int32)
    want, hw = aggregate_flat_grads(flat, mask, cfg, None, None, step=step)
    old = repetition.FINGERPRINT_BLOCK
    repetition.FINGERPRINT_BLOCK = 1024  # several blocks, the last clamped
    try:
        got, hg = aggregate_flat_grads(flat.reshape((6,) + layout), mask,
                                       cfg, None, None, step=step)
    finally:
        repetition.FINGERPRINT_BLOCK = old
    assert got.shape == (d,)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(want), np.asarray(one.mean(axis=0)))
    for key in ("flagged", "bad_rows"):
        assert np.array_equal(np.asarray(hg[key]), np.asarray(hw[key]))
    assert np.array_equal(np.asarray(hg["flagged"]), np.asarray(mask))
    assert float(hg["vote_agree"]) == float(hw["vote_agree"])


def test_cli_trains_the_new_network_coded_and_attacked(tmp_path):
    from draco_tpu import cli

    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps(SPEC))
    last = cli.main([
        "--network", "LatentMoeLM", "--model-spec", str(spec), "--dataset",
        "synthetic-text", "--approach", "maj_vote", "--num-workers", "3",
        "--group-size", "3", "--worker-fail", "1", "--err-mode", "rev_grad",
        "--batch-size", "2", "--seq-len", "32", "--vocab", "64",
        "--max-steps", "8", "--eval-freq", "0", "--train-dir", "", "--lr",
        "0.05", "--log-every", "1"])
    assert float(last["det_tp"]) == 1.0 and float(last["loss"]) < 4.2
