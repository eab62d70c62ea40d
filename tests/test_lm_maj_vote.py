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
from draco_tpu.ops.kda_rule import kda_runs_in_kernels  # noqa: E402
from draco_tpu.parallel import make_mesh_2d  # noqa: E402
from draco_tpu.parallel.sp_step import build_sp_train_setup  # noqa: E402
from draco_tpu.parallel.token_loop import (  # noqa: E402
    run_token_loop, step_tokens,
)

TINY = os.path.join(ROOT, "benchmark", "testdata", "latent-moe-tiny.json")
with open(TINY) as fh:
    SPEC = json.load(fh)["train_config"]["model_spec"]
SPECS = {"LatentMoeLM": SPEC}
for _network, _file in (("HybridMoeLM", "hybrid-moe-tiny.json"),
                        ("WindowedMoeLM", "windowed-moe-tiny.json"),
                        ("LoopedLM", "looped-tiny.json"),
                        ("ShortConvMoeLM", "conv-moe-tiny.json"),
                        ("KdaMoeLM", "kda-moe-tiny.json")):
    with open(os.path.join(ROOT, "benchmark", "testdata", _file)) as fh:
        SPECS[_network] = json.load(fh)["train_config"]["model_spec"]
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
    if network in SPECS:
        base.update(model_spec=SPECS[network])
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


@pytest.fixture(scope="module", params=["LatentMoeLM", "TransformerLM",
                                        "HybridMoeLM", "WindowedMoeLM",
                                        "LoopedLM", "ShortConvMoeLM",
                                        "KdaMoeLM"])
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


def test_the_looped_network_reports_its_exits_counters():
    _, rows = _run(_cfg("LoopedLM", num_workers=3, max_steps=3))
    for r in rows:
        assert r["loop_passes"] == SPECS["LoopedLM"]["total_ut_steps"] == 4
        assert 1.0 < r["exit_pass_mean"] < 4.0
        assert 0.0 < r["exit_entropy"] <= np.log(4) + 1e-6
        assert np.isfinite(r["exit_ce_first"])
        assert np.isfinite(r["exit_ce_last"])
    # the gate starts at a bias of zero: 1/2, 1/4, 1/8, 1/8
    assert rows[0]["exit_pass_mean"] == pytest.approx(1.875, abs=0.05)


def test_the_short_conv_networks_counters_ride_in_every_record(runs):
    """Of the fixture's networks the one with the convolution operators:
    its counters are in every record of the attacked run."""
    (_, rows), _ = runs
    if "short_conv_layers" not in rows[0]:
        assert "tied_head" not in rows[0]
        return
    for r in rows:
        assert r["short_conv_layers"] == 4.0 and r["tied_head"] == 1.0
        assert 0.0 < r["short_conv_absmax"] < 100.0
        assert r["moe_dropped"] == r["moe_full_dispatch"] == 0.0
        # two groups of three lanes, 2 x 32 tokens, top-2, four layers
        assert 0 < r["moe_assignments_held"] <= 2 * 32 * 2 * 4


def test_the_kda_networks_counters_ride_in_every_record(runs):
    """Of the fixture's networks the one with the per-channel rule: its
    counters are in every record of the attacked run, as stated."""
    (_, rows), _ = runs
    if "kda_layers" not in rows[0]:
        assert "heads_held" not in rows[0]
        return
    for r in rows:
        assert r["kda_layers"] == 4.0 and r["heads_held"] == 2.0
        # the KDA layers whose rule took the Pallas kernels
        # (ops/kda_rule.kda_runs_in_kernels): none off the chip
        assert r["kda_kernel_layers"] == 4.0 * kda_runs_in_kernels(
            (1, 64, 2, 128), (1, 64, 2, 128)) == 0.0
        assert 0.0 < r["kda_state_absmax"] < 100.0
        # 32-token rows: one chunk, closed with tokens that do not decay
        assert -1000.0 < r["kda_decay_min"] < 0.0
        assert r["moe_dropped"] == r["moe_full_dispatch"] == 0.0
        # two groups of three lanes, 2 x 32 tokens, top-3, four layers
        assert 0 < r["moe_assignments_held"] <= 2 * 32 * 3 * 4


def test_the_fused_head_trains_the_same_under_the_vote(monkeypatch):
    """The exits' rows cut into blocks (``HEAD_BLOCK_BYTES`` patched
    small), so the head takes its gradients in the forward pass (models/
    spec_lm.weighted_nll) on every lane: the counter says so, the lanes
    still agree bitwise, the vote names the adversary, and the losses are
    the whole-array form's to float32 rounding."""
    from draco_tpu.models import spec_lm

    cfg = _cfg("LoopedLM", num_workers=3, max_steps=3)
    _, whole = _run(cfg)
    # 64 rows a block: four exits of 2 x 32 rows are four blocks
    monkeypatch.setattr(spec_lm, "HEAD_BLOCK_BYTES", 64 * 4 * cfg.vocab)
    _, fused = _run(cfg)
    for a, b in zip(fused, whole):
        assert b["head_blocks_fused"] == 0.0 and a["head_blocks_fused"] == 4.0
        assert a["det_adv"] == a["det_tp"] == a["located_errors"] == 1.0
        assert a["vote_agree"] == pytest.approx(2 / 3)
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)
        assert a["exit_ce_last"] == pytest.approx(b["exit_ce_last"], rel=1e-5)


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


@pytest.mark.parametrize("network", ["LatentMoeLM", "TransformerLM",
                                     "HybridMoeLM", "WindowedMoeLM",
                                     "LoopedLM", "ShortConvMoeLM",
                                     "KdaMoeLM"])
def test_lanes_in_turn_train_the_same_as_side_by_side(network, monkeypatch):
    """The large side of ``sp_step.LANES_IN_TURN_BYTES`` at the tiny size:
    lanes in turn (``lax.map``), each layer rematerialised, the stack in
    tiles (a d that is no multiple of 1024, so the last tile is closed with
    zeros), attack, finite check and fingerprints in one sweep a block at a
    time, the leaves cut from the winner's row (here off whole lines: each
    from the lines that hold it) — what the d = 425 M cells run. Same
    verdicts, same training."""
    from draco_tpu.coding import repetition
    from draco_tpu.parallel import sp_step

    cfg = _cfg(network, num_workers=3, max_steps=3)
    side_by_side, rows1 = _run(cfg)
    monkeypatch.setattr(sp_step, "LANES_IN_TURN_BYTES", 0)
    monkeypatch.setattr(repetition, "FINGERPRINT_BLOCK", 2048)
    setup = build_sp_train_setup(cfg, make_mesh_2d(3, 1, jax.devices()[:1]))
    assert setup.dim % 1024  # the padded case
    # ... whose rows the lanes write into the stack in whole-line pieces
    assert setup.row_layout.zeros == -setup.dim % 1024
    assert setup.row_layout.joined_leaves > 1
    in_turn, rows2 = _run(cfg)
    for a, b in zip(jax.tree.leaves(side_by_side), jax.tree.leaves(in_turn)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    for r1, r2 in zip(rows1, rows2):
        assert r2["det_adv"] == r2["det_tp"] == r2["located_errors"] == 1.0
        assert r2["vote_agree"] == r1["vote_agree"] == pytest.approx(2 / 3)
        assert r2["loss"] == pytest.approx(r1["loss"], rel=1e-5)


# the published leaf tables: configuration file -> the layout facts of a
# lane's row (joined leaves, their elements, closing zeros, d)
PUBLISHED = {
    "kanana2": ("LatentMoeLM", "kanana-2-30b-a3b-ep16.json",
                (0, 0, 0, 424_961_024)),
    "mellum2": ("WindowedMoeLM", "mellum2-12b-a2.5b-ep8.json",
                (0, 0, 768, 340_349_184)),
    "qwen3next": ("HybridMoeLM", "qwen3-next-80b-a3b-ep32.json",
                  (2, 192, 960, 424_340_544)),
    "ouro": ("LoopedLM", "ouro-2.6b-l4.json", (2, 2049, 1023, 406_884_353)),
    "lfm2": ("ShortConvMoeLM", "lfm2-8b-a1b-ep4.json",
             (2, 128, 768, 507_820_288)),
    "kimilinear": ("KdaMoeLM", "kimi-linear-48b-a3b-ep32-tp2.json",
                   (1, 64, 192, 510_692_160)),
}


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("case", ["LatentMoeLM", "TransformerLM",
                                  "HybridMoeLM", "WindowedMoeLM",
                                  "LoopedLM", "ShortConvMoeLM", "KdaMoeLM",
                                  *PUBLISHED])
def test_a_lanes_row_is_written_in_whole_lines(case):
    """``sp_step._write_row``: the leaves cut into pieces that each start
    and end on a 128-wide line, each written into its range of the lane's
    row. At the tiny widths (leaves off the lines everywhere: long joined
    runs) the row is the padded flat ravel bit for bit, the other lanes'
    rows are not touched and ``unravel`` hands every leaf back; on the
    published leaf tables (shapes only) the recorded layout reads what the
    cells run: every leaf a piece of its own but qwen3next's two (3, 32)
    leaves and ouro's gate (a one-element bias and 2 048 weights), which
    close the row together with its zeros, and lfm2's two (1, 64) q/k norm
    weights, one line together; kimilinear's (4, 16) ``A_log`` closes its
    row with 192 zeros."""
    import jax.numpy as jnp

    from draco_tpu.models import build_lm
    from draco_tpu.parallel.sp_step import (
        STACK_LANES, _write_row, row_layout,
    )
    from draco_tpu.training.step import _flatten_tree, _make_unravel

    if case in PUBLISHED:
        network, file, facts = PUBLISHED[case]
        with open(os.path.join(ROOT, "benchmark", "configs", file)) as fh:
            spec = json.load(fh)["train_config"]["model_spec"]
        lm = build_lm(_cfg(network, model_spec=spec,
                           vocab=spec["vocab_rows"]))
        tree = jax.eval_shape(lm.init, jax.random.key(0))
    else:
        params = build_lm(_cfg(case)).init(jax.random.key(0))
        leaves, treedef = jax.tree.flatten(params)
        # a gradient's worth of bits in every leaf, a -0.0 among them
        tree = jax.tree.unflatten(treedef, [
            jax.random.normal(jax.random.key(i), x.shape).at[
                (0,) * x.ndim].set(-0.0) for i, x in enumerate(leaves)])
    unravel, dim, offsets = _make_unravel(tree)
    sizes = np.diff(offsets)
    layout = row_layout(sizes)
    # the pieces tile the leaves (and the zeros, one more leaf after them)
    # in order, and each ends on a line
    assert layout.zeros == -dim % (8 * STACK_LANES)
    assert layout.lines * STACK_LANES == dim + layout.zeros
    ends = [hi for _, hi in layout.pieces]
    assert [lo for lo, _ in layout.pieces] == [0] + ends[:-1]
    assert ends[-1] == len(sizes) + bool(layout.zeros)
    padded = np.cumsum(list(sizes) + [layout.zeros])
    assert all(padded[hi - 1] % STACK_LANES == 0 for hi in ends)
    stack = jax.ShapeDtypeStruct((2, layout.lines, STACK_LANES), jnp.float32)
    if case in PUBLISHED:
        assert (layout.joined_leaves, layout.joined_size, layout.zeros,
                dim) == facts
        out = jax.eval_shape(lambda s, t: _write_row(s, 1, t, layout),
                             stack, tree)
        assert (out.shape, out.dtype) == (stack.shape, stack.dtype)
        return
    assert layout.zeros and layout.joined_leaves > 1
    assert layout.joined_size == sum(
        sizes[lo:hi].sum() for lo, hi in layout.pieces if hi - lo > 1)
    got = jax.jit(lambda s, t: _write_row(s, 1, t, layout))(
        jnp.full(stack.shape, jnp.nan), tree)
    want = jnp.pad(_flatten_tree(tree), (0, layout.zeros)).reshape(
        -1, STACK_LANES)
    assert np.array_equal(_bits(got[1]), _bits(want))
    assert np.isnan(np.asarray(got[0])).all()
    for a, b in zip(jax.tree.leaves(unravel(got[1])), jax.tree.leaves(tree)):
        assert a.shape == b.shape and np.array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("layout", [(1, -1), (8, -1), (-1, 8, 128),
                                    (-1, 128)])
def test_a_stack_with_rows_of_several_axes_votes_the_same(layout):
    """A large stack is kept (n, d / 128, 128) (sp_step.STACK_LANES): the
    vote hashes it a block at a time, the attack applied to each block as
    read; same bits, same verdict as the (n, d) stack, and the winner comes
    back as the stack's rows are laid out."""
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
    assert got.shape == flat[0].reshape(layout).shape
    assert np.array_equal(np.asarray(got).reshape(-1), np.asarray(want))
    assert np.array_equal(np.asarray(want), np.asarray(one.mean(axis=0)))
    for key in ("flagged", "bad_rows"):
        assert np.array_equal(np.asarray(hg[key]), np.asarray(hw[key]))
    assert np.array_equal(np.asarray(hg["flagged"]), np.asarray(mask))
    assert float(hg["vote_agree"]) == float(hw["vote_agree"])


@pytest.mark.parametrize("network", ["LatentMoeLM", "LoopedLM"])
def test_cli_trains_the_new_network_coded_and_attacked(network, tmp_path):
    from draco_tpu import cli

    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps(SPECS[network]))
    last = cli.main([
        "--network", network, "--model-spec", str(spec), "--dataset",
        "synthetic-text", "--approach", "maj_vote", "--num-workers", "3",
        "--group-size", "3", "--worker-fail", "1", "--err-mode", "rev_grad",
        "--batch-size", "2", "--seq-len", "32", "--vocab", "64",
        "--max-steps", "8", "--eval-freq", "0", "--train-dir", "", "--lr",
        "0.05", "--log-every", "1"])
    assert float(last["det_tp"]) == 1.0 and float(last["loss"]) < 4.2


def _vote_both_ways(raw, mask, err_mode, groups, present, key,
                    method="fingerprint"):
    """The vote over ``raw`` with the adversary's rows attacked — lazily
    (``row_map``: the stack is read once, the attacked stack never stored)
    and over today's materialised stack (``attacks.inject_plain``) — and
    the fingerprint words and finite flags each side's sweep read."""
    import jax.numpy as jnp

    from draco_tpu import attacks
    from draco_tpu.coding import repetition

    code = repetition.build_repetition_code(raw.shape[0], raw.shape[0] // groups)
    lead = (slice(None),) + (None,) * (raw.ndim - 1)
    row_map = (lambda x: attacks.attack_plain(x, err_mode), mask)
    stored = jnp.where(mask[lead], attacks.attack_plain(raw, err_mode), raw)
    rows = (groups, code.r) + raw.shape[1:]
    lazy = repetition.majority_vote(code, raw, present=present, key=key,
                                    method=method, with_health=True,
                                    row_map=row_map)
    eager = repetition.majority_vote(code, stored, present=present, key=key,
                                     method=method, with_health=True)
    words_lazy = repetition._row_fingerprints(
        raw.reshape(rows), key, read=lambda b: jnp.where(
            mask.reshape(rows[:2] + (1,) * (raw.ndim - 1)),
            attacks.attack_plain(b, err_mode), b))
    words_eager = repetition._row_fingerprints(stored.reshape(rows), key)
    return lazy, eager, words_lazy, words_eager


@pytest.mark.parametrize("with_present", [False, True])
@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("err_mode", ["rev_grad", "constant"])
@pytest.mark.parametrize("layout", [(-1,), (-1, 8, 128), (-1, 128)])
def test_the_lazy_sweep_votes_as_the_stored_attack_bit_for_bit(
        layout, err_mode, groups, with_present, monkeypatch):
    """ISSUE 29: attack, finite check and fingerprints in ONE sweep of the
    stack against the attacked stack stored first — same ``voted``, same
    health, same two fingerprint words — over the (n, d) stack and the
    tiled ones (several blocks, the last clamped back), one group and
    several, with and without stragglers."""
    import jax.numpy as jnp

    from draco_tpu.coding import repetition

    monkeypatch.setattr(repetition, "FINGERPRINT_BLOCK", 3 * 1024)
    r, d = 3, 10 * 1024  # 10 tiles a row: blocks of 3, 3, 3 and a clamped 1
    n = r * groups
    one = jax.random.normal(jax.random.key(29), (groups, d))
    raw = jnp.repeat(one, r, axis=0).reshape((n,) + layout)
    mask = jnp.zeros((n,), bool).at[jnp.arange(groups) * r + 1].set(True)
    # a straggler in the last group, an honest one: its adversary ties the
    # group's one present honest member, and the lower index wins
    present = jnp.ones((n,), bool).at[n - 1].set(False) if with_present \
        else None
    (voted, hl), (want, he), wl, we = _vote_both_ways(
        raw, mask, err_mode, groups, present, jax.random.key(7))
    assert voted.shape == raw.shape[1:]
    assert np.array_equal(np.asarray(voted), np.asarray(want))
    for k in ("vote_agree", "flagged", "flagged_groups"):
        assert np.array_equal(np.asarray(hl[k]), np.asarray(he[k])), k
    # rows as computed on the lazy side; the stored attack wrote finite rows
    assert not np.asarray(hl["bad_rows"]).any()
    assert not np.asarray(he["bad_rows"]).any()
    for a, b in zip(wl[:2], we[:2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if present is None:
        assert np.array_equal(np.asarray(hl["flagged"]), np.asarray(mask))
        assert np.array_equal(np.asarray(voted).reshape(-1),
                              np.asarray(one.mean(axis=0)))


@pytest.mark.parametrize("method", ["fingerprint", "exact"])
@pytest.mark.parametrize("layout", [(-1,), (-1, 128)])
def test_an_adversarial_majority_wins_with_its_attacked_row(layout, method):
    """Two of three lanes attacked: they agree with each other, win the
    vote, and what comes back is the ATTACKED row bit for bit — the
    winner's row gets the map on its way out (the stack holds the raw
    one)."""
    import jax.numpy as jnp

    one = jax.random.normal(jax.random.key(3), (1, 4 * 1024))
    raw = jnp.repeat(one, 3, axis=0).reshape((3,) + layout)
    mask = jnp.asarray([True, False, True])
    (voted, hl), (want, he), _, _ = _vote_both_ways(
        raw, mask, "rev_grad", 1, None, None, method)
    assert np.array_equal(np.asarray(voted), np.asarray(want))
    assert np.array_equal(np.asarray(voted).reshape(-1),
                          np.asarray(-100.0 * one[0]))
    assert np.array_equal(np.asarray(hl["flagged"]), [False, True, False])
    assert np.array_equal(np.asarray(hl["flagged"]), np.asarray(he["flagged"]))


@pytest.mark.parametrize("layout", [(-1,), (-1, 128)])
def test_a_nan_under_a_constant_attack_is_still_named(layout):
    """``bad_rows`` are the rows AS COMPUTED (PR 28's order): a NaN in the
    adversary's raw row is named although the ``constant`` attack
    overwrites it before the fingerprints see the row — through the seam,
    on the lazy path and on the stored one (``vote_check="exact"`` keeps
    it)."""
    import jax.numpy as jnp

    from draco_tpu.parallel.common import _vote_row_map, aggregate_flat_grads

    one = jax.random.normal(jax.random.key(5), (1, 4 * 1024))
    raw = jnp.repeat(one, 3, axis=0).at[1, 77].set(jnp.nan)
    raw = raw.reshape((3,) + layout)
    mask = jnp.asarray([False, True, False])
    step = jnp.asarray(2, jnp.int32)
    out = {}
    for check in ("fingerprint", "exact"):
        cfg = _cfg("TransformerLM", num_workers=3, err_mode="constant",
                   vote_check=check)
        assert (_vote_row_map(cfg, mask) is None) == (check == "exact")
        out[check] = aggregate_flat_grads(raw, mask, cfg, None, None,
                                          step=step)
    for voted, health in out.values():
        assert np.array_equal(np.asarray(health["bad_rows"]), np.asarray(mask))
        assert np.array_equal(np.asarray(health["flagged"]), np.asarray(mask))
        assert np.array_equal(np.asarray(voted).reshape(-1),
                              np.asarray(one[0]))
