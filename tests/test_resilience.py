"""Resilience layer (draco_tpu/resilience, ISSUE 6): deterministic fault
injection, the in-graph step guard, prefetcher supervision, checkpoint
hardening, and the preemption round trip.

The load-bearing claims:

* the guard is bitwise-TRANSPARENT on clean runs (guards-enabled params ==
  unguarded params; the flipped equivalence suites additionally pin
  guard_trips == 0 under live adversaries + stragglers);
* each injected fault class ends in a classified outcome — masked, guarded
  skip, named error, or resumable preemption — never a hang or an unnamed
  traceback (the committed ``baselines_out/chaos_matrix.json`` pins the
  full fault × loop matrix; the cnn_k4 mini-matrix re-runs live here).
"""

import json
import os

import jax
import numpy as np
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.data.datasets import load_dataset
from draco_tpu.resilience import (
    FaultPlan,
    InjectedFaultError,
    SupervisedPrefetcher,
    plan_from_cfg,
    restore_with_walkback,
)
from draco_tpu.resilience.faults import apply_over_budget, apply_straggle
from draco_tpu.runtime import make_mesh
from draco_tpu.training.trainer import Trainer
from draco_tpu.utils import checkpoint as ckpt


@pytest.fixture(scope="module")
def ds():
    return load_dataset("synthetic-mnist", synthetic_train=256,
                        synthetic_test=64)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def make_cfg(**kw):
    base = dict(
        network="FC", dataset="synthetic-mnist", batch_size=4, lr=0.05,
        num_workers=8, approach="cyclic", worker_fail=1, redundancy="shared",
        err_mode="rev_grad", max_steps=4, eval_freq=0, train_dir="",
        log_every=1, compile_guard="raise", step_guard="on",
        compress_ckpt=True,
    )
    base.update(kw)
    return TrainConfig(**base)


def run_trainer(ds, mesh, tmp=None, **kw):
    tr = Trainer(make_cfg(**kw, train_dir=str(tmp) if tmp else ""),
                 mesh=mesh, dataset=ds, quiet=True)
    try:
        tr.run()
    finally:
        tr.close()
    return tr


def params_vec(tr):
    return np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr.state.params))]
    )


def records(tmp):
    return [json.loads(l) for l in open(os.path.join(str(tmp),
                                                     "metrics.jsonl"))]


def status(tmp):
    return json.load(open(os.path.join(str(tmp), "status.json")))


# --------------------------------------------------------------------------
# fault plan: grammar + seeded determinism (the attacks.py discipline)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_fault_plan_parse_grammar_and_determinism():
    p1 = FaultPlan.parse("nan_grad@5,inf_grad@6:w3,prefetch_hang@2:d7,"
                         "sigterm@9", 428, 8)
    p2 = FaultPlan.parse("nan_grad@5,inf_grad@6:w3,prefetch_hang@2:d7,"
                         "sigterm@9", 428, 8)
    assert p1 == p2  # same seed => bit-identical plan (frozen dataclasses)
    kinds = [e.kind for e in p1.events]
    assert kinds == ["nan_grad", "inf_grad", "prefetch_hang", "sigterm"]
    nan = p1.events[0]
    assert 0 <= nan.worker < 8  # seeded draw, in range
    assert FaultPlan.parse("nan_grad@5", 428, 8).events[0].worker \
        == nan.worker  # ...and stable across parses
    assert p1.events[1].worker == 3  # explicit :wN wins
    assert p1.events[2].duration_s == 7.0
    # a different seed moves the seeded worker draw eventually; the plan
    # stays valid either way
    assert FaultPlan.parse("nan_grad@5", 1, 8).events[0].worker is not None
    for bad in ("what@3", "nan_grad@0", "nan_grad@2:w9", "nan_grad"):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad, 428, 8)


@pytest.mark.core
def test_fault_plan_episode_grammar_windows_and_recurrence():
    """ISSUE 14 satellite: windowed/recurring specs — ``kind@a-b`` with
    optional ``:every<k>`` stride — parse, validate, expand to the right
    occurrence sets, and round-trip through ``FaultPlan.spec()``."""
    p = FaultPlan.parse(
        "straggle@20-60:w3:d4:every10,adversary@5-40:w2,nan_grad@8-10:w1",
        428, 8)
    churn, adv, nan = p.events
    assert list(churn.occurrences(1, 100)) == [20, 30, 40, 50, 60]
    assert list(churn.occurrences(35, 100)) == [40, 50, 60]
    assert adv.every == 1 and list(adv.occurrences(38, 39)) == [38, 39]
    assert nan.occurs_at(9) and not nan.occurs_at(11)
    # round-trip: spec() is canonical (workers pinned explicit) and
    # re-parsing reproduces the exact plan
    assert p.spec() == ("straggle@20-60:w3:d4:every10,adversary@5-40:w2,"
                        "nan_grad@8-10:w1")
    assert FaultPlan.parse(p.spec(), 428, 8) == p
    # seeded-draw workers become explicit on the way out, and stay stable
    q = FaultPlan.parse("straggle@5-9", 428, 8)
    assert f":w{q.events[0].worker}" in q.spec()
    assert FaultPlan.parse(q.spec(), 428, 8) == q
    # parse-time validation: inverted windows, strides without a window,
    # windows on one-checkpoint kinds, fractional step dwell
    for bad in ("nan_grad@9-5", "sigterm@5:every2", "ckpt_corrupt@5-9",
                "straggle@5-9:d1.5", "adversary@5:d0.5",
                "straggle@5-9:every0"):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad, 428, 8)


@pytest.mark.core
def test_episode_schedule_application():
    """Windowed events land on the host schedules exactly: adversary
    episodes mark their window (within budget), windowed straggle is
    absent exactly DURING the window, recurring churn drops d steps per
    occurrence, and the point form stays sustained-to-the-end."""
    import numpy as np

    from draco_tpu.resilience import faults as fm

    plan = FaultPlan.parse(
        "adversary@5-8:w2,straggle@10-13:w4,straggle@20-28:w5:d2:every4,"
        "straggle@30:w6", 428, 8)
    adv = fm.apply_adversary(np.zeros((35, 8), bool), plan)
    assert sorted(adv[:, 2].nonzero()[0]) == [5, 6, 7, 8]
    st = fm.apply_straggle(None, plan, 8, 34)
    assert sorted(st[:, 4].nonzero()[0]) == [10, 11, 12, 13]  # window only
    assert sorted(st[:, 5].nonzero()[0]) == [20, 21, 24, 25, 28, 29]
    assert sorted(st[:, 6].nonzero()[0]) == [30, 31, 32, 33, 34]  # to end
    # config-level: approx rejects adversary-marking kinds
    with pytest.raises(ValueError, match="not expressible"):
        make_cfg(approach="approx", worker_fail=0, redundancy="shared",
                 fault_spec="adversary@5:w2").validate()
    # config.validate() surfaces parse errors at config time
    with pytest.raises(ValueError):
        make_cfg(fault_spec="bogus@1").validate()


@pytest.mark.core
def test_over_budget_schedule_mutation():
    adv = np.zeros((10, 8), dtype=bool)
    adv[:, 0] = True  # s=1 live adversary every step
    plan = plan_from_cfg(make_cfg(fault_spec="over_budget@4"))
    out = apply_over_budget(adv, plan, worker_fail=1)
    assert out[4].sum() == 2  # pushed to s+1, exactly at the event step
    assert all(out[t].sum() == 1 for t in range(10) if t != 4)
    assert adv[4].sum() == 1  # input never mutated
    out2 = apply_over_budget(adv, plan, worker_fail=1)
    np.testing.assert_array_equal(out, out2)  # seeded => deterministic
    assert apply_over_budget(adv, None, 1) is adv  # no plan => passthrough


@pytest.mark.core
def test_straggle_schedule_mutation():
    """``straggle`` events (ISSUE 8): sustained per-worker drops overlay
    the seeded straggler schedule — to the run's end without :d, for a
    dwell of :d steps with it; an existing schedule is copied, None
    materializes a fresh table, and no-straggle plans pass through."""
    plan = plan_from_cfg(make_cfg(
        approach="approx", worker_fail=0, code_redundancy=1.5,
        fault_spec="straggle@3:w2,straggle@6:w5:d2"))
    # None in: a fresh (n_steps + 1, n) table materializes
    out = apply_straggle(None, plan, num_workers=8, n_steps=10)
    assert out.shape == (11, 8)
    assert out[3:, 2].all() and not out[:3, 2].any()  # sustained to the end
    assert out[6:8, 5].all() and not out[8:, 5].any()  # dwell 2, recovers
    assert not out[:6, 5].any()
    # existing schedule: overlay, input never mutated
    base = np.zeros((11, 8), dtype=bool)
    base[:, 0] = True
    out2 = apply_straggle(base, plan, 8, 10)
    assert out2[:, 0].all() and out2[3:, 2].all()
    assert not base[:, 2].any()
    # passthrough without straggle events / without a plan
    p2 = plan_from_cfg(make_cfg(fault_spec="nan_grad@2"))
    assert apply_straggle(base, p2, 8, 10) is base
    assert apply_straggle(base, None, 8, 10) is base
    # an explicit :w beyond the worker count is a parse error
    with pytest.raises(ValueError, match="out of range"):
        FaultPlan.parse("straggle@3:w8", 428, 8)


def test_straggle_fault_end_to_end_bitwise(ds, mesh, tmp_path):
    """A straggle@3:w3:d2 fault on the approx family: worker 3's rows stop
    arriving for steps 3-4 and return at 5, in BOTH regimes bitwise —
    every record's residual sits under its bound, the absent worker is
    never accused, and the guard never trips (within-bound decode error
    is the family's normal operating state)."""
    from draco_tpu.obs.forensics import record_masks

    vecs = {}
    for k in (1, 4):
        d = tmp_path / f"straggle_k{k}"
        tr = run_trainer(ds, mesh, tmp=d, approach="approx", worker_fail=0,
                         code_redundancy=1.5, max_steps=6, steps_per_call=k,
                         fault_spec="straggle@3:w3:d2")
        vecs[k] = params_vec(tr)
        recs = [r for r in records(d) if "loss" in r]
        assert len(recs) == 6
        for r in recs:
            masks = record_masks(r, 8)
            assert masks["present"][3] == (r["step"] not in (3, 4))
            assert masks["accused"] == (False,) * 8
            assert r["decode_residual"] <= r["decode_residual_bound"] + 1e-5
            assert r["guard_trips"] == 0.0 and r["skipped_steps"] == 0.0
        st = status(d)
        assert st["state"] == "done"
        assert st["forensics"]["accused_total"] == 0
        assert st["forensics"]["trust"] == [1.0] * 8
    np.testing.assert_array_equal(vecs[1], vecs[4])


# --------------------------------------------------------------------------
# in-graph step guard
# --------------------------------------------------------------------------

@pytest.mark.core
def test_guard_clean_run_bitwise_transparent(ds, mesh):
    """Guard on vs off on a clean run (live adversary inside budget): final
    params bitwise-identical, guard columns present and all-zero."""
    import tempfile

    d = tempfile.mkdtemp()
    on = run_trainer(ds, mesh, tmp=d, step_guard="on")
    off = run_trainer(ds, mesh, step_guard="off")
    np.testing.assert_array_equal(params_vec(on), params_vec(off))
    recs = [r for r in records(d) if "loss" in r]
    assert recs and all(r["guard_trips"] == 0.0
                        and r["skipped_steps"] == 0.0 for r in recs)
    assert status(d)["guard"] == {"trips": 0.0, "skipped_steps": 0.0}
    assert status(d)["state"] == "done"


@pytest.mark.core
def test_nan_fault_guard_skips_and_training_continues(ds, mesh, tmp_path):
    """The core chaos smoke: a non-adversarial worker emits a NaN gradient
    mid-run. Unguarded, the decode is poisoned for good; guarded, exactly
    that step is skipped (branchless passthrough) and training continues
    finite — in BOTH regimes, bitwise-identically."""
    vecs = {}
    for k in (1, 3):
        d = tmp_path / f"k{k}"
        tr = run_trainer(ds, mesh, tmp=d, steps_per_call=k,
                         fault_spec="nan_grad@2")
        vecs[k] = params_vec(tr)
        assert np.all(np.isfinite(vecs[k]))
        per_step = {r["step"]: (r["guard_trips"], r["skipped_steps"])
                    for r in records(d) if "loss" in r}
        assert per_step[2][0] >= 1 and per_step[2][1] == 1.0
        assert all(v == (0.0, 0.0) for s, v in per_step.items() if s != 2)
        assert status(d)["state"] == "done"
        assert status(d)["guard"]["skipped_steps"] == 1.0
    np.testing.assert_array_equal(vecs[1], vecs[3])
    unguarded = run_trainer(ds, mesh, step_guard="off",
                            fault_spec="nan_grad@2")
    assert not np.all(np.isfinite(params_vec(unguarded)))


def test_over_budget_fault_guarded(ds, mesh, tmp_path):
    """Adversary count pushed past the s budget: the decode cannot certify
    the step (loud residual / located > s) and the guard skips it."""
    tr = run_trainer(ds, mesh, tmp=tmp_path, fault_spec="over_budget@3")
    assert np.all(np.isfinite(params_vec(tr)))
    per_step = {r["step"]: r["skipped_steps"]
                for r in records(tmp_path) if "loss" in r}
    assert per_step[3] == 1.0
    assert sum(per_step.values()) == 1.0


# --------------------------------------------------------------------------
# prefetcher: bounded waits, named stall, supervised restart
# --------------------------------------------------------------------------

def test_prefetch_stall_is_named_not_a_hang():
    """A hung worker thread surfaces as PrefetchStallError after the bounded
    queue wait — carrying the stalled request and the last tracer span —
    instead of blocking the main loop forever."""
    import time

    from draco_tpu.data.prefetch import (PrefetchStallError,
                                         TokenChunkPrefetcher)

    def gen(step):
        if step >= 3:
            time.sleep(5)  # the hang
        return np.zeros((2, 2), np.int32)

    p = TokenChunkPrefetcher(gen, timeout_s=0.2)
    try:
        p.get((1, 2), (3, 2))  # healthy cold gather, submit (3,2) to worker
        t0 = time.perf_counter()
        with pytest.raises(PrefetchStallError) as ei:
            p.get((3, 2))
        assert time.perf_counter() - t0 < 3.0  # bounded, not the sleep
        assert ei.value.request == (3, 2)
        assert ei.value.timeout_s == 0.2
        # close() after an observed stall must NOT join the hung worker
        t0 = time.perf_counter()
        p.close()
        assert time.perf_counter() - t0 < 1.0
    finally:
        p.abandon()

    # the cold-start path is bounded too: a persistently hung source must
    # not convert the supervisor's retry into an unbounded MAIN-thread hang
    p2 = TokenChunkPrefetcher(lambda step: time.sleep(5), timeout_s=0.2)
    try:
        t0 = time.perf_counter()
        with pytest.raises(PrefetchStallError):
            p2.get((3, 2))
        assert time.perf_counter() - t0 < 3.0
    finally:
        p2.abandon()


def test_prefetch_worker_exception_propagates_by_name():
    from draco_tpu.data.prefetch import TokenChunkPrefetcher

    def gen(step):
        if step == 3:
            raise InjectedFaultError("boom at 3")
        return np.zeros((2, 2), np.int32)

    p = TokenChunkPrefetcher(gen, timeout_s=5.0)
    try:
        p.get((1, 2), (3, 2))
        with pytest.raises(InjectedFaultError):
            p.get((3, 2))
    finally:
        p.abandon()


def test_supervised_prefetcher_restarts_bounded():
    class Flaky:
        """Fails its first `fail` gets across all instances, then works."""

        built = 0
        remaining = 2

        def __init__(self):
            type(self).built += 1
            self.depth = 0

        def get(self, key):
            if type(self).remaining > 0:
                type(self).remaining -= 1
                raise InjectedFaultError("transient")
            return ("ok", key)

        def close(self):
            pass

    Flaky.built, Flaky.remaining = 0, 2
    sup = SupervisedPrefetcher(Flaky, restarts=3, backoff_s=0.001)
    assert sup.get("x") == ("ok", "x")  # two restarts masked the fault
    assert sup.restarts_used == 2 and Flaky.built == 3

    Flaky.built, Flaky.remaining = 0, 2
    sup0 = SupervisedPrefetcher(Flaky, restarts=1, backoff_s=0.001)
    with pytest.raises(InjectedFaultError):  # bounded: original error wins
        sup0.get("x")


# --------------------------------------------------------------------------
# checkpoint hardening: checksum sidecar, named corruption, walk-back, GC
# --------------------------------------------------------------------------

def _fake_state():
    return {"w": np.arange(64, dtype=np.float32).reshape(8, 8),
            "b": np.ones((8,), np.float32)}


def _abstract(state):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state)


@pytest.mark.core
def test_dcg_corruption_is_named_with_checksums(tmp_path):
    d = str(tmp_path)
    state = _fake_state()
    path = ckpt.save(d, 1, state, compress=True)
    assert os.path.isfile(path + ".sha256")  # sidecar written
    ckpt.verify(d, 1)  # clean bytes verify
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ckpt.CheckpointCorruptError) as ei:
        ckpt.load(d, 1, _abstract(state))
    # named, with path + expected/actual checksum — never a struct.error
    assert ei.value.path == path
    assert ei.value.expected and ei.value.actual
    assert ei.value.expected != ei.value.actual


def test_dcg_truncation_is_named(tmp_path):
    d = str(tmp_path)
    state = _fake_state()
    path = ckpt.save(d, 1, state, compress=True)
    raw = open(path, "rb").read()
    # remove the sidecar to prove the structural walk alone catches the
    # truncation (old checkpoints predate sidecars)
    os.remove(path + ".sha256")
    open(path, "wb").write(raw[: len(raw) // 2])
    with pytest.raises(ckpt.CheckpointCorruptError, match="truncated"):
        ckpt.load(d, 1, _abstract(state))
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.verify(d, 1)


def test_torn_header_is_corrupt_and_walkback_survives(tmp_path):
    """A sidecar-less .dcg whose MAGIC bytes are torn classifies as
    CheckpointCorruptError (not a plain ValueError the walk-back would
    die on), and walk-back retries past it."""
    d = str(tmp_path)
    state = _fake_state()
    ckpt.save(d, 2, state, compress=True)
    path = ckpt.save(d, 4, state, compress=True)
    os.remove(path + ".sha256")  # pre-hardening checkpoint: no sidecar
    raw = bytearray(open(path, "rb").read())
    raw[0] ^= 0xFF  # torn magic
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ckpt.CheckpointCorruptError, match="magic"):
        ckpt.load(d, 4, _abstract(state))
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.verify(d, 4)
    _, step, skipped = restore_with_walkback(d, -1, _abstract(state))
    assert step == 2 and skipped[0][0] == 4


def test_resume_minus_one_empty_dir_starts_fresh(ds, mesh, tmp_path):
    """checkpoint_step=-1 against an empty train_dir (first incarnation
    under a restart controller) starts fresh instead of crash-looping on
    FileNotFoundError — and still matches the plain run bitwise."""
    plain = run_trainer(ds, mesh)
    fresh = run_trainer(ds, mesh, tmp=tmp_path / "empty",
                        checkpoint_step=-1)
    np.testing.assert_array_equal(params_vec(plain), params_vec(fresh))
    # an explicit positive step that is missing still errors
    with pytest.raises(FileNotFoundError):
        run_trainer(ds, mesh, tmp=tmp_path / "e2", checkpoint_step=7)


def test_terminal_states_do_not_leak_stale_keys(tmp_path):
    from draco_tpu.obs.heartbeat import RunHeartbeat

    hb = RunHeartbeat(str(tmp_path))
    hb.beat(3, 10)
    hb.terminal("preempted", cause="graceful stop on SIGTERM",
                resumable_step=3)
    out = hb.terminal("done")
    assert out["state"] == "done"
    assert "cause" not in out and "resumable_step" not in out
    assert out["step"] == 3  # run context survives


def test_restore_walkback_skips_corrupt_newest(tmp_path):
    d = str(tmp_path)
    state = _fake_state()
    ckpt.save(d, 2, state, compress=True)
    newer = {k: v + 1 for k, v in state.items()}
    path = ckpt.save(d, 4, newer, compress=True)
    raw = bytearray(open(path, "rb").read())
    raw[-5] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    loaded, step, skipped = restore_with_walkback(d, -1, _abstract(state))
    assert step == 2 and len(skipped) == 1 and skipped[0][0] == 4
    np.testing.assert_array_equal(loaded["w"], state["w"])
    # nothing loadable at all => the corruption error propagates
    raw2 = bytearray(open(os.path.join(d, "model_step_2.dcg"), "rb").read())
    raw2[-5] ^= 0xFF
    open(os.path.join(d, "model_step_2.dcg"), "wb").write(bytes(raw2))
    with pytest.raises(ckpt.CheckpointCorruptError):
        restore_with_walkback(d, -1, _abstract(state))


def test_keep_checkpoints_gc(tmp_path):
    d = str(tmp_path)
    state = _fake_state()
    for step in (1, 2, 3):
        ckpt.save(d, step, state, compress=True)  # keep=0: grows freely
    assert ckpt.available_steps(d) == [1, 2, 3]
    ckpt.save(d, 4, state, compress=True, keep=2)
    assert ckpt.available_steps(d) == [3, 4]
    assert not os.path.exists(os.path.join(d, "model_step_1.dcg.sha256"))
    # GC never deletes the newest, even at keep=1
    ckpt.save(d, 5, state, compress=True, keep=1)
    assert ckpt.available_steps(d) == [5]


# --------------------------------------------------------------------------
# terminal heartbeat states + SIGTERM round trip
# --------------------------------------------------------------------------

def test_crash_writes_terminal_status(ds, mesh, tmp_path):
    """An unsupervised injected prefetch crash escapes as the named error
    AND stamps status.json state=crashed with a one-line cause."""
    with pytest.raises(InjectedFaultError):
        run_trainer(ds, mesh, tmp=tmp_path, fault_spec="prefetch_crash@2",
                    prefetch_restarts=0)
    st = status(tmp_path)
    assert st["state"] == "crashed"
    assert "InjectedFaultError" in st["cause"]


def test_prefetch_crash_supervision_masks(ds, mesh, tmp_path):
    """With supervision on (the default), the same injected crash is fully
    masked: restart + deterministic re-gather reproduce the clean run
    bitwise."""
    clean = run_trainer(ds, mesh)
    tr = run_trainer(ds, mesh, tmp=tmp_path, fault_spec="prefetch_crash@2",
                     steps_per_call=2)
    np.testing.assert_array_equal(params_vec(clean), params_vec(tr))
    assert status(tmp_path)["state"] == "done"


def test_sigterm_resume_round_trip(ds, mesh, tmp_path):
    """SIGTERM mid-run: the loop stops at the boundary, snaps a resumable
    checkpoint, writes state=preempted — and resuming from it reproduces
    the uninterrupted run bitwise (the elasticity mechanism)."""
    clean = run_trainer(ds, mesh, eval_freq=2)
    d = tmp_path / "pre"
    run_trainer(ds, mesh, tmp=d, eval_freq=2, fault_spec="sigterm@2")
    st = status(d)
    assert st["state"] == "preempted"
    assert st["resumable_step"] == 2
    assert "SIGTERM" in st["cause"]
    assert ckpt.exists(str(d), 2)
    resumed = run_trainer(ds, mesh, tmp=d, eval_freq=2,
                          checkpoint_step=st["resumable_step"])
    np.testing.assert_array_equal(params_vec(clean), params_vec(resumed))
    assert status(d)["state"] == "done"


# --------------------------------------------------------------------------
# the fault × loop matrix: live cnn_k4 mini-matrix + the committed artifact
# --------------------------------------------------------------------------

def test_chaos_mini_matrix_cnn_k4(tmp_path):
    """Every fault class through the chunked CNN trainer via the real
    harness (tools/chaos_run.py): each cell classifies as masked / guarded
    / recovered / preempted_resumed — no hangs, no unnamed tracebacks."""
    from tools import chaos_run

    out = tmp_path / "chaos.json"
    rc = chaos_run.main(["--loops", "cnn_k4", "--out", str(out),
                         "--workdir", str(tmp_path / "work")])
    data = json.load(open(out))
    assert rc == 0, data
    assert data["all_ok"]
    # straggle is the approx family's cell (a sustained drop on an exact
    # code just re-tests the over_budget locator failure), the adversary
    # episode runs on the dedicated random-attack loops (cnn_rand_*,
    # ISSUE 14), and the drift episode on the autopilot wire-dial loop
    # (ap_wire_*, ISSUE 15), and a whole leaf group's drop on the tree
    # loops (tree_*, ISSUE 17) — every other fault class runs here
    assert {r["fault"] for r in data["rows"]} \
        == set(chaos_run.FAULTS) - {"straggle", "subtree_straggle"} \
        - set(chaos_run.RAND_FAULTS) - set(chaos_run.WIRE_FAULTS)
    outcomes = {r["fault"]: r["outcome"] for r in data["rows"]}
    assert outcomes["nan_grad"] == "guarded"
    assert outcomes["over_budget"] == "guarded"
    assert outcomes["prefetch_crash"] == "masked"
    assert outcomes["sigterm"] == "preempted_resumed"
    assert outcomes["ckpt_corrupt"] == "recovered_walkback"
    assert outcomes["ckpt_truncate"] == "recovered_walkback"


@pytest.mark.core
def test_committed_chaos_matrix_covers_every_fault_class():
    """The committed artifact (the full matrix: CNN + two LM routes, eager
    + chunked) shows every fault class handled — the perf_watch fold gates
    on any cell flipping."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "baselines_out", "chaos_matrix.json")
    data = json.load(open(path))
    assert data["all_ok"]
    from tools import chaos_run

    assert set(data["fault_classes"]) == set(chaos_run.FAULTS)
    assert all(v["ok"] for v in data["fault_classes"].values())
    loops = {r["loop"] for r in data["rows"]}
    # coded-DP trainer + >= 2 LM routes + the approx family (ISSUE 8),
    # eager and chunked regimes
    assert {"cnn_k1", "cnn_k4", "lm_k1", "lm_k4", "lm_tp_k4",
            "approx_k1", "approx_k4", "cnn_rand_k1", "cnn_rand_k4"} <= loops
    assert not any(r["outcome"] == "FAILED" for r in data["rows"])
    # the approx cells: straggle degrades boundedly (victim absent, never
    # accused, every residual within its bound), nan_grad stays guarded
    # AND attributed, sigterm still round-trips bitwise
    approx = {(r["loop"], r["fault"]): r for r in data["rows"]
              if r["loop"].startswith("approx")}
    for k in ("approx_k1", "approx_k4"):
        assert approx[(k, "straggle")]["outcome"] == "degraded_bounded"
        assert approx[(k, "straggle")]["never_accused"]
        assert approx[(k, "nan_grad")]["outcome"] == "guarded"
        assert approx[(k, "nan_grad")]["attributed"]
        assert approx[(k, "sigterm")]["outcome"] == "preempted_resumed"
    # the tree topology cells (ISSUE 17): sigterm round-trips on both tree
    # loops, and the subtree-straggle cell (an entire leaf group absent at
    # once) degrades boundedly with the straggle incident attributed to
    # exactly the victim group — none of them ever accused
    assert {"cnn_tree_k4", "approx_tree_k4"} <= loops
    tree = {(r["loop"], r["fault"]): r for r in data["rows"]
            if "_tree" in r["loop"]}
    assert tree[("cnn_tree_k4", "sigterm")]["outcome"] == \
        "preempted_resumed"
    assert tree[("approx_tree_k4", "sigterm")]["outcome"] == \
        "preempted_resumed"
    sub = tree[("approx_tree_k4", "subtree_straggle")]
    assert sub["outcome"] == "degraded_bounded"
    assert sub["never_accused"]
    assert sub["incident"]["raised"] == ["straggle"]
    # every committed cell carries an incident verdict with ok true
    # (obs/incidents.py, ISSUE 13): the expected incident type raised with
    # the right worker attribution, nothing spurious — and the attributed
    # fault classes really raised their attributed incident
    for r in data["rows"]:
        assert isinstance(r.get("incident"), dict), r
        assert r["incident"]["ok"], r
    for r in data["rows"]:
        if r["fault"] == "nan_grad":
            assert "nonfinite" in r["incident"]["raised"], r
        if r["fault"] == "over_budget":
            assert "guard" in r["incident"]["raised"], r
        if r["fault"] == "straggle":
            # the sustained drop raises the attributed straggle incident
            # (ISSUE 14 — the autopilot's dial-down evidence)
            assert r["incident"]["raised"] == ["straggle"], r
        if r["fault"] == "adversary":
            # the seeded random attack (ISSUE 14 satellite): detected,
            # attributed and excised — one within-budget step opens NO
            # incident (trust EW is the hysteresis)
            assert r["outcome"] == "attributed_excised", r
            assert r["attributed"] and r["detected"], r
            assert r["incident"]["raised"] == [], r
        if r["fault"] in ("sigterm", "ckpt_corrupt", "ckpt_truncate"):
            assert r["incident"]["raised"] == [], r
    # perf_watch folds the matrix: a masked->crashed flip gates nonzero
    from tools import perf_watch

    metrics = {}
    perf_watch.fold_chaos(root, metrics)
    assert metrics["chaos.all_ok"]["value"] == 1.0
    broken = {k: dict(v, value=0.0) if k.startswith("chaos.") else v
              for k, v in metrics.items()}
    report = perf_watch.compare(metrics, broken, {})
    assert not report["ok"]
    assert any(r["metric"].startswith("chaos.")
               for r in report["regressions"])
