"""Scan-chunked trainer (cfg.steps_per_call > 1): bitwise equivalence with
the eager loop, chunk-boundary snapping, mid-chunk resume, vectorized range
batching, live schedules past the precomputed table, and the pre-r4
checkpoint format-break message.

The equivalence tests are the load-bearing ones: train_many is the SAME
coded step (fwd/bwd → encode → gather → decode → update) scan-chained K at
a time, so K ∈ {1, 4} must produce identical final parameters and an
identical metrics stream — under a live adversary AND a straggler-drop
schedule, for all three approaches. FC keeps the compiles cheap; nothing
here depends on the network.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from draco_tpu import rng as drng
from draco_tpu.config import TrainConfig
from draco_tpu.data import batching
from draco_tpu.data.datasets import load_dataset
from draco_tpu.runtime import make_mesh
from draco_tpu.training.trainer import Trainer


@pytest.fixture(scope="module")
def ds():
    return load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def make_cfg(**kw):
    base = dict(
        network="FC",
        dataset="synthetic-mnist",
        batch_size=4,
        lr=0.01,
        momentum=0.9,
        num_workers=8,
        max_steps=6,
        eval_freq=0,
        train_dir="",
        log_every=1,
        # strict compile sentinel (ISSUE 5): any steady-state recompilation
        # of a labelled program raises at the dispatch site, so every test
        # in this suite doubles as a 0-retrace assertion
        compile_guard="raise",
        # in-graph step guard enabled suite-wide (ISSUE 6): the guard must
        # be bitwise-transparent on clean runs — the equivalence tests
        # additionally pin guard_trips == 0 per record
        step_guard="on",
        # incident engine enabled suite-wide (ISSUE 13): host-side only,
        # so K∈{1,4} must stay bitwise with the watch ON and a clean run
        # must raise ZERO incidents (_assert_telemetry_artifacts)
        incident_watch="on",
    )
    base.update(kw)
    return TrainConfig(**base)


def params_vec(tr):
    return np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(tr.state.params))]
    )


def metric_stream(train_dir):
    """[(step, {metric: value})] from metrics.jsonl, timing keys dropped —
    the cross-loop-comparable part of the record stream."""
    out = []
    with open(os.path.join(train_dir, "metrics.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            if "loss" not in rec:
                continue  # eval records
            # every t_* key is a host clock (the eager loop's records also
            # carry t_comp's parts and t_book, utils/metrics.Segments), and
            # ``ahead`` says how that loop sent the step, not what it learnt
            vals = {k: v for k, v in rec.items()
                    if k not in ("time", "step", "ahead")
                    and not k.startswith("t_")}
            out.append((rec["step"], vals))
    return out


# --------------------------------------------------------------------------
# chunked vs eager equivalence — all three approaches, adversary + stragglers
# --------------------------------------------------------------------------

# the exact coded approaches run with the numerics observatory AND the
# bf16 shadow wire enabled suite-wide (obs/numerics.py, ISSUE 10): the
# watch must not perturb the f32 path — these very tests pin K∈{1,4}
# bitwise equality with it on — and _assert_decode_health pins the shadow
# columns (flag agreement 1.0, detection preserved under quantization)
# per record. baseline stays watch-free (no coded wire, no optional
# columns — PR 4); the approx family's watch coverage lives in the LM
# suite's tp/approx wire-study cells + tools/wire_study.py, keeping this
# suite's compile bill inside the tier-1 budget.
_WATCH = dict(numerics_watch="on", shadow_wire="bf16")

APPROACHES = {
    # n=9 so the cyclic joint budget t + e <= s holds with a LIVE adversary
    # and a straggler drop in the same run (s=2, t=1, e=1, n > 4s)
    "cyclic": dict(approach="cyclic", num_workers=9, worker_fail=2,
                   adversary_count=1, err_mode="rev_grad",
                   straggle_mode="drop", straggle_count=1,
                   redundancy="shared", **_WATCH),
    "maj_vote": dict(approach="maj_vote", group_size=4, worker_fail=1,
                     err_mode="rev_grad", straggle_mode="drop",
                     straggle_count=1, **_WATCH),
    "baseline": dict(approach="baseline", mode="geometric_median",
                     worker_fail=1, err_mode="rev_grad",
                     straggle_mode="drop", straggle_count=1),
    # the approximate family (ISSUE 8): no live adversary (config.validate
    # rejects one — no Byzantine certificate), two seeded drops per step
    # inside the ⌈αn⌉ = 2 design budget — the residual-vs-bound certificate
    # is asserted per record in _assert_decode_health
    "approx": dict(approach="approx", worker_fail=0, redundancy="shared",
                   code_redundancy=1.5, straggler_alpha=0.25,
                   straggle_mode="drop", straggle_count=2),
}


@pytest.mark.parametrize("approach", sorted(APPROACHES))
def test_chunked_equals_eager_bitwise(ds, approach, tmp_path):
    """Same final params AND same metrics stream for K=1 (eager loop) vs
    K=4 (scan-chunked, with a remainder chunk since 6 % 4 != 0) — run with
    the full telemetry spine enabled (trace_dir + heartbeat, ISSUE 4),
    which must not perturb either regime."""
    kw = APPROACHES[approach]
    mesh = make_mesh(kw.get("num_workers", 8))
    out = {}
    for k in (1, 4):
        d = str(tmp_path / f"{approach}_k{k}")
        tr = Trainer(make_cfg(**kw, steps_per_call=k, train_dir=d,
                              trace_dir=d),
                     mesh=mesh, dataset=ds, quiet=True)
        # the chunked run additionally captures a jax.profiler window
        # (ISSUE 9): the capture must observe, never perturb — metrics
        # stay bitwise-equal to the unprofiled eager run, still under
        # compile_guard="raise" with 0 steady retraces
        last = tr.run(profile_dir=(d if k == 4 else None))
        out[k] = (params_vec(tr), metric_stream(d), last)
        # the sentinel saw the run's compiles and zero steady-state
        # recompiles (compile_guard="raise" would already have failed the
        # dispatch — this pins the counter surface too)
        snap = tr.compile_watch.snapshot()
        assert snap["compiles"] >= 1 and snap["steady_recompiles"] == 0
        tr.close()
    np.testing.assert_array_equal(out[1][0], out[4][0])
    assert out[1][1] == out[4][1]  # identical per-step metric values
    assert [s for s, _ in out[4][1]] == list(range(1, 7))
    # the returned last-record agrees on the training metrics too
    for key in ("loss", "prec1", "present"):
        assert out[1][2][key] == out[4][2][key]
    _assert_decode_health(approach, out[4][1], kw)
    _assert_telemetry_artifacts(tmp_path / f"{approach}_k4", approach)


def test_approx_full_participation_matches_uncoded_mean(mesh):
    """With every worker present the approx decode IS the uncoded mean
    (v = 1 feasible ⇒ u = 1 ⇒ exact, coding/approx.py): one jitted
    train_step of approach='approx' from the shared seeded init lands
    allclose (f32 solve noise) to one step of the plain baseline mean on
    the SAME batch — the acceptance pin of ISSUE 8."""
    from draco_tpu.training.step import build_train_setup

    kw = dict(APPROACHES["approx"], straggle_mode="none", straggle_count=0)
    x = np.asarray(np.random.RandomState(5).rand(8, 4, 28, 28, 1),
                   np.float32)
    y = np.asarray(np.random.RandomState(6).randint(0, 10, (8, 4)),
                   np.int32)
    mask = np.zeros(8, dtype=bool)
    vecs = {}
    for name, akw in (("approx", kw),
                      ("baseline", dict(approach="baseline", mode="normal"))):
        setup = build_train_setup(make_cfg(**akw), mesh,
                                  dataset_name="synthetic-mnist")
        state, _ = setup.train_step(setup.state, jnp.asarray(x),
                                    jnp.asarray(y), jnp.asarray(mask))
        vecs[name] = np.concatenate([
            np.ravel(v) for v in jax.tree.leaves(jax.device_get(state.params))
        ])
    np.testing.assert_allclose(vecs["approx"], vecs["baseline"],
                               rtol=1e-5, atol=1e-6)


def _assert_decode_health(approach, stream, kw):
    """Decode-health columns (in-graph, ISSUE 4) on every train record:
    detection precision AND recall are 1.0 against the seeded adversary +
    straggler schedules — flagged set == live adversary set, step by step —
    and the cyclic residual sits at float noise (the exactness guarantee
    observable). The packed per-worker forensics masks (obs/forensics,
    ISSUE 7) pin the attribution EXACTLY: accused == adversarial ∧ present
    bit for bit (per-worker precision/recall 1.0 — an absent worker is
    never an accused worker). The baseline approach has no exactness
    certificate and must emit neither health nor forensics columns."""
    from draco_tpu.obs import forensics as fx

    n = kw.get("num_workers", 8)
    adv = drng.adversary_schedule(428, 6, n, kw.get("adversary_count",
                                                    kw["worker_fail"]))
    strag = drng.straggler_schedule(428, 6, n, kw["straggle_count"])
    for step, vals in stream:
        # guards enabled suite-wide: a clean run (adversary + stragglers
        # inside budget) never trips and never skips an update
        assert vals["guard_trips"] == 0.0, (step, vals)
        assert vals["skipped_steps"] == 0.0, (step, vals)
        if approach == "baseline":
            assert "det_tp" not in vals and "decode_residual" not in vals
            assert "wmask_accused0" not in vals
            assert "nx_wire_absmax" not in vals and "shadow_err" not in vals
            continue
        # numerics observatory + bf16 shadow wire (obs/numerics.py, ISSUE
        # 10) on the watch-enabled approaches: range stats sane and
        # finite, and quantization changes NO accusation — flag agreement
        # exactly 1.0 on every step, end-to-end shadow error at bf16
        # rounding scale
        if kw.get("shadow_wire"):
            assert vals["nx_wire_absmax"] > 0 and vals["nx_wire_rms"] > 0
            for stage in ("grad", "wire", "agg"):
                assert vals[f"nx_{stage}_nonfinite"] == 0.0, (step, stage)
                assert 0.0 <= vals[f"nx_{stage}_uf_int8"] <= 1.0
                assert 0.0 <= vals[f"nx_{stage}_of_bf16"] <= 1.0
            assert vals["shadow_flag_agree"] == 1.0, (step, vals)
            assert 0.0 <= vals["shadow_err"] < 0.05, (step, vals)
        if approach == "approx":
            # the residual-vs-bound certificate per record (ISSUE 8): the
            # measured decode error never exceeds the arrived support's
            # analytic optimal-decoding bound, and a full-participation
            # step decodes exactly (both sit at f32 noise)
            assert vals["decode_residual"] <= \
                vals["decode_residual_bound"] + 1e-5, (step, vals)
            if not strag[step].any():
                assert vals["decode_residual"] < 1e-4
                assert vals["decode_residual_bound"] < 1e-4
            assert 0.0 < vals["recovered_fraction"] <= 1.0
            # no located-error machinery at all on this family
            assert "det_tp" not in vals and "located_errors" not in vals
            masks = fx.record_masks(vals, n)
            assert masks is not None, (step, vals)
            assert masks["present"] == tuple(~strag[step]), step
            assert masks["adv"] == (False,) * n  # no live adversary
            # a scheduled straggler is NEVER an accused worker — the
            # family's whole accusation surface is the non-finite ingest
            # check, silent on clean runs
            assert masks["accused"] == (False,) * n, (step, masks)
            continue
        want = int((adv[step] & ~strag[step]).sum())  # detectable truth
        assert vals["det_adv"] == want, (step, vals)
        assert vals["det_tp"] == want  # recall = 1.0
        assert vals["located_errors"] == want  # precision = 1.0
        # detection P/R == 1.0 PRESERVED under the bf16 shadow (the ISSUE
        # 10 acceptance pin): the shadow flag set scores identically
        assert vals["shadow_det_flagged"] == want, (step, vals)
        assert vals["shadow_det_tp"] == want
        masks = fx.record_masks(vals, n)
        assert masks is not None, (step, vals)
        assert masks["adv"] == tuple(adv[step]), step
        assert masks["present"] == tuple(~strag[step]), step
        # per-worker attribution exact: accused == adversarial ∧ present
        assert masks["accused"] == tuple(adv[step] & ~strag[step]), (
            step, masks)
        if approach == "cyclic":
            assert vals["decode_residual"] < 1e-3
        else:
            pres = int((~strag[step]).sum())
            assert vals["vote_agree"] == pytest.approx((pres - want) / pres)
            assert vals["flagged_groups"] == (1 if want else 0)


def _assert_telemetry_artifacts(run_dir, approach):
    """The K=4 run is a 2-chunk CPU-mesh run (ranges (1,4),(5,2)): its
    trace.json must parse as Chrome trace events with the host phases,
    nested prefetcher spans and counter events, and status.json must report
    detection precision/recall 1.0 (cyclic/maj_vote)."""
    trace = json.load(open(run_dir / "trace.json"))
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"gather", "upload", "dispatch", "sync", "flush"} <= names
    assert len([e for e in spans if e["name"] == "dispatch"]) == 2  # 2 chunks
    for e in spans:
        assert {"ts", "dur", "pid", "tid"} <= set(e) and e["dur"] >= 0
    # prefetch spans nest inside the trainer's gather span (same thread)
    gathers = [e for e in spans if e["name"] == "gather"]
    inner = [e for e in spans if e["name"].startswith("prefetch.")]
    assert inner, names
    assert any(
        g["tid"] == i["tid"] and g["ts"] <= i["ts"]
        and i["ts"] + i["dur"] <= g["ts"] + g["dur"] + 1e-3
        for i in inner for g in gathers)
    assert any(e["ph"] == "C" for e in events)  # queue-depth counters
    status = json.load(open(run_dir / "status.json"))
    assert status["step"] == 6 and status["steps_per_s"] > 0
    assert np.isfinite(status["loss"])
    assert status["prefetch_depth"] in (0, 1)
    # the heartbeat surfaces the compile counters (ISSUE 5)
    assert status["compiles"] >= 1 and status["compile_s"] > 0
    assert status["steady_recompiles"] == 0
    # the incident engine (ISSUE 13) ran on every cell of this suite and a
    # CLEAN run — live adversary + stragglers all inside budget — raises
    # ZERO incidents: the no-flapping/no-false-positive contract, at the
    # same time the bitwise assertions above prove the watch perturbs
    # nothing. No event ever fired, so no incidents.jsonl exists either.
    inc = status["incidents"]
    assert inc["total"] == 0 and inc["open"] == [] and inc["by_type"] == {}
    assert not os.path.exists(run_dir / "incidents.jsonl")
    # ... and the compile ledger sits next to the trace, attributing the
    # chunked program's builds (main chunk k=4 + remainder k=2)
    ledger = [json.loads(l) for l in open(run_dir / "compiles.jsonl")]
    labels = {r["program"] for r in ledger if r["program"]}
    assert {"train_many[4]", "train_many[2]"} <= labels
    assert not any(r["steady_recompile"] for r in ledger)
    compile_events = [e for e in events if e.get("cat") == "compile"]
    assert len(compile_events) == len(ledger) == status["compiles"]
    # the static wire-bytes ledger (ISSUE 10) rides every status payload;
    # the folded numerics block only on watch-enabled runs (the coded
    # approaches here — baseline runs watch-free)
    wire = status["wire"]
    assert wire["family"] == APPROACHES[approach]["approach"]
    assert wire["bytes_per_worker"]["f32"] == \
        (2 if approach == "cyclic" else 1) * 4 * wire["dim"]
    assert wire["bytes_per_worker"]["bf16"] * 2 == \
        wire["bytes_per_worker"]["f32"]
    if approach == "baseline":
        assert "numerics" not in status
        assert "decode_health" not in status
        assert "forensics" not in status
    elif approach == "approx":
        # residual-vs-bound certificate in the heartbeat (ISSUE 8) — and
        # the forensics interplay pin: scheduled stragglers are erasures,
        # so NO accusations, NO episodes, and the trust vector never
        # decays (absence is not evidence; obs/forensics docstring)
        health = status["decode_health"]
        assert health["decode_residual"] <= \
            health["decode_residual_bound"] + 1e-5
        assert 0.0 < health["recovered_fraction"] <= 1.0
        fxb = status["forensics"]
        assert fxb["accused_total"] == 0 and fxb["episodes_total"] == 0
        assert fxb["top_suspects"] == []
        assert fxb["trust"] == [1.0] * 8
        assert status["schema"] == 5
    else:
        health = status["decode_health"]
        assert health["precision"] == 1.0 and health["recall"] == 1.0
        assert health["adv_total"] > 0  # the adversary was really live
        # the per-worker ledger (ISSUE 7): accusations exist, every accused
        # worker was truly adversarial (per-worker precision/recall 1.0),
        # and status carries the versioned schema
        fxb = status["forensics"]
        assert fxb["accused_total"] > 0 and fxb["episodes_total"] > 0
        assert fxb["top_suspects"] and all(
            t["trust"] < 1.0 for t in fxb["top_suspects"])
        assert status["schema"] == 5
        # the folded numerics block (ISSUE 10): worst-case shadow error
        # bounded, flag agreement never dipped below 1.0
        nx = status["numerics"]
        assert nx["shadow_flag_agree_min"] == 1.0
        assert 0.0 <= nx["shadow_err_max"] < 0.05
        assert nx["nx_wire_absmax"] > 0 and nx["nx_grad_nonfinite_max"] == 0.0
    # the profiled window's device block: the capture + anchor landed, the
    # window wrote the scope map of the train_many programs it dispatched
    # (obs/profiling.py) and the heartbeat folded the per-phase attribution
    # from the capture's xplane — the map covers what ran
    from draco_tpu.obs import device_attr

    assert device_attr.find_capture(str(run_dir)) is not None
    anchor = device_attr.load_anchor(str(run_dir))
    assert anchor is not None and anchor["steps_profiled"] == 6
    assert anchor["tracer_ts_us"] is not None  # shared-clock anchor stamped
    dev = status["device"]
    assert dev["profiled_steps"] == 6
    assert dev["total_device_us"] > 0
    assert sum(dev["phase_fracs"].values()) == pytest.approx(1.0, abs=2e-3)
    assert "error" not in dev, dev
    assert dev["attributed_frac"] > 0.9 and dev["decode_share"] > 0.0
    sm = device_attr.load_scope_map(str(run_dir))
    assert [p["module"] for p in sm["programs"]] == ["jit_many_body"]


@pytest.mark.core
def test_chunked_smoke_fast(ds, mesh):
    """Tier-1/core smoke: small FC model, K=3 with a remainder chunk,
    adversary on — the chunked loop trains and the loss moves."""
    cfg = make_cfg(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                   redundancy="shared", steps_per_call=3, max_steps=7,
                   log_every=1000)
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    first = tr.run(max_steps=1)  # remainder-sized chunk (k=1)
    last = tr.run()
    tr.close()
    assert np.isfinite(last["loss"])
    assert last["loss"] < first["loss"]
    assert last["step"] == 7
    assert last["honest_located"] == 6.0


def test_chunk_ranges_snap_to_eval_and_remainder(ds, mesh):
    """Chunk boundaries: eval_freq multiples and max_steps always end a
    chunk, chunks never exceed K, and the ranges tile [start, n] exactly."""
    tr = Trainer(make_cfg(steps_per_call=4, eval_freq=6, max_steps=15),
                 mesh=mesh, dataset=ds, quiet=True)
    ranges = tr._chunk_ranges(1, 15)
    assert ranges == [(1, 4), (5, 2), (7, 4), (11, 2), (13, 3)]
    flat = [s + i for s, k in ranges for i in range(k)]
    assert flat == list(range(1, 16))
    # resume mid-grid: first chunk shortens to the next boundary
    assert tr._chunk_ranges(5, 12) == [(5, 2), (7, 4), (11, 2)]
    tr.close()


def test_resume_from_checkpoint_mid_chunk(ds, mesh, tmp_path):
    """A K=4 run checkpoints at eval boundaries (3, 6, 9); resuming from
    step 3 — mid-chunk relative to the K grid — must land on the exact same
    parameters as the uninterrupted run."""
    base = dict(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                redundancy="shared", steps_per_call=4, max_steps=10,
                eval_freq=3, train_dir=str(tmp_path))
    t1 = Trainer(make_cfg(**base), mesh=mesh, dataset=ds, quiet=True)
    t1.run()
    v1 = params_vec(t1)
    t1.close()
    from draco_tpu.utils import checkpoint as ckpt

    assert ckpt.available_steps(str(tmp_path)) == [3, 6, 9]
    t2 = Trainer(make_cfg(**base, checkpoint_step=3), mesh=mesh, dataset=ds,
                 quiet=True)
    assert t2._start_step == 4
    t2.run()
    v2 = params_vec(t2)
    t2.close()
    np.testing.assert_array_equal(v1, v2)


# --------------------------------------------------------------------------
# vectorized range batching == per-step batching
# --------------------------------------------------------------------------

def test_range_indices_match_per_step():
    """Every *_range row must be bitwise identical to the per-step function —
    including across an epoch boundary (n_samples small vs the range)."""
    n, workers, bs, seed = 100, 4, 8, 428
    step0, k = 1, 9  # baseline bpe = 12: crosses no epoch; cyclic bpe = 3: crosses two
    got = batching.indices_baseline_range(n, step0, k, workers, bs, seed)
    want = np.stack([batching.indices_baseline(n, step0 + i, workers, bs, seed)
                     for i in range(k)])
    np.testing.assert_array_equal(got, want)

    seeds = drng.group_seeds(seed, 2)
    got = batching.indices_grouped_range(n, step0, k, workers, 2, bs, seeds)
    want = np.stack([batching.indices_grouped(n, step0 + i, workers, 2, bs, seeds)
                     for i in range(k)])
    np.testing.assert_array_equal(got, want)

    got = batching.indices_cyclic_range(n, step0, k, workers, bs, seed)
    want = np.stack([batching.indices_cyclic(n, step0 + i, workers, bs, seed)
                     for i in range(k)])
    np.testing.assert_array_equal(got, want)


def test_range_indices_cross_epoch_baseline():
    """Force the baseline/grouped epoch boundary too (bpe small)."""
    n, workers, bs, seed = 40, 2, 16, 7  # bpe = 2
    got = batching.indices_baseline_range(n, 0, 7, workers, bs, seed)
    want = np.stack([batching.indices_baseline(n, i, workers, bs, seed)
                     for i in range(7)])
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# schedules stay live past the precomputed table (regression: the old
# min(step, cfg.max_steps) clamp replayed the last row forever)
# --------------------------------------------------------------------------

def test_schedule_extends_past_table(ds, mesh):
    cfg = make_cfg(approach="baseline", mode="geometric_median",
                   worker_fail=2, err_mode="rev_grad", max_steps=4,
                   straggle_mode="drop", straggle_count=1, log_every=1000)
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    old_adv = tr._adv_schedule.copy()
    old_str = tr._straggle_schedule.copy()
    assert old_adv.shape[0] == 5
    tr.run(max_steps=12)  # block-wise callers go past cfg.max_steps
    # extended, prefix-stable, and equal to a fresh full-length draw
    assert tr._adv_schedule.shape[0] == 13
    np.testing.assert_array_equal(tr._adv_schedule[:5], old_adv)
    np.testing.assert_array_equal(
        tr._adv_schedule,
        drng.adversary_schedule(cfg.seed, 12, cfg.num_workers,
                                cfg.num_adversaries))
    np.testing.assert_array_equal(tr._straggle_schedule[:5], old_str)
    np.testing.assert_array_equal(
        tr._straggle_schedule,
        drng.straggler_schedule(cfg.seed, 12, cfg.num_workers,
                                cfg.straggle_count))
    # the tail is a live draw, not the frozen last row (whp for 2-of-8)
    tail = tr._adv_schedule[5:]
    assert not all(np.array_equal(row, old_adv[4]) for row in tail)
    tr.close()


def test_chunked_run_past_table_matches_eager(ds, mesh):
    """Both loops agree when run(max_steps) overruns cfg.max_steps — the
    chunked path extends the same schedules the eager path now uses."""
    kw = dict(approach="cyclic", worker_fail=1, err_mode="rev_grad",
              redundancy="shared", max_steps=3, log_every=1000)
    vecs = {}
    for k in (1, 4):
        tr = Trainer(make_cfg(**kw, steps_per_call=k), mesh=mesh, dataset=ds,
                     quiet=True)
        tr.run(max_steps=9)
        vecs[k] = params_vec(tr)
        tr.close()
    np.testing.assert_array_equal(vecs[1], vecs[4])


# --------------------------------------------------------------------------
# pre-r4 checkpoint format break surfaces a named error (ADVICE r4)
# --------------------------------------------------------------------------

def test_pre_r4_opt_state_restore_names_format_break(tmp_path):
    """Restoring a bare-rule (pre-unification) opt state into the current
    chain(rule, scale_by_schedule) structure must raise the explanatory
    ValueError naming the opt-state unification, not a raw pytree error."""
    import optax

    from draco_tpu.training.step import TrainState
    from draco_tpu.utils import checkpoint as ckpt

    params = {"w": jnp.ones((3,))}
    old = TrainState(params=params,
                     opt_state=optax.sgd(0.01, momentum=0.9).init(params),
                     batch_stats=None, step=jnp.asarray(1, jnp.int32))
    ckpt.save(str(tmp_path), 5, old)

    new_opt = optax.chain(optax.sgd(1.0, momentum=0.9),
                          optax.scale_by_schedule(lambda t: 0.01))
    new = TrainState(params=params, opt_state=new_opt.init(params),
                     batch_stats=None, step=jnp.asarray(1, jnp.int32))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype), new)
    with pytest.raises(ValueError, match="opt-state unification"):
        ckpt.load(str(tmp_path), 5, abstract)
