"""Scan-chunked LM token loop (cfg.steps_per_call > 1 on the TransformerLM
routes): bitwise equivalence with the eager loop, mid-chunk resume, the
in-graph token stream, and the eval/checkpoint guard split.

The equivalence tests are the load-bearing ones: ``train_token_many`` is the
SAME coded LM step (token slice → vmapped lane fwd/bwd → encode →
aggregate/decode → update) scan-chained K at a time
(parallel/common.make_token_train_many + parallel/token_loop.py), so
K ∈ {1, 4} must produce identical final parameters and an identical metrics
stream — under a live rev-grad adversary AND a straggler-drop schedule, on
both parallelism styles (sp: shard_map ring attention; tp: GSPMD folded
mesh). Tiny models keep the compiles cheap; nothing here depends on scale.
"""

import json
import os

import jax
import numpy as np
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.parallel import make_mesh_2d
from draco_tpu.parallel.mesh import make_folded_wtp_mesh
from draco_tpu.parallel.sp_step import train_sp
from draco_tpu.parallel.tp_step import build_tp_train_setup, train_tp
from draco_tpu.parallel.token_loop import run_token_loop
from draco_tpu.utils import checkpoint as ckpt


def make_cfg(**kw):
    base = dict(
        network="TransformerLM", dataset="synthetic-text", batch_size=4,
        lr=0.05, momentum=0.9, num_workers=8, approach="baseline",
        mode="normal", worker_fail=0, err_mode="rev_grad", seq_len=16,
        vocab=32, model_dim=32, model_heads=2, model_layers=1, max_steps=7,
        eval_freq=0, train_dir="", log_every=1000,
        # strict compile sentinel (ISSUE 5): a steady-state recompilation
        # of a labelled route program raises at the dispatch site, so every
        # run in this suite doubles as a 0-retrace assertion
        compile_guard="raise",
        # in-graph step guard enabled suite-wide (ISSUE 6): the guard must
        # be bitwise-transparent on clean runs — the equivalence tests
        # additionally pin guard_trips == 0 per record
        step_guard="on",
        # incident engine enabled suite-wide (ISSUE 13): host-side only,
        # so K∈{1,4} must stay bitwise with the watch ON and a clean run
        # must raise ZERO incidents (_assert_route_telemetry)
        incident_watch="on",
    )
    base.update(kw)
    return TrainConfig(**base)


def params_vec(state):
    return np.concatenate(
        [np.ravel(x) for x in jax.tree.leaves(jax.device_get(state.params))]
    )


def metric_stream(train_dir):
    """[(step, split, loss)] from metrics.jsonl, timing keys dropped — the
    cross-regime-comparable part of the record stream."""
    out = []
    with open(os.path.join(train_dir, "metrics.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            out.append((rec["step"], rec.get("split", "train"), rec["loss"]))
    return out


# --------------------------------------------------------------------------
# chunked vs eager equivalence — both parallelism styles, live rev-grad
# adversary + straggler drops, eval/checkpoint boundaries interleaved
# --------------------------------------------------------------------------

# sp: shard_map ring attention on a (4 w × 2 sp) mesh, robust aggregation;
# tp: GSPMD folded mesh, cyclic code in the joint adversary+straggler
# regime (s=2, t=1, e=1 needs n > 4s ⇒ n=9, folded onto 3 devices)
ROUTES = {
    "sp": dict(
        kw=dict(num_workers=4, seq_shards=2, mode="geometric_median",
                worker_fail=1, straggle_mode="drop", straggle_count=1),
        train=lambda cfg, prof=None: train_sp(cfg, make_mesh_2d(4, 2),
                                              quiet=True, profile_dir=prof),
    ),
    # the cyclic tp route runs with the numerics observatory + bf16 shadow
    # wire enabled (obs/numerics.py, ISSUE 10): K∈{1,4} equality must hold
    # with the watch on, and _assert_route_telemetry pins the shadow
    # columns (flag agreement 1.0, detection preserved under quantization)
    "tp": dict(
        kw=dict(num_workers=9, approach="cyclic", worker_fail=2,
                adversary_count=1, redundancy="shared",
                straggle_mode="drop", straggle_count=1,
                numerics_watch="on", shadow_wire="bf16"),
        train=lambda cfg, prof=None: train_tp(cfg, make_folded_wtp_mesh(9),
                                              quiet=True, profile_dir=prof),
    ),
    # the approximate family on the single-shard fold (ISSUE 8): no live
    # adversary (validate rejects one), two seeded drops per step inside
    # the ⌈αn⌉ = 2 budget — the per-record residual-vs-bound certificate
    # and absent≠accused are asserted in _assert_route_telemetry
    # the approx route carries the watch too (numerics + bf16 shadow on
    # the optimal-decoding family's wire) — its exact-code counterpart is
    # the tp cell above, so both observatory families are pinned on this
    # loop
    "approx": dict(
        kw=dict(num_workers=8, approach="approx", worker_fail=0,
                redundancy="shared", code_redundancy=1.5,
                straggler_alpha=0.25, straggle_mode="drop",
                straggle_count=2, numerics_watch="on", shadow_wire="bf16"),
        train=lambda cfg, prof=None: train_sp(cfg, make_mesh_2d(8, 1),
                                              quiet=True, profile_dir=prof),
    ),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_chunked_equals_eager_bitwise(route, tmp_path):
    """Same final params AND same metrics stream (train records at
    log_every=1 + eval records at eval_freq=3) for K=1 (eager loop) vs K=4
    (scan-chunked with remainder chunks, since the eval boundary snaps
    chunks to 3 and 7 % 3 != 0) — run with the telemetry spine enabled
    (trace_dir + heartbeat, ISSUE 4), which must not perturb either
    regime."""
    r = ROUTES[route]
    out = {}
    for k in (1, 4):
        d = str(tmp_path / f"{route}_k{k}")
        cfg = make_cfg(**r["kw"], steps_per_call=k, train_dir=d,
                       trace_dir=d, eval_freq=3, log_every=1)
        # the chunked run additionally captures a jax.profiler window
        # (ISSUE 9): the capture must observe, never perturb — metrics
        # stay bitwise-equal to the unprofiled eager run, still under
        # compile_guard="raise" with 0 steady retraces
        state, metrics = r["train"](cfg, d if k == 4 else None)
        out[k] = (params_vec(state), metric_stream(d), float(metrics["loss"]))
    np.testing.assert_array_equal(out[1][0], out[4][0])
    assert out[1][1] == out[4][1]  # identical per-step metric values
    assert [s for s, split, _ in out[4][1] if split == "train"] == list(
        range(1, 8))
    assert [s for s, split, _ in out[4][1] if split == "eval"] == [3, 6]
    assert out[1][2] == out[4][2]
    _assert_route_telemetry(route, r["kw"], tmp_path / f"{route}_k4")


def _assert_route_telemetry(route, kw, run_dir):
    """LM telemetry on the K=4 run: the cyclic route's decode-health
    columns report detection precision/recall 1.0 vs the seeded schedules
    in every train record and in status.json; trace.json carries the host
    phases plus the token prefetcher's own labeled worker-thread lane."""
    from draco_tpu import rng as drng

    recs = [json.loads(l)
            for l in open(os.path.join(run_dir, "metrics.jsonl"))]
    train = [r for r in recs if r.get("split") != "eval" and "loss" in r]
    # guards enabled suite-wide: a clean run (live adversary + stragglers
    # all inside budget) must never trip — and never skip an update
    for r in train:
        assert r["guard_trips"] == 0.0, r
        assert r["skipped_steps"] == 0.0, r
    status_guard = json.load(
        open(os.path.join(run_dir, "status.json"))).get("guard")
    assert status_guard == {"trips": 0.0, "skipped_steps": 0.0}
    if kw.get("approach") == "cyclic":
        from draco_tpu.obs import forensics as fx

        n = kw["num_workers"]
        adv = drng.adversary_schedule(428, 8, n, kw["adversary_count"])
        strag = drng.straggler_schedule(428, 8, n, kw["straggle_count"])
        for r in train:
            want = int((adv[r["step"]] & ~strag[r["step"]]).sum())
            assert r["det_adv"] == want
            assert r["det_tp"] == want  # recall = 1.0
            assert r["located_errors"] == want  # precision = 1.0
            assert r["decode_residual"] < 1e-3
            # numerics observatory + bf16 shadow (ISSUE 10): finite range
            # stats, flag agreement exactly 1.0, detection P/R preserved
            # under quantization — on the REAL folded w×tp GSPMD mesh
            assert r["nx_wire_absmax"] > 0 and r["nx_wire_rms"] > 0
            assert r["nx_grad_nonfinite"] == 0.0
            assert r["shadow_flag_agree"] == 1.0, r
            assert 0.0 <= r["shadow_err"] < 0.05, r
            assert r["shadow_det_flagged"] == want
            assert r["shadow_det_tp"] == want
            # per-worker attribution exact (packed forensics masks, ISSUE
            # 7): accused == adversarial ∧ present, bit for bit — an
            # absent worker is never an accused worker
            masks = fx.record_masks(r, n)
            assert masks is not None, r
            assert masks["adv"] == tuple(adv[r["step"]])
            assert masks["present"] == tuple(~strag[r["step"]])
            assert masks["accused"] == tuple(
                adv[r["step"]] & ~strag[r["step"]]), (r["step"], masks)
        status = json.load(open(os.path.join(run_dir, "status.json")))
        health = status["decode_health"]
        assert health["precision"] == 1.0 and health["recall"] == 1.0
        assert health["adv_total"] > 0
        # the per-worker ledger block + versioned schema (ISSUE 7)
        fxb = status["forensics"]
        assert fxb["num_workers"] == n and fxb["accused_total"] > 0
        assert fxb["top_suspects"]
        assert status["schema"] == 5
    elif kw.get("approach") == "approx":
        from draco_tpu.obs import forensics as fx

        n = kw["num_workers"]
        strag = drng.straggler_schedule(428, 8, n, kw["straggle_count"])
        for r in train:
            # the residual-vs-bound certificate per record (ISSUE 8) + no
            # located-error machinery on this family
            assert r["decode_residual"] <= \
                r["decode_residual_bound"] + 1e-5, r
            assert 0.0 < r["recovered_fraction"] <= 1.0
            assert "det_tp" not in r and "located_errors" not in r
            # watch columns on this family too (ISSUE 10): shadow flag
            # surface is the non-finite wire rows — empty on a clean run
            assert r["nx_wire_absmax"] > 0
            assert r["shadow_flag_agree"] == 1.0 and \
                r["shadow_det_flagged"] == 0.0
            assert 0.0 <= r["shadow_err"] < 0.05, r
            masks = fx.record_masks(r, n)
            assert masks is not None, r
            assert masks["present"] == tuple(~strag[r["step"]])
            assert masks["adv"] == (False,) * n
            # a scheduled straggler is never an accused worker
            assert masks["accused"] == (False,) * n, (r["step"], masks)
        status = json.load(open(os.path.join(run_dir, "status.json")))
        health = status["decode_health"]
        assert health["decode_residual"] <= \
            health["decode_residual_bound"] + 1e-5
        # the ledger holds: absence decays nothing — no accusations, no
        # episodes, full trust on every worker
        fxb = status["forensics"]
        assert fxb["accused_total"] == 0 and fxb["episodes_total"] == 0
        assert fxb["trust"] == [1.0] * n
        assert status["schema"] == 5
    else:
        assert all("det_tp" not in r for r in train)
        assert all("wmask_accused0" not in r for r in train)
    trace = json.load(open(os.path.join(run_dir, "trace.json")))
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"gather", "dispatch", "flush", "prefetch.assemble"} <= names
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assembles = {e["tid"] for e in spans if e["name"] == "prefetch.assemble"}
    dispatches = {e["tid"] for e in spans if e["name"] == "dispatch"}
    # the worker thread has its own labeled lane, distinct from the main
    # loop's dispatch lane (cold-start assembly runs on main; steady-state
    # chunks on the worker)
    worker_tids = {t for t in assembles
                   if lanes.get(t) == "token-chunk-prefetch"}
    assert worker_tids, lanes  # the worker thread got its own labeled lane
    assert not (worker_tids & dispatches)  # ...distinct from the main loop's
    assert any(e["ph"] == "C" and e["name"] == "prefetch_depth"
               for e in events)
    # compile sentinel surface (ISSUE 5): status.json carries the counters,
    # the ledger attributes the chunked driver's builds per chunk shape
    # (main chunks k=3 snapped to eval_freq=3 + remainder k=1), and the
    # trace grew a compile-category lane
    status = json.load(open(os.path.join(run_dir, "status.json")))
    assert status["compiles"] >= 1 and status["compile_s"] > 0
    assert status["steady_recompiles"] == 0
    # the incident engine (ISSUE 13) ran on every cell of this suite and a
    # CLEAN run — live adversary + stragglers all inside budget — raises
    # ZERO incidents (no-flapping contract), while the bitwise assertions
    # above prove the watch perturbs nothing; no event → no incidents.jsonl
    inc = status["incidents"]
    assert inc["total"] == 0 and inc["open"] == [] and inc["by_type"] == {}
    assert not os.path.exists(os.path.join(run_dir, "incidents.jsonl"))
    ledger = [json.loads(l)
              for l in open(os.path.join(run_dir, "compiles.jsonl"))]
    labels = {r["program"] for r in ledger if r["program"]}
    assert {"train_token_many[3]", "train_token_many[1]"} <= labels
    assert not any(r["steady_recompile"] for r in ledger)
    assert any(e.get("cat") == "compile" for e in events)
    # the profiled window's device surface (ISSUE 9): capture + shared-clock
    # anchor landed, the window wrote the scope map of the two chunk
    # programs it dispatched (k=3 and the k=1 remainder: one module name,
    # folded as one) and the heartbeat folded the capture into the
    # ``device`` status block
    from draco_tpu.obs import device_attr

    assert device_attr.find_capture(str(run_dir)) is not None
    anchor = device_attr.load_anchor(str(run_dir))
    assert anchor is not None and anchor["steps_profiled"] == 7
    assert anchor["tracer_ts_us"] is not None
    dev = status["device"]
    assert dev["profiled_steps"] == 7 and dev["total_device_us"] > 0
    assert "error" not in dev, dev
    assert dev["attributed_frac"] > 0.9 and dev["decode_share"] > 0.0


def test_device_token_gen_bitwise_and_distinct():
    """cfg.token_gen='device' regenerates the batches in-graph: K=1 and K=4
    agree bitwise (both run the scanned driver), and the device stream is a
    different deterministic draw from the host stream."""
    mesh = make_folded_wtp_mesh(8)
    vecs = {}
    for k in (1, 4):
        cfg = make_cfg(approach="cyclic", worker_fail=1, redundancy="shared",
                       steps_per_call=k, token_gen="device")
        setup = build_tp_train_setup(cfg, mesh)
        state, metrics = run_token_loop(setup, cfg, quiet=True)
        assert np.isfinite(float(metrics["loss"]))
        vecs[k] = params_vec(state)
    np.testing.assert_array_equal(vecs[1], vecs[4])

    # the two streams are distinct deterministic draws with the same shape/
    # range contract (ramp mod vocab)
    from draco_tpu.parallel.sp_step import synthetic_text, synthetic_text_in_graph

    host = synthetic_text(428, 1, 8, 4, 16, 32)
    dev = np.asarray(synthetic_text_in_graph(428, 1, 8, 4, 16, 32))
    assert host.shape == dev.shape and dev.dtype == np.int32
    assert dev.min() >= 0 and dev.max() < 32
    assert not np.array_equal(host, dev)


@pytest.mark.core
def test_chunked_token_loop_smoke_fast():
    """Tier-1/core smoke: tiny LM, K=3 with a remainder chunk, live
    adversary — the chunked loop trains and the loss moves."""
    kw = dict(approach="cyclic", worker_fail=1, redundancy="shared",
              steps_per_call=3)
    mesh = make_folded_wtp_mesh(8)
    cfg = make_cfg(**kw)
    setup = build_tp_train_setup(cfg, mesh)
    _, first = run_token_loop(setup, cfg, steps=1, quiet=True)
    cfg2 = make_cfg(**kw)
    setup2 = build_tp_train_setup(cfg2, mesh)
    state, last = run_token_loop(setup2, cfg2, steps=7, quiet=True)
    assert int(state.step) == 8
    assert np.isfinite(last["loss"])
    assert last["loss"] < float(first["loss"])


def test_resume_from_checkpoint_mid_chunk(tmp_path):
    """A K=4 run checkpoints at eval boundaries (3, 6, 9); resuming from
    step 3 — mid-chunk relative to the K grid — must land on the exact same
    parameters as the uninterrupted run."""
    kw = dict(approach="cyclic", worker_fail=1, redundancy="shared",
              steps_per_call=4, eval_freq=3, train_dir=str(tmp_path),
              max_steps=10)
    cfg = make_cfg(**kw)
    state_full, _ = train_tp(cfg, make_folded_wtp_mesh(8), quiet=True)
    assert ckpt.available_steps(str(tmp_path)) == [3, 6, 9]
    cfg_res = make_cfg(**kw, checkpoint_step=3)
    state_res, _ = train_tp(cfg_res, make_folded_wtp_mesh(8), steps=7,
                            quiet=True)
    np.testing.assert_array_equal(params_vec(state_full),
                                  params_vec(state_res))


# --------------------------------------------------------------------------
# the eval/checkpoint guard split (previously one `eval_freq and train_dir`
# guard: no checkpoints without eval, no eval without a train_dir)
# --------------------------------------------------------------------------

def test_checkpoint_without_eval(tmp_path):
    """eval_freq=0 with a train_dir still saves the final state — in both
    regimes, at the same step."""
    for k in (1, 4):
        d = str(tmp_path / f"k{k}")
        cfg = make_cfg(steps_per_call=k, eval_freq=0, train_dir=d)
        train_tp(cfg, make_folded_wtp_mesh(8), steps=5, quiet=True)
        assert ckpt.available_steps(d) == [5]


def test_eval_without_train_dir_runs():
    """eval_freq without a train_dir evaluates (records print-only) instead
    of silently skipping; no checkpoint dir appears."""
    cfg = make_cfg(eval_freq=2, train_dir="", steps_per_call=4)
    state, metrics = train_tp(cfg, make_folded_wtp_mesh(8), steps=4,
                              quiet=True)
    assert int(state.step) == 5
    assert np.isfinite(float(metrics["loss"]))


# --------------------------------------------------------------------------
# config surface: the TransformerLM steps_per_call ban is lifted
# --------------------------------------------------------------------------

def test_validate_accepts_steps_per_call_on_all_lm_routes():
    """config.validate passes steps_per_call > 1 for every LM route config
    (single-shard, sp, tp, pp, ep) — the pre-PR ban is gone."""
    routes = [
        dict(),                                        # single-shard
        dict(num_workers=4, seq_shards=2),             # sp
        dict(num_workers=4, tensor_shards=2),          # tp
        dict(num_workers=2, pipeline_shards=2,
             model_layers=2),                          # pp
        dict(num_workers=4, moe_experts=2,
             expert_shards=2),                         # ep
    ]
    for kw in routes:
        make_cfg(**kw, steps_per_call=8).validate()


def test_token_gen_validation():
    with pytest.raises(ValueError, match="token_gen"):
        make_cfg(token_gen="banana").validate()
    with pytest.raises(ValueError, match="TransformerLM"):
        TrainConfig(network="FC", token_gen="device").validate()
