"""Incident engine (draco_tpu/obs/incidents.py, ISSUE 13): detector units
on synthesized column streams (onset/offset hysteresis, no flapping on a
single noisy step, worker attribution), the declarative registry +
threshold-override grammar, the incidents.jsonl event stream and its
torn-tail-tolerant replay (obs/replay.py + tools/incident_report.py), the
live production-loop wiring (clean run -> ZERO incidents AND bitwise-
unchanged training; nan_grad -> attributed nonfinite incident), and the
terminal-write coverage satellite (the SIGTERM/crash status.json carries
the final ``incidents`` block even when no beat ever did)."""

import json
import os

import numpy as np
import pytest

from draco_tpu.obs import incidents as inc
from draco_tpu.obs import replay


def rec(step, accused=0, present=0b11111111, adv=None, **cols):
    """A synthesized train record with packed forensics masks (n <= 8)."""
    r = {"step": step, "loss": 1.0, "wmask_accused0": accused,
         "wmask_present0": present,
         "wmask_adv0": accused if adv is None else adv}
    r.update(cols)
    return r


# --------------------------------------------------------------------------
# registry + thresholds
# --------------------------------------------------------------------------

@pytest.mark.core
def test_detector_registry_enumerable():
    """The detector set is declaratively registered: every spec names a
    severity, a source, and a thresholds dict carrying the hysteresis
    pair — the enumerability the chaos matrix and PERF_HISTORY.md §15 rest on."""
    table = inc.detector_table()
    names = {t["name"] for t in table}
    assert {"throughput", "decode_residual", "trust", "guard", "nonfinite",
            "numerics_drift", "compile_storm", "starvation"} <= names
    for t in table:
        assert t["severity"] in inc.SEVERITIES
        assert t["source"] in inc.SOURCES
        assert {"on_count", "off_count"} <= set(t["thresholds"])


@pytest.mark.core
def test_threshold_override_grammar():
    assert inc.parse_thresholds("trust.floor=0.4, guard.off_count=2") == {
        "trust.floor": 0.4, "guard.off_count": 2.0}
    assert inc.parse_thresholds("") == {}
    with pytest.raises(ValueError, match="unknown incident detector"):
        inc.parse_thresholds("bogus.floor=1")
    with pytest.raises(ValueError, match="no threshold"):
        inc.parse_thresholds("trust.bogus=1")
    with pytest.raises(ValueError, match="not"):
        inc.parse_thresholds("trust.floor")
    # config.validate rejects bad specs at config time
    from draco_tpu.config import TrainConfig

    with pytest.raises(ValueError, match="unknown incident detector"):
        TrainConfig(incident_thresholds="bogus.x=1").validate()
    with pytest.raises(ValueError, match="incident_watch"):
        TrainConfig(incident_watch="maybe").validate()


# --------------------------------------------------------------------------
# detector units on synthesized streams
# --------------------------------------------------------------------------

@pytest.mark.core
def test_trust_collapse_onset_offset_and_attribution():
    """~4 consecutive accusations pull EW trust below the 0.5 floor ->
    an attributed onset; sustained clean steps recover trust -> offset."""
    eng = inc.IncidentEngine(num_workers=4)
    for s in range(1, 8):
        eng.observe(rec(s, accused=0b0100, present=0b1111))
    opens = eng.open_episodes()
    assert len(opens) == 1 and opens[0]["type"] == "trust"
    assert opens[0]["workers"] == [2] and opens[0]["onset_step"] == 4
    for s in range(8, 20):
        eng.observe(rec(s, accused=0, present=0b1111))
    assert eng.open_episodes() == []
    (ep,) = [e for e in eng.episodes if e["type"] == "trust"]
    assert ep["offset_step"] > ep["onset_step"]
    # an ABSENT worker's trust holds: absence is an erasure, not evidence
    # for the ACCUSATION detectors — what sustained absence DOES raise is
    # the straggle incident (ISSUE 14: the autopilot's dial-down signal),
    # attributed to the absent worker
    eng2 = inc.IncidentEngine(num_workers=4)
    for s in range(1, 12):
        eng2.observe(rec(s, accused=0, present=0b1011))  # w2 always absent
    assert [e["type"] for e in eng2.open_episodes()] == ["straggle"]
    assert eng2.open_episodes()[0]["workers"] == [2]
    assert not any(e["type"] == "trust" for e in eng2.all_episodes())


@pytest.mark.core
def test_single_noisy_step_never_flaps():
    """The no-flapping contract: one loud decode_residual (on_count=2)
    and one noisy numerics record (on_count=3) open NOTHING."""
    eng = inc.IncidentEngine()
    eng.observe({"step": 1, "loss": 1.0, "decode_residual": 5.0})
    eng.observe({"step": 2, "loss": 1.0, "decode_residual": 1e-6})
    eng.observe({"step": 3, "loss": 1.0, "decode_residual": float("nan")})
    eng.observe({"step": 4, "loss": 1.0, "decode_residual": 1e-6})
    assert eng.total_onsets == 0
    # two consecutive crossings DO open (NaN counts as a crossing), and
    # the episode's onset is the first hot step
    for s, r in ((5, 2.0), (6, float("nan"))):
        eng.observe({"step": s, "loss": 1.0, "decode_residual": r})
    assert eng.total_onsets == 1
    assert eng.open_episodes()[0]["onset_step"] == 5


@pytest.mark.core
def test_approx_residual_drift_toward_bound():
    """The approx branch: EW of residual/bound crossing bound_frac fires;
    healthy ratios (~0.6, the committed straggler_study band) never do;
    an outright bound violation fires regardless of the EW."""
    eng = inc.IncidentEngine()
    for s in range(1, 12):
        eng.observe({"step": s, "loss": 1.0, "decode_residual": 0.6,
                     "decode_residual_bound": 1.0})
    assert eng.total_onsets == 0
    for s in range(12, 24):  # EW (alpha=0.25) needs ~9 steps to cross 0.95
        eng.observe({"step": s, "loss": 1.0, "decode_residual": 0.99,
                     "decode_residual_bound": 1.0})
    assert eng.total_onsets == 1
    eng2 = inc.IncidentEngine()
    for s in (1, 2):  # violation: residual ABOVE the analytic bound
        eng2.observe({"step": s, "loss": 1.0, "decode_residual": 1.5,
                      "decode_residual_bound": 1.0})
    assert eng2.total_onsets == 1
    # narrow-wire slack (ISSUE 15): on a bf16/int8 wire the measured
    # residual carries quantization error the analytic bound (drops only)
    # does not price — make_engine widens the approx branch by the dtype's
    # slack (same widening guards.assess applies), so a clean int8-wire
    # run sitting just past the bound is NOT an incident (the slack comes
    # off the measured residual before BOTH the violation check and the
    # EW drift ratio), while a real violation past the slack still fires
    eng3 = inc.IncidentEngine(
        thresholds={"decode_residual.slack": 0.1})
    for s in range(1, 12):
        eng3.observe({"step": s, "loss": 1.0, "decode_residual": 1.04,
                      "decode_residual_bound": 1.0})
    assert eng3.total_onsets == 0
    for s in (12, 13):
        eng3.observe({"step": s, "loss": 1.0, "decode_residual": 1.5,
                      "decode_residual_bound": 1.0})
    assert eng3.total_onsets == 1


@pytest.mark.core
def test_guard_burn_and_nonfinite_attribution():
    """Hard signals run at on_count=1: a guard trip and a non-finite
    ingest fraction each open immediately, attributed via the step's
    accused mask; off_count clean steps close them."""
    eng = inc.IncidentEngine(num_workers=8)
    eng.observe(rec(1))
    eng.observe(rec(2, accused=0b1000, guard_trips=1.0, skipped_steps=1.0,
                    nx_grad_nonfinite=0.01, nx_wire_nonfinite=0.0))
    assert eng.total_onsets == 2
    by_type = {e["type"]: e for e in eng.open_episodes()}
    assert by_type["guard"]["workers"] == [3]
    assert by_type["nonfinite"]["workers"] == [3]
    assert by_type["nonfinite"]["evidence"]["nonfinite_frac"] == 0.01
    for s in range(3, 9):
        eng.observe(rec(s, guard_trips=0.0, nx_grad_nonfinite=0.0,
                        nx_wire_nonfinite=0.0))
    assert eng.open_episodes() == []
    assert {e["type"] for e in eng.episodes} == {"guard", "nonfinite"}


@pytest.mark.core
def test_numerics_drift_histogram_shift():
    """The exponent histogram shifting from its own warm baseline fires
    only after on_count consecutive observations — and only once the warm
    baseline (first `warmup` watched records) exists."""
    eng = inc.IncidentEngine()

    def nxrec(step, lo):
        # all mass in bin 0 (baseline) vs bin 5 (shifted)
        hist = {f"nx_wire_exp{i}": 0.0 for i in range(6)}
        hist["nx_wire_exp0" if lo else "nx_wire_exp5"] = 1.0
        return {"step": step, "loss": 1.0, "nx_wire_uf_bf16": 0.0,
                "nx_wire_of_bf16": 0.0, **hist}

    for s in range(1, 7):  # warmup (4) + 2 stable
        eng.observe(nxrec(s, lo=True))
    eng.observe(nxrec(7, lo=False))  # single shifted step: no flap
    eng.observe(nxrec(8, lo=True))
    assert eng.total_onsets == 0
    for s in range(9, 12):  # 3 consecutive shifted steps: onset
        eng.observe(nxrec(s, lo=False))
    assert eng.total_onsets == 1
    ep = eng.open_episodes()[0]
    assert ep["type"] == "numerics_drift" and ep["severity"] == "warn"
    assert ep["evidence"]["hist_shift"] == 1.0


@pytest.mark.core
def test_throughput_regression_against_warm_baseline():
    """Beat-source: the EW steps/s falling >40% below the warm baseline
    (EW frozen after warmup_beats inter-beat rates) opens after on_count
    slow beats; recovery closes it."""
    t = [0.0]
    eng = inc.IncidentEngine(clock=lambda: t[0])

    def beat(step, dt):
        t[0] += dt
        eng.observe_beat(step, {})

    step = 0
    for _ in range(4):  # warmup: 10 steps/s
        step += 10
        beat(step, 1.0)
    assert eng.total_onsets == 0
    for _ in range(6):  # collapse to 1 step/s
        step += 10
        beat(step, 10.0)
    assert eng.total_onsets == 1
    ep = eng.open_episodes()[0]
    assert ep["type"] == "throughput"
    assert ep["evidence"]["baseline_steps_per_s"] == pytest.approx(10.0)
    for _ in range(4):  # recovery
        step += 10
        beat(step, 1.0)
    assert eng.open_episodes() == []
    # warmup_beats=0 is a legal override: the first rate becomes the
    # baseline instead of firing against None (which crashed the loop)
    t2 = [0.0]
    eng2 = inc.IncidentEngine(clock=lambda: t2[0],
                              thresholds={"throughput.warmup_beats": 0.0})
    for dt in (1.0, 1.0, 1.0):
        t2[0] += dt
        eng2.observe_beat(int(t2[0] * 10), {})
    assert eng2.total_onsets == 0


@pytest.mark.core
def test_compile_storm_and_starvation_beats():
    """compile_storm fires on any steady-recompile delta between beats;
    starvation fires on a supervised prefetcher restart, or on the queue
    depth pinned at zero for depth_beats consecutive beats."""
    eng = inc.IncidentEngine()
    eng.observe_beat(4, {"steady_recompiles": 0, "prefetch_depth": 1,
                         "prefetch_restarts": 0})
    assert eng.total_onsets == 0
    eng.observe_beat(8, {"steady_recompiles": 2, "prefetch_depth": 1,
                         "prefetch_restarts": 0})
    assert [e["type"] for e in eng.open_episodes()] == ["compile_storm"]
    eng.observe_beat(12, {"steady_recompiles": 2, "prefetch_depth": 1,
                          "prefetch_restarts": 1})
    types = {e["type"] for e in eng.open_episodes()}
    assert "starvation" in types
    # depth starving: three consecutive zero-depth beats (fresh engine)
    eng2 = inc.IncidentEngine()
    for s in (4, 8):
        eng2.observe_beat(s, {"prefetch_depth": 0})
    assert eng2.total_onsets == 0  # two zero beats: below depth_beats
    eng2.observe_beat(12, {"prefetch_depth": 0})
    assert [e["type"] for e in eng2.open_episodes()] == ["starvation"]


# --------------------------------------------------------------------------
# event stream + offline replay
# --------------------------------------------------------------------------

@pytest.mark.core
def test_event_stream_and_replay_roundtrip(tmp_path):
    """The live engine streams onset/offset lines; a fresh engine replayed
    over the same records reproduces the ledger exactly (the
    incident_report diff contract); a torn tail is tolerated; a clean run
    writes NO file."""
    from tools import incident_report

    d = tmp_path / "run"
    d.mkdir()
    recs = [rec(s, accused=(0b0010 if 3 <= s <= 9 else 0))
            for s in range(1, 16)]
    with open(d / "metrics.jsonl", "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
    eng = inc.IncidentEngine(num_workers=4,
                             out_path=str(d / "incidents.jsonl"))
    for r in recs:
        eng.observe(r)
    eng.finalize()
    events = list(replay.iter_jsonl(str(d / "incidents.jsonl")))
    assert [e["event"] for e in events] == ["onset", "offset"]
    assert events[0]["type"] == "trust" and events[0]["workers"] == [1]
    rc = incident_report.main([str(d), "--num-workers", "4"])
    assert rc == 0
    rep = json.load(open(d / "incidents_report.json"))
    assert rep["diff"]["match"] and not rep["diff"]["only_replay"]
    # a DIVERGENT ledger (hand-edited onset) exits 1 naming the divergence
    with open(d / "incidents.jsonl", "a") as fh:
        fh.write(json.dumps({"v": 1, "event": "onset", "type": "guard",
                             "severity": "critical", "source": "record",
                             "onset_step": 12, "last_step": 12, "steps": 1,
                             "workers": [0], "evidence": {}}) + "\n")
    assert incident_report.main([str(d), "--num-workers", "4"]) == 1
    # torn tail on top: still folds (the divergence verdict stands)
    with open(d / "incidents.jsonl", "a") as fh:
        fh.write('{"v": 1, "event": "ons')
    assert incident_report.main([str(d), "--num-workers", "4"]) == 1
    # clean engine: no event, no file
    eng2 = inc.IncidentEngine(num_workers=4,
                              out_path=str(d / "none.jsonl"))
    for s in range(1, 10):
        eng2.observe(rec(s))
    eng2.finalize()
    assert not os.path.exists(d / "none.jsonl")


@pytest.mark.core
def test_resumed_overlapping_stream_degrades_to_carry_through(tmp_path):
    """A resumed run APPENDS overlapping steps to metrics.jsonl: two live
    engine instances with reset state observed that stream, which one
    continuous replay engine cannot reproduce — the strict diff must
    degrade to a carry-through (exit 0), not a false DIVERGED."""
    from tools import incident_report

    d = tmp_path / "resumed"
    d.mkdir()
    recs = [rec(s) for s in range(1, 7)] + [rec(s) for s in range(4, 9)]
    with open(d / "metrics.jsonl", "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
    # a ledger entry the continuous replay would NOT reproduce
    with open(d / "incidents.jsonl", "w") as fh:
        fh.write(json.dumps({"v": 1, "event": "onset", "type": "guard",
                             "severity": "critical", "source": "record",
                             "onset_step": 5, "last_step": 5, "steps": 1,
                             "workers": [1], "evidence": {}}) + "\n")
    assert incident_report.main([str(d), "--num-workers", "8"]) == 0
    rep = json.load(open(d / "incidents_report.json"))
    assert rep["diff"]["full_coverage"] is False
    assert rep["diff"]["match"] is False  # unverified, not asserted
    # a GAP-FREE resume is detectable from the ledger itself: the second
    # engine instance's seq counter resets, so a second onset stream in
    # one file degrades the strict diff even with contiguous steps
    d2 = tmp_path / "gapfree"
    d2.mkdir()
    recs2 = [rec(s, guard_trips=float(s in (2, 7)), skipped_steps=0.0)
             for s in range(1, 10)]
    with open(d2 / "metrics.jsonl", "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs2) + "\n")
    for lo, hi in ((1, 5), (5, 10)):  # two engine instances, appending
        eng = inc.IncidentEngine(num_workers=8,
                                 out_path=str(d2 / "incidents.jsonl"))
        for r in recs2[lo - 1:hi - 1]:
            eng.observe(r)
        eng.finalize()
    assert incident_report.main([str(d2), "--num-workers", "8"]) == 0
    rep2 = json.load(open(d2 / "incidents_report.json"))
    assert rep2["diff"]["multi_run_ledger"] is True
    assert rep2["diff"]["full_coverage"] is False


@pytest.mark.core
def test_replay_scaffold_tolerance(tmp_path):
    """obs/replay.py — the one JSONL tolerance rule: missing file, empty
    file, blank lines, torn tail, non-dict lines."""
    p = tmp_path / "m.jsonl"
    assert list(replay.iter_jsonl(str(p))) == []
    p.write_text("")
    assert replay.train_records(str(p)) == []
    p.write_text('\n{"step": 1, "loss": 1.0}\n[1,2]\n'
                 '{"step": 2, "split": "eval", "loss": 9}\n'
                 '{"step": 3, "loss": 2.0}\n{"step": 4, "lo')
    recs = replay.train_records(str(p))
    assert [r["step"] for r in recs] == [1, 3]
    assert replay.record_at_step(str(p), 3)["loss"] == 2.0
    assert replay.record_at_step(str(p), 99) is None


# --------------------------------------------------------------------------
# live production-loop wiring
# --------------------------------------------------------------------------

def _cnn_cfg(**kw):
    from draco_tpu.config import TrainConfig

    base = dict(network="FC", dataset="synthetic-mnist", approach="cyclic",
                worker_fail=1, redundancy="shared", batch_size=4,
                num_workers=8, max_steps=6, eval_freq=0, log_every=1,
                lr=0.05, step_guard="on", numerics_watch="on",
                incident_watch="on")
    base.update(kw)
    return TrainConfig(**base)


def _run(cfg):
    import jax

    from draco_tpu.training.trainer import Trainer

    t = Trainer(cfg, quiet=True)
    try:
        t.run()
    finally:
        t.close()
    return np.concatenate([np.ravel(x) for x in jax.tree.leaves(
        jax.device_get(t.state.params))])


@pytest.mark.core
def test_live_clean_run_zero_incidents_and_bitwise(tmp_path):
    """The acceptance pin: incident_watch=on on a clean run raises ZERO
    incidents, stamps the schema-4 ``incidents`` block, writes no
    incidents.jsonl — and the final params are BITWISE identical to the
    watch-off run (the engine is host-side only)."""
    d_on, d_off = str(tmp_path / "on"), str(tmp_path / "off")
    v_on = _run(_cnn_cfg(train_dir=d_on))
    v_off = _run(_cnn_cfg(train_dir=d_off, incident_watch="off"))
    np.testing.assert_array_equal(v_on, v_off)
    st = json.load(open(os.path.join(d_on, "status.json")))
    assert st["schema"] == 5 and st["state"] == "done"
    assert st["incidents"] == {"total": 0, "open": [], "by_type": {},
                               "thresholds": {}, "last": None}
    assert not os.path.exists(os.path.join(d_on, "incidents.jsonl"))
    # watch off: no block at all
    st_off = json.load(open(os.path.join(d_off, "status.json")))
    assert "incidents" not in st_off


def test_live_nan_grad_raises_attributed_incident(tmp_path):
    """nan_grad@3:w5 through the real chunked trainer: the nonfinite
    incident opens AT the fault step attributed to exactly worker 5, the
    guard incident rides along, and the offline replay reproduces the
    ledger (incident_report exit 0)."""
    from tools import incident_report

    d = str(tmp_path / "nan")
    _run(_cnn_cfg(train_dir=d, steps_per_call=3,
                  fault_spec="nan_grad@3:w5"))
    events = list(replay.iter_jsonl(os.path.join(d, "incidents.jsonl")))
    onsets = {e["type"]: e for e in events if e["event"] == "onset"}
    assert set(onsets) == {"nonfinite", "guard"}
    assert onsets["nonfinite"]["onset_step"] == 3
    assert onsets["nonfinite"]["workers"] == [5]
    assert onsets["guard"]["workers"] == [5]
    st = json.load(open(os.path.join(d, "status.json")))
    assert st["incidents"]["total"] == 2
    assert st["incidents"]["by_type"] == {"guard": 1, "nonfinite": 1}
    assert incident_report.main([d]) == 0


@pytest.mark.core
def test_open_episode_worker_growth_replays_clean(tmp_path):
    """An episode still OPEN at run end whose worker set grew after onset:
    the ledger's onset line carries the onset-time set, the replay the
    grown union — the diff must compare open episodes by identity, not by
    the moving worker set (a correct ledger must not read DIVERGED)."""
    from tools import incident_report

    d = tmp_path / "grow"
    d.mkdir()
    recs = [rec(1, accused=0b0100, nx_grad_nonfinite=0.1,
                nx_wire_nonfinite=0.0),
            rec(2, accused=0b1000, nx_grad_nonfinite=0.1,
                nx_wire_nonfinite=0.0)]
    with open(d / "metrics.jsonl", "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
    eng = inc.IncidentEngine(num_workers=8,
                             out_path=str(d / "incidents.jsonl"))
    for r in recs:
        eng.observe(r)
    eng.finalize()
    assert eng.open_episodes()[0]["workers"] == [2, 3]  # grew after onset
    onsets = [e for e in replay.iter_jsonl(str(d / "incidents.jsonl"))]
    assert onsets[0]["workers"] == [2]  # ledger froze the onset-time set
    assert incident_report.main([str(d), "--num-workers", "8"]) == 0


@pytest.mark.core
def test_replay_uses_the_runs_own_thresholds(tmp_path):
    """The live engine stamps its non-default overrides into the status
    block; the replay must fold with THOSE (e.g. make_engine's implicit
    cyclic_tol <- guard_residual_tol), not the registry defaults — a run
    with a loosened tolerance must not falsely diverge offline."""
    from tools import incident_report

    d = tmp_path / "tol"
    d.mkdir()
    # residual 0.01 x4: fires under the default 1e-3, quiet under 0.1
    recs = [{"step": s, "loss": 1.0, "decode_residual": 0.01}
            for s in range(1, 5)]
    with open(d / "metrics.jsonl", "w") as fh:
        fh.write("\n".join(json.dumps(r) for r in recs) + "\n")
    eng = inc.IncidentEngine(
        num_workers=8, out_path=str(d / "incidents.jsonl"),
        thresholds={"decode_residual.cyclic_tol": 0.1})
    for r in recs:
        eng.observe(r)
    assert eng.total_onsets == 0  # quiet under the loosened tolerance
    block = eng.status_block()
    assert block["thresholds"] == {"decode_residual.cyclic_tol": 0.1}
    with open(d / "status.json", "w") as fh:
        json.dump({"schema": 4, "state": "done", "step": 4,
                   "incidents": block,
                   "forensics": {"num_workers": 8}}, fh)
    eng.finalize()
    assert incident_report.main([str(d)]) == 0
    rep = json.load(open(d / "incidents_report.json"))
    assert rep["replayed"] == []  # no false decode_residual episode


def test_device_token_gen_clean_run_zero_incidents(tmp_path):
    """The device token-gen LM route has NO host prefetch path: its beats
    must not report a constant queue depth 0 (which read as starvation) —
    a clean ≥3-beat device-gen run raises ZERO incidents."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import train_sp

    d = str(tmp_path / "devgen")
    cfg = TrainConfig(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=4, approach="baseline", mode="normal", worker_fail=0,
        seq_len=16, vocab=32, model_dim=32, model_heads=2, model_layers=1,
        max_steps=9, eval_freq=3, log_every=1, lr=0.05,
        token_gen="device", incident_watch="on", train_dir=d)
    train_sp(cfg, make_mesh_2d(4, 1), quiet=True)
    st = json.load(open(os.path.join(d, "status.json")))
    assert st["state"] == "done"
    assert st["incidents"]["total"] == 0, st["incidents"]
    assert "prefetch_depth" not in st  # no prefetcher, no depth claim
    assert not os.path.exists(os.path.join(d, "incidents.jsonl"))


def test_terminal_write_carries_final_incidents_block(tmp_path):
    """The satellite fix (the PR 9 ``device`` bug, re-fixed for
    ``incidents``): a SIGTERM-preempted run whose incident fired AFTER the
    last beat — here eval_freq=0, so NO beat ever runs before the stop —
    must still carry the final ``incidents`` block in its terminal
    status.json, incidents included."""
    d = str(tmp_path / "term")
    _run(_cnn_cfg(train_dir=d, eval_freq=0,
                  fault_spec="nan_grad@2:w4,sigterm@3"))
    st = json.load(open(os.path.join(d, "status.json")))
    assert st["state"] == "preempted" and st["schema"] == 5
    inc_block = st["incidents"]
    assert inc_block["total"] == 2  # nonfinite + guard, post-last-beat
    assert {e["type"] for e in inc_block["open"]} <= {"guard", "nonfinite"}
    assert inc_block["by_type"] == {"guard": 1, "nonfinite": 1}
    # the event stream survived the preemption too (flushed per event)
    onsets = [e for e in replay.iter_jsonl(
        os.path.join(d, "incidents.jsonl")) if e["event"] == "onset"]
    assert {e["type"] for e in onsets} == {"guard", "nonfinite"}
    assert all(e["workers"] == [4] for e in onsets)
