"""Telemetry spine (draco_tpu/obs + in-graph decode health, ISSUE 4), the
compile/retrace sentinel (obs/compile_watch.py, ISSUE 5), and per-worker
Byzantine forensics (obs/forensics.py, ISSUE 7).

Unit layer: the span tracer emits valid Chrome trace events and is a strict
no-op when disabled; the heartbeat folds per-step detection counts into
precision/recall and rewrites status.json atomically; MetricWriter buffers
to flush/close boundaries; Segments times with a monotonic clock; the
decode/vote health values are correct (and raise the fault signal beyond
the locator budget) straight off the coding primitives; trace_report folds
the artifacts; the compile sentinel attributes XLA executable builds to
labelled dispatch windows, writes the compiles.jsonl ledger + trace compile
lane, and its steady-state guard trips on a deliberately shape-polymorphic
control. The integration layer — health columns flowing through both
production loops, eager == chunked bitwise with telemetry enabled AND the
compile guard in strict mode (steady-state recompiles == 0),
trace.json/status.json/compiles.jsonl from real runs — rides the existing
K ∈ {1, 4} equivalence suites (tests/test_chunked_trainer.py,
tests/test_chunked_token_loop.py) so it costs no extra training runs.
"""

import json
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu.obs import (
    NULL_TRACER,
    CompileWatch,
    RetraceError,
    RetraceWarning,
    RunHeartbeat,
    SpanTracer,
)
from draco_tpu.obs.tracer import NullTracer


# --------------------------------------------------------------------------
# SpanTracer
# --------------------------------------------------------------------------

@pytest.mark.core
def test_tracer_emits_valid_chrome_trace(tmp_path):
    """Nested spans, a worker-thread lane, counters, metadata — and the
    file parses as the Chrome trace event format Perfetto loads."""
    path = str(tmp_path / "trace.json")
    tr = SpanTracer(path)
    with tr.span("outer", step=1):
        with tr.span("inner"):
            pass
        tr.counter("queue_depth", 1)
    tr.instant("marker")

    def worker():
        tr.name_thread("worker-lane")
        with tr.span("worker-span"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    tr.close()

    payload = json.load(open(path))
    events = payload["traceEvents"]
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner", "worker-span"}
    for e in spans.values():  # required Chrome-trace fields, µs numbers
        assert {"ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    # nesting is wall-clock containment on the same tid
    outer, inner = spans["outer"], spans["inner"]
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"step": 1}
    # the worker thread got its own labeled lane
    assert spans["worker-span"]["tid"] != outer["tid"]
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert lanes[spans["worker-span"]["tid"]] == "worker-lane"
    assert lanes[outer["tid"]] == "main"
    counters = [e for e in events if e["ph"] == "C"]
    assert counters and counters[0]["args"] == {"queue_depth": 1}
    assert any(e["ph"] == "i" for e in events)


@pytest.mark.core
def test_disabled_tracer_is_a_strict_noop(tmp_path):
    """The disabled path allocates nothing and touches no file: span()
    returns the one shared context-manager object, every method is inert,
    and a loop run with NULL_TRACER leaves no artifact."""
    assert isinstance(NULL_TRACER, NullTracer)
    assert NULL_TRACER.enabled is False
    cm1 = NULL_TRACER.span("a", k=1)
    cm2 = NULL_TRACER.span("b")
    assert cm1 is cm2  # no per-span allocation
    with cm1:
        NULL_TRACER.counter("c", 1)
        NULL_TRACER.instant("i")
        NULL_TRACER.name_thread("t")
    NULL_TRACER.flush()
    NULL_TRACER.close()
    assert list(tmp_path.iterdir()) == []
    # construction rule: no trace_dir (or a non-main process) -> the
    # singleton, never a new object
    from draco_tpu.obs import make_tracer
    assert make_tracer("", True) is NULL_TRACER
    assert make_tracer(str(tmp_path), False) is NULL_TRACER


def test_tracer_flush_is_atomic_and_incremental(tmp_path):
    path = str(tmp_path / "trace.json")
    tr = SpanTracer(path)
    with tr.span("first"):
        pass
    tr.flush()
    mid = json.load(open(path))
    assert {e["name"] for e in mid["traceEvents"] if e["ph"] == "X"} == \
        {"first"}
    with tr.span("second"):
        pass
    tr.close()
    final = json.load(open(path))
    assert {e["name"] for e in final["traceEvents"] if e["ph"] == "X"} == \
        {"first", "second"}
    assert not (tmp_path / "trace.json.tmp").exists()


# --------------------------------------------------------------------------
# RunHeartbeat
# --------------------------------------------------------------------------

@pytest.mark.core
def test_heartbeat_precision_recall_and_payload(tmp_path):
    hb = RunHeartbeat(str(tmp_path))
    for step in range(1, 5):
        hb.observe({"step": step, "loss": 2.0 - 0.1 * step, "prec1": 0.5,
                    "decode_residual": 1e-7, "located_errors": 1.0,
                    "det_tp": 1.0, "det_adv": 1.0})
    payload = hb.beat(4, total_steps=8, extra={"prefetch_depth": 1})
    on_disk = json.load(open(tmp_path / "status.json"))
    assert on_disk == payload
    assert payload["step"] == 4 and payload["total_steps"] == 8
    assert payload["steps_per_s"] > 0 and payload["eta_s"] >= 0
    assert payload["loss"] == pytest.approx(1.6)
    assert payload["prefetch_depth"] == 1
    h = payload["decode_health"]
    assert h["precision"] == 1.0 and h["recall"] == 1.0
    assert h["flagged_total"] == 4.0 and h["adv_total"] == 4.0
    assert h["decode_residual"] == pytest.approx(1e-7)
    assert not (tmp_path / "status.json.tmp").exists()
    # a missed detection shows up as recall < 1
    hb.observe({"step": 5, "loss": 1.0, "located_errors": 0.0,
                "det_tp": 0.0, "det_adv": 1.0})
    h = hb.beat(5, 8)["decode_health"]
    assert h["recall"] == pytest.approx(4 / 5) and h["precision"] == 1.0


@pytest.mark.core
def test_heartbeat_disabled_is_noop(tmp_path):
    hb = RunHeartbeat(None)
    hb.observe({"step": 1, "loss": 1.0})
    assert hb.beat(1, 10) is None
    hb2 = RunHeartbeat(str(tmp_path), enabled=False)
    assert hb2.beat(1, 10) is None
    assert list(tmp_path.iterdir()) == []
    # no health section when the route emits no detection columns
    hb3 = RunHeartbeat(str(tmp_path))
    hb3.observe({"step": 1, "loss": 1.0})
    assert "decode_health" not in hb3.beat(1, 2)


@pytest.mark.core
def test_heartbeat_schema_version(tmp_path):
    """Every status.json payload — beats AND terminals, including a
    terminal written before any beat — carries the schema version
    (consumers assert it when present, tolerate its absence)."""
    from draco_tpu.obs import STATUS_SCHEMA

    hb = RunHeartbeat(str(tmp_path))
    payload = hb.beat(1, 2)
    assert payload["schema"] == STATUS_SCHEMA
    assert json.load(open(tmp_path / "status.json"))["schema"] == \
        STATUS_SCHEMA
    hb2 = RunHeartbeat(str(tmp_path / "crash_early"))
    term = hb2.terminal("crashed", cause="boom")  # no beat ever happened
    assert term["schema"] == STATUS_SCHEMA and term["state"] == "crashed"


@pytest.mark.core
def test_heartbeat_tolerates_missing_column_families(tmp_path):
    """Optional column families (health / guard / forensics) may be absent
    per record — a baseline route emits none, eval records carry none, and
    a mixed-route train_dir interleaves both. Records without a family
    must not advance or poison its accumulators, and a TRAILING record
    without the health family must not hide the cumulative health block
    (regression: decode_health() used to key off the newest record)."""
    hb = RunHeartbeat(str(tmp_path), num_workers=4)
    hb.observe({"step": 1, "loss": 2.0, "located_errors": 1.0,
                "det_tp": 1.0, "det_adv": 1.0, "guard_trips": 0.0,
                "skipped_steps": 0.0, "decode_residual": 1e-7})
    # baseline-route record: no health, no guard, no forensics columns
    hb.observe({"step": 2, "loss": 1.9})
    payload = hb.beat(2, 4)
    h = payload["decode_health"]
    assert h["precision"] == 1.0 and h["recall"] == 1.0
    assert h["flagged_total"] == 1.0 and h["adv_total"] == 1.0
    assert h["decode_residual"] == pytest.approx(1e-7)
    assert payload["guard"] == {"trips": 0.0, "skipped_steps": 0.0}
    assert payload["loss"] == pytest.approx(1.9)  # progress still newest
    # an eval-shaped record (no step-metrics at all) is equally harmless
    hb.observe({"step": 2, "split": "eval"})
    assert hb.beat(2, 4)["decode_health"]["adv_total"] == 1.0


# --------------------------------------------------------------------------
# obs/forensics.py — packed masks, record round trip, the ledger
# --------------------------------------------------------------------------

@pytest.mark.core
def test_forensics_mask_pack_roundtrip():
    """pack -> f32 block -> host record int -> JSON -> unpack is exact for
    every n in the supported range, INCLUDING masks whose packed word is a
    float32 NaN bit pattern (workers 23..30 all accused) — the case a
    float()/JSON path would silently destroy. n > 64 raises the named
    bound."""
    from draco_tpu.obs import forensics as fx

    rng = np.random.RandomState(7)
    for n in (1, 7, 24, 31, 32, 33, 64):
        for _ in range(10):
            m = rng.rand(n) < 0.5
            packed = np.asarray(jax.jit(fx.pack_bits)(jnp.asarray(m)))
            assert packed.dtype == np.float32
            assert packed.shape == (fx.num_mask_words(n),)
            words = [fx.record_value(f"{fx.MASK_PREFIX}accused0", w)
                     for w in packed]
            words = json.loads(json.dumps(words))  # the JSONL round trip
            assert all(isinstance(w, int) for w in words)
            assert fx.unpack_bits(words, n) == tuple(bool(b) for b in m)
    # adversarial patterns: packed word is an f32 NaN / Inf bit pattern
    for n, idx in ((32, range(23, 32)), (32, range(0, 32)),
                   (31, range(23, 31))):
        m = np.array([i in idx for i in range(n)])
        packed = np.asarray(fx.pack_bits(jnp.asarray(m)))
        words = json.loads(json.dumps(
            [fx.record_value(f"{fx.MASK_PREFIX}adv0", w) for w in packed]))
        assert fx.unpack_bits(words, n) == tuple(m)
    with pytest.raises(ValueError, match="num_workers <= 64"):
        fx.num_mask_words(65)
    assert fx.mask_metric_names(8) == (
        "wmask_accused0", "wmask_present0", "wmask_adv0")
    assert len(fx.mask_metric_names(33)) == 6  # two words per kind


@pytest.mark.core
def test_forensics_pack_bits_sharded_mask_matches_replicated():
    """Regression (caught by the chaos tp cell): packing a mesh-SHARDED
    mask must agree bit-for-bit with packing the same mask replicated. The
    original pad-concat+reshape formulation shifted every bit by one under
    the GSPMD partitioner on the w×tp mesh — worker 3's accusation landed
    on bit 4 — while the fetched mask itself was correct."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from draco_tpu.obs import forensics as fx
    from draco_tpu.parallel.mesh import make_mesh_wtp
    from draco_tpu.runtime import WORKER_AXIS

    mesh = make_mesh_wtp(4, 2)
    rng = np.random.RandomState(11)
    for _ in range(8):
        mask = rng.rand(8) < 0.4
        md = jax.device_put(jnp.asarray(mask),
                            NamedSharding(mesh, P(WORKER_AXIS)))
        with mesh:
            sharded = np.asarray(jax.jit(fx.pack_bits)(md))
        replicated = np.asarray(fx.pack_bits(jnp.asarray(mask)))
        np.testing.assert_array_equal(sharded.view(np.uint32),
                                      replicated.view(np.uint32))
        assert fx.unpack_bits(
            [int(w) for w in sharded.view(np.uint32)], 8
        ) == tuple(bool(b) for b in mask)


@pytest.mark.core
def test_forensics_pack_gates_absent_workers():
    """An absent worker is never an accused worker: pack_mask_columns
    re-gates the accusation set by presence, whatever the caller passed."""
    from draco_tpu.obs import forensics as fx

    accused = jnp.asarray([True, True, False, False])
    present = jnp.asarray([True, False, True, False])
    cols = fx.pack_mask_columns(accused, present, jnp.zeros(4, bool))
    masks = fx.record_masks(
        {k: fx.record_value(k, v) for k, v in cols.items()}, 4)
    assert masks["accused"] == (True, False, False, False)
    assert masks["present"] == (True, False, True, False)
    # present=None means everyone present
    cols = fx.pack_mask_columns(accused, None, jnp.zeros(4, bool))
    masks = fx.record_masks(
        {k: fx.record_value(k, v) for k, v in cols.items()}, 4)
    assert masks["present"] == (True,) * 4
    assert masks["accused"] == (True, True, False, False)


def _mask_record(step, accused, present, adv):
    """A materialized record with packed forensics columns (host ints)."""
    from draco_tpu.obs import forensics as fx

    cols = fx.pack_mask_columns(jnp.asarray(accused, bool),
                                jnp.asarray(present, bool),
                                jnp.asarray(adv, bool))
    rec = {"step": step, "loss": 1.0}
    rec.update({k: fx.record_value(k, v) for k, v in cols.items()})
    return rec


@pytest.mark.core
def test_accusation_ledger_counters_trust_episodes():
    """The ledger folds per-step masks into per-worker counters, an EW
    trust score, and attack EPISODES: consecutive accusations are one
    episode; a present-and-clean step closes it; an ABSENT step neither
    accuses nor exonerates (the episode stays open across the gap)."""
    from draco_tpu.obs.forensics import AccusationLedger

    lg = AccusationLedger(4)
    ones = [True] * 4
    # steps 1-3: worker 1 accused (and truly adversarial)
    for step in (1, 2, 3):
        assert lg.observe(_mask_record(step, [0, 1, 0, 0], ones,
                                       [0, 1, 0, 0]))
    # step 4: worker 1 ABSENT — not accused, episode must stay open
    assert lg.observe(_mask_record(4, [0, 0, 0, 0], [1, 0, 1, 1],
                                   [0, 0, 0, 0]))
    # step 5: worker 1 back and accused again — SAME episode, extended;
    # worker 2 falsely accused (honest) — a new 1-step episode
    assert lg.observe(_mask_record(5, [0, 1, 1, 0], ones, [0, 1, 0, 0]))
    # step 6: everyone clean — both episodes close
    assert lg.observe(_mask_record(6, [0, 0, 0, 0], ones, [0, 0, 0, 0]))
    # a record with no forensics columns is ignored, not an error
    assert not lg.observe({"step": 7, "loss": 0.5})

    rows = {r["worker"]: r for r in lg.worker_rows()}
    assert rows[1]["accused"] == 4 and rows[1]["tp"] == 4
    assert rows[1]["present"] == 5  # absent step 4 not counted
    assert rows[1]["precision"] == 1.0 and rows[1]["recall"] == 1.0
    assert rows[2]["accused"] == 1 and rows[2]["fp"] == 1
    assert rows[2]["precision"] == 0.0  # falsely accused once, never adv
    assert rows[0]["accused"] == 0 and rows[0]["trust"] == 1.0
    assert rows[1]["trust"] < rows[2]["trust"] < 1.0
    eps = lg.all_episodes()
    assert len(eps) == 2 and not lg.open_episodes()
    w1 = next(e for e in eps if e["worker"] == 1)
    assert (w1["start"], w1["end"], w1["steps"]) == (1, 5, 4)
    w2 = next(e for e in eps if e["worker"] == 2)
    assert (w2["start"], w2["end"], w2["steps"]) == (5, 5, 1)
    summary = lg.summary()
    assert summary["top_suspects"][0]["worker"] == 1
    assert summary["open_episodes"] == 0 and summary["episodes_total"] == 2

    # an episode still running at the last step reports as open
    lg2 = AccusationLedger(2)
    lg2.observe(_mask_record(1, [1, 0], [1, 1], [1, 0]))
    lg2.observe(_mask_record(2, [1, 0], [1, 1], [1, 0]))
    (ep,) = lg2.open_episodes()
    assert ep["open"] and ep["steps"] == 2
    assert lg2.summary()["open_episodes"] == 1


@pytest.mark.core
def test_heartbeat_forensics_block(tmp_path):
    """status.json grows the forensics block when the route ships mask
    columns and num_workers is wired; stays absent otherwise."""
    hb = RunHeartbeat(str(tmp_path), num_workers=4)
    hb.observe(_mask_record(1, [0, 0, 1, 0], [1, 1, 1, 1], [0, 0, 1, 0]))
    payload = hb.beat(1, 2)
    fx_block = payload["forensics"]
    assert fx_block["num_workers"] == 4
    assert fx_block["top_suspects"] == [
        {"worker": 2, "accused": 1, "trust": fx_block["trust"][2]}]
    assert fx_block["open_episodes"] == 1
    # no num_workers -> no ledger -> no block (backward compatible)
    hb2 = RunHeartbeat(str(tmp_path / "plain"))
    hb2.observe(_mask_record(1, [0, 1], [1, 1], [0, 1]))
    assert "forensics" not in hb2.beat(1, 2)


@pytest.mark.core
def test_forensics_straggler_never_accused_both_codes():
    """End of the in-graph chain for both codes under straggler drops: the
    packed accusation set never contains an absent worker — an erasure is
    known-missing, not evidence (cyclic flags present rows only; the vote
    neither counts nor flags absent members; pack re-gates by presence)."""
    from draco_tpu.coding import cyclic, repetition
    from draco_tpu.obs import forensics as fx
    from draco_tpu.parallel.common import accusation_mask

    rng = np.random.RandomState(5)
    code = cyclic.build_cyclic_code(8, 1)
    g = rng.randn(8, 64).astype(np.float32)
    rf = jnp.asarray(1.0 + rng.randn(64).astype(np.float32))
    er, ei = cyclic.encode_shared(code, jnp.asarray(g))
    # worker 6 is an adversary AND worker 2 straggles (t+e <= s... s=1:
    # use an erasure-only step and an adversary-only step)
    pres = jnp.asarray(np.arange(8) != 2)
    er_d = er * pres[:, None]
    ei_d = ei * pres[:, None]
    _, _, h = parity.run_jitted(
        cyclic.decode, code, er_d, ei_d, rf, present=pres, with_health=True)
    h["bad_rows"] = fx.nonfinite_rows(jnp.asarray(g))
    accused = np.asarray(accusation_mask(h, pres))
    assert not accused[2]  # absent != accused
    assert accused.sum() == 0  # erasure-only: nobody accused

    rep = repetition.build_repetition_code(8, 4)
    rows = np.tile(rng.randn(2, 1, 16).astype(np.float32),
                   (1, 4, 1)).reshape(8, 16)
    bad = rows.copy()
    bad[5] *= -100.0  # adversary... who also straggles
    pres = jnp.asarray(np.arange(8) != 5)
    _, vh = repetition.majority_vote(rep, jnp.asarray(bad), present=pres,
                                     with_health=True)
    cols = fx.pack_mask_columns(
        vh["flagged"] | fx.nonfinite_rows(jnp.asarray(bad)), pres,
        jnp.asarray(np.arange(8) == 5))
    masks = fx.record_masks(
        {k: fx.record_value(k, v) for k, v in cols.items()}, 8)
    assert not any(masks["accused"])  # its row never arrived


@pytest.mark.core
def test_cyclic_loud_rows_attribute_beyond_budget():
    """The forensic-only loud-row mask: beyond the locator budget (2
    corrupt rows, s=1) the fitted-codeword flag set is blind to rows the
    fit absorbed, but the magnitude outliers ARE the corrupt rows — the
    accusation union must name both. In budget, loud adds nothing beyond
    the exact flag set (precision stays 1.0)."""
    from draco_tpu.coding import cyclic
    from draco_tpu.parallel.common import accusation_mask

    code = cyclic.build_cyclic_code(8, 1)
    rng = np.random.RandomState(0)
    g = rng.randn(8, 64).astype(np.float32)
    rf = jnp.asarray(1.0 + rng.randn(64).astype(np.float32))
    er, ei = cyclic.encode_shared(code, jnp.asarray(g))
    decode = parity.jitted(cyclic.decode, code, with_health=True)
    for rows in ([2, 5], [0, 4], [1, 6], [3, 7]):
        er2, ei2 = er, ei
        for r in rows:
            er2, ei2 = er2.at[r].mul(-100.0), ei2.at[r].mul(-100.0)
        _, _, h = decode(er2, ei2, rf)
        accused = np.asarray(accusation_mask(h))
        assert set(rows) <= set(np.nonzero(accused)[0].tolist()), (
            rows, np.nonzero(accused)[0])
    # in budget: accusation == the exact flag set (no honest loud rows)
    er1, ei1 = er.at[3].mul(-100.0), ei.at[3].mul(-100.0)
    _, _, h1 = decode(er1, ei1, rf)
    np.testing.assert_array_equal(np.asarray(accusation_mask(h1)),
                                  np.arange(8) == 3)
    # clean: nobody accused
    _, _, h0 = decode(er, ei, rf)
    assert np.asarray(accusation_mask(h0)).sum() == 0


@pytest.mark.core
def test_nonfinite_rows_attribute_through_shared_encode():
    """A NaN gradient row smears across EVERY codeword under the shared
    algebraic encode (0·NaN = NaN), so the wire can't attribute it — the
    ingest check (nonfinite_rows on the raw rows) must, exactly."""
    from draco_tpu.coding import cyclic
    from draco_tpu.obs import forensics as fx
    from draco_tpu.parallel.common import accusation_mask

    code = cyclic.build_cyclic_code(8, 1)
    rng = np.random.RandomState(1)
    g = rng.randn(8, 64).astype(np.float32)
    g[3, 17] = np.nan
    rf = jnp.asarray(1.0 + rng.randn(64).astype(np.float32))
    er, ei = cyclic.encode_shared(code, jnp.asarray(g))
    assert not np.isfinite(np.asarray(er)).all(axis=1).any()  # all smeared
    _, _, h = parity.run_jitted(
        cyclic.decode, code, er, ei, rf, with_health=True)
    h["bad_rows"] = fx.nonfinite_rows(jnp.asarray(g))
    np.testing.assert_array_equal(np.asarray(accusation_mask(h)),
                                  np.arange(8) == 3)
    # the (n, hat_s, d) simulate-mode stack reduces over the lane axes too
    g3 = rng.randn(4, 3, 8).astype(np.float32)
    g3[2, 1, 0] = np.inf
    np.testing.assert_array_equal(np.asarray(fx.nonfinite_rows(
        jnp.asarray(g3))), np.arange(4) == 2)


# --------------------------------------------------------------------------
# MetricWriter buffering + Segments monotonic clock (utils/metrics.py)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_metric_writer_buffers_until_flush_or_close(tmp_path):
    from draco_tpu.utils.metrics import MetricWriter

    w = MetricWriter(str(tmp_path), quiet=True, buffer_records=64)
    path = tmp_path / "metrics.jsonl"
    for step in range(3):
        w.write({"step": step, "loss": 1.0})
    assert path.read_text() == ""  # buffered: no per-record file traffic
    w.flush()
    assert len(path.read_text().splitlines()) == 3
    w.write({"step": 3, "loss": 1.0})
    w.close()  # tail safety: close drains the buffer
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["step"] for r in recs] == [0, 1, 2, 3]
    assert all("time" in r for r in recs)  # record stamps stay wall-clock

    # the configurable cap: buffer_records=2 auto-flushes on the 2nd write
    w2 = MetricWriter(str(tmp_path / "b"), quiet=True, buffer_records=2)
    w2.write({"step": 0})
    assert (tmp_path / "b" / "metrics.jsonl").read_text() == ""
    w2.write({"step": 1})
    assert len((tmp_path / "b" / "metrics.jsonl").read_text()
               .splitlines()) == 2
    w2.close()


@pytest.mark.core
def test_segments_use_monotonic_clock(monkeypatch):
    """A wall-clock step backwards (NTP slew) mid-segment must not corrupt
    the duration — begin/end read time.perf_counter, not time.time."""
    import draco_tpu.utils.metrics as metrics_mod

    walltimes = iter([1e9, 1e9 - 3600.0])  # time.time jumps back an hour
    monkeypatch.setattr(metrics_mod.time, "time",
                        lambda: next(walltimes, 0.0))
    seg = metrics_mod.Segments()
    seg.begin("comp")
    seg.end()
    assert 0.0 <= seg.t["comp"] < 1.0
    assert seg.as_dict() == {"t_comp": round(seg.t["comp"], 6)}


# --------------------------------------------------------------------------
# decode / vote health straight off the coding primitives
# --------------------------------------------------------------------------

@pytest.mark.core
def test_cyclic_decode_health_flags_exactly_the_corrupt_rows():
    from draco_tpu.coding import cyclic

    code = cyclic.build_cyclic_code(8, 1)
    rng = np.random.RandomState(0)
    g = rng.randn(8, 64).astype(np.float32)
    rf = jnp.asarray(1.0 + rng.randn(64).astype(np.float32))
    er, ei = cyclic.encode_shared(code, jnp.asarray(g))
    # clean: nothing flagged, residual is float noise
    _, _, h = parity.run_jitted(
        cyclic.decode, code, er, ei, rf, with_health=True)
    assert float(h["residual"]) < 1e-4
    assert np.asarray(h["flagged"]).sum() == 0
    # one corrupt row (rev_grad magnitude): flagged exactly, residual ~ 0
    er1, ei1 = er.at[3].mul(-99.0), ei.at[3].mul(-99.0)
    _, honest, h1 = parity.run_jitted(
        cyclic.decode, code, er1, ei1, rf, with_health=True)
    np.testing.assert_array_equal(
        np.asarray(h1["flagged"]),
        np.arange(8) == 3)
    assert float(h1["residual"]) < 1e-4
    assert not bool(np.asarray(honest)[3])
    # erasure-only: stragglers are known-missing, never "detected"
    pres = np.arange(8) != 5
    _, _, h2 = parity.run_jitted(
        cyclic.decode, code, er * pres[:, None], ei * pres[:, None], rf,
        present=jnp.asarray(pres), with_health=True)
    assert np.asarray(h2["flagged"]).sum() == 0
    assert float(h2["residual"]) < 1e-4


@pytest.mark.core
def test_cyclic_decode_health_raises_fault_beyond_budget():
    """t = s+1 corruptions exceed the exactness guarantee: the health
    signal must say so — flagged count over budget and/or a loud
    residual — instead of reporting a clean decode."""
    from draco_tpu.coding import cyclic

    code = cyclic.build_cyclic_code(8, 1)
    rng = np.random.RandomState(1)
    g = rng.randn(8, 64).astype(np.float32)
    rf = jnp.asarray(1.0 + rng.randn(64).astype(np.float32))
    er, ei = cyclic.encode_shared(code, jnp.asarray(g))
    decode = parity.jitted(cyclic.decode, code, with_health=True)
    for rows in ([2, 5], [0, 4], [1, 6]):
        er2, ei2 = er, ei
        for r in rows:
            er2, ei2 = er2.at[r].mul(-99.0), ei2.at[r].mul(-99.0)
        _, _, h = decode(er2, ei2, rf)
        flagged = int(np.asarray(h["flagged"]).sum())
        assert flagged > code.s or float(h["residual"]) > 1e-4, (
            rows, flagged, float(h["residual"]))


@pytest.mark.core
def test_cyclic_decode_layers_health_unions_layers():
    from draco_tpu.coding import cyclic

    code = cyclic.build_cyclic_code(8, 1)
    rng = np.random.RandomState(2)
    g = rng.randn(8, 24).astype(np.float32)
    rf = jnp.asarray(1.0 + rng.randn(24).astype(np.float32))
    er, ei = cyclic.encode_shared(code, jnp.asarray(g))
    # corrupt row 4 only inside the second layer's coordinates [10, 24)
    er = er.at[4, 10:].add(100.0)
    _, _, h = parity.run_jitted(cyclic.decode_layers, code, er, ei, rf,
                              offsets=[0, 10, 24], with_health=True)
    np.testing.assert_array_equal(np.asarray(h["flagged"]),
                                  np.arange(8) == 4)
    assert float(h["residual"]) < 1e-4
    assert np.ndim(h["residual"]) == 0


@pytest.mark.core
def test_majority_vote_health():
    from draco_tpu.coding import repetition

    code = repetition.build_repetition_code(8, 4)
    rng = np.random.RandomState(3)
    rows = np.tile(rng.randn(2, 1, 16).astype(np.float32),
                   (1, 4, 1)).reshape(8, 16)
    # all honest: full agreement, nothing flagged
    voted, h = repetition.majority_vote(code, jnp.asarray(rows),
                                        with_health=True)
    assert float(h["vote_agree"]) == 1.0
    assert int(h["flagged_groups"]) == 0
    assert np.asarray(h["flagged"]).sum() == 0
    # one adversary in group 1: flagged exactly, agreement drops by 1/8
    bad = rows.copy()
    bad[5] *= -100.0
    voted_b, hb = repetition.majority_vote(code, jnp.asarray(bad),
                                           with_health=True)
    np.testing.assert_array_equal(np.asarray(hb["flagged"]),
                                  np.arange(8) == 5)
    assert float(hb["vote_agree"]) == pytest.approx(7 / 8)
    assert int(hb["flagged_groups"]) == 1
    np.testing.assert_array_equal(np.asarray(voted_b), np.asarray(voted))
    # an absent member neither votes nor is flagged
    pres = np.arange(8) != 5
    _, hp = repetition.majority_vote(code, jnp.asarray(bad),
                                     present=jnp.asarray(pres),
                                     with_health=True)
    assert np.asarray(hp["flagged"]).sum() == 0
    assert float(hp["vote_agree"]) == 1.0
    # health is an opt-in second return: the bare call is unchanged
    bare = repetition.majority_vote(code, jnp.asarray(bad))
    np.testing.assert_array_equal(np.asarray(bare), np.asarray(voted_b))


# --------------------------------------------------------------------------
# CompileWatch — compile ledger + steady-state retrace guard (ISSUE 5)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_compile_watch_ledger_attribution_and_trace_lane(tmp_path):
    """A labelled dispatch window's builds land in compiles.jsonl with the
    program name and lowering seconds, unlabelled builds record with
    program null, the tracer gets a compile-category lane event per build,
    and the process-wide counters advance."""
    from draco_tpu.obs.compile_watch import global_stats

    tracer = SpanTracer(str(tmp_path / "trace.json"))
    before = global_stats()
    with CompileWatch(ledger_dir=str(tmp_path), tracer=tracer) as w:
        f = jax.jit(lambda x: x * 3.0)
        x = jnp.ones(7)  # utility fill build happens OUTSIDE the label
        with w.expect("prog_a"):
            f(x)
        with w.expect("prog_a"):
            f(x)  # warm: cached, no build
        jax.jit(lambda x: x - 1.0)(x)  # unlabelled build
    tracer.close()
    after = global_stats()

    assert w.builds >= 2 and after["builds"] - before["builds"] >= w.builds
    assert w.steady_recompiles == 0
    assert w.builds_by_program.get("prog_a", 0) >= 1
    snap = w.snapshot()
    assert snap["compiles"] == w.builds
    assert snap["compile_s"] > 0 and snap["steady_recompiles"] == 0

    rows = [json.loads(l) for l in open(tmp_path / "compiles.jsonl")]
    assert len(rows) == w.builds
    labelled = [r for r in rows if r["program"] == "prog_a"]
    assert labelled and all(not r["steady_recompile"] for r in rows)
    assert all(r.get("lower_s", 0) >= 0 for r in rows)
    assert any(r["program"] is None for r in rows)  # the unlabelled builds

    trace = json.load(open(tmp_path / "trace.json"))
    compile_events = [e for e in trace["traceEvents"]
                      if e.get("cat") == "compile"]
    assert len(compile_events) == w.builds
    assert any(e["args"]["program"] == "prog_a" for e in compile_events)
    for e in compile_events:
        assert e["ph"] == "X" and e["dur"] >= 0


@pytest.mark.core
def test_compile_watch_retrace_guard_trips_on_shape_polymorphic_control():
    """The deliberately shape-polymorphic control: same label, new input
    shape each dispatch. Strict mode raises at the dispatch site after the
    warmup window; warn mode emits RetraceWarning and counts; a cold
    window paying several sub-builds (the program + operand fills) is ONE
    warmup unit and never trips."""
    w = CompileWatch(guard="raise").start()
    try:
        f = jax.jit(lambda x: x * 2.0)
        with w.expect("poly"):
            f(jnp.ones(3))  # cold window: program + fill builds — warmup
        with w.expect("poly"):
            f(jnp.ones(3))  # warm window: no builds
        assert w.steady_recompiles == 0
        with pytest.raises(RetraceError, match="steady-state recompilation"):
            with w.expect("poly"):
                f(jnp.ones((4, 4)))  # the retrace
        assert w.steady_recompiles == 1
    finally:
        w.stop()

    w2 = CompileWatch(guard="warn").start()
    try:
        g = jax.jit(lambda x: x + 2.0)
        with w2.expect("poly2"):
            g(jnp.ones(2))
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            with w2.expect("poly2"):
                g(jnp.ones((2, 2)))
        assert any(issubclass(r.category, RetraceWarning) for r in rec)
        assert w2.steady_recompiles >= 1
    finally:
        w2.stop()

    # guard="off" records but never warns/raises; unlabelled builds are
    # never guarded in any mode
    w3 = CompileWatch(guard="off").start()
    try:
        h = jax.jit(lambda x: x - 2.0)
        with w3.expect("poly3"):
            h(jnp.ones(2))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with w3.expect("poly3"):
                h(jnp.ones((3, 2)))
            jax.jit(lambda x: x / 2.0)(jnp.ones(5))  # unlabelled
        assert w3.steady_recompiles >= 1  # counted, silently
    finally:
        w3.stop()


@pytest.mark.core
def test_compile_watch_warmup_and_key_variants(tmp_path):
    """``key`` separates legitimate shape variants (the chunked loops'
    remainder chunks): each (name, key) label warms up independently. A
    raised warmup budget allows that many compiling windows."""
    w = CompileWatch(guard="raise").start()
    try:
        f = jax.jit(lambda x: x.sum())
        with w.expect("many", key=4):
            f(jnp.ones(4))
        with w.expect("many", key=2):  # remainder chunk: its own warmup
            f(jnp.ones(2))
        assert w.steady_recompiles == 0
        assert set(w.builds_by_program) >= {"many[4]", "many[2]"}
    finally:
        w.stop()

    w2 = CompileWatch(guard="raise", warmup=2).start()
    try:
        g = jax.jit(lambda x: x.max())
        with w2.expect("p"):
            g(jnp.ones(3))
        with w2.expect("p"):
            g(jnp.ones((2, 3)))  # second compiling window: within warmup=2
        assert w2.steady_recompiles == 0
        with pytest.raises(RetraceError):
            with w2.expect("p"):
                g(jnp.ones((3, 3)))  # third: beyond warmup
    finally:
        w2.stop()


@pytest.mark.core
def test_make_compile_watch_construction_rule(tmp_path):
    """Ledger goes next to the trace when tracing, else next to
    metrics.jsonl; non-main processes never write a ledger; config
    validates the guard mode."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.obs import make_compile_watch

    cfg = TrainConfig(trace_dir=str(tmp_path / "t"),
                      train_dir=str(tmp_path / "d"))
    w = make_compile_watch(cfg, NULL_TRACER, True)
    assert w.path == str(tmp_path / "t" / "compiles.jsonl")
    w.stop()
    w = make_compile_watch(TrainConfig(train_dir=str(tmp_path / "d")),
                           NULL_TRACER, True)
    assert w.path == str(tmp_path / "d" / "compiles.jsonl")
    w.stop()
    w = make_compile_watch(cfg, NULL_TRACER, False)  # non-main process
    assert w.path is None
    w.stop()
    with pytest.raises(ValueError, match="compile_guard"):
        TrainConfig(compile_guard="explode").validate()
    with pytest.raises(ValueError, match="guard"):
        CompileWatch(guard="explode")


# --------------------------------------------------------------------------
# tools/trace_report.py
# --------------------------------------------------------------------------

@pytest.mark.core
def test_trace_report_folds_trace_and_metrics(tmp_path, capsys):
    from tools.trace_report import main, make_report

    events = [
        {"name": "dispatch", "ph": "X", "ts": 0.0, "dur": 3000.0,
         "pid": 1, "tid": 1},
        {"name": "dispatch", "ph": "X", "ts": 4000.0, "dur": 1000.0,
         "pid": 1, "tid": 1},
        {"name": "gather", "ph": "X", "ts": 3000.0, "dur": 500.0,
         "pid": 1, "tid": 2},
        {"name": "prefetch_depth", "ph": "C", "ts": 10.0, "pid": 1,
         "args": {"prefetch_depth": 1}},
    ]
    (tmp_path / "trace.json").write_text(
        json.dumps({"traceEvents": events}))
    with open(tmp_path / "metrics.jsonl", "w") as fh:
        fh.write(json.dumps({"step": 1, "loss": 2.0, "t_fetch": 0.25,
                             "t_comp": 1.0}) + "\n")
        fh.write(json.dumps({"step": 2, "loss": 1.5, "t_fetch": 0.25,
                             "t_comp": 1.0}) + "\n")
        fh.write(json.dumps({"step": 2, "split": "eval", "loss": 1.4})
                 + "\n")

    report = make_report(str(tmp_path / "trace.json"),
                         str(tmp_path / "metrics.jsonl"))
    assert report["traced_wall_ms"] == pytest.approx(5.0)
    d = report["phases"]["dispatch"]
    assert d["count"] == 2 and d["total_ms"] == pytest.approx(4.0)
    assert d["share"] == pytest.approx(0.8)
    assert report["counters"]["prefetch_depth"]["max"] == 1
    assert report["metrics"]["train_records"] == 2
    assert report["metrics"]["t_comp_total_s"] == pytest.approx(2.0)

    out_json = tmp_path / "report.json"
    rc = main([str(tmp_path), "--json", str(out_json)])
    assert rc == 0
    table = capsys.readouterr().out
    assert "dispatch" in table and "80.0%" in table
    assert json.load(open(out_json))["phases"]["gather"]["count"] == 1


@pytest.mark.core
def test_trace_report_surfaces_guard_and_decode_health(tmp_path, capsys):
    """The jax-free report header folds the PR 6 guard columns (cumulative
    trips/skips) and the run's decode-health precision/recall from the
    per-step counts — previously invisible to this path — and validates
    the status.json schema version when one is present."""
    from draco_tpu.obs import STATUS_SCHEMA
    from tools.trace_report import fold_status, main, make_report

    events = [{"name": "dispatch", "ph": "X", "ts": 0.0, "dur": 1000.0,
               "pid": 1, "tid": 1}]
    (tmp_path / "trace.json").write_text(json.dumps(
        {"traceEvents": events}))
    with open(tmp_path / "metrics.jsonl", "w") as fh:
        fh.write(json.dumps({"step": 1, "loss": 2.0, "guard_trips": 0.0,
                             "skipped_steps": 0.0, "located_errors": 1.0,
                             "det_tp": 1.0, "det_adv": 1.0}) + "\n")
        fh.write(json.dumps({"step": 2, "loss": 9.0, "guard_trips": 2.0,
                             "skipped_steps": 1.0, "located_errors": 2.0,
                             "det_tp": 1.0, "det_adv": 1.0}) + "\n")
    (tmp_path / "status.json").write_text(json.dumps(
        {"schema": STATUS_SCHEMA, "state": "done", "step": 2}))

    report = make_report(str(tmp_path / "trace.json"),
                         str(tmp_path / "metrics.jsonl"))
    m = report["metrics"]
    assert m["guard_trips"] == 2.0 and m["skipped_steps"] == 1.0
    assert m["det_precision"] == round(2 / 3, 4)  # rounded in the fold
    assert m["det_recall"] == 1.0
    assert report["run_status"]["schema"] == STATUS_SCHEMA
    rc = main([str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "guard: trips=2 skipped_steps=1" in out
    assert "decode health: precision=0.6667 recall=1.0000" in out

    # an unknown schema version is a loud failure, not a silent misfold
    (tmp_path / "status.json").write_text(json.dumps(
        {"schema": 99, "state": "done"}))
    with pytest.raises(SystemExit, match="schema 99"):
        fold_status(str(tmp_path / "status.json"))


@pytest.mark.core
def test_trace_report_tolerates_partial_artifacts(tmp_path, capsys):
    """A killed run's leftovers must still fold: missing metrics.jsonl,
    then an empty one, then one with a torn tail line — and the tracer's
    droppedEvents count is surfaced in the header instead of silently
    omitted (the trace is a sliding window when it's nonzero)."""
    from tools.trace_report import main, make_report

    events = [{"name": "dispatch", "ph": "X", "ts": 0.0, "dur": 1000.0,
               "pid": 1, "tid": 1}]
    (tmp_path / "trace.json").write_text(json.dumps(
        {"traceEvents": events, "droppedEvents": 123}))

    # missing metrics.jsonl
    report = make_report(str(tmp_path / "trace.json"),
                         str(tmp_path / "metrics.jsonl"))
    assert "metrics" not in report
    assert report["dropped_events"] == 123
    rc = main([str(tmp_path)])
    assert rc == 0
    head = capsys.readouterr().out.splitlines()[0]
    assert "DROPPED EVENTS: 123" in head

    # empty metrics.jsonl
    (tmp_path / "metrics.jsonl").write_text("")
    report = make_report(str(tmp_path / "trace.json"),
                         str(tmp_path / "metrics.jsonl"))
    assert report["metrics"]["train_records"] == 0

    # torn tail line (run killed mid-write) + blank lines
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "loss": 2.0}) + "\n\n"
        + '{"step": 2, "los')
    report = make_report(str(tmp_path / "trace.json"),
                         str(tmp_path / "metrics.jsonl"))
    assert report["metrics"]["train_records"] == 1
    # a clean trace reports dropped_events == 0 and no header warning
    (tmp_path / "trace.json").write_text(json.dumps(
        {"traceEvents": events}))
    rc = main([str(tmp_path)])
    assert rc == 0
    head = capsys.readouterr().out.splitlines()[0]
    assert "DROPPED" not in head


# --------------------------------------------------------------------------
# obs/numerics.py — the wire & numerics observatory (ISSUE 10)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_numerics_stage_columns_and_exponent_histogram():
    """Known tensor -> exact range stats: absmax/rms over finite elements,
    threshold fractions over all elements, exponent-bin fractions summing
    to the finite-nonzero fraction."""
    from draco_tpu.obs import numerics as nx

    x = jnp.asarray([1.0, -2.0, 0.5, 0.0, 2.0 ** -20, 2.0 ** 10,
                     -(2.0 ** -30), 300.0], jnp.float32)
    cols = {k: float(v) for k, v in nx.stage_columns("wire", [x],
                                                     block=4).items()}
    assert cols["nx_wire_absmax"] == pytest.approx(1024.0)
    assert cols["nx_wire_rms"] == pytest.approx(
        float(np.sqrt(np.mean(np.square(np.asarray(x))))), rel=1e-6)
    # bf16 shares f32's exponent range, so only f32 SUBNORMALS sit under
    # the bf16 subnormal minimum — and XLA:CPU flushes those to zero
    # before the stats see them, so the honest count here is 0 (the
    # column matters on non-FTZ backends and for future narrower dtypes)
    assert cols["nx_wire_uf_bf16"] == 0.0
    assert cols["nx_wire_of_bf16"] == 0.0
    assert cols["nx_wire_nonfinite"] == 0.0
    # exponent bins cover the finite nonzero elements exactly
    hist = sum(cols[f"nx_wire_exp{i}"] for i in range(nx.NUM_EXP_BINS))
    assert hist == pytest.approx(7 / 8)  # one exact zero excluded
    assert cols["nx_wire_exp5"] == pytest.approx(2 / 8)  # 2^10 and 300
    assert cols["nx_wire_exp1"] == pytest.approx(2 / 8)  # 2^-20, 2^-30
    # int8 underflow threshold is per 4-element block: in block [1,-2,.5,0]
    # nothing sits under absmax/254; in block [2^-20, 2^10, -2^-30, 300]
    # the two tiny values round to zero at scale 1024/127
    assert cols["nx_wire_uf_int8"] == pytest.approx(2 / 8)


@pytest.mark.core
def test_numerics_columns_nan_safe_sentinels():
    """An injected NaN/Inf never reaches a stats column: absmax/rms mask
    to the finite elements, the fractions stay in [0, 1], and the
    nonfinite fraction carries the fault signal (the chaos-matrix
    NaN-safety contract)."""
    from draco_tpu.obs import numerics as nx

    x = jnp.asarray([[1.0, float("nan"), 2.0, float("inf")],
                     [0.5, 1.5, -1.0, 3.0]], jnp.float32)
    cols = {k: float(v) for k, v in nx.stage_columns("grad", [x],
                                                     block=4).items()}
    assert all(np.isfinite(v) for v in cols.values()), cols
    assert cols["nx_grad_nonfinite"] == pytest.approx(2 / 8)
    assert cols["nx_grad_absmax"] == pytest.approx(3.0)
    # all-nonfinite input still yields finite sentinels
    bad = jnp.full((4,), float("nan"), jnp.float32)
    cols = {k: float(v) for k, v in nx.stage_columns("agg", [bad],
                                                     block=4).items()}
    assert all(np.isfinite(v) for v in cols.values()), cols
    assert cols["nx_agg_nonfinite"] == 1.0 and cols["nx_agg_absmax"] == 0.0


@pytest.mark.core
def test_quantize_rows_bf16_int8_and_row_identity():
    """bf16 nearest == the astype round trip; int8 per-block error is
    bounded by half an LSB of the block scale; bitwise-identical rows
    quantize bitwise-identically under BOTH rounding modes (maj_vote's
    soundness condition); stochastic rounding is deterministic per key."""
    from draco_tpu.obs import numerics as nx

    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(3, 40).astype(np.float32) * 10.0)
    qb = nx.quantize_rows(x, "bf16")
    np.testing.assert_array_equal(
        np.asarray(qb),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    qi = np.asarray(nx.quantize_rows(x, "int8", block=16))
    xn = np.asarray(x)
    # per-(row, 16-block) scale: |err| <= scale/2 = absmax/254
    for r in range(3):
        for b0 in range(0, 40, 16):
            blk = xn[r, b0:b0 + 16]
            scale = np.abs(blk).max() / 127.0
            assert np.max(np.abs(qi[r, b0:b0 + 16] - blk)) <= scale / 2 + 1e-7
    # identical rows stay identical (shared noise draw across rows)
    import jax as _jax

    same = jnp.broadcast_to(x[0], (4, 40))
    key = _jax.random.key(3)
    for mode in ("bf16", "int8"):
        q = np.asarray(nx.quantize_rows(same, mode, block=16, key=key))
        assert all(np.array_equal(q[0], q[i]) for i in range(4))
        q2 = np.asarray(nx.quantize_rows(same, mode, block=16, key=key))
        np.testing.assert_array_equal(q, q2)  # keyed == deterministic
    # int8 of a non-finite input maps to 0 (no NaN encoding on an integer
    # wire); bf16 keeps the NaN (bf16 has one)
    bad = jnp.asarray([[1.0, float("nan")]], jnp.float32)
    assert np.asarray(nx.quantize_rows(bad, "int8", block=2))[0, 1] == 0.0
    assert np.isnan(np.asarray(nx.quantize_rows(bad, "bf16"))[0, 1])


@pytest.mark.core
def test_wire_ledger_arithmetic():
    """Logical bytes ledger: cyclic ships re+im (2 words/element), others
    one; int8 adds one f32 scale per block; per-step = n x per-worker."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.obs import numerics as nx

    cfg = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                      shadow_block=256)
    led = nx.wire_ledger(cfg, 1000)
    per = led["bytes_per_worker"]
    assert per["f32"] == 2 * 4 * 1000
    assert per["bf16"] == 2 * 2 * 1000
    assert per["int8"] == 2 * 1000 + 4 * 2 * 4  # 4 blocks of 256 per half
    assert led["bytes_per_step"] == {k: v * 8 for k, v in per.items()}
    cfg2 = TrainConfig(approach="maj_vote", group_size=4, num_workers=8)
    led2 = nx.wire_ledger(cfg2, 1000)
    assert led2["bytes_per_worker"]["f32"] == 4 * 1000


@pytest.mark.core
def test_shadow_columns_sentinel_and_agreement():
    """A fault-poisoned shadow comparison lands at the finite sentinel
    (-1.0), never NaN; flag agreement counts present workers only and the
    shadow detection counts score against the seeded truth."""
    from draco_tpu.obs import numerics as nx

    agg = jnp.asarray([1.0, 2.0], jnp.float32)
    flags = jnp.asarray([False, True, False, False])
    sflags = jnp.asarray([False, True, True, False])
    present = jnp.asarray([True, True, True, False])
    adv = jnp.asarray([False, True, False, False])
    cols = nx.shadow_columns(agg, agg * 1.01, 1e-3, flags, sflags, adv,
                             present)
    vals = {k: float(v) for k, v in cols.items()}
    assert vals["shadow_err"] == pytest.approx(0.01, rel=1e-3)
    # worker 2 disagrees; worker 3 is absent and does not count
    assert vals["shadow_flag_agree"] == pytest.approx(2 / 3)
    assert vals["shadow_det_flagged"] == 2.0 and vals["shadow_det_tp"] == 1.0
    poisoned = nx.shadow_columns(
        jnp.asarray([float("nan"), 1.0]), agg, float("nan"), flags, sflags,
        adv, present)
    assert float(poisoned["shadow_err"]) == nx.SHADOW_SENTINEL
    assert float(poisoned["shadow_residual"]) == nx.SHADOW_SENTINEL


@pytest.mark.core
def test_heartbeat_numerics_and_wire_blocks(tmp_path):
    """The heartbeat folds nx_/shadow_ columns into the ``numerics``
    status block (last values, running max of the danger fractions,
    running MIN of the flag agreement) and carries the static ``wire``
    ledger stamped via set_wire — both under the current schema."""
    from draco_tpu.obs import STATUS_SCHEMA

    hb = RunHeartbeat(str(tmp_path))
    hb.set_wire({"family": "cyclic", "dim": 10,
                 "bytes_per_worker": {"f32": 80, "bf16": 40, "int8": 14}})
    hb.observe({"step": 1, "loss": 1.0, "nx_wire_absmax": 5.0,
                "nx_wire_rms": 1.0, "nx_wire_uf_int8": 0.1,
                "nx_grad_nonfinite": 0.0, "shadow_err": 0.01,
                "shadow_flag_agree": 1.0})
    hb.observe({"step": 2, "loss": 0.9, "nx_wire_absmax": 4.0,
                "nx_wire_rms": 0.9, "nx_wire_uf_int8": 0.3,
                "nx_grad_nonfinite": 0.0, "shadow_err": 0.002,
                "shadow_flag_agree": 0.5})
    payload = hb.beat(2, 4)
    assert payload["schema"] == STATUS_SCHEMA == 5
    assert payload["wire"]["bytes_per_worker"]["bf16"] == 40
    nxb = payload["numerics"]
    assert nxb["nx_wire_absmax"] == 4.0  # last value
    assert nxb["nx_wire_uf_int8_max"] == pytest.approx(0.3)  # running max
    assert nxb["shadow_err_max"] == pytest.approx(0.01)
    assert nxb["shadow_flag_agree_min"] == pytest.approx(0.5)  # running min
    # a fault-poisoned shadow comparison (the -1.0 sentinel) is COUNTED,
    # never folded into the extremes — shadow_err_max must not hide it
    hb.observe({"step": 3, "loss": 2.0, "shadow_err": -1.0,
                "shadow_residual": -1.0, "shadow_flag_agree": -1.0})
    nxb = hb.beat(3, 4)["numerics"]
    assert nxb["shadow_err_max"] == pytest.approx(0.01)  # sentinel excluded
    assert nxb["shadow_flag_agree_min"] == pytest.approx(0.5)
    assert nxb["shadow_sentinel_steps"] == 1
    # watch-free runs carry neither block
    hb2 = RunHeartbeat(str(tmp_path / "plain"))
    hb2.observe({"step": 1, "loss": 1.0})
    p2 = hb2.beat(1, 2)
    assert "numerics" not in p2 and "wire" not in p2


def test_numerics_nan_fault_live_columns_finite(tmp_path):
    """Live NaN-safety pin (ISSUE 10 satellite): under an injected
    nan_grad fault the numerics columns carry finite sentinels, the
    nonfinite-fraction column goes loud at the fault step, the rest of
    the metric block still parses, and the step guard trips."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    d = str(tmp_path / "run")
    ds = load_dataset("synthetic-mnist", synthetic_train=128,
                      synthetic_test=32)
    cfg = TrainConfig(network="FC", dataset="synthetic-mnist", batch_size=4,
                      num_workers=8, approach="cyclic", worker_fail=1,
                      err_mode="rev_grad", redundancy="shared", max_steps=5,
                      eval_freq=0, train_dir=d, log_every=1, step_guard="on",
                      numerics_watch="on", shadow_wire="bf16",
                      fault_spec="nan_grad@3:w2", steps_per_call=5)
    tr = Trainer(cfg, mesh=make_mesh(8), dataset=ds, quiet=True)
    tr.run()
    tr.close()
    recs = [json.loads(l) for l in open(tmp_path / "run" / "metrics.jsonl")]
    train = [r for r in recs if "loss" in r and r.get("split") != "eval"]
    assert [r["step"] for r in train] == [1, 2, 3, 4, 5]
    for r in train:
        for k, v in r.items():
            if k.startswith(("nx_", "shadow_")):
                assert np.isfinite(v), (r["step"], k, v)
    fault = train[2]
    assert fault["nx_grad_nonfinite"] > 0.0  # the fault is VISIBLE
    assert fault["guard_trips"] >= 1.0 and fault["skipped_steps"] == 1.0
    # shadow comparison at the fault step degrades to the sentinel or a
    # finite value — never NaN (columns asserted finite above); clean
    # steps stay pristine
    clean = [r for r in train if r["step"] != 3]
    assert all(r["nx_grad_nonfinite"] == 0.0 for r in clean)
    assert all(r["guard_trips"] == 0.0 for r in clean)
    status = json.load(open(tmp_path / "run" / "status.json"))
    assert status["numerics"]["nx_grad_nonfinite_max"] > 0.0
    assert status["wire"]["family"] == "cyclic"
