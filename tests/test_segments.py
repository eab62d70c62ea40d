"""Streaming segmented wire (ISSUE 16): the quantum/bounds algebra, the
segmented ledger, the S=1 bitwise rail, S∈{2,4} equivalence on both
production loops (bounded-err aggregate, IDENTICAL detection P/R, guard
trips and forensics masks vs S=1, under a live adversary + straggler
drops, compile_guard="raise", 0 steady retraces), the autopilot
segments_up/segments_down dials, the decode-on-arrival pipeline rails,
and the flipped-row controls proving the perf_watch segment gates live.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu.config import TrainConfig
from draco_tpu.obs import numerics as nx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Q = nx.SEGMENT_QUANTUM


# --------------------------------------------------------------------------
# quantum + bounds algebra (jax-free units)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_segment_quantum_pins_tile_d():
    """SEGMENT_QUANTUM is the jax-free mirror of the decode kernels'
    d-tile: the two constants must never drift apart, or segment cuts
    stop landing on kernel tile boundaries."""
    from draco_tpu.ops import coded

    assert nx.SEGMENT_QUANTUM == coded.TILE_D


@pytest.mark.core
def test_wire_segment_bounds_algebra():
    b = nx.wire_segment_bounds(4 * Q, 4)
    assert b == (0, Q, 2 * Q, 3 * Q, 4 * Q)
    # monotone cover with quantum-aligned interior cuts, uneven d
    d = 2 * Q + 1808
    b = nx.wire_segment_bounds(d, 2)
    assert b[0] == 0 and b[-1] == d
    assert list(b) == sorted(set(b))
    assert all(c % Q == 0 for c in b[1:-1])
    # d smaller than one quantum collapses to a single segment, never
    # sub-quantum slivers
    assert nx.wire_segment_bounds(100, 4) == (0, 100)
    assert nx.wire_segment_bounds(Q, 8) == (0, Q)
    # degenerate sizes
    assert nx.wire_segment_bounds(0, 2) == (0, 0)
    assert nx.wire_segment_bounds(d, 1) == (0, d)
    # more segments than whole quanta: every emitted segment still real
    b = nx.wire_segment_bounds(3 * Q, 8)
    assert b == (0, Q, 2 * Q, 3 * Q)
    # int8 block that does not divide the quantum: cuts fall back to the
    # scale-block granularity so no block ever straddles a cut
    b = nx.wire_segment_bounds(1000, 2, block=48)
    assert b[0] == 0 and b[-1] == 1000
    assert all(c % 48 == 0 for c in b[1:-1]) and len(b) == 3


@pytest.mark.core
def test_cfg_segment_bounds_block_alignment():
    """cfg_segment_bounds is THE one bounds source: int8 wires align cuts
    to the per-block scale granularity (the quantize-then-slice bitwise
    invariance contract), f32 wires only to the kernel d-tile."""
    f32 = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                      redundancy="shared", wire_segments=2)
    i8 = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                     redundancy="shared", wire_segments=2,
                     wire_dtype="int8", shadow_block=48)
    d = 2 * Q + 96
    assert nx.cfg_segment_bounds(f32, d) == nx.wire_segment_bounds(d, 2)
    assert nx.cfg_segment_bounds(i8, d) == nx.wire_segment_bounds(d, 2,
                                                                  block=48)
    # shadow_block dividing the quantum keeps the quantum cuts
    i8b = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                      redundancy="shared", wire_segments=2,
                      wire_dtype="int8", shadow_block=64)
    assert nx.cfg_segment_bounds(i8b, d) == nx.wire_segment_bounds(d, 2)


@pytest.mark.core
def test_wire_ledger_segments_block():
    """The ledger's segments block: per-segment physical bytes sum
    EXACTLY to the per-worker/per-step rows for every wire dtype — the
    block-aligned cuts hide no padding at the seams."""
    d = 3 * Q + 1000
    for kw, s in ((dict(), 1), (dict(wire_segments=4), 4),
                  (dict(wire_segments=2, wire_dtype="int8",
                        shadow_round="stochastic"), 2),
                  (dict(wire_segments=2, wire_dtype="bf16"), 2)):
        cfg = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                          redundancy="shared", **kw)
        led = nx.wire_ledger(cfg, d)
        seg = led["segments"]
        assert seg["count"] == len(seg["bounds"]) - 1 == s
        assert seg["bounds"][0] == 0 and seg["bounds"][-1] == d
        assert sum(seg["physical_bytes_per_worker"]) == \
            led["physical_bytes_per_worker"]
        assert sum(seg["physical_bytes_per_step"]) == \
            led["physical_bytes_per_step"]
        assert len(seg["physical_bytes_per_worker"]) == s


@pytest.mark.core
def test_config_rejects_bad_segments():
    with pytest.raises(ValueError, match="wire_segments"):
        TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                    redundancy="shared", wire_segments=0).validate()
    with pytest.raises(ValueError, match="coded approach"):
        TrainConfig(approach="baseline", wire_segments=2).validate()
    # every coded family may segment (maj_vote wire/ledger-only)
    for ap, kw in (("cyclic", dict(worker_fail=1, redundancy="shared")),
                   ("maj_vote", dict(group_size=4, worker_fail=1)),
                   ("approx", dict(worker_fail=0, redundancy="shared",
                                   code_redundancy=1.5))):
        TrainConfig(approach=ap, num_workers=8, wire_segments=2,
                    **kw).validate()


# --------------------------------------------------------------------------
# decode units: the S=1 rail and the segmented fold
# --------------------------------------------------------------------------

def _cyclic_fixture(n=8, s=1, d=3 * Q):
    from draco_tpu.coding import cyclic

    code = cyclic.build_cyclic_code(n, s)
    rs = np.random.RandomState(7)
    grads = jnp.asarray(rs.randn(n, d).astype(np.float32) * 0.1)
    r_re, r_im = cyclic.encode_shared(code, grads)
    # one live corrupt row — the locator must find it in EVERY segment
    r_re = r_re.at[2].multiply(-50.0)
    r_im = r_im.at[2].multiply(-50.0)
    rf = jnp.asarray(rs.choice([-1.0, 1.0], d).astype(np.float32))
    return code, grads, r_re, r_im, rf


def test_cyclic_single_segment_is_the_unsegmented_decode():
    """decode_segments over the trivial (0, d) partition agrees with the
    unsegmented decode: same honest set, same health verdict, aggregate
    to float noise (the vmapped locator lowers differently, so the
    PRODUCTION S=1 bitwise rail is structural — training/step.py never
    enters the segmented path at S=1; the loop-level tests below pin
    that)."""
    from draco_tpu.coding import cyclic

    code, _, r_re, r_im, rf = _cyclic_fixture()
    dec, honest, health = parity.run_jitted(cyclic.decode, code, r_re, r_im, rf,
                                        with_health=True)
    d1, h1, he1 = parity.run_jitted(cyclic.decode_segments, code, r_re, r_im,
                                  rf, bounds=(0, r_re.shape[1]),
                                  with_health=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(d1),
                               rtol=1e-5, atol=1e-7)
    assert h1.shape == (1, code.n)
    np.testing.assert_array_equal(np.asarray(honest), np.asarray(h1[0]))
    np.testing.assert_array_equal(np.asarray(health["flagged"]),
                                  np.asarray(he1["flagged"]))
    # both residuals sit at float-noise scale; compare absolutely
    np.testing.assert_allclose(float(health["residual"]),
                               float(he1["residual"]), atol=1e-6)


@pytest.mark.parametrize("segs", [2, 3])
def test_cyclic_segmented_fold(segs):
    """S>1: bounded-err aggregate vs the unsegmented decode, every
    segment's locator finds the corrupt row (flagged fold = union is
    IDENTICAL to the unsegmented flag set), and each segment's honest
    mask keeps exactly n-2s rows."""
    from draco_tpu.coding import cyclic

    code, grads, r_re, r_im, rf = _cyclic_fixture()
    d = r_re.shape[1]
    bounds = nx.wire_segment_bounds(d, segs)
    assert len(bounds) == segs + 1
    dec, _, health = parity.run_jitted(
        cyclic.decode, code, r_re, r_im, rf, with_health=True)
    dS, hS, heS = parity.run_jitted(cyclic.decode_segments, code, r_re, r_im,
                                  rf, bounds=bounds, with_health=True)
    truth = np.asarray(jnp.sum(grads, axis=0)) / code.n
    np.testing.assert_allclose(np.asarray(dS), truth, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dS), np.asarray(dec),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(heS["flagged"]),
                                  np.asarray(health["flagged"]))
    assert bool(heS["flagged"][2])
    assert float(heS["residual"]) < 1e-3
    hS = np.asarray(hS)
    assert hS.shape == (segs, code.n)
    assert (hS.sum(axis=1) == code.n - 2 * code.s).all()
    assert not hS[:, 2].any()  # the corrupt row never recombines


def test_approx_segmented_decode_is_exact():
    """The approx family's decode matvec is column-separable and its
    weight solve presence-only: the segmented decode equals the
    unsegmented one BITWISE, and the residual health (accumulated across
    segments before the sqrt) agrees to float noise."""
    from draco_tpu.coding import approx

    n, d = 8, 2 * Q + 512
    code = approx.build_approx_code(n, 1.5)
    rs = np.random.RandomState(11)
    grads = jnp.asarray(rs.randn(n, d).astype(np.float32) * 0.1)
    rows = approx.encode_shared(code, grads)
    present = jnp.asarray(np.array([True] * n))
    present = present.at[3].set(False).at[6].set(False)
    out, v, health = approx.decode(code, rows, present=present,
                                   with_health=True, batch_grads=grads)
    outS, vS, healthS = approx.decode_segments(
        code, rows, nx.wire_segment_bounds(d, 2), present=present,
        with_health=True, batch_grads=grads)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outS))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vS))
    np.testing.assert_allclose(float(health["residual"]),
                               float(healthS["residual"]), rtol=1e-5)
    assert float(healthS["bound"]) == float(health["bound"])


# --------------------------------------------------------------------------
# production-loop equivalence: CNN Trainer, S ∈ {1, 2, 4} × K ∈ {1, 4}
# --------------------------------------------------------------------------

# the committed adversarial scenario (tests/test_chunked_trainer.py): a
# LIVE rev_grad adversary plus a straggler drop inside the cyclic joint
# budget (n=9, s=2, t=1, e=1), guards + incident engine on, strict
# compile sentinel — every run here is also a 0-retrace assertion
CYC = dict(approach="cyclic", num_workers=9, worker_fail=2,
           adversary_count=1, err_mode="rev_grad", straggle_mode="drop",
           straggle_count=1, redundancy="shared")

# detection / guard / forensics columns that must be IDENTICAL between a
# segmented run and its S=1 twin, step by step: the per-segment locators
# fold to ONE per-step verdict (decode_segments docstring), so P/R, guard
# trips and the packed accusation masks cannot move. (honest_located is
# deliberately absent: which honest rows recombine may shift per segment;
# loss/prec drift at f32 noise with the aggregate.)
DET_COLS = ("det_adv", "det_tp", "located_errors", "guard_trips",
            "skipped_steps", "present")


def _train_cfg(**kw):
    base = dict(network="FC", dataset="synthetic-mnist", batch_size=4,
                lr=0.01, momentum=0.9, num_workers=8, max_steps=6,
                eval_freq=0, train_dir="", log_every=1,
                compile_guard="raise", step_guard="on",
                incident_watch="on")
    base.update(kw)
    return TrainConfig(**base)


def _stream(train_dir):
    out = []
    with open(os.path.join(train_dir, "metrics.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            if "loss" in rec and rec.get("split") != "eval":
                out.append(rec)
    return out


def _assert_detection_equal(stream_s, stream_1, n):
    from draco_tpu.obs.forensics import record_masks

    assert len(stream_s) == len(stream_1) > 0
    for rs_, r1 in zip(stream_s, stream_1):
        assert rs_["step"] == r1["step"]
        for col in DET_COLS:
            # routes differ in which columns they emit ("present" is
            # trainer-only) but segmented/unsegmented twins must agree
            # on the set AND the values
            assert (col in rs_) == (col in r1), (r1["step"], col)
            if col in r1:
                assert rs_[col] == r1[col], (r1["step"], col)
        assert "det_adv" in r1  # the live-adversary columns must exist
        ms, m1 = record_masks(rs_, n), record_masks(r1, n)
        assert ms is not None and m1 is not None
        # the packed forensics bitmasks fold across segments to the SAME
        # verdict: accused / adversarial / present bit for bit
        for key in ("accused", "adv", "present"):
            assert ms[key] == m1[key], (r1["step"], key)


def test_cnn_segmented_equivalence(tmp_path):
    """S ∈ {1, 2, 4} × K ∈ {1, 4} under the live adversary + straggler:
    K∈{1,4} stays bitwise within every S (the scan chain is untouched by
    segmentation); S>1 keeps a bounded-err aggregate and IDENTICAL
    detection columns + forensics masks vs S=1; the S=2 chunked run's
    status ledger and dispatch spans carry the segment count while the
    S=1 trace records stay segment-free (the bitwise rail)."""
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    ds = load_dataset("synthetic-mnist", synthetic_train=512,
                      synthetic_test=64)
    mesh = make_mesh(9)
    out = {}
    for s in (1, 2, 4):
        for k in (1, 4):
            d = str(tmp_path / f"s{s}_k{k}")
            tr = Trainer(_train_cfg(**CYC, steps_per_call=k,
                                    wire_segments=s, train_dir=d,
                                    trace_dir=d),
                         mesh=mesh, dataset=ds, quiet=True)
            tr.run()
            snap = tr.compile_watch.snapshot()
            assert snap["steady_recompiles"] == 0
            out[s, k] = (np.concatenate([
                np.ravel(x) for x in
                jax.tree.leaves(jax.device_get(tr.state.params))]),
                _stream(d))
            tr.close()
    for s in (1, 2, 4):
        # both loops: eager vs scan-chunked bitwise within the S
        np.testing.assert_array_equal(out[s, 1][0], out[s, 4][0])
        det = [{c: r[c] for c in DET_COLS} for r in out[s, 1][1]]
        assert det == [{c: r[c] for c in DET_COLS} for r in out[s, 4][1]]
    for s in (2, 4):
        # bounded-err aggregate, identical verdicts vs the S=1 twin
        np.testing.assert_allclose(out[s, 4][0], out[1, 4][0],
                                   rtol=5e-4, atol=1e-5)
        _assert_detection_equal(out[s, 4][1], out[1, 4][1], 9)
        assert any(out[s, 4][0] != out[1, 4][0]), \
            "segmented decode unexpectedly bitwise — rail not exercised"

    # the segmented status ledger (obs/numerics.wire_ledger)
    status = json.load(open(tmp_path / "s2_k4" / "status.json"))
    seg = status["wire"]["segments"]
    assert seg["count"] == len(seg["bounds"]) - 1 == 2
    assert sum(seg["physical_bytes_per_worker"]) == \
        status["wire"]["physical_bytes_per_worker"]
    # dispatch spans carry the live segment count ONLY when S>1
    # (control/engine.py): S=1 trace records stay byte-identical to the
    # pre-segmentation suites
    for s, want in ((1, None), (2, 2)):
        trace = json.load(open(tmp_path / f"s{s}_k4" / "trace.json"))
        spans = [e for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "dispatch"]
        assert spans
        for e in spans:
            assert (e.get("args") or {}).get("segments") == want, (s, e)


# --------------------------------------------------------------------------
# production-loop equivalence: LM sp route, S=2 vs S=1
# --------------------------------------------------------------------------

def test_lm_sp_segmented_equivalence(tmp_path):
    """The same fold discipline through the LM single-shard route
    (parallel/common.aggregate_flat_grads — the seam all five LM routes
    share and, since ISSUE 28, the CNN step too: the CNN cases above and
    this one run the same tail): S=2 vs S=1 under a live adversary, K=4 scan, strict compile
    sentinel — bounded-err params, identical detection columns and
    forensics masks per record."""
    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import train_sp

    out = {}
    for s in (1, 2):
        d = str(tmp_path / f"lm_s{s}")
        cfg = _train_cfg(
            network="TransformerLM", dataset="synthetic-text",
            batch_size=2, max_steps=8, eval_freq=4, steps_per_call=4,
            seq_len=16, vocab=64, model_dim=64, model_heads=2,
            model_layers=1, approach="cyclic", worker_fail=1,
            adversary_count=1, err_mode="rev_grad", redundancy="shared",
            wire_segments=s, train_dir=d)
        state, metrics = train_sp(cfg, make_mesh_2d(cfg.num_workers, 1),
                                  quiet=True)
        assert np.isfinite(metrics["loss"])
        out[s] = (np.concatenate([
            np.ravel(x) for x in
            jax.tree.leaves(jax.device_get(state.params))]), _stream(d))
    np.testing.assert_allclose(out[2][0], out[1][0], rtol=5e-4, atol=1e-5)
    _assert_detection_equal(out[2][1], out[1][1], 8)
    # the model really spans >1 segment (else this test proves nothing)
    status = json.load(open(tmp_path / "lm_s2" / "status.json"))
    assert status["wire"]["segments"]["count"] == 2


# --------------------------------------------------------------------------
# autopilot segment dials
# --------------------------------------------------------------------------

def test_autopilot_segment_dials(tmp_path):
    """The straggler ladder's first rung (control/autopilot.py): a
    sustained straggle episode fires segments_up — a warm program swap to
    the SAME family at S=2 (its own compile-sentinel label, compiled
    once) — and sustained straggle-quiet evidence fires segments_down
    back to the configured count, both attributed, 0 steady retraces,
    ending in the base regime."""
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.training.trainer import Trainer

    d = str(tmp_path / "ap")
    cfg = TrainConfig(
        network="FC", dataset="synthetic-mnist", batch_size=4, lr=0.02,
        momentum=0.9, num_workers=8, max_steps=20, eval_freq=4,
        train_dir=d, log_every=1, steps_per_call=4, approach="cyclic",
        worker_fail=1, adversary_count=0, err_mode="rev_grad",
        redundancy="shared", step_guard="on", incident_watch="on",
        compile_guard="raise", autopilot="on",
        # the family dials are parked so the scenario isolates the
        # segment rung; segments_max=2 caps the up-dial at one swap
        autopilot_policy=("segments_up_boundaries=1,segments_max=2,"
                          "segments_down_boundaries=1,"
                          "dial_down_boundaries=99,clean_boundaries=99"),
        incident_thresholds="straggle.streak=2",
        fault_spec="straggle@5-12:w5",
    )
    ds = load_dataset("synthetic-mnist", synthetic_train=512,
                      synthetic_test=64)
    tr = Trainer(cfg, dataset=ds, quiet=True)
    last = tr.run()
    snap = tr.compile_watch.snapshot()
    tr.close()
    assert np.isfinite(last["loss"]) and last["step"] == 20
    assert snap["steady_recompiles"] == 0

    rems = [json.loads(l) for l in
            open(os.path.join(d, "incidents.jsonl"))]
    rems = [e for e in rems if e.get("event") == "remediation"]
    assert [e["action"] for e in rems] == ["segments_up", "segments_down"]
    up, down = rems
    assert up["regime"]["tag"] == "cyclic_r3_seg2"
    assert up["regime"]["wire_segments"] == 2
    assert up["trigger"]["type"] in ("straggle", "starvation")
    assert up["evidence"]["wire_segments_before"] == 1
    assert up["evidence"]["wire_segments_after"] == 2
    assert up["evidence"]["executable"] == "compiled"
    assert down["regime"]["tag"] == "cyclic_r3"
    assert down["evidence"]["wire_segments_after"] == 1

    # warm-swap compile contract: the segmented program built exactly
    # once under its own sentinel label
    ledger = [json.loads(l) for l in
              open(os.path.join(d, "compiles.jsonl"))]
    labels = {}
    for r in ledger:
        if r["program"]:
            labels[r["program"]] = labels.get(r["program"], 0) + 1
    assert labels.get("train_many@cyclic_r3_seg2[4]") == 1, labels
    assert not any(r["steady_recompile"] for r in ledger)

    st = json.load(open(os.path.join(d, "status.json")))
    assert st["state"] == "done"
    assert st["control"]["regime"]["tag"] == "cyclic_r3"
    assert st["control"]["swaps"] == 2
    # the wire ledger was re-stamped back to the single-segment shape
    assert st["wire"]["segments"]["count"] == 1


# --------------------------------------------------------------------------
# decode-on-arrival pipeline rails (control/engine.SegmentPipeline)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_segment_pipeline_rails():
    """The measurement harness's two rails: pipelined interleaves
    transfer j+1 between decode j's dispatch and its drain (the overlap
    window); serial drains first, forbidding overlap by construction."""
    from draco_tpu.control.engine import SegmentPipeline
    from draco_tpu.obs.tracer import NullTracer

    calls = []

    def mk(pipelined):
        calls.clear()
        return SegmentPipeline(
            NullTracer(),
            put=lambda j, h: calls.append(("put", j)) or h * 10,
            decode=lambda j, dev: calls.append(("decode", j)) or dev + j,
            drain=lambda out: calls.append(("drain", out)),
            pipelined=pipelined)

    p = mk(True)
    res = p.run([1, 2, 3])
    assert res == [10, 21, 32]
    assert [(e["name"], e["segment"]) for e in p.events] == [
        ("segment_xfer", 0), ("segment_decode", 0),
        ("segment_xfer", 1), ("segment_drain", 0),
        ("segment_decode", 1), ("segment_xfer", 2),
        ("segment_drain", 1), ("segment_decode", 2),
        ("segment_drain", 2)]
    over, inflight = p.overlap_us()
    assert over >= 0.0 and inflight >= 0.0

    p = mk(False)
    assert p.run([1, 2, 3]) == [10, 21, 32]
    assert [(e["name"], e["segment"]) for e in p.events] == [
        ("segment_xfer", 0), ("segment_decode", 0), ("segment_drain", 0),
        ("segment_xfer", 1), ("segment_decode", 1), ("segment_drain", 1),
        ("segment_xfer", 2), ("segment_decode", 2), ("segment_drain", 2)]
    over, inflight = p.overlap_us()
    assert over == 0.0  # drain precedes the next transfer: no overlap
    assert p.run([]) == []


# --------------------------------------------------------------------------
# perf_watch segment gates — the flipped-row controls
# --------------------------------------------------------------------------

def test_perf_watch_segment_gates_flipped_rows(tmp_path):
    """The ISSUE 16 fold (tools/perf_watch.fold_segment_study): the
    per-cell segment counts and per-segment physical bytes are PINNED in
    BOTH directions; the ms/step win and the overlap fractions are a CPU
    run's wall clock and gate nothing."""
    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    path = root / "baselines_out" / "segment_study.json"
    out = root / "report.json"

    def artifact(win_ms=20.0, win_overlap=0.5, s1_overlap=0.0,
                 seg_bytes=(400, 400), count=2):
        return {"all_ok": True, "rows": [
            {"dtype": "f32", "segments": 1, "ms_per_step": 100.0,
             "overlap_frac": s1_overlap,
             "wire": {"segments": {"count": 1,
                                   "physical_bytes_per_worker": [800]}},
             "ok": True},
            {"dtype": "f32", "segments": 2, "ms_per_step": 80.0,
             "overlap_frac": 0.5,
             "wire": {"segments": {
                 "count": count,
                 "physical_bytes_per_worker": list(seg_bytes)}},
             "ok": True},
        ], "win": {"dtype": "f32", "segments": 2,
                   "ms_per_step_win": win_ms, "win_frac": win_ms / 100.0,
                   "overlap_frac": win_overlap}}

    path.write_text(json.dumps(artifact()))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert set(snap["metrics"]) == {
        "segment.f32.s1.segments_count",
        "segment.f32.s1.seg0_bytes_per_worker",
        "segment.f32.s2.segments_count",
        "segment.f32.s2.seg0_bytes_per_worker",
        "segment.f32.s2.seg1_bytes_per_worker"}
    assert perf_watch.main(["--root", str(root)]) == 0  # clean
    # a win that turns into a loss, overlap that vanishes or appears at
    # S=1: wall-clock measures of a CPU run, not folded
    path.write_text(json.dumps(artifact(win_ms=-5.0, win_overlap=0.0,
                                        s1_overlap=0.1)))
    assert perf_watch.main(["--root", str(root)]) == 0

    def gated(art, *metrics):
        path.write_text(json.dumps(art))
        assert perf_watch.main(["--root", str(root), "--json",
                                str(out)]) == 1
        regs = {r["metric"] for r in
                json.loads(out.read_text())["regressions"]}
        for m in metrics:
            assert m in regs, (m, regs)

    # per-segment bytes pinned in BOTH directions
    gated(artifact(seg_bytes=(401, 400)),
          "segment.f32.s2.seg0_bytes_per_worker")
    gated(artifact(seg_bytes=(399, 400)),
          "segment.f32.s2.seg0_bytes_per_worker")
    # a segment silently appearing is a wire-format change, never noise
    gated(artifact(count=3), "segment.f32.s2.segments_count")
