"""Hierarchical CodedReduce tree aggregation (ISSUE 17): the plan/fold
algebra, the ledger's per-level byte sums, config validation, tree-vs-flat
detection + forensics equality under a live adversary AND a straggler drop,
K∈{1,4} × g∈{2,4} production-loop equivalence at compile_guard="raise"
with 0 steady retraces, the LM sp-route parity, the autopilot
fanout_down/fanout_up dials, and the flipped-row controls proving the
perf_watch tree gates live.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu.config import TrainConfig
from draco_tpu.coding import topology as topo
from draco_tpu.obs import numerics as nx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# plan + fold algebra (jax-free units)
# --------------------------------------------------------------------------

@pytest.mark.core
def test_tree_plan_algebra():
    p = topo.tree_plan(8, 4)
    assert (p.num_groups, p.levels, p.level_fanouts) == (2, 2, (2,))
    assert p.group_slices == ((0, 4), (4, 8))
    assert p.level_widths == (2, 1)
    p = topo.tree_plan(32, 4)
    assert (p.num_groups, p.levels) == (8, 3)
    assert p.level_fanouts == (4, 2)
    assert p.level_widths == (8, 2, 1)
    # explicit depth: 8 groups over 3 combine levels of fan-in 2
    p = topo.tree_plan(32, 4, levels=4)
    assert p.level_fanouts == (4, 2, 1)
    # a depth the fan-in cannot realize is an error, not a silent clamp
    with pytest.raises(ValueError, match="cannot fold"):
        topo.tree_plan(32, 2, levels=2)
    # degenerate shapes refused loudly
    with pytest.raises(ValueError, match="num_workers % tree_fanout"):
        topo.tree_plan(10, 4)
    with pytest.raises(ValueError, match="at least 2 leaf groups"):
        topo.tree_plan(8, 8)
    with pytest.raises(ValueError, match=">= 2"):
        topo.tree_plan(8, 1)


@pytest.mark.core
def test_group_worker_fail_caps():
    """Per-group budget: the flat s capped by the small code's existence
    bound g > 4*s_g."""
    assert topo.group_worker_fail(4, 1) == 0
    assert topo.group_worker_fail(8, 1) == 1
    assert topo.group_worker_fail(8, 3) == 1
    assert topo.group_worker_fail(16, 3) == 3
    assert topo.group_worker_fail(4, 0) == 0


@pytest.mark.core
def test_tree_ledger_block_sums():
    """The leaf level's ingest bytes are EXACTLY the flat per-step bytes
    (the same n codeword rows, partitioned — no padding at the seams);
    combine levels price the decoded f32 partial traffic."""
    d = 10_000
    for n, g, dtype in ((8, 4, "f32"), (16, 4, "bf16"), (32, 8, "int8")):
        kw = {} if dtype == "f32" else {"wire_dtype": dtype}
        cfg = TrainConfig(approach="cyclic", num_workers=n, worker_fail=1,
                          adversary_count=0, redundancy="shared",
                          topology="tree", tree_fanout=g, **kw)
        led = nx.wire_ledger(cfg, d)
        tb = led["tree"]
        lb = tb["level_bytes_per_step"]
        assert len(lb) == tb["levels"]
        assert lb[0] == led["physical_bytes_per_step"]
        assert tb["ingest_bytes_per_group"] * tb["num_groups"] == lb[0]
        widths = tb["level_widths"]
        for l in range(1, tb["levels"]):
            assert lb[l] == widths[l - 1] * topo.PARTIAL_BYTES * d
        # per-NODE ingest is constant in n: fan-in * partial bytes
        assert tb["node_ingest_bytes"][1:] == [
            f * topo.PARTIAL_BYTES * d for f in tb["level_fanouts"]]


@pytest.mark.core
def test_config_rejects_bad_tree():
    base = dict(approach="cyclic", num_workers=8, worker_fail=1,
                adversary_count=0, redundancy="shared", topology="tree")
    TrainConfig(**base, tree_fanout=4).validate()
    with pytest.raises(ValueError, match="tree_fanout"):
        TrainConfig(**{**base, "num_workers": 10}, tree_fanout=4).validate()
    with pytest.raises(ValueError, match="redundancy='shared'"):
        TrainConfig(**{**base, "redundancy": "simulate"},
                    tree_fanout=4).validate()
    with pytest.raises(ValueError, match="shadow"):
        TrainConfig(**base, tree_fanout=4, shadow_wire="f32").validate()
    # declared adversary load above the worst-case per-group budget
    with pytest.raises(ValueError, match="per-group"):
        TrainConfig(**{**base, "adversary_count": 1},
                    err_mode="rev_grad", tree_fanout=4).validate()
    # g=8 has s_g=1: one adversary fits
    TrainConfig(**{**base, "num_workers": 16, "adversary_count": 1},
                err_mode="rev_grad", tree_fanout=8).validate()
    with pytest.raises(ValueError, match="maj_vote|cyclic/approx"):
        TrainConfig(approach="maj_vote", group_size=4, worker_fail=1,
                    num_workers=8, topology="tree",
                    tree_fanout=4).validate()


# --------------------------------------------------------------------------
# decode units: fold equality vs flat, live adversary + straggler drop
# --------------------------------------------------------------------------

def _tree_fixture(n=16, g=8, d=4096, seed=3):
    cfg = TrainConfig(approach="cyclic", num_workers=n, worker_fail=1,
                      adversary_count=0, redundancy="shared",
                      topology="tree", tree_fanout=g)
    tcode = topo.build_tree_code(cfg)
    rs = np.random.RandomState(seed)
    grads = jnp.asarray(rs.randn(n, d).astype(np.float32) * 0.1)
    rf = jnp.asarray(rs.choice([-1.0, 1.0], d).astype(np.float32))
    return tcode, grads, rf


@pytest.mark.core
def test_combine_partials_is_the_flat_mean():
    plan = topo.tree_plan(32, 4)
    rs = np.random.RandomState(0)
    parts = jnp.asarray(rs.randn(plan.num_groups, 64).astype(np.float32))
    out = topo.combine_partials(plan, parts)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(parts).mean(axis=0),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.core
def test_tree_encode_is_blockwise_flat_encode():
    """Group j's encoded rows are the small code's flat encode of that
    group's batch rows BIT-FOR-BIT (same kernel, same operands)."""
    from draco_tpu.coding import cyclic

    tcode, grads, _ = _tree_fixture()
    e_re, e_im = topo.encode_tree(tcode, grads)
    for lo, hi in tcode.plan.group_slices:
        fr, fi = cyclic.encode_shared(tcode.group_code, grads[lo:hi])
        np.testing.assert_array_equal(np.asarray(e_re[lo:hi]),
                                      np.asarray(fr))
        np.testing.assert_array_equal(np.asarray(e_im[lo:hi]),
                                      np.asarray(fi))


@pytest.mark.core
def test_tree_detection_equals_flat_live_adversary():
    """The fold's load-bearing property: the SAME live rev_grad adversary
    decoded flat (n=16, s=1) and tree (g=8, s_g=1) flags the SAME row —
    detection P/R identical — and both aggregates stay at the true
    mean."""
    from draco_tpu.coding import cyclic

    tcode, grads, rf = _tree_fixture()
    n = tcode.plan.n
    flat = cyclic.build_cyclic_code(n, 1)
    adv_row = 11  # inside group 1 — the fold must map the accusation back
    fr, fi = cyclic.encode_shared(flat, grads)
    tr, ti = topo.encode_tree(tcode, grads)
    fr, fi = fr.at[adv_row].multiply(-50.0), fi.at[adv_row].multiply(-50.0)
    tr, ti = tr.at[adv_row].multiply(-50.0), ti.at[adv_row].multiply(-50.0)
    dec_f, hon_f, hl_f = parity.run_jitted(
        cyclic.decode, flat, fr, fi, rf, with_health=True)
    dec_t, hon_t, hl_t = topo.decode_tree_cyclic(tcode, tr, ti, rf)
    truth = np.asarray(jnp.mean(grads, axis=0))
    np.testing.assert_allclose(np.asarray(dec_t), truth, rtol=2e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(dec_f), truth, rtol=2e-4,
                               atol=1e-5)
    fl_f = np.asarray(hl_f["flagged"], bool)
    fl_t = np.asarray(hl_t["flagged"], bool)
    np.testing.assert_array_equal(fl_t, fl_f)
    assert fl_t[adv_row] and fl_t.sum() == 1
    assert hon_t.shape == (n,)
    assert not bool(np.asarray(hon_t)[adv_row])


@pytest.mark.core
def test_tree_straggler_drop_never_accused():
    """A dropped worker decodes as an erasure in ITS group, the decode
    stays exact, and the victim is never accused — matching flat."""
    from draco_tpu.coding import cyclic

    tcode, grads, rf = _tree_fixture()
    n = tcode.plan.n
    flat = cyclic.build_cyclic_code(n, 1)
    drop = 9
    present = jnp.ones((n,), bool).at[drop].set(False)
    fr, fi = cyclic.encode_shared(flat, grads)
    tr, ti = topo.encode_tree(tcode, grads)
    dec_f, _, hl_f = parity.run_jitted(
        cyclic.decode, flat, fr, fi, rf, present=present, with_health=True)
    dec_t, _, hl_t = topo.decode_tree_cyclic(tcode, tr, ti, rf,
                                             present=present)
    truth = np.asarray(jnp.mean(grads, axis=0))
    np.testing.assert_allclose(np.asarray(dec_t), truth, rtol=2e-4,
                               atol=1e-5)
    fl_f = np.asarray(hl_f["flagged"], bool)
    fl_t = np.asarray(hl_t["flagged"], bool)
    np.testing.assert_array_equal(fl_t, fl_f)
    assert not fl_t[drop]


@pytest.mark.core
def test_tree_approx_residual_within_bound():
    """The approx tree: root residual measured by the FLAT formula, the
    folded bound sqrt(sum bound_j^2) still certifies it under a drop."""
    from draco_tpu.coding import approx

    n, g, d = 8, 4, 2048
    cfg = TrainConfig(approach="approx", num_workers=n, worker_fail=0,
                      redundancy="shared", code_redundancy=2.0,
                      assignment_scheme="pairwise", topology="tree",
                      tree_fanout=g)
    tcode = topo.build_tree_code(cfg)
    assert tcode.family == "approx"
    rs = np.random.RandomState(5)
    grads = jnp.asarray(rs.randn(n, d).astype(np.float32) * 0.1)
    rows = topo.encode_tree(tcode, grads)
    present = jnp.ones((n,), bool).at[2].set(False)
    dec, v, hl = topo.decode_tree_approx(tcode, rows, present=present,
                                         batch_grads=grads)
    assert v.shape == (n,)
    assert float(hl["residual"]) <= float(hl["bound"]) + 1e-6
    assert 0.0 < float(hl["recovered_fraction"]) <= 1.0
    # full presence decodes the exact mean, residual at float noise
    dec0, _, hl0 = topo.decode_tree_approx(tcode, rows,
                                           batch_grads=grads)
    np.testing.assert_allclose(np.asarray(dec0),
                               np.asarray(jnp.mean(grads, axis=0)),
                               rtol=2e-4, atol=1e-5)
    assert float(hl0["residual"]) < 1e-3


# --------------------------------------------------------------------------
# production-loop equivalence: CNN Trainer, g ∈ {flat, 2, 4} × K ∈ {1, 4}
# --------------------------------------------------------------------------

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


def _assert_detection_equal(stream_t, stream_f, n):
    from draco_tpu.obs.forensics import record_masks

    assert len(stream_t) == len(stream_f) > 0
    for rt, rf_ in zip(stream_t, stream_f):
        assert rt["step"] == rf_["step"]
        for col in DET_COLS:
            assert (col in rt) == (col in rf_), (rf_["step"], col)
            if col in rf_:
                assert rt[col] == rf_[col], (rf_["step"], col)
        mt, mf = record_masks(rt, n), record_masks(rf_, n)
        assert mt is not None and mf is not None
        for key in ("accused", "adv", "present"):
            assert mt[key] == mf[key], (rf_["step"], key)


def test_cnn_tree_loop_equivalence(tmp_path):
    """g ∈ {flat, 2, 4} × K ∈ {1, 4} on the CNN Trainer (n=8,
    worker_fail=0 so every fanout is feasible): K∈{1,4} stays bitwise
    within every topology, tree aggregates stay within float noise of
    flat, 0 steady retraces everywhere, and the status ledger carries the
    per-level tree block whose leaf level equals the flat bytes."""
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    ds = load_dataset("synthetic-mnist", synthetic_train=512,
                      synthetic_test=64)
    mesh = make_mesh(8)
    out = {}
    for g in (0, 2, 4):
        for k in (1, 4):
            d = str(tmp_path / f"g{g}_k{k}")
            kw = dict(approach="cyclic", worker_fail=0, adversary_count=0,
                      redundancy="shared", steps_per_call=k, train_dir=d)
            if g:
                kw.update(topology="tree", tree_fanout=g)
            tr = Trainer(_train_cfg(**kw), mesh=mesh, dataset=ds,
                         quiet=True)
            tr.run()
            snap = tr.compile_watch.snapshot()
            assert snap["steady_recompiles"] == 0, (g, k)
            out[g, k] = np.concatenate([
                np.ravel(x) for x in
                jax.tree.leaves(jax.device_get(tr.state.params))])
            tr.close()
    for g in (0, 2, 4):
        # eager vs scan-chunked bitwise within the topology
        np.testing.assert_array_equal(out[g, 1], out[g, 4])
    for g in (2, 4):
        # tree combine = mean of group means = the flat mean, to f32 noise
        np.testing.assert_allclose(out[g, 4], out[0, 4], rtol=5e-4,
                                   atol=1e-5)

    status = json.load(open(tmp_path / "g4_k4" / "status.json"))
    tb = status["wire"]["tree"]
    assert tb["fanout"] == 4 and tb["num_groups"] == 2
    assert tb["level_bytes_per_step"][0] == \
        status["wire"]["physical_bytes_per_step"]
    # flat twins carry NO tree block — the flat wire format is untouched
    status_flat = json.load(open(tmp_path / "g0_k4" / "status.json"))
    assert "tree" not in status_flat["wire"]


def test_cnn_tree_detection_parity_loop(tmp_path):
    """n=16, g=8 (s_g=1) under a LIVE rev_grad adversary, then under a
    straggler drop: the tree run's detection columns and packed forensics
    masks equal the flat run's per record, and the straggle victim is
    never accused."""
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    ds = load_dataset("synthetic-mnist", synthetic_train=512,
                      synthetic_test=64)
    mesh = make_mesh(16)
    cases = {
        "adv": dict(adversary_count=1, err_mode="rev_grad"),
        "strag": dict(adversary_count=0, straggle_mode="drop",
                      straggle_count=1),
    }
    for case, kw in cases.items():
        streams = {}
        for g in (0, 8):
            d = str(tmp_path / f"{case}_g{g}")
            ckw = dict(approach="cyclic", num_workers=16, worker_fail=1,
                       redundancy="shared", steps_per_call=4,
                       train_dir=d, **kw)
            if g:
                ckw.update(topology="tree", tree_fanout=g)
            tr = Trainer(_train_cfg(**ckw), mesh=mesh, dataset=ds,
                         quiet=True)
            last = tr.run()
            assert np.isfinite(last["loss"])
            assert tr.compile_watch.snapshot()["steady_recompiles"] == 0
            streams[g] = _stream(d)
            tr.close()
        _assert_detection_equal(streams[8], streams[0], 16)
        if case == "adv":
            assert any(r.get("det_tp", 0) > 0 for r in streams[8]), \
                "live adversary never detected — parity proves nothing"


# --------------------------------------------------------------------------
# LM route parity: the aggregate_flat_grads seam (the CNN step's too)
# --------------------------------------------------------------------------

def test_lm_sp_tree_parity(tmp_path):
    """The tree fold through the LM single-shard route
    (parallel/common.aggregate_flat_grads — the seam all five LM routes
    share and, since ISSUE 28, the CNN step too: the CNN cases above and
    this one run the same tail): g=4 vs flat at n=8, K=4 scan, strict compile sentinel —
    params within float noise, and the status wire ledger carries the
    tree block."""
    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import train_sp

    out = {}
    for g in (0, 4):
        d = str(tmp_path / f"lm_g{g}")
        kw = dict(
            network="TransformerLM", dataset="synthetic-text",
            batch_size=2, max_steps=8, eval_freq=4, steps_per_call=4,
            seq_len=16, vocab=64, model_dim=64, model_heads=2,
            model_layers=1, approach="cyclic", worker_fail=0,
            adversary_count=0, redundancy="shared", train_dir=d)
        if g:
            kw.update(topology="tree", tree_fanout=g)
        cfg = _train_cfg(**kw)
        state, metrics = train_sp(cfg, make_mesh_2d(cfg.num_workers, 1),
                                  quiet=True)
        assert np.isfinite(metrics["loss"])
        out[g] = np.concatenate([
            np.ravel(x) for x in
            jax.tree.leaves(jax.device_get(state.params))])
    np.testing.assert_allclose(out[4], out[0], rtol=5e-4, atol=1e-5)
    status = json.load(open(tmp_path / "lm_g4" / "status.json"))
    tb = status["wire"]["tree"]
    assert tb["fanout"] == 4
    assert sum(tb["level_bytes_per_step"][:1]) == \
        status["wire"]["physical_bytes_per_step"]


# --------------------------------------------------------------------------
# autopilot fanout dials
# --------------------------------------------------------------------------

def test_autopilot_fanout_dials(tmp_path):
    """The straggler ladder's second rung (control/autopilot.py): a
    sustained straggle episode under topology='tree' fires fanout_down —
    a warm swap to the same family at half the fan-in (its own
    compile-sentinel label `_g2`) — and sustained straggle-quiet evidence
    fires fanout_up back to the configured fanout, both attributed, 0
    steady retraces, ending in the base regime."""
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.training.trainer import Trainer

    d = str(tmp_path / "ap")
    cfg = TrainConfig(
        network="FC", dataset="synthetic-mnist", batch_size=4, lr=0.02,
        momentum=0.9, num_workers=8, max_steps=20, eval_freq=4,
        train_dir=d, log_every=1, steps_per_call=4, approach="cyclic",
        worker_fail=0, adversary_count=0, redundancy="shared",
        topology="tree", tree_fanout=4, step_guard="on",
        incident_watch="on", compile_guard="raise", autopilot="on",
        # park the segment rung + family dials so the scenario isolates
        # the fanout rung; boundaries=1 fire on the first boundary with
        # the matching evidence
        autopilot_policy=("fanout_down_boundaries=1,fanout_up_boundaries=1,"
                          "segments_up_boundaries=99,"
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
    assert [e["action"] for e in rems] == ["fanout_down", "fanout_up"]
    down, up = rems
    assert down["regime"]["tag"] == "cyclic_r1_g2"
    assert down["regime"]["tree_fanout"] == 2
    assert down["trigger"]["type"] in ("straggle", "starvation")
    assert down["evidence"]["tree_fanout_before"] == 4
    assert down["evidence"]["tree_fanout_after"] == 2
    assert down["evidence"]["executable"] == "compiled"
    assert up["regime"]["tag"] == "cyclic_r1_g4"
    assert up["evidence"]["tree_fanout_after"] == 4

    ledger = [json.loads(l) for l in
              open(os.path.join(d, "compiles.jsonl"))]
    labels = {}
    for r in ledger:
        if r["program"]:
            labels[r["program"]] = labels.get(r["program"], 0) + 1
    assert labels.get("train_many@cyclic_r1_g2[4]") == 1, labels
    assert not any(r["steady_recompile"] for r in ledger)

    st = json.load(open(os.path.join(d, "status.json")))
    assert st["state"] == "done"
    assert st["control"]["regime"]["tag"] == "cyclic_r1_g4"
    assert st["control"]["swaps"] == 2
    # the wire ledger was re-stamped back to the configured tree shape
    assert st["wire"]["tree"]["fanout"] == 4


# --------------------------------------------------------------------------
# perf_watch tree gates — the flipped-row controls
# --------------------------------------------------------------------------

def test_perf_watch_tree_gates_flipped_rows(tmp_path):
    """The ISSUE 17 fold (tools/perf_watch.fold_tree_study): the bytes_ok /
    detection-parity bools gate at tolerance 0 and the per-level bytes are
    PINNED in BOTH directions; the decode times, the win they decide and
    the crossover n are a CPU run's wall clock and gate nothing."""
    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    path = root / "baselines_out" / "tree_study.json"
    out = root / "report.json"

    def artifact(win=True, bytes_ok=True, det_ok=True,
                 level_bytes=(4096, 1024), crossover=8):
        return {"all_ok": True, "crossover": {"critical_path_n": crossover},
                "rows": [
            {"kind": "flat", "n": 16, "decode_ms": 10.0},
            {"kind": "tree", "n": 16, "fanout": 8,
             "critical_path_ms": 6.0, "leaf_decode_ms": 5.0,
             "sequential_total_ms": 12.0, "win": win,
             "bytes_ok": bytes_ok,
             "detection": {"checked": True, "ok": det_ok,
                           "precision_tree": 1.0, "recall_tree": 1.0},
             "ledger": {"tree": {
                 "level_bytes_per_step": list(level_bytes)}},
             "ok": True},
        ]}

    path.write_text(json.dumps(artifact()))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert set(snap["metrics"]) == {
        "tree.n16.g8.bytes_ok", "tree.n16.g8.detection_ok",
        "tree.n16.g8.precision_tree", "tree.n16.g8.recall_tree",
        "tree.n16.g8.level0_bytes_per_step",
        "tree.n16.g8.level1_bytes_per_step"}
    assert perf_watch.main(["--root", str(root)]) == 0  # clean
    # the decode win lost, the crossover moved: wall-clock, not folded
    path.write_text(json.dumps(artifact(win=False, crossover=16)))
    assert perf_watch.main(["--root", str(root)]) == 0

    def gated(art, *metrics):
        path.write_text(json.dumps(art))
        assert perf_watch.main(["--root", str(root), "--json",
                                str(out)]) == 1
        regs = {r["metric"] for r in
                json.loads(out.read_text())["regressions"]}
        for m in metrics:
            assert m in regs, (m, regs)

    # the byte-sum honesty pin breaking gates
    gated(artifact(bytes_ok=False), "tree.n16.g8.bytes_ok")
    # detection parity breaking gates
    gated(artifact(det_ok=False), "tree.n16.g8.detection_ok")
    # per-level bytes pinned in BOTH directions
    gated(artifact(level_bytes=(4097, 1024)),
          "tree.n16.g8.level0_bytes_per_step")
    gated(artifact(level_bytes=(4095, 1024)),
          "tree.n16.g8.level0_bytes_per_step")
