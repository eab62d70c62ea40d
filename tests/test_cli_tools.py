"""CLI surface, evaluator process, single-machine path, cluster tooling.

Covers the reference's L6/L7 layers (SURVEY.md §1): distributed_nn.py flag
surface, distributed_evaluator.py's checkpoint-polling loop,
single_machine.py, and tools/pytorch_ec2.py's command structure (ours:
tools/tpu_pod.py in --dry-run mode — control flow without GCP credentials).
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_single_machine_smoke(tmp_path):
    from draco_tpu import single_machine

    last = single_machine.main([
        "--network", "FC", "--dataset", "synthetic-mnist",
        "--batch-size", "16", "--max-steps", "15",
        "--eval-freq", "0", "--train-dir", "", "--log-every", "1000",
    ])
    assert np.isfinite(last["loss"])


def test_evaluator_reads_checkpoints(tmp_path):
    """Train with checkpointing, then run the evaluator once over train_dir —
    the reference's NFS-polling evaluate path (distributed_evaluator.py:75-90)."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training import evaluator
    from draco_tpu.training.trainer import Trainer

    d = str(tmp_path / "run")
    ds = load_dataset("synthetic-mnist", synthetic_train=128, synthetic_test=64)
    cfg = TrainConfig(network="FC", dataset="synthetic-mnist", batch_size=4,
                      num_workers=4, approach="baseline", max_steps=4,
                      eval_freq=2, train_dir=d, log_every=1000,
                      test_batch_size=64)
    tr = Trainer(cfg, mesh=make_mesh(4), dataset=ds, quiet=True)
    tr.run()
    tr.close()

    from draco_tpu.utils import checkpoint as ckpt
    assert ckpt.available_steps(d) == [2, 4]

    out = []
    import contextlib, io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        evaluator.main([
            "--network", "FC", "--dataset", "synthetic-mnist",
            "--num-workers", "4", "--train-dir", d,
            "--test-batch-size", "64", "--once",
        ])
    out = buf.getvalue()
    # one line per checkpoint with top-1/top-5 (reference print format)
    steps = re.findall(r"Cur Step:(\d+)", out)
    assert steps == ["2", "4"]
    assert all(0.0 <= float(p) <= 1.0 for p in re.findall(r"Prec@1: ([0-9.]+)", out))


def test_tpu_pod_dry_run_command_structure():
    def run(*args):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tpu_pod.py"),
             "--dry-run", *args],
            capture_output=True, text=True, timeout=60,
        )
        assert p.returncode == 0, p.stderr
        return p.stdout

    out = run("launch", "--name", "pod1", "--type", "v5litepod-16", "--spot")
    assert "gcloud compute tpus tpu-vm create pod1" in out and "--spot" in out

    out = run("train", "--name", "pod1", "--", "--approach", "cyclic",
              "--num-workers", "16")
    assert "--worker=all" in out and "draco_tpu.cli" in out and "cyclic" in out

    out = run("kill", "--name", "pod1")
    assert "pkill" in out

    out = run("terminate", "--name", "pod1")
    assert "delete pod1" in out


def test_cli_rejects_bad_flag_combination():
    from draco_tpu import cli

    with pytest.raises(ValueError, match="straggler budget"):
        cfg = cli.config_from_args(
            cli.add_fit_args(__import__("argparse").ArgumentParser()).parse_args([
                "--approach", "cyclic", "--num-workers", "9",
                "--worker-fail", "2", "--straggle-mode", "drop",
                "--straggle-count", "5",
            ])
        )


def test_profile_flag_writes_trace(tmp_path):
    from draco_tpu.config import TrainConfig
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.trainer import Trainer

    ds = load_dataset("synthetic-mnist", synthetic_train=64, synthetic_test=16)
    cfg = TrainConfig(network="FC", dataset="synthetic-mnist", batch_size=4,
                      num_workers=4, approach="baseline", max_steps=6,
                      eval_freq=0, train_dir="", log_every=1000)
    tr = Trainer(cfg, mesh=make_mesh(4), dataset=ds, quiet=True)
    prof = str(tmp_path / "trace")
    tr.run(profile_dir=prof, profile_steps=(2, 4))
    tr.close()
    found = []
    for root, _, files in os.walk(prof):
        found.extend(f for f in files if f.endswith((".pb", ".json.gz", ".trace.json.gz")))
    assert found, f"no profiler artifacts under {prof}"


def test_compressed_checkpoint_roundtrip_and_evaluator(tmp_path):
    """--compress-ckpt writes .dcg archives; resume and the evaluator's
    train_dir polling must both auto-detect them (the reference's
    --compress-grad wire toggle, re-homed to where bytes still cross a
    slow link in the SPMD design)."""
    import contextlib
    import io

    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training import evaluator
    from draco_tpu.training.trainer import Trainer
    from draco_tpu.utils import checkpoint as ckpt

    d = str(tmp_path / "run")
    ds = load_dataset("synthetic-mnist", synthetic_train=128, synthetic_test=64)
    base = dict(network="FC", dataset="synthetic-mnist", batch_size=4,
                num_workers=4, approach="baseline", max_steps=4,
                eval_freq=2, train_dir=d, log_every=1000,
                test_batch_size=64, compress_ckpt=True)
    mesh = make_mesh(4)
    tr = Trainer(TrainConfig(**base), mesh=mesh, dataset=ds, quiet=True)
    tr.run()
    tr.close()

    assert os.path.isfile(os.path.join(d, "model_step_2.dcg"))
    assert ckpt.available_steps(d) == [2, 4]

    # resume from the compressed archive: params must match exactly
    tr2 = Trainer(TrainConfig(**{**base, "checkpoint_step": 4}),
                  mesh=mesh, dataset=ds, quiet=True)
    assert tr2._start_step == 5
    a = np.concatenate([np.ravel(x) for x in jax.tree.leaves(
        jax.device_get(tr.state.params))])
    b = np.concatenate([np.ravel(x) for x in jax.tree.leaves(
        jax.device_get(tr2.state.params))])
    np.testing.assert_array_equal(a, b)
    tr2.close()

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        evaluator.main([
            "--network", "FC", "--dataset", "synthetic-mnist",
            "--num-workers", "4", "--train-dir", d,
            "--test-batch-size", "64", "--once",
        ])
    assert re.findall(r"Cur Step:(\d+)", buf.getvalue()) == ["2", "4"]


def test_compressed_checkpoint_rejects_multihost(monkeypatch):
    import jax

    from draco_tpu.utils import checkpoint as ckpt

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(ValueError, match="single-host"):
        ckpt.save("/tmp/nowhere", 1, {"a": np.zeros(3)}, compress=True)


def test_timing_protocol_helpers():
    """fetch_scalar syncs through pytrees; timeit_device returns a sane
    per-call time for a known-cost function (tools/_timing.py — the
    protocol the study tools share)."""
    import jax.numpy as jnp

    from tools import _timing as timing

    out = {"a": jnp.arange(4.0), "b": (jnp.ones((2, 2)),)}
    assert timing.fetch_scalar(out) == 0.0

    rtt = timing.measure_rtt(reps=5)
    assert 0.0 <= rtt < 5.0

    def f(x):
        return x * 2.0

    dt = timing.timeit_device(f, jnp.ones((8, 8)), reps=5, rtt=rtt)
    assert 0.0 <= dt < 5.0


def test_every_import_of_a_tool_names_a_module_that_exists():
    """A tool's import inside a function or a branch is not run by the
    smokes here (a protocol chosen only off the CPU, a study's second
    phase behind a skip flag), so a deleted module can stay imported
    unseen: PR 44 deleted the pre-ledger benchmark under two such imports.
    Every ``import`` / ``from`` of every ``tools/*.py`` and of the entry
    points at the root, at any depth, names a module that can be found."""
    import ast
    import glob
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = sorted(glob.glob(os.path.join(root, "tools", "*.py"))
                   + glob.glob(os.path.join(root, "*.py")))
    assert len(files) > 30
    missing = []
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module]
            else:
                continue
            for name in names:
                try:
                    found = importlib.util.find_spec(name) is not None
                except ModuleNotFoundError:  # a parent package is missing
                    found = False
                if not found:
                    missing.append(f"{os.path.relpath(path, root)}:"
                                   f"{node.lineno}: {name}")
    assert not missing, missing


def test_time_to_acc_tool(tmp_path):
    """tools/time_to_acc.py converges on the synthetic set and records a
    monotone wall-clock curve (stand-in for the reference's evaluator
    convergence oracle, distributed_evaluator.py:92-110)."""
    import json

    from tools import time_to_acc

    out = tmp_path / "tta.json"
    rc = time_to_acc.main([
        "--out", str(out), "--network", "FC", "--dataset", "synthetic-mnist",
        "--approach", "baseline", "--worker-fail", "0", "--err-mode", "rev_grad",
        "--num-workers", "4", "--batch-size", "16", "--lr", "0.05",
        "--target", "0.5", "--eval-every", "10", "--max-steps", "120",
    ])
    rep = json.loads(out.read_text())
    assert rc == 0 and rep["reached"] is not None
    assert rep["reached"]["prec1_test"] >= 0.5
    walls = [c["train_wall_s"] for c in rep["curve"]]
    assert walls == sorted(walls)
    assert rep["real_data_available"] is False


def test_decode_study_tool(tmp_path):
    """tools/decode_study.py smoke: one (n, s) scaling row with the
    decode-vs-geomedian ratio."""
    import json

    from tools import decode_study

    out = tmp_path / "study.json"
    rc = decode_study.main([
        "--out", str(out), "--cpu-mesh", "4", "--d", "4096",
        "--ns", "8", "--ss", "1", "--reps", "2",
    ])
    rep = json.loads(out.read_text())
    assert rc == 0
    row = rep["scaling"][0]
    assert row["decode_ms"] > 0 and row["geomedian_ms_same_n"] > 0
    assert row["decode_vs_geomedian"] > 0


def test_convergence_grid_tool(tmp_path):
    """tools/convergence_grid.py smoke: one row produces a multi-point
    curve under the shared schedule."""
    import json

    from tools import convergence_grid

    out = tmp_path / "grid.json"
    rc = convergence_grid.main([
        "--out", str(out), "--cpu-mesh", "4", "--network", "FC",
        "--num-workers", "4", "--batch-size", "8", "--rows", "mean_clean",
        "--eval-every", "5", "--max-steps", "15", "--target", "0.99",
    ])
    rep = json.loads(out.read_text())
    assert rc == 0
    curve = rep["rows"]["mean_clean"]["curve"]
    assert len(curve) >= 2
    assert [c["step"] for c in curve] == sorted(c["step"] for c in curve)


def test_lm_time_to_loss_tool(tmp_path):
    """tools/lm_time_to_loss.py: the LM-scale convergence-under-attack
    oracle — cyclic decode learns past the undefended mean under one
    rev_grad adversary, and the wall-clock curve is monotone."""
    import json

    from tools import lm_time_to_loss

    out = tmp_path / "lm_tta.json"
    lm_time_to_loss.main([
        "--out", str(out), "--cpu-mesh", "4", "--num-workers", "8",
        "--batch-size", "1", "--seq-len", "32", "--model-dim", "32",
        "--model-heads", "2", "--model-layers", "1", "--vocab", "32",
        "--max-steps", "20", "--eval-every", "10", "--target", "0.2",
        "--eval-batches", "2",
        "--variants", "lm_cyclic_s1_shared,lm_mean_under_attack",
    ])
    rep = json.loads(out.read_text())
    cyc = rep["variants"]["lm_cyclic_s1_shared"]
    mean = rep["variants"]["lm_mean_under_attack"]
    assert "error" not in cyc and "error" not in mean
    # cyclic improves on its own start; the poisoned mean ends up worse
    assert cyc["curve"][-1]["eval_loss"] < cyc["curve"][0]["eval_loss"]
    assert cyc["final_eval_loss"] < mean["final_eval_loss"]
    walls = [c["train_wall_s"] for c in cyc["curve"]]
    assert walls == sorted(walls)


def test_perf_watch_snapshot_and_injected_regression(tmp_path):
    """tools/perf_watch.py (jax-free): folds a synthetic lint artifact,
    snapshots a baseline, passes clean, exits nonzero on an injected 20%
    peak-memory jump (and on analytic flops moving 5%), treats improvements
    as non-fatal — and folds no time: a CPU's milliseconds gate nothing."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    lint = {"all_ok": True, "rows": [
        {"name": "p1", "ok": True, "seconds": 8.4,
         "rules": {"constant_bloat": {"ok": True, "module_bytes": 1000},
                   "memory_budget": {"ok": True, "flops": 1e6,
                                     "memory": {"peak_bytes": 5000}}}},
        {"name": "control_x", "ok": True, "control": True, "rules": {}},
    ]}

    def write(peak_bytes=5000, flops=1e6):
        budget = lint["rows"][0]["rules"]["memory_budget"]
        budget["memory"]["peak_bytes"], budget["flops"] = peak_bytes, flops
        (root / "baselines_out" / "program_lint.json").write_text(
            json.dumps(lint))

    write()
    # no baseline yet -> distinct exit code with the --snapshot hint
    assert perf_watch.main(["--root", str(root)]) == 2
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert set(snap["metrics"]) == {
        "lint.all_ok", "lint.p1.module_bytes", "lint.p1.peak_bytes",
        "lint.p1.flops"}  # controls excluded, the row's seconds not folded
    assert {m["kind"] for m in snap["metrics"].values()} <= {
        "bytes", "flops", "ok", "pinned"} == set(perf_watch.KINDS)
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    # peak memory 20% up: nonzero exit, the metric is named
    write(peak_bytes=6000)
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    rep = json.loads(out.read_text())
    assert [r["metric"] for r in rep["regressions"]] == ["lint.p1.peak_bytes"]
    assert rep["regressions"][0]["rel_change"] == pytest.approx(0.2)
    # inside the bytes tolerance it passes; the tolerance is the caller's
    write(peak_bytes=5400)
    assert perf_watch.main(["--root", str(root)]) == 0
    assert perf_watch.main(["--root", str(root), "--tol-bytes", "0.05"]) == 1

    # 20% less: improvements never gate
    write(peak_bytes=4000)
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 0
    rep = json.loads(out.read_text())
    assert any(r["metric"] == "lint.p1.peak_bytes"
               for r in rep["improvements"])

    # analytic flops do not drift without an algorithm change: 5% gates
    write(flops=1.05e6)
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in
            json.loads(out.read_text())["regressions"]}
    assert regs == {"lint.p1.flops"}


def test_forensics_report_smoke(tmp_path, capsys):
    """tools/forensics_report.py (jax-free): folds a metrics.jsonl with
    packed mask columns into the per-worker table + episode list and
    writes forensics.json; tolerates a torn tail line, an empty file, and
    a missing file exactly like trace_report."""
    import json

    from tools import forensics_report

    def rec(step, accused, present, adv):
        words = lambda bits: sum(1 << i for i, b in enumerate(bits) if b)
        return {"step": step, "loss": 1.0,
                "wmask_accused0": words(accused),
                "wmask_present0": words(present),
                "wmask_adv0": words(adv)}

    d = tmp_path / "run"
    d.mkdir()
    ones = [1] * 4
    with open(d / "metrics.jsonl", "w") as fh:
        # worker 2 adversarial for steps 1-2 (one episode), clean step 3;
        # worker 0 absent at step 2; an eval record and a torn tail ride
        fh.write(json.dumps(rec(1, [0, 0, 1, 0], ones, [0, 0, 1, 0])) + "\n")
        fh.write(json.dumps(rec(2, [0, 0, 1, 0], [0, 1, 1, 1],
                                [0, 0, 1, 0])) + "\n")
        fh.write(json.dumps(rec(3, [0, 0, 0, 0], ones, [0, 0, 0, 0])) + "\n")
        fh.write(json.dumps({"step": 3, "split": "eval", "loss": 0.9})
                 + "\n\n")
        fh.write('{"step": 4, "los')  # torn tail of a killed run

    rc = forensics_report.main([str(d), "--num-workers", "4"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "3/3 records carried masks" in out
    assert "worker 2: steps 1-2 (2 accused)" in out
    assert "top suspects: w2" in out
    rep = json.loads((d / "forensics.json").read_text())
    w2 = rep["workers"][2]
    assert w2["accused"] == 2 and w2["tp"] == 2 and w2["precision"] == 1.0
    assert rep["workers"][0]["present"] == 2  # absent step not counted
    assert len(rep["episodes"]) == 1 and not rep["episodes"][0]["open"]
    # worker count can come from the present masks when the flag is absent
    rep2 = forensics_report.make_report(str(d / "metrics.jsonl"))
    assert rep2["num_workers"] == 4

    # empty + missing files fold to an empty report, not a crash
    e = tmp_path / "empty"
    e.mkdir()
    (e / "metrics.jsonl").write_text("")
    assert forensics_report.main([str(e)]) == 0
    assert "no forensics columns" in capsys.readouterr().out
    m = tmp_path / "missing"
    m.mkdir()
    assert forensics_report.main([str(m)]) == 0


def test_perf_watch_gates_on_flipped_chaos_attribution(tmp_path):
    """A worker-targeted chaos cell whose forensics attribution flips to
    false must gate perf_watch nonzero (tolerance 0) and name the cell."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    matrix = {"all_ok": True, "rows": [
        {"loop": "cnn_k4", "fault": "nan_grad", "ok": True,
         "outcome": "guarded", "injected": [3], "accused": [3],
         "attributed": True},
        {"loop": "cnn_k4", "fault": "sigterm", "ok": True,
         "outcome": "preempted_resumed"},
    ]}
    (root / "baselines_out" / "chaos_matrix.json").write_text(
        json.dumps(matrix))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "chaos.cnn_k4.nan_grad.attributed" in snap["metrics"]
    assert "chaos.cnn_k4.sigterm.attributed" not in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    matrix["rows"][0]["attributed"] = False  # the forensics regression
    matrix["rows"][0]["accused"] = [0, 7]
    (root / "baselines_out" / "chaos_matrix.json").write_text(
        json.dumps(matrix))
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = [r["metric"] for r in json.loads(out.read_text())["regressions"]]
    assert "chaos.cnn_k4.nan_grad.attributed" in regs


def test_perf_watch_gates_on_flipped_chaos_incident(tmp_path):
    """ISSUE 13 acceptance control: a chaos cell whose expected incident
    goes absent or mis-attributed (``incident.ok`` flips false) must gate
    perf_watch nonzero at tolerance 0 and name cell + metric — the proof
    the incident gate is live, not decorative."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    matrix = {"all_ok": True, "rows": [
        {"loop": "cnn_k4", "fault": "nan_grad", "ok": True,
         "outcome": "guarded", "injected": [3], "accused": [3],
         "attributed": True,
         "incident": {"ok": True, "raised": ["guard", "nonfinite"],
                      "required": ["nonfinite"]}},
        {"loop": "approx_k4", "fault": "straggle", "ok": True,
         "outcome": "degraded_bounded",
         "incident": {"ok": True, "raised": [], "required": []}},
    ]}
    path = root / "baselines_out" / "chaos_matrix.json"
    path.write_text(json.dumps(matrix))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "chaos.cnn_k4.nan_grad.incident_ok" in snap["metrics"]
    assert "chaos.approx_k4.straggle.incident_ok" in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    # the detector goes blind: the expected incident is no longer raised
    matrix["rows"][0]["incident"] = {
        "ok": False, "raised": ["guard"], "required": ["nonfinite"],
        "detail": "expected incident 'nonfinite' not raised"}
    path.write_text(json.dumps(matrix))
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = [r["metric"] for r in json.loads(out.read_text())["regressions"]]
    assert "chaos.cnn_k4.nan_grad.incident_ok" in regs
    # ...and a SPURIOUS incident on a clean-telemetry cell gates too
    matrix["rows"][0]["incident"]["ok"] = True
    matrix["rows"][1]["incident"] = {
        "ok": False, "raised": ["throughput"], "required": [],
        "detail": "spurious incident(s): ['throughput']"}
    path.write_text(json.dumps(matrix))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = [r["metric"] for r in json.loads(out.read_text())["regressions"]]
    assert "chaos.approx_k4.straggle.incident_ok" in regs


def test_straggler_study_tool(tmp_path):
    """tools/straggler_study.py smoke (ISSUE 8): approx cells at e ∈ {0, 2}
    train on the chunked production loop, carry the residual-vs-bound
    certificate, and the compute-to-target column scales by the family's
    redundancy."""
    import json

    from tools import straggler_study

    out = tmp_path / "study.json"
    rc = straggler_study.main([
        "--out", str(out), "--cpu-mesh", "8", "--families", "approx",
        "--drops", "0,2", "--max-steps", "14", "--target-loss", "1.9",
    ])
    rep = json.loads(out.read_text())
    assert rc == 0 and rep["all_ok"]
    assert len(rep["rows"]) == 2
    for row in rep["rows"]:
        assert row["family"] == "approx" and row["feasible"]
        assert row["reached_target"] and row["residual_within_bound"]
        assert row["guard_trips_total"] == 0.0
        # compute axis = steps x round(r*n) = steps x 12 at r=1.5, n=8
        assert row["compute_to_target"] == row["steps_to_target"] * 12
        assert 0.0 < row["recovered_fraction_min"] <= 1.0
        assert row["ms_per_step"] > 0
    # full participation decodes exactly; two drops pay a real residual
    e0, e2 = rep["rows"]
    assert e0["residual_max"] < 1e-4 <= e2["residual_max"]
    # a partial sweep (--families approx) must NOT claim the unswept
    # exact family was infeasible
    assert rep["crossover"]["0"] == "approx (only family swept)"


def test_perf_watch_gates_on_flipped_straggler_bound(tmp_path):
    """A straggler-study cell whose measured residual exceeds its analytic
    bound (residual_within_bound flipping false) must gate perf_watch
    nonzero at tolerance 0 and name the cell — same for a lost batch
    coverage and an exact-code cell silently claiming feasibility it does
    not have."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    study = {"all_ok": True, "rows": [
        {"family": "approx", "drop_count": 2, "feasible": True,
         "reached_target": True, "residual_within_bound": True,
         "recovered_fraction_min": 1.0, "ms_per_step": 50.0, "ok": True},
        {"family": "cyclic", "drop_count": 3, "feasible": False},
    ]}
    (root / "baselines_out" / "straggler_study.json").write_text(
        json.dumps(study))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "straggler.approx.e2.residual_within_bound" in snap["metrics"]
    # infeasible cells fold ONLY their feasibility flag
    assert "straggler.cyclic.e3.feasible" in snap["metrics"]
    assert "straggler.cyclic.e3.reached_target" not in snap["metrics"]
    # the wall column is a CPU's clock: not folded
    assert "straggler.approx.e2.ms_per_step" not in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    study["rows"][0]["residual_within_bound"] = False
    study["rows"][0]["recovered_fraction_min"] = 0.875
    study["all_ok"] = False
    (root / "baselines_out" / "straggler_study.json").write_text(
        json.dumps(study))
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert {"straggler.approx.e2.residual_within_bound",
            "straggler.approx.e2.recovered_fraction_min",
            "straggler.all_ok"} <= regs

    # the feasibility flag is kind "pinned": the budget-infeasible cyclic
    # cell silently claiming feasibility (0 -> 1, the "good" direction for
    # an ok-kind bool) must ALSO gate — feasibility changes are semantic,
    # never improvements
    study["rows"][0]["residual_within_bound"] = True
    study["rows"][0]["recovered_fraction_min"] = 1.0
    study["all_ok"] = True
    study["rows"][1]["feasible"] = True
    (root / "baselines_out" / "straggler_study.json").write_text(
        json.dumps(study))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "straggler.cyclic.e3.feasible" in regs


def test_autopilot_study_infeasible_cell_fast(tmp_path):
    """tools/autopilot_study.py partial sweep: the fixed-approx cell is
    infeasible BY CONSTRUCTION under the adversary scenario (config.
    validate: no Byzantine certificate) and a partial sweep can never
    certify beats_fixed — exit 1 with the structure intact."""
    import json

    from tools import autopilot_study

    out = tmp_path / "ap.json"
    rc = autopilot_study.main(["--cells", "approx_r1.5",
                               "--out", str(out)])
    assert rc == 1
    data = json.loads(out.read_text())
    (row,) = data["rows"]
    assert row["cell"] == "approx_r1.5" and row["feasible"] is False
    assert "adversary" in row["detail"]
    assert data["infeasible_fixed"] == ["approx_r1.5"]
    assert data["autopilot_beats_fixed"] is False
    assert data["scenario"].count("@") == 3  # the committed 3-episode plan


def test_perf_watch_gates_on_flipped_autopilot_certificates(tmp_path):
    """The autopilot-study certificates gate at tolerance 0 in BOTH
    directions: beats_fixed or quarantine_clean flipping false is a
    control-loop regression; the infeasible fixed-approx cell silently
    claiming feasibility (the 'good' direction) is a semantic change in
    the family's validation and must gate too (kind 'pinned')."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    study = {"all_ok": True, "autopilot_beats_fixed": True, "rows": [
        {"cell": "autopilot", "feasible": True, "reached_target": True,
         "remediations_attributed": True, "dialed_down": True,
         "dialed_up": True, "quarantine_clean": True, "ok": True},
        {"cell": "cyclic_r3", "feasible": True, "reached_target": True,
         "ok": True},
        {"cell": "approx_r1.5", "feasible": False},
    ]}
    path = root / "baselines_out" / "autopilot_study.json"
    path.write_text(json.dumps(study))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "autopilot.autopilot_beats_fixed" in snap["metrics"]
    assert "autopilot.autopilot.quarantine_clean" in snap["metrics"]
    # infeasible cells fold ONLY their (pinned) feasibility flag
    assert "autopilot.approx_r1.5.feasible" in snap["metrics"]
    assert "autopilot.approx_r1.5.reached_target" not in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    study["autopilot_beats_fixed"] = False
    study["all_ok"] = False
    study["rows"][0]["quarantine_clean"] = False
    path.write_text(json.dumps(study))
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert {"autopilot.autopilot_beats_fixed",
            "autopilot.autopilot.quarantine_clean",
            "autopilot.all_ok"} <= regs

    # the pinned direction: fixed approx silently becoming feasible gates
    study["autopilot_beats_fixed"] = True
    study["all_ok"] = True
    study["rows"][0]["quarantine_clean"] = True
    study["rows"][2]["feasible"] = True
    path.write_text(json.dumps(study))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "autopilot.approx_r1.5.feasible" in regs


def test_perf_watch_passes_on_committed_artifacts():
    """The committed baselines_out/perf_watch.json snapshot must match the
    committed round artifacts — the same gate a future round runs."""
    from tools import perf_watch

    assert perf_watch.main(["--root", REPO]) == 0


def test_device_profile_check_gates_on_flipped_decode_share(tmp_path,
                                                            capsys):
    """tools/device_profile.py --check (jax-free): the committed artifact
    passes its self-consistency gate; a flipped decode-share row exits 1
    and names the cell + metric; a broken phase sum and an un-tripped
    mismatch control gate too (ISSUE 9 acceptance)."""
    import json

    from tools import device_profile

    committed = os.path.join(REPO, "baselines_out", "device_profile.json")
    assert device_profile.main(["--check", "--artifact", committed]) == 0
    capsys.readouterr()

    data = json.load(open(committed))
    cell = next(r for r in data["cells"] if not r.get("control"))
    # flip the decode-share column without touching the phase rows it is
    # derived from — the check recomputes and names the drift
    cell["programs"][0]["decode_share"] = round(
        cell["programs"][0]["decode_share"] + 0.25, 4)
    bad = tmp_path / "device_profile.json"
    bad.write_text(json.dumps(data))
    assert device_profile.main(["--check", "--artifact", str(bad)]) == 1
    out = capsys.readouterr().out
    assert cell["cell"] in out and "decode_share" in out

    # a phase row edited out from under the total breaks the sums contract
    data = json.load(open(committed))
    cell = next(r for r in data["cells"] if not r.get("control"))
    cell["programs"][0]["phases"]["draco_comp"]["time_us"] = 0.0
    bad.write_text(json.dumps(data))
    assert device_profile.main(["--check", "--artifact", str(bad)]) == 1
    assert "phase rows sum" in capsys.readouterr().out

    # the seeded mismatch control must have tripped
    data = json.load(open(committed))
    next(r for r in data["cells"] if r.get("control"))["ok"] = False
    bad.write_text(json.dumps(data))
    assert device_profile.main(["--check", "--artifact", str(bad)]) == 1
    assert "control did not trip" in capsys.readouterr().out


def test_perf_watch_gates_on_flipped_device_metrics(tmp_path):
    """The explicit-collective instruction count of device_profile.json is
    pinned at tolerance 0 in BOTH directions (a collective vanishing from
    the trace is as much a semantic change as one appearing) and the
    mismatch control must stay tripped; a phase's share of the CPU trace's
    time is not folded, so a decode share that grows gates nothing."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()

    def artifact(decode_share, ar_instr, control_ok=True):
        phases = {
            "draco_comp": {"time_us": 700.0, "frac": 0.7, "events": 10},
            "draco_encode": {"time_us": 50.0, "frac": 0.05, "events": 2},
            "draco_decode": {"time_us": decode_share * 1000.0,
                             "frac": decode_share, "events": 5},
            "draco_update": {"time_us": 30.0, "frac": 0.03, "events": 1},
            "other": {"time_us": 20.0, "frac": 0.02, "events": 1},
            "unattributed": {"time_us": 0.0, "frac": 0.0, "events": 0},
        }
        counts = {"all_reduce": ar_instr, "all_gather": 0, "all_to_all": 0,
                  "collective_permute": 5, "reduce_scatter": 0}
        led = {k: {"instructions": counts[k], "events": counts[k] * 8,
                   "bytes": counts[k] * 4096, "time_us": 1.0}
               for k in counts}
        return {"schema": 1, "all_ok": True, "cells": [
            {"cell": "lm_sp_k4", "steps_per_call": 4, "ok": True,
             "programs": [{
                 "module": "jit_many_body", "total_device_us": 1000.0,
                 "phases": phases, "decode_share": decode_share,
                 "collectives": {"explicit": led,
                                 "gspmd": {}},
                 "cross_check": {"ok": True, "expected": counts,
                                 "observed": counts},
             }]},
            {"cell": "control_extra_all_gather", "control": True,
             "ok": control_ok},
        ]}

    path = root / "baselines_out" / "device_profile.json"
    path.write_text(json.dumps(artifact(0.20, 2)))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "device.lm_sp_k4.draco_decode_share" not in snap["metrics"]
    assert "device.lm_sp_k4.coll.all_reduce.instructions" in snap["metrics"]
    assert "device.control_extra_all_gather.tripped" in snap["metrics"]
    # zero-count kinds with a zero manifest don't spam the metric set
    assert "device.lm_sp_k4.coll.all_to_all.instructions" \
        not in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    # decode share grows 30% relative: a CPU trace's time, no gate
    path.write_text(json.dumps(artifact(0.26, 2)))
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 0

    # an explicit collective VANISHING (2 -> 1, the "good" direction for a
    # lower-better kind) still gates: the ledger is pinned, not scored
    path.write_text(json.dumps(artifact(0.20, 1)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert {"device.lm_sp_k4.coll.all_reduce.instructions",
            "device.lm_sp_k4.coll.all_reduce.bytes"} <= regs

    # the mismatch control silently not tripping gates too
    path.write_text(json.dumps(artifact(0.20, 2, control_ok=False)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "device.control_extra_all_gather.tripped" in regs


@pytest.mark.slow
def test_wire_study_tool(tmp_path):
    """tools/wire_study.py smoke (ISSUE 10; slow-marked — the live-cell
    behavior is already pinned in tier 1 by the watch-enabled K∈{1,4}
    equivalence suites, and the committed artifact by --check +
    check_artifacts + the perf_watch flipped-row gates): a cyclic bf16
    cell runs the shadow-quantized wire with a LIVE adversary, and
    detection survives quantization — flag agreement 1.0, shadow P/R 1.0,
    bounded end-to-end error, and the logical bytes ledger at the
    program's real dimension."""
    import json

    from tools import wire_study

    out = tmp_path / "wire.json"
    rc = wire_study.main([
        "--out", str(out), "--cpu-mesh", "8", "--families", "cyclic",
        "--dtypes", "bf16", "--ks", "1", "--max-steps", "6",
    ])
    rep = json.loads(out.read_text())
    assert rc == 0 and rep["all_ok"]
    row = rep["rows"][0]
    assert row["family"] == "cyclic" and row["dtype"] == "bf16"
    assert row["steps"] == 6
    assert row["det_preserved"]
    assert row["shadow_flag_agree_min"] == 1.0
    assert row["det_precision_shadow"] == 1.0
    assert row["det_recall_shadow"] == 1.0
    assert row["adv_total"] > 0  # the adversary was really live
    assert 0.0 <= row["shadow_err_max"] < 0.05
    assert row["guard_trips_total"] == 0.0
    per = row["wire"]["bytes_per_worker"]
    assert per["bf16"] * 2 == per["f32"] and per["int8"] < per["bf16"]
    # the REAL-wire cell (ISSUE 15) rides the same invocation: bounded
    # end-to-end error vs the f32 twin, P/R 1.0 on the narrow wire's own
    # flags, and the materialized bytes ARE the logical bf16 candidate
    real = next(r for r in rep["rows"] if r.get("mode") == "real")
    assert real["det_precision"] == 1.0 and real["det_recall"] == 1.0
    assert 0.0 < real["end_to_end_err"] < 2e-2
    assert real["wire"]["wire_dtype"] == "bf16"
    assert real["wire"]["physical_bytes_per_worker"] \
        == real["wire"]["bytes_per_worker"]["bf16"]
    # the locator cells replay the PR 10 blocker: λ=0 reproduces it, the
    # committed λ solves it
    locs = {bool(r["regularized"]): r for r in rep["rows"]
            if r.get("mode") == "locator" and r["dtype"] == "bf16"}
    assert not locs[False]["usable"] and locs[True]["usable"]


def test_wire_study_check_names_failures(tmp_path):
    """--check (jax-free) trips on a stale ledger, a lost bf16 detection
    pin, and a false all_ok — naming the cell."""
    import json

    from tools import wire_study

    committed = os.path.join(REPO, "baselines_out", "wire_study.json")
    data = json.load(open(committed))
    assert wire_study.main(["--check", "--artifact", committed]) == 0

    bad = tmp_path / "wire_study.json"
    # ledger bytes inconsistent with dim
    d2 = json.loads(json.dumps(data))
    d2["rows"][0]["wire"]["bytes_per_worker"]["f32"] += 4
    bad.write_text(json.dumps(d2))
    assert wire_study.main(["--check", "--artifact", str(bad)]) == 1

    # a bf16 row losing detection must fail even if its ok flag lies
    d2 = json.loads(json.dumps(data))
    row = next(r for r in d2["rows"] if r["dtype"] == "bf16")
    row["det_preserved"] = False
    bad.write_text(json.dumps(d2))
    assert wire_study.main(["--check", "--artifact", str(bad)]) == 1

    d2 = json.loads(json.dumps(data))
    d2["all_ok"] = False
    bad.write_text(json.dumps(d2))
    assert wire_study.main(["--check", "--artifact", str(bad)]) == 1


def test_perf_watch_gates_on_flipped_wire_metrics(tmp_path):
    """The wire-study fold (ISSUE 10): shadow residual / flag agreement
    are PINNED at tolerance 0 — a flipped row gates in BOTH directions
    (the live flipped-row control of the acceptance criteria) — and a
    det_preserved flip or shadow-recall drop gates as 0-tolerance ok."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()

    def artifact(residual=0.0001, agree=1.0, preserved=True, recall=1.0):
        row = {"family": "cyclic", "dtype": "bf16", "k": 4,
               "shadow_err_max": 0.005, "shadow_residual_max": residual,
               "shadow_flag_agree_min": agree, "det_preserved": preserved,
               "det_precision_shadow": 1.0, "det_recall_shadow": recall,
               "wire": {"bytes_per_worker": {"f32": 800, "bf16": 400,
                                             "int8": 214}},
               "ok": True}
        return {"all_ok": True, "rows": [row]}

    path = root / "baselines_out" / "wire_study.json"
    path.write_text(json.dumps(artifact()))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "wire.cyclic.bf16.k4.shadow_residual_max" in snap["metrics"]
    assert "wire.cyclic.bf16.k4.bytes_per_worker" in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    out = root / "report.json"
    # the flipped shadow-residual row: a DECREASE also gates (pinned)
    path.write_text(json.dumps(artifact(residual=0.00005)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.cyclic.bf16.k4.shadow_residual_max" in regs

    # flag agreement dipping below 1.0 gates
    path.write_text(json.dumps(artifact(agree=0.875)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.cyclic.bf16.k4.shadow_flag_agree_min" in regs

    # detection lost under quantization gates
    path.write_text(json.dumps(artifact(preserved=False, recall=0.8)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert {"wire.cyclic.bf16.k4.det_preserved",
            "wire.cyclic.bf16.k4.det_recall_shadow"} <= regs


def test_perf_watch_gates_on_flipped_real_wire_metrics(tmp_path):
    """The ISSUE 15 real-wire fold: narrow-wire detection P/R and the
    pinned end-to-end error gate at tolerance 0 in BOTH directions; the
    physical bytes ride at the bytes tolerance (a ballooning wire gates,
    an honest dim change inside tolerance does not); the locator cells'
    blocker certificate is pinned BOTH ways — the λ=0 row silently
    becoming usable gates exactly like the regularized row losing it."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()

    def artifact(err=0.0002, prec=1.0, phys=214, unreg_usable=False,
                 reg_usable=True):
        rows = [
            {"mode": "real", "family": "cyclic", "dtype": "int8", "k": 4,
             "end_to_end_err": err, "det_precision": prec,
             "det_recall": 1.0, "det_preserved": prec == 1.0,
             "wire": {"bytes_per_worker": {"f32": 800, "bf16": 400,
                                           "int8": 214},
                      "wire_dtype": "int8",
                      "physical_bytes_per_worker": phys},
             "ok": True},
            {"mode": "locator", "n": 32, "s": 3, "dtype": "int8",
             "lam": 0.0, "regularized": False, "usable": unreg_usable,
             "honest_dev_max_noadv": 136.9, "adv_dev_min": 0.333,
             "ok": not unreg_usable},
            {"mode": "locator", "n": 32, "s": 3, "dtype": "int8",
             "lam": 0.015625, "regularized": True, "usable": reg_usable,
             "honest_dev_max_noadv": 0.24, "adv_dev_min": 0.333,
             "ok": reg_usable},
        ]
        return {"all_ok": all(r["ok"] for r in rows), "rows": rows}

    path = root / "baselines_out" / "wire_study.json"
    path.write_text(json.dumps(artifact()))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    for key in ("wire.real.cyclic.int8.k4.det_precision",
                "wire.real.cyclic.int8.k4.end_to_end_err",
                "wire.real.cyclic.int8.k4.physical_bytes_per_worker",
                "wire.locator.n32s3.int8.unreg.usable",
                "wire.locator.n32s3.int8.reg.usable"):
        assert key in snap["metrics"], key
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    out = root / "report.json"
    # end-to-end err is PINNED: an IMPROVEMENT gates too
    path.write_text(json.dumps(artifact(err=0.0001)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.real.cyclic.int8.k4.end_to_end_err" in regs

    # lost precision on the real wire gates as ok-kind
    path.write_text(json.dumps(artifact(prec=0.8)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.real.cyclic.int8.k4.det_precision" in regs

    # a ballooning physical wire gates at the bytes tolerance
    path.write_text(json.dumps(artifact(phys=800)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.real.cyclic.int8.k4.physical_bytes_per_worker" in regs

    # the blocker certificate flips BOTH ways
    path.write_text(json.dumps(artifact(unreg_usable=True)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.locator.n32s3.int8.unreg.usable" in regs
    path.write_text(json.dumps(artifact(reg_usable=False)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "wire.locator.n32s3.int8.reg.usable" in regs


def test_wire_study_check_real_and_locator_rows(tmp_path):
    """wire_study --check (ISSUE 15): the committed artifact passes; a
    mutated real row (physical bytes diverging from the ledger, P/R
    dropping) or a flipped locator certificate is caught and named."""
    import copy
    import json

    from tools import wire_study

    committed = os.path.join(REPO, "baselines_out", "wire_study.json")
    data = json.load(open(committed))
    assert wire_study.main(["--check", "--artifact", committed]) == 0

    bad = tmp_path / "wire_study.json"

    def mutate(fn):
        d = copy.deepcopy(data)
        fn(d)
        bad.write_text(json.dumps(d))
        return wire_study.main(["--check", "--artifact", str(bad)])

    def first(d, mode):
        return next(r for r in d["rows"] if r.get("mode") == mode)

    # materialized bytes diverging from the logical candidate row
    assert mutate(lambda d: first(d, "real")["wire"].update(
        physical_bytes_per_worker=999999)) == 1
    # detection lost on the real wire
    def drop_pr(d):
        r = next(r for r in d["rows"] if r.get("mode") == "real"
                 and r["family"] == "cyclic")
        r["det_precision"] = 0.5
    assert mutate(drop_pr) == 1
    # the λ=0 blocker "solved" (exact path changed) trips
    def flip_unreg(d):
        r = next(r for r in d["rows"] if r.get("mode") == "locator"
                 and not r["regularized"])
        r["usable"] = True
    assert mutate(flip_unreg) == 1
    # the regularized threshold drifting off the committed table trips
    def drift_thr(d):
        r = next(r for r in d["rows"] if r.get("mode") == "locator"
                 and r["regularized"])
        r["threshold"] = r["threshold"] * 2
    assert mutate(drift_thr) == 1


def test_perf_watch_gates_on_flipped_chaos_numerics(tmp_path):
    """The nan_grad cells' ISSUE 10 NaN-safety flags (numerics_finite /
    fault_visible) gate perf_watch at tolerance 0."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()
    matrix = {"all_ok": True, "rows": [
        {"loop": "cnn_k4", "fault": "nan_grad", "ok": True,
         "outcome": "guarded", "attributed": True,
         "numerics_finite": True, "fault_visible": True},
    ]}
    (root / "baselines_out" / "chaos_matrix.json").write_text(
        json.dumps(matrix))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    assert "chaos.cnn_k4.nan_grad.numerics_finite" in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0

    matrix["rows"][0]["numerics_finite"] = False
    (root / "baselines_out" / "chaos_matrix.json").write_text(
        json.dumps(matrix))
    out = root / "report.json"
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = [r["metric"] for r in json.loads(out.read_text())["regressions"]]
    assert "chaos.cnn_k4.nan_grad.numerics_finite" in regs


def test_check_artifacts_tool(tmp_path, capsys):
    """tools/check_artifacts.py (jax-free, ISSUE 10 satellite): one
    command re-verifies every committed artifact and exits 0 on the
    repo; a root with a broken artifact exits 1 NAMING the first
    failing check."""
    from tools import check_artifacts

    assert check_artifacts.main(["--root", REPO]) == 0
    out = capsys.readouterr().out
    assert "all" in out and "passed" in out

    # an empty root has no perf_watch baseline: the first check fails
    # and is named
    (tmp_path / "baselines_out").mkdir()
    assert check_artifacts.main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "FAILED at 'perf_watch'" in out

    # a root whose perf_watch passes but whose wire study is broken names
    # THAT check: copy the committed snapshot world minus wire_study
    import json
    import shutil

    for f in ("perf_watch.json", "program_lint.json", "chaos_matrix.json",
              "straggler_study.json", "device_profile.json",
              "wire_study.json"):
        src = os.path.join(REPO, "baselines_out", f)
        if os.path.exists(src):
            shutil.copy(src, tmp_path / "baselines_out" / f)
    study = json.load(open(tmp_path / "baselines_out" / "wire_study.json"))
    # break the ledger ARITHMETIC of a column perf_watch does not fold
    # (the f32 bytes of a bf16 row), so the perf_watch check still passes
    # and the failure is attributed to the wire_study verifier
    study["rows"][0]["wire"]["bytes_per_worker"]["f32"] += 4
    (tmp_path / "baselines_out" / "wire_study.json").write_text(
        json.dumps(study))
    # the artifacts not copied here fold as missing (non-fatal without
    # --strict-missing)
    assert check_artifacts.main(["--root", str(tmp_path)]) == 1
    assert "FAILED at 'wire_study --check'" in capsys.readouterr().out


def test_device_profile_check_gates_on_pallas_claim(tmp_path, capsys):
    """The ISSUE 12 acceptance gate: every PALLAS_CLAIMS pair in the
    committed device profile shows the fused-decode cell's decode share
    STRICTLY below its same-shape xla pair; a flipped pallas cell exits 1
    naming the pair, and a half-missing pair gates too."""
    import json

    from tools import device_profile

    committed = os.path.join(REPO, "baselines_out", "device_profile.json")
    data = json.load(open(committed))
    cells = {r.get("cell") for r in data["cells"]}
    for p, x in device_profile.PALLAS_CLAIMS.items():
        assert {p, x} <= cells, "committed artifact must hold EVERY pair"
    pal, xla = next(iter(sorted(device_profile.PALLAS_CLAIMS.items())))
    assert device_profile.main(["--check", "--artifact", committed]) == 0
    capsys.readouterr()

    # flip the pallas cell's decode share above its xla pair — keep the
    # phase rows consistent so ONLY the claim gate trips
    bad_data = json.load(open(committed))
    pal_row = next(r for r in bad_data["cells"] if r.get("cell") == pal)
    xla_row = next(r for r in bad_data["cells"] if r.get("cell") == xla)
    xla_share = xla_row["programs"][0]["decode_share"]
    prog = pal_row["programs"][0]
    dec = prog["phases"]["draco_decode"]
    comp = prog["phases"]["draco_comp"]
    total = prog["total_device_us"]
    new_frac = round(xla_share + 0.1, 4)
    moved = new_frac * total - dec["time_us"]
    dec["time_us"] = round(dec["time_us"] + moved, 1)
    comp["time_us"] = round(comp["time_us"] - moved, 1)
    dec["frac"] = new_frac
    comp["frac"] = round(comp["time_us"] / total, 4)
    prog["decode_share"] = new_frac
    bad = tmp_path / "device_profile.json"
    bad.write_text(json.dumps(bad_data))
    assert device_profile.main(["--check", "--artifact", str(bad)]) == 1
    out = capsys.readouterr().out
    assert pal in out and "not strictly below" in out

    # a claim pair with its xla half missing is incomplete, never
    # skipped — and a regeneration that drops BOTH cells of a claimed
    # pair fails too (the claim may never silently go unenforced)
    bad_data = json.load(open(committed))
    bad_data["cells"] = [r for r in bad_data["cells"]
                         if r.get("cell") != xla]
    bad.write_text(json.dumps(bad_data))
    assert device_profile.main(["--check", "--artifact", str(bad)]) == 1
    assert "claim pair missing/incomplete" in capsys.readouterr().out
    bad_data = json.load(open(committed))
    bad_data["cells"] = [r for r in bad_data["cells"]
                         if r.get("cell") not in (pal, xla)]
    bad.write_text(json.dumps(bad_data))
    assert device_profile.main(["--check", "--artifact", str(bad)]) == 1
    assert pal in capsys.readouterr().out


def test_perf_watch_gates_on_flipped_sharding_axis_ledger(tmp_path):
    """The sharding auditor's per-axis collective ledger (lint rule 8,
    ISSUE 18) folds as ``lint.<program>.coll.<axis>.{ops,bytes}`` and is
    PINNED at tolerance 0 in BOTH directions: an all-reduce moving to a
    different mesh axis — or vanishing, the 'good' direction for a
    lower-better kind — is a topology change, never an improvement."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()

    def lint(sp_ops=7, sp_bytes=500745, w_bytes=1024):
        rules = {
            "constant_bloat": {"ok": True, "module_bytes": 1000},
            "memory_budget": {"ok": True, "flops": 1e6,
                              "memory": {"peak_bytes": 5000}},
            "collective_axes": {
                "ok": True,
                "axis_ledger": {"sp": {"ops": sp_ops, "bytes": sp_bytes},
                                "w": {"ops": 2, "bytes": w_bytes}}},
        }
        return {"all_ok": True, "rows": [
            {"name": "lm_sp_ring_step", "ok": True, "rules": rules},
            {"name": "control_wrong_axis_psum", "ok": True,
             "control": True, "expected_fail": "collective_axes",
             "rules": {}},
        ]}

    path = root / "baselines_out" / "program_lint.json"
    path.write_text(json.dumps(lint()))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    for key in ("lint.lm_sp_ring_step.coll.sp.ops",
                "lint.lm_sp_ring_step.coll.sp.bytes",
                "lint.lm_sp_ring_step.coll.w.bytes"):
        assert key in snap["metrics"], key
        assert snap["metrics"][key]["kind"] == "pinned", key
    # control rows never fold ledger metrics
    assert "lint.control_wrong_axis_psum.coll" not in str(snap)
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    out = root / "report.json"
    # bytes growing on an axis gates...
    path.write_text(json.dumps(lint(sp_bytes=600000)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "lint.lm_sp_ring_step.coll.sp.bytes" in regs

    # ...and an op VANISHING from an axis (7 -> 6, the 'good' direction)
    # gates identically: the ledger is pinned, not scored
    path.write_text(json.dumps(lint(sp_ops=6)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "lint.lm_sp_ring_step.coll.sp.ops" in regs

    # w-axis bytes shrinking gates too (both-direction on a second axis)
    path.write_text(json.dumps(lint(w_bytes=512)))
    assert perf_watch.main(["--root", str(root), "--json", str(out)]) == 1
    regs = {r["metric"] for r in json.loads(out.read_text())["regressions"]}
    assert "lint.lm_sp_ring_step.coll.w.bytes" in regs


def test_check_artifacts_sharding_audit_and_lint_config(tmp_path):
    """check_artifacts' ISSUE 18 checks (jax-free): a stale six-rule
    artifact, a program row missing a rule-7 verdict, and a blunted
    negative control each fail 'sharding audit coverage' with the first
    failure named; a repo root without a lint config fails 'lint config
    present'."""
    import json

    from tools.check_artifacts import (
        _check_lint_config, _check_sharding_audit,
    )

    root = tmp_path
    (root / "baselines_out").mkdir()
    path = root / "baselines_out" / "program_lint.json"

    def artifact():
        rules7_9 = {"sharding_contract": {"ok": True},
                    "collective_axes": {"ok": True},
                    "replication_leaks": {"ok": True}}
        controls = [
            {"name": n, "control": True, "ok": True, "expected_fail": f}
            for n, f in (
                ("control_resharded_carry", "sharding_contract"),
                ("control_unnormalized_spec", "sharding_contract"),
                ("control_unmatched_param", "sharding_contract"),
                ("control_wrong_axis_psum", "collective_axes"),
                ("control_replicated_wire", "replication_leaks"),
            )]
        return {"all_ok": True,
                "rules": ["sharding_contract", "collective_axes",
                          "replication_leaks"],
                "rows": [{"name": "p1", "ok": True,
                          "rules": dict(rules7_9)}] + controls}

    path.write_text(json.dumps(artifact()))
    assert _check_sharding_audit(str(root)) is None

    # stale rule list (regenerated from a six-rule checkout)
    art = artifact()
    art["rules"] = ["constant_bloat"]
    path.write_text(json.dumps(art))
    assert "regenerate" in _check_sharding_audit(str(root))

    # a program row without the rule-9 verdict
    art = artifact()
    del art["rows"][0]["rules"]["replication_leaks"]
    path.write_text(json.dumps(art))
    err = _check_sharding_audit(str(root))
    assert "p1" in err and "replication_leaks" in err

    # a red verdict on a program row names the rule
    art = artifact()
    art["rows"][0]["rules"]["collective_axes"] = {
        "ok": False, "error": "psum over 'w' not in the manifest"}
    path.write_text(json.dumps(art))
    err = _check_sharding_audit(str(root))
    assert "p1" in err and "collective_axes" in err

    # a live control silently going green (blunted defect) fails
    art = artifact()
    ctrl = next(r for r in art["rows"]
                if r["name"] == "control_replicated_wire")
    ctrl["ok"] = False
    path.write_text(json.dumps(art))
    assert "control_replicated_wire" in _check_sharding_audit(str(root))

    # ...and a missing control fails by name
    art = artifact()
    art["rows"] = [r for r in art["rows"]
                   if r["name"] != "control_wrong_axis_psum"]
    path.write_text(json.dumps(art))
    assert "control_wrong_axis_psum" in _check_sharding_audit(str(root))

    # lint config: absent fails; present-with-line-length passes; a
    # config that pins no line budget fails
    assert "no ruff.toml" in _check_lint_config(str(root))
    (root / "ruff.toml").write_text("line-length = 79\n")
    assert _check_lint_config(str(root)) is None
    (root / "ruff.toml").write_text("[lint]\nselect = ['E']\n")
    assert "line-length" in _check_lint_config(str(root))


def test_fleet_report_and_study_check_on_committed_artifact(tmp_path):
    """ISSUE 19: tools/fleet_report.py runs jax-free on a bare checkout
    (empty root exits 0), and the committed fleet_slo.json passes its
    own --check re-verification — the same gate check_artifacts runs."""
    import json

    from tools import fleet_report, fleet_study

    empty = tmp_path / "none"
    empty.mkdir()
    assert fleet_report.main(["--runs-root", str(empty)]) == 0

    payload = json.load(
        open(os.path.join(REPO, "baselines_out", "fleet_slo.json")))
    assert fleet_study.verify_payload(payload) == []
    rows = payload["rows"]
    assert len(rows) >= 6
    assert {r["loop"] for r in rows} == {"cnn", "lm"}
    kinds = {r["kind"] for r in rows}
    assert {"clean", "adversary", "straggler", "autopilot"} <= kinds
    assert all(r["budget_burned"] == 0.0 for r in rows)
    for r in rows:
        if r["kind"] in ("adversary", "autopilot"):
            det = r["slo"]["detection_quality"]
            assert det["precision"] == det["recall"] == 1.0
            assert det["adv_total"] > 0  # live, not vacuous
        if r["kind"] == "autopilot":
            mttr = r["slo"]["incident_mttr"]
            assert mttr["mttr_s"] is not None and mttr["mttr_s"] >= 0
            assert mttr["unattributed"] == 0


def test_fleet_study_check_gates_on_flipped_rows(tmp_path):
    """The flipped-row controls: every certificate the committed fleet
    artifact pins must FAIL verify_payload when hand-flipped — stale
    status schema refused, budget burn, detection P/R, MTTR
    attribution, and an ok bool disagreeing with its own row."""
    import copy
    import json

    from tools import fleet_study

    base = json.load(
        open(os.path.join(REPO, "baselines_out", "fleet_slo.json")))

    def flip(mut):
        p = copy.deepcopy(base)
        mut(p)
        return "\n".join(fleet_study.verify_payload(p))

    assert fleet_study.verify_payload(copy.deepcopy(base)) == []

    def stale(p):
        p["status_schema"] -= 1
    assert "stale artifact" in flip(stale)

    def burn(p):
        p["rows"][0]["budget_burned"] = 2.0
    assert "burned 2" in flip(burn)

    def bad_precision(p):
        row = next(r for r in p["rows"] if r["kind"] == "adversary")
        row["slo"]["detection_quality"]["precision"] = 0.9
    assert "P/R 0.9" in flip(bad_precision)

    def vacuous(p):
        row = next(r for r in p["rows"] if r["kind"] == "adversary")
        row["slo"]["detection_quality"]["adv_total"] = 0
    assert "vacuous" in flip(vacuous)

    def unattributed(p):
        row = next(r for r in p["rows"] if r["kind"] == "autopilot")
        row["slo"]["incident_mttr"]["unattributed"] = 1
    assert "unattributed" in flip(unattributed)

    def ok_disagrees(p):
        p["rows"][0]["ok"] = False
    out = flip(ok_disagrees)
    assert "disagrees" in out or "all_ok" in out

    def crashed(p):
        p["rows"][0]["state"] = "crashed"
    assert "terminal state 'crashed'" in flip(crashed)

    # ...and check_artifacts surfaces the same failure by check name
    import io
    from contextlib import redirect_stdout

    from tools import check_artifacts

    root = tmp_path / "root"
    (root / "baselines_out").mkdir(parents=True)
    stale_p = copy.deepcopy(base)
    stale_p["status_schema"] -= 1
    (root / "baselines_out" / "fleet_slo.json").write_text(
        json.dumps(stale_p))
    err = check_artifacts._check_fleet_slo(str(root))
    assert err and "stale artifact" in err


def test_perf_watch_gates_on_flipped_fleet_certificates(tmp_path):
    """The fleet_slo gate at tolerance 0 in BOTH directions: an SLO
    verdict flipping false, a clean cell starting to burn budget, and
    the detection P/R certificate moving off 1.0 are regressions; a
    burning row silently going quiet (the 'good' direction of a pinned
    metric) must gate too, as must the cell count changing."""
    import json

    from tools import perf_watch

    root = tmp_path
    (root / "baselines_out").mkdir()

    def artifact(ok=True, burned=0.0, precision=1.0, cells=2):
        rows = [{
            "cell": "cnn_adversary", "kind": "adversary",
            "state": "done", "run_id": "rid1", "ok": ok,
            "budget_burned": burned,
            "slo": {
                "detection_quality": {
                    "evaluated": True, "ok": precision == 1.0,
                    "verdict": "ok" if precision == 1.0 else "violated",
                    "precision": precision, "recall": 1.0},
                "incident_mttr": {
                    "evaluated": True, "ok": True, "verdict": "ok",
                    "mttr_s": 2.5, "unattributed": 0,
                    "attributed": 1},
            }}]
        if cells > 1:
            rows.append({"cell": "lm_clean", "kind": "clean",
                         "state": "done", "run_id": "rid2", "ok": True,
                         "budget_burned": 0.0, "slo": {}})
        return {"all_ok": ok, "rows": rows[:cells]}

    path = root / "baselines_out" / "fleet_slo.json"
    path.write_text(json.dumps(artifact()))
    assert perf_watch.main(["--root", str(root), "--snapshot"]) == 0
    snap = json.loads(
        (root / "baselines_out" / "perf_watch.json").read_text())
    for key in ("fleet_slo.all_ok", "fleet_slo.cells",
                "fleet_slo.cnn_adversary.ok",
                "fleet_slo.cnn_adversary.budget_burned",
                "fleet_slo.cnn_adversary.detection.precision",
                "fleet_slo.cnn_adversary.mttr_attributed",
                "fleet_slo.lm_clean.budget_burned"):
        assert key in snap["metrics"], key
    assert "fleet_slo.cnn_adversary.mttr_s" not in snap["metrics"]
    assert perf_watch.main(["--root", str(root)]) == 0  # clean

    out = root / "report.json"

    def regs():
        assert perf_watch.main(
            ["--root", str(root), "--json", str(out)]) == 1
        return {r["metric"]
                for r in json.loads(out.read_text())["regressions"]}

    # direction 1: a cell starts burning + its SLO verdict flips
    path.write_text(json.dumps(artifact(ok=False, burned=3.0,
                                        precision=0.9)))
    assert {"fleet_slo.all_ok", "fleet_slo.cnn_adversary.ok",
            "fleet_slo.cnn_adversary.budget_burned",
            "fleet_slo.cnn_adversary.detection.precision",
            "fleet_slo.cnn_adversary.detection_quality.ok"} <= regs()

    # direction 2 (pinned): P/R drifting ABOVE the pinned value is a
    # contract change, not an improvement — rebaseline consciously
    path.write_text(json.dumps(artifact(precision=1.1)))
    assert "fleet_slo.cnn_adversary.detection.precision" in regs()

    # a cell disappearing gates on the pinned cell count
    path.write_text(json.dumps(artifact(cells=1)))
    assert "fleet_slo.cells" in regs()
