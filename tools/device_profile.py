#!/usr/bin/env python
"""Device-time attribution driver: profile both production loops and fold
the captures into the committed per-phase / per-collective device ledger
(``baselines_out/device_profile.json``, ISSUE 9).

Each CELL is a short production-loop run (8 steps, jax.profiler window over
steps [3, 8) — chunk-snapped under K>1) of a registered chip-bound program
at the program-linter's CI shapes, so the fold can join the PR 5
``cost_analysis`` columns and cross-check the runtime trace's explicit
collectives against the SAME Manifest counts the static audit pinned
(``baselines_out/program_lint.json``). A mismatch is a hard error: the
static audit and the runtime trace must agree (obs/device_attr.cross_check).

  python tools/device_profile.py --run                 # drive all 10 cells
                                                       #  (subprocess each),
                                                       #  fold, write artifact
  python tools/device_profile.py --run --cells lm_sp_k4
  python tools/device_profile.py --fold --work DIR     # re-fold existing
                                                       #  cell dirs, no jax
  python tools/device_profile.py --check               # jax-free self-check
                                                       #  of the committed
                                                       #  artifact (sums,
                                                       #  cross-check rows,
                                                       #  control tripped)

The parent process is jax-free (pure artifact folding; usable on a laptop
against cell dirs scp'd from a chip job) — only the internal ``--run-cell``
subprocess imports jax. Each cell also runs with the host span tracer
(``trace_dir``) so the fold can emit the merged host+device Perfetto
timeline (``<cell>/merged_timeline.json``, obs/device_attr.merge_timeline):
host tracer lanes + device phase lanes on the shared clock the profiler
window anchored (obs/profiling.py).

Folded by ``tools/perf_watch.py``: phase-fraction metrics at the time-kind
tolerance (a decode-share regression gates round-over-round), collective
instruction/byte counts pinned at tolerance 0.

CPU-fallback caveat (PERF_HISTORY.md §8c/§12): on this container the capture is the
XLA:CPU trace shape — attribution works through the runner-dumped scope map
(optimized-HLO metadata), absolute times are not chip times, and there is
no honest hardware peak, so roofline rows carry achieved rates without
peak fractions.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from draco_tpu.obs import device_attr  # noqa: E402  (jax-free module)

ARTIFACT_REL = os.path.join("baselines_out", "device_profile.json")
LINT_REL = os.path.join("baselines_out", "program_lint.json")

MAX_STEPS = 8           # two K=4 chunks; window [3, 8) profiles steps 3-7
PROFILE_STEPS = (3, 8)  # (K=1) or the whole chunked run 1-8 (K=4)
NUM_DEVICES = 8

# cell -> (loop kind, steps_per_call, lint row whose Manifest counts +
# cost columns the fold joins, config overrides). The K=4 cells join the
# closest registered row: collective counts are per-instruction (a K-fused
# scan compiles its body once, so they are K-independent) and the linter's
# flops column counts the scan body once (per-step figure) — PERF_HISTORY.md §8.
CELLS = {
    "cnn_cyclic_k1": ("cnn", 1, "cnn_cyclic_step", {}),
    "cnn_cyclic_k4": ("cnn", 4, "cnn_cyclic_many_k2", {}),
    "cnn_majvote_k1": ("cnn", 1, "cnn_majvote_step",
                       dict(approach="maj_vote", group_size=4)),
    "cnn_majvote_k4": ("cnn", 4, "cnn_majvote_step",
                       dict(approach="maj_vote", group_size=4)),
    "cnn_approx_k1": ("cnn", 1, "cnn_approx_step",
                      dict(approach="approx", worker_fail=0,
                           redundancy="shared", code_redundancy=1.5)),
    "cnn_approx_k4": ("cnn", 4, "cnn_approx_step",
                      dict(approach="approx", worker_fail=0,
                           redundancy="shared", code_redundancy=1.5)),
    "lm_sp_k1": ("lm_sp", 1, "lm_sp_ring_step", {}),
    "lm_sp_k4": ("lm_sp", 4, "lm_sp_ring_many_k2", {}),
    "lm_tp_k1": ("lm_tp", 1, "lm_tp2_step", {}),
    "lm_tp_k4": ("lm_tp", 4, "lm_tp2_many_k2", {}),
    # fused-decode cells (ISSUE 12): decode_impl="pallas" at the SAME
    # shapes as an xla-path pair cell, so the "decode share dropped"
    # claim is a committed, diffed artifact. On this container the pallas
    # dispatch runs the kernels' fused reference lowering (CPU fallback,
    # ops/decode_kernels.resolve_decode_impl; PERF_HISTORY.md §14).
    "cnn_approx_pallas_k1": ("cnn", 1, "cnn_approx_pallas_step",
                             dict(approach="approx", worker_fail=0,
                                  redundancy="shared", code_redundancy=1.5,
                                  decode_impl="pallas")),
    "cnn_approx_pallas_k4": ("cnn", 4, "cnn_approx_pallas_step",
                             dict(approach="approx", worker_fail=0,
                                  redundancy="shared", code_redundancy=1.5,
                                  decode_impl="pallas")),
    "lm_sp_approx_k4": ("lm_sp", 4, "lm_sp_ring_approx_many_k2",
                        dict(approach="approx", worker_fail=0,
                             code_redundancy=1.5, step_guard="on")),
    "lm_sp_approx_pallas_k4": ("lm_sp", 4,
                               "lm_sp_ring_approx_pallas_many_k2",
                               dict(approach="approx", worker_fail=0,
                                    code_redundancy=1.5, step_guard="on",
                                    decode_impl="pallas")),
    "lm_tp_approx_k4": ("lm_tp", 4, "lm_tp2_approx_many_k2",
                        dict(approach="approx", worker_fail=0,
                             code_redundancy=1.5, step_guard="on")),
    "lm_tp_approx_pallas_k4": ("lm_tp", 4, "lm_tp2_approx_pallas_many_k2",
                               dict(approach="approx", worker_fail=0,
                                    code_redundancy=1.5, step_guard="on",
                                    decode_impl="pallas")),
    # cyclic layer-granularity pair: committed as same-shape evidence of
    # the fused path running the production loop end-to-end; NO
    # share-drop claim on the CPU fallback (the layer decode there is at
    # the per-segment matvec floor, within noise of the xla path — the
    # cyclic kernel's win is TPU-side HBM traffic, PERF_HISTORY.md §14), so this
    # pair is absent from PALLAS_CLAIMS.
    "cnn_cyclic_layer_k1": ("cnn", 1, "cnn_cyclic_layer_step",
                            dict(decode_granularity="layer")),
    "cnn_cyclic_layer_pallas_k1": ("cnn", 1, "cnn_cyclic_layer_pallas_step",
                                   dict(decode_granularity="layer",
                                        decode_impl="pallas")),
}

# pallas cell -> same-shape xla-path cell whose decode self-time share the
# pallas cell's committed row must undercut STRICTLY (the ISSUE 12
# acceptance criterion; enforced by --check, proven live by the flipped-row
# test in tests/test_cli_tools.py). Only the SCANNED LM cells claim: the
# fused win reproduces there run-over-run, while the CNN cells' shares
# move ±3% with XLA:CPU fusion-attribution noise (eager k1 even inverts —
# the true-mean matvec cannot fuse into the grads producer the way the
# xla path's axis-0 reduction does), so those pallas cells are committed
# as same-shape evidence WITHOUT the claim (PERF_HISTORY.md §14).
PALLAS_CLAIMS = {
    "lm_sp_approx_pallas_k4": "lm_sp_approx_k4",
    "lm_tp_approx_pallas_k4": "lm_tp_approx_k4",
}


# --------------------------------------------------------------------------
# --run-cell: the only jax-touching path (always a subprocess of --run)
# --------------------------------------------------------------------------

def _dump_scope_map(cell: str, k: int, lint_row: str, fn, args, mesh,
                    out_dir: str) -> dict:
    """AOT-compile the cell's profiled program and dump the attribution
    scope map next to the (future) capture. Compiled BEFORE the run so the
    heartbeat's on-stop fold can already attribute; XLA:CPU compilation is
    deterministic for a fixed program, so the re-compiled instruction names
    match the names the executed trace will carry (obs/device_attr.py)."""
    with mesh:
        text = fn.lower(*args).compile().as_text()
    scope = device_attr.scope_map_from_hlo(text)
    scope["lint_row"] = lint_row
    payload = {"schema": 1, "cell": cell, "steps_per_call": k,
               "programs": [scope]}
    with open(os.path.join(out_dir, "device_scope_map.json"), "w") as fh:
        json.dump(payload, fh)
    return scope


def run_cell(cell: str, out_dir: str) -> int:
    """Drive one cell: scope-map dump + an 8-step production-loop run with
    the profiler window, host tracer, heartbeat, and compile_guard="raise"
    (the capture must observe, never perturb — a retrace here is a bug)."""
    import jax  # noqa: F401  (the jax-touching path)
    import jax.numpy as jnp
    import numpy as np

    kind, k, lint_row, overrides = CELLS[cell]
    os.makedirs(out_dir, exist_ok=True)
    common = dict(max_steps=MAX_STEPS, eval_freq=0, log_every=1,
                  steps_per_call=k, train_dir=out_dir, trace_dir=out_dir,
                  compile_guard="raise")

    if kind == "cnn":
        from draco_tpu import rng as drng
        from draco_tpu.config import TrainConfig
        from draco_tpu.data.datasets import load_dataset
        from draco_tpu.models import input_shape
        from draco_tpu.runtime import make_mesh
        from draco_tpu.training.trainer import Trainer

        kw = dict(network="LeNet", dataset="synthetic-mnist",
                  approach="cyclic", batch_size=2, num_workers=8,
                  worker_fail=1, err_mode="rev_grad", lr=0.01, momentum=0.9)
        kw.update(overrides)
        kw.update(common)
        cfg = TrainConfig(**kw)
        mesh = make_mesh(cfg.num_workers)
        ds = load_dataset(cfg.dataset, synthetic_train=512,
                          synthetic_test=64)
        tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
        n, b = cfg.num_workers, cfg.batch_size
        shape = input_shape(cfg.dataset)
        adv = drng.adversary_schedule(cfg.seed, k + 1, n,
                                      cfg.num_adversaries)
        if k > 1:
            args = (tr.setup.state,
                    jnp.zeros((k, n, b) + shape, jnp.float32),
                    jnp.zeros((k, n, b), jnp.int32),
                    jnp.asarray(np.asarray(adv[1:k + 1])), None)
            fn = tr.setup.train_many
        else:
            args = (tr.setup.state,
                    jnp.zeros((n, b) + shape, jnp.float32),
                    jnp.zeros((n, b), jnp.int32),
                    jnp.asarray(np.asarray(adv[1])))
            fn = tr.setup.train_step
        _dump_scope_map(cell, k, lint_row, fn, args, mesh, out_dir)
        tr.run(profile_dir=out_dir, profile_steps=PROFILE_STEPS)
        tr.close()
        return 0

    from draco_tpu.analysis.registry import (
        Manifest, built_token_program, ci_lm_config,
    )
    from draco_tpu.parallel.token_loop import run_token_loop

    if kind == "lm_sp":
        from draco_tpu.parallel.mesh import make_mesh_2d
        from draco_tpu.parallel.sp_step import build_sp_train_setup

        cfg = ci_lm_config(seq_shards=2, **overrides, **common)
        mesh = make_mesh_2d(4, 2)
        setup = build_sp_train_setup(cfg, mesh)
        tag = "sp"
    elif kind == "lm_tp":
        from draco_tpu.parallel.mesh import make_mesh_wtp
        from draco_tpu.parallel.tp_step import build_tp_train_setup

        cfg = ci_lm_config(tensor_shards=2, **overrides, **common)
        mesh = make_mesh_wtp(4, 2)
        setup = build_tp_train_setup(cfg, mesh)
        tag = "tp"
    else:
        raise SystemExit(f"unknown cell kind {kind!r}")
    bp = built_token_program(cell, cfg, mesh, setup, Manifest(),
                             many=(k > 1), k=k)
    _dump_scope_map(cell, k, lint_row, bp.fn, bp.args, mesh, out_dir)
    run_token_loop(setup, cfg, quiet=True, tag=tag, profile_dir=out_dir,
                   profile_steps=PROFILE_STEPS)
    return 0


# --------------------------------------------------------------------------
# fold: capture dirs + program_lint.json -> the committed artifact (jax-free)
# --------------------------------------------------------------------------

def _lint_rows(root: str) -> dict:
    data = device_attr.load_json(os.path.join(root, LINT_REL))
    if not data:
        raise SystemExit(f"no {LINT_REL} under {root} — run "
                         f"tools/program_lint.py first (the fold joins its "
                         f"Manifest counts and cost columns)")
    return {r.get("name"): r for r in data.get("rows", [])}


def _expected_counts(lint_row: dict):
    """The program's linted Manifest collective counts. The linter records
    ``observed`` == the Manifest expectation on every green row (rules.py
    fails the row otherwise), so the committed artifact IS the manifest for
    a jax-free consumer; a row without the rule cross-checks nothing."""
    rule = (lint_row.get("rules") or {}).get("collectives")
    if not rule or not rule.get("ok"):
        return None
    return rule.get("observed")


def fold_cell(cell: str, cell_dir: str, lint_rows: dict) -> dict:
    """One committed-artifact row: phase ledger + collective ledger +
    manifest cross-check + roofline join + merged-timeline summary."""
    _, k, lint_name, _ = CELLS[cell]
    fold = device_attr.fold_capture(cell_dir, strict=True)
    if fold is None:
        raise SystemExit(f"{cell}: no profiler capture under {cell_dir}")
    anchor = fold.get("anchor") or {}
    steps = anchor.get("steps_profiled")
    lint_row = lint_rows.get(lint_name) or {}
    row = {"cell": cell, "steps_per_call": k, "lint_row": lint_name,
           "decode_impl": CELLS[cell][3].get("decode_impl", "xla"),
           "steps_profiled": steps, "programs": []}
    for prog in fold["programs"]:
        expected = _expected_counts(lint_row)
        # the hard-error contract: raises CollectiveMismatchError on drift
        check = device_attr.cross_check(prog["collectives"], expected,
                                        f"{cell}/{prog['module']}")
        entry = {
            "module": prog["module"],
            "total_device_us": round(prog["total_device_us"], 1),
            "wall_us": round(prog["wall_us"], 1),
            "phases": {name: {"time_us": round(r["time_us"], 1),
                              "frac": round(r["frac"], 4),
                              "events": r["events"]}
                       for name, r in prog["phases"].items()},
            "decode_share": round(
                prog["phases"]["draco_decode"]["frac"], 4),
            "collectives": prog["collectives"],
            "cross_check": check,
            "roofline": device_attr.roofline(
                prog["total_device_us"], steps or 0, lint_row),
        }
        row["programs"].append(entry)
    row["ok"] = all(p["cross_check"].get("ok") for p in row["programs"])
    # merged host+device timeline (run artifact, not committed): host
    # tracer lanes + device lanes on the anchored shared clock
    row["merged_timeline"] = _write_timeline(cell_dir, fold)
    return row


def _write_timeline(cell_dir: str, fold: dict):
    trace_path = os.path.join(cell_dir, "trace.json")
    host = device_attr.load_json(trace_path)
    host_events = (host or {}).get("traceEvents") or []
    cap = device_attr.find_capture(cell_dir)
    if cap is None:
        return None
    dev_events, _ = device_attr.load_trace(cap)
    scope = ((device_attr.load_scope_map(cell_dir) or {}).get("programs")
             or [None])[0]
    # cap the device lanes to the longest 100k slices (XLA:CPU conv thunks
    # emit ~1M sub-ms events on the CNN cells) — the drop count rides in
    # the payload AND the committed summary, never silently
    merged = device_attr.merge_timeline(host_events, dev_events, scope,
                                        fold.get("anchor"),
                                        max_device_events=100_000)
    out_path = os.path.join(cell_dir, "merged_timeline.json.gz")
    with gzip.open(out_path, "wt") as fh:
        json.dump(merged, fh)
    dev_n = sum(1 for e in merged["traceEvents"]
                if e.get("cat") == "device")
    mt = merged["mergedTimeline"]
    # path relative to the work dir: the committed artifact must not embed
    # a machine-local temp path (dead pointer + spurious diff per rerun) —
    # the driver prints the work dir holding the cells at exit
    rel_path = os.path.join(os.path.basename(cell_dir.rstrip(os.sep)),
                            os.path.basename(out_path))
    return {"path": rel_path, "anchored": mt["anchored"],
            "anchor_kind": mt.get("anchor_kind"),
            "device_offset_us": mt["device_offset_us"],
            "host_events": len(host_events), "device_events": dev_n,
            "dropped_device_events": mt["droppedDeviceEvents"]}


def seeded_mismatch_control(rows: list) -> dict:
    """The negative control proving the cross-check path live (the PR 3
    controls.py pattern): take a real cell's observed ledger, seed one
    EXTRA all-gather instruction into a copy, and demand the reconciliation
    against the true Manifest counts raises naming the kind. ``ok`` means
    "tripped exactly as required"."""
    base = next((p for r in rows if not r.get("control")
                 for p in r["programs"]
                 if p["cross_check"].get("expected") is not None), None)
    if base is None:
        return {"cell": "control_extra_all_gather", "control": True,
                "ok": False, "error": "no cell with manifest counts folded"}
    doctored = json.loads(json.dumps(base["collectives"]))
    doctored["explicit"]["all_gather"]["instructions"] += 1
    try:
        device_attr.cross_check(doctored, base["cross_check"]["expected"],
                                "control_extra_all_gather")
    except device_attr.CollectiveMismatchError as e:
        tripped = "all_gather" in str(e)
        return {"cell": "control_extra_all_gather", "control": True,
                "ok": tripped, "seeded_on": base["module"],
                "error": str(e)[:300]}
    return {"cell": "control_extra_all_gather", "control": True,
            "ok": False,
            "error": "seeded extra all-gather did NOT trip cross_check"}


def fold_all(work: str, cells: list, root: str) -> dict:
    lint_rows = _lint_rows(root)
    rows = [fold_cell(c, os.path.join(work, c), lint_rows) for c in cells]
    rows.append(seeded_mismatch_control(rows))
    return {
        "schema": 1,
        "tool": "tools/device_profile.py --run",
        "method": (
            "8-step production-loop runs (Trainer / run_token_loop) at the "
            "program-linter CI shapes with a jax.profiler window over steps "
            "[3, 8) (chunk-snapped under K>1), compile_guard=raise; device "
            "events attributed per-thread-self-time to the draco_* named "
            "scopes via the runner-dumped optimized-HLO scope map; explicit "
            "collectives cross-checked against the linted Manifest counts "
            "(mismatch = hard error, proven live by the seeded control row)"
        ),
        "profile_steps": list(PROFILE_STEPS),
        "devices": NUM_DEVICES,
        "cpu_fallback": True,  # this container has no TPU (PERF_HISTORY.md §8c)
        "all_ok": all(r.get("ok") for r in rows),
        "cells": rows,
    }


# --------------------------------------------------------------------------
# --check: jax-free self-consistency gate on the committed artifact
# --------------------------------------------------------------------------

def check_artifact(path: str, out=None) -> int:
    """Validate the committed artifact's internal contracts: per program
    the phase rows (incl. the explicit residual rows) sum to
    total_device_us, decode_share equals the decode row's fraction, every
    cross-check row agrees observed == expected, the seeded mismatch
    control actually tripped, and every PALLAS_CLAIMS pair shows the
    fused-decode cell's decode self-time share STRICTLY below its
    same-shape xla pair (the ISSUE 12 acceptance gate). Exit 1 naming
    each violated metric — the CI gate tests/test_cli_tools.py drives
    with flipped decode-share rows."""
    out = out if out is not None else sys.stdout
    data = device_attr.load_json(path)
    if not data:
        print(f"device_profile --check: no artifact at {path}", file=out)
        return 1
    bad = []
    shares = {}
    for row in data.get("cells", []):
        if not row.get("control") and len(row.get("programs", [])) == 1:
            shares[row.get("cell")] = float(
                row["programs"][0].get("decode_share", -1.0))
    for pal, xla in sorted(PALLAS_CLAIMS.items()):
        if pal not in shares or xla not in shares:
            # every claimed pair is REQUIRED in the committed artifact — a
            # regeneration that drops the cells must fail here, not let
            # the strictly-below claim silently go unenforced
            bad.append(f"{pal}: claim pair missing/incomplete (needs both "
                       f"{pal} and {xla} cells)")
            continue
        if not shares[pal] < shares[xla]:
            bad.append(f"{pal}: decode share {shares[pal]} not strictly "
                       f"below xla pair {xla} ({shares[xla]})")
    for row in data.get("cells", []):
        cell = row.get("cell")
        if row.get("control"):
            if not row.get("ok"):
                bad.append(f"{cell}: mismatch control did not trip "
                           f"({row.get('error')})")
            continue
        for prog in row.get("programs", []):
            total = float(prog.get("total_device_us", 0.0))
            phases = prog.get("phases", {})
            sum_us = sum(float(p.get("time_us", 0.0))
                         for p in phases.values())
            # rounded to 0.1 us per row in the artifact
            if abs(sum_us - total) > max(1e-6 * total,
                                         0.1 * (len(phases) + 1)):
                bad.append(f"{cell}: phase rows sum {sum_us:.1f} != "
                           f"total_device_us {total:.1f}")
            dec = phases.get("draco_decode", {})
            share = float(prog.get("decode_share", -1.0))
            if abs(share - float(dec.get("frac", 0.0))) > 5e-4:
                bad.append(f"{cell}: decode_share {share} != "
                           f"draco_decode frac {dec.get('frac')}")
            check = prog.get("cross_check", {})
            exp, obs = check.get("expected"), check.get("observed")
            if exp is not None and exp != obs:
                bad.append(f"{cell}: cross_check expected {exp} != "
                           f"observed {obs}")
            if not check.get("ok"):
                bad.append(f"{cell}: cross_check not ok")
    if not data.get("all_ok") and not bad:
        bad.append("all_ok is false")
    if bad:
        for b in bad:
            print(f"device_profile FAIL: {b}", file=out)
        return 1
    n = len([r for r in data.get('cells', []) if not r.get('control')])
    print(f"device_profile --check: {n} cells + control consistent", file=out)
    return 0


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def _spawn_cells(cells: list, work: str) -> None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={NUM_DEVICES}"
        ).strip()
    for cell in cells:
        out_dir = os.path.join(work, cell)
        print(f"device_profile: running cell {cell} -> {out_dir}",
              flush=True)
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--run-cell", cell, "--out", out_dir],
            env=env, capture_output=True, text=True, timeout=1800)
        if res.returncode != 0:
            sys.stderr.write(res.stdout[-2000:] + res.stderr[-4000:])
            raise SystemExit(f"cell {cell} failed (rc={res.returncode})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--run", action="store_true",
                    help="drive the cells as subprocesses, then fold")
    ap.add_argument("--fold", action="store_true",
                    help="fold existing cell dirs under --work (no jax)")
    ap.add_argument("--check", action="store_true",
                    help="self-check the committed artifact (no jax)")
    ap.add_argument("--run-cell", default="", help=argparse.SUPPRESS)
    ap.add_argument("--out", default="", help=argparse.SUPPRESS)
    ap.add_argument("--cells", default="",
                    help="comma-separated cell subset (default: all)")
    ap.add_argument("--work", default="",
                    help="cell run dir (default: a temp dir under --run; "
                         "required for --fold)")
    ap.add_argument("--root", default=".",
                    help="repo root holding baselines_out/")
    ap.add_argument("--artifact", default="",
                    help=f"artifact path (default <root>/{ARTIFACT_REL})")
    args = ap.parse_args(argv)

    artifact = args.artifact or os.path.join(args.root, ARTIFACT_REL)
    if args.run_cell:
        return run_cell(args.run_cell, args.out or ".")
    if args.check:
        return check_artifact(artifact)

    cells = ([c.strip() for c in args.cells.split(",") if c.strip()]
             or list(CELLS))
    unknown = [c for c in cells if c not in CELLS]
    if unknown:
        raise SystemExit(f"unknown cells {unknown}; known: {list(CELLS)}")
    if args.run:
        work = args.work or tempfile.mkdtemp(prefix="device_profile_")
        _spawn_cells(cells, work)
    elif args.fold:
        if not args.work:
            raise SystemExit("--fold needs --work (the cell run dir)")
        work = args.work
    else:
        raise SystemExit("pick one of --run / --fold / --check")

    payload = fold_all(work, cells, args.root)
    os.makedirs(os.path.dirname(artifact) or ".", exist_ok=True)
    with open(artifact, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    n_ok = sum(1 for r in payload["cells"] if r.get("ok"))
    print(f"device_profile: {n_ok}/{len(payload['cells'])} rows ok -> "
          f"{artifact}  (cells under {work})")
    return 0 if payload["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
