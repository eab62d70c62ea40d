#!/usr/bin/env python
"""Wire study: what does a bf16/int8 worker→aggregator wire do to decode
error and Byzantine detection? — ISSUE 10's shadow calibration matrix plus,
since ISSUE 15, the REAL narrow wire's committed evidence:

**Shadow rows** (the PR 10 matrix, unchanged): the f32 wire ships, the
shadow decode measures the candidate dtype alongside it.

**Real rows** (``"mode": "real"``): ``cfg.wire_dtype`` is SET — the
codewords physically cross the sharding boundary as bf16/int8 buffers and
the λ-regularized, quantization-aware decode is the only decode. Each cell
trains the same workload twice (narrow wire vs an f32 twin, identical
seeds) and records the end-to-end relative parameter error, detection P/R
on the narrow wire's OWN flag columns under a live adversary, guard
cleanliness, and the ledger's physical bytes/worker/step with the ratio vs
the f32 row — the ISSUE 15 acceptance pins (P/R 1.0 preserved, bytes ≤
0.50×/≈0.25×).

**Locator cells** (``"mode": "locator"``): the PR 10 blocker replayed at
n=32 s=3 — synthetic encodes quantized to the narrow dtype, decoded with
the UNREGULARIZED (λ=0) and the λ-regularized locator, recording the worst
honest-row deviation with no adversary (the rank-deficient amplification),
the margins with s live adversaries, and whether the committed
per-(n, s, dtype) threshold (obs/numerics.WIRE_REL_TOL_TABLE, committed
here as ``threshold_table``) separates them. λ=0 must reproduce the
blocker (NOT usable); λ must solve it.

ROADMAP item 4 will narrow the coded wire; this study is the measurement
foundation it gets built and regression-gated on. Each cell trains the same
FC/synthetic-mnist workload under {cyclic, maj_vote, approx} ×
{bf16, int8} × K∈{1,4} with ``numerics_watch=on`` and ``shadow_wire`` set —
the f32 path alone updates params, the shadow decode of the quantized
codewords rides the same step body — and records, from the run's own
metrics.jsonl:

  shadow_err_max        worst-step relative L2 error of the shadow
                        aggregate vs the f32 aggregate — the end-to-end
                        cost of the narrow dtype
  shadow_residual_max   worst-step shadow decode-health residual
  shadow_flag_agree_min worst-step fraction of present workers whose
                        shadow detection flag equals the f32 flag — 1.0
                        means quantization changed NO accusation
  det_precision/recall (_shadow)
                        detection P/R vs the seeded schedules, on the f32
                        AND the shadow flag sets — the exact-code cells run
                        a LIVE rev_grad adversary, so "detection survives
                        the narrow wire" is measured, not assumed
  wire                  the logical bytes ledger (obs/numerics.wire_ledger)
                        — f32/bf16/int8 bytes per worker per step at the
                        program's registered shapes

``tools/perf_watch.py`` folds the committed artifact: the shadow residual /
flag-agreement columns gate round-over-round as pinned tolerance-0 kinds
(proven live by the flipped-row control in tests/test_cli_tools.py), the
detection bools at tolerance 0, wire bytes at the bytes tolerance.

``--check`` re-verifies a committed artifact jax-free (ledger arithmetic
— including the ISSUE 16 pin that the ledger's per-segment physical bytes
sum exactly to the per-worker/per-step rows — bf16 detection-preserved
pins, all_ok roll-up) — wired into tools/check_artifacts.py.

Usage (CPU, ~2 min):
  python tools/wire_study.py --cpu-mesh 8
  python tools/wire_study.py --check
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_WORKERS = 8
FAMILIES = {
    # live rev_grad adversary on both exact codes: the study must show
    # detection P/R under quantization, not just decode error
    "cyclic": dict(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                   redundancy="shared"),
    "maj_vote": dict(approach="maj_vote", group_size=4, worker_fail=1,
                     err_mode="rev_grad"),
    # the approx family rejects live adversaries (no Byzantine
    # certificate); its fault axis is seeded drops inside the α budget
    "approx": dict(approach="approx", worker_fail=0, redundancy="shared",
                   code_redundancy=1.5, straggler_alpha=0.25,
                   straggle_mode="drop", straggle_count=1),
}
DTYPES = ("bf16", "int8")
KS = (1, 4)

# real-wire acceptance bounds (ISSUE 15): end-to-end relative parameter
# error vs the f32 twin, and physical-bytes ratio vs the f32 ledger row.
# The int8 ratio is 0.25 + 1/64: one f32 scale per 256-element block — the
# committed ledger's own arithmetic, which the headline "0.25×" rounds.
REAL_ERR_MAX = {"bf16": 2e-2, "int8": 1e-1}
REAL_RATIO_MAX = {"bf16": 0.505, "int8": 0.26}

# the PR 10 blocker shape the locator cells replay
LOCATOR_SHAPE = (32, 3)
LOCATOR_TRIALS = 12
LOCATOR_D = 4096


def _fold_prec_recall(tp, flagged, adv):
    """Detection precision/recall with the empty-denominator healthy-state
    convention (obs/heartbeat.decode_health)."""
    return ((tp / flagged) if flagged else 1.0,
            (tp / adv) if adv else 1.0)


def run_cell(family: str, dtype: str, k: int, args, mesh, ds) -> dict:
    from draco_tpu.config import TrainConfig
    from draco_tpu.obs import numerics as numerics_mod
    from draco_tpu.training.trainer import Trainer

    d = tempfile.mkdtemp(prefix=f"wire_{family}_{dtype}_k{k}_")
    cfg = TrainConfig(
        network="FC", dataset="synthetic-mnist", batch_size=4, lr=0.05,
        momentum=0.9, num_workers=NUM_WORKERS, max_steps=args.max_steps,
        eval_freq=0, train_dir=d, log_every=1, steps_per_call=k,
        step_guard="on", compile_guard="raise",
        numerics_watch="on", shadow_wire=dtype,
        shadow_round=args.shadow_round, **FAMILIES[family],
    )
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    try:
        tr.run()
        dim = tr.setup.dim
    finally:
        tr.close()
    recs = []
    with open(os.path.join(d, "metrics.jsonl")) as fh:
        for line in fh:
            r = json.loads(line)
            if "loss" in r and r.get("split") != "eval":
                recs.append(r)
    shutil.rmtree(d, ignore_errors=True)

    exact = family in ("cyclic", "maj_vote")
    tp = sum(r.get("det_tp", 0.0) for r in recs)
    adv = sum(r.get("det_adv", 0.0) for r in recs)
    flagged = sum(r.get("located_errors", 0.0) for r in recs)
    stp = sum(r["shadow_det_tp"] for r in recs)
    sflagged = sum(r["shadow_det_flagged"] for r in recs)
    prec, rec = _fold_prec_recall(tp, flagged, adv)
    sprec, srec = _fold_prec_recall(stp, sflagged, adv)
    row = {
        "family": family, "dtype": dtype, "k": k,
        "steps": len(recs),
        "shadow_err_max": round(max(r["shadow_err"] for r in recs), 6),
        "shadow_residual_max": round(
            max(r["shadow_residual"] for r in recs), 6),
        "shadow_flag_agree_min": round(
            min(r["shadow_flag_agree"] for r in recs), 6),
        "det_precision": round(prec, 6), "det_recall": round(rec, 6),
        "det_precision_shadow": round(sprec, 6),
        "det_recall_shadow": round(srec, 6),
        "adv_total": adv,
        "wire_absmax_max": round(
            max(r["nx_wire_absmax"] for r in recs), 6),
        "wire_uf_int8_max": round(
            max(r["nx_wire_uf_int8"] for r in recs), 6),
        "wire_of_bf16_max": round(
            max(r["nx_wire_of_bf16"] for r in recs), 6),
        "guard_trips_total": sum(r.get("guard_trips", 0.0) for r in recs),
        "loss_final": round(recs[-1]["loss"], 6),
        "wire": numerics_mod.wire_ledger(cfg, dim),
    }
    # detection survives the narrow wire: shadow P/R both 1.0 with a live
    # adversary (exact codes); the approx cells' surface is flag agreement
    row["det_preserved"] = bool(
        (not exact or (sprec == 1.0 and srec == 1.0 and adv > 0))
        and row["shadow_flag_agree_min"] == 1.0)
    # every shadow column stayed finite (the NaN sentinel is -1.0 — a
    # clean run must never produce it)
    clean = all(r["shadow_err"] >= 0 and r["shadow_residual"] >= 0
                and r["shadow_flag_agree"] >= 0 for r in recs)
    row["ok"] = bool(row["det_preserved"] and clean
                     and row["guard_trips_total"] == 0.0
                     and row["steps"] == args.max_steps)
    return row


# --------------------------------------------------------------------------
# real-wire cells (ISSUE 15)
# --------------------------------------------------------------------------


def _train(cfg, mesh, ds):
    """Run the production Trainer; return (flat params, train records,
    dim)."""
    import jax
    import numpy as np

    from draco_tpu.training.trainer import Trainer

    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    try:
        tr.run()
        dim = tr.setup.dim
        pv = np.concatenate([
            np.ravel(x)
            for x in jax.tree.leaves(jax.device_get(tr.state.params))])
    finally:
        tr.close()
    recs = []
    with open(os.path.join(cfg.train_dir, "metrics.jsonl")) as fh:
        for line in fh:
            r = json.loads(line)
            if "loss" in r and r.get("split") != "eval":
                recs.append(r)
    return pv, recs, dim


def run_real_cell(family: str, dtype: str, k: int, args, mesh, ds,
                  f32_twins: dict) -> dict:
    """One REAL-narrow-wire cell: train with cfg.wire_dtype=dtype, compare
    end-to-end against the cached f32 twin of the same (family, k), and
    score detection on the narrow wire's OWN flag columns."""
    import numpy as np

    from draco_tpu.config import TrainConfig
    from draco_tpu.obs import numerics as numerics_mod

    def mk(wire):
        d = tempfile.mkdtemp(prefix=f"wirereal_{family}_{wire}_k{k}_")
        return TrainConfig(
            network="FC", dataset="synthetic-mnist", batch_size=4, lr=0.05,
            momentum=0.9, num_workers=NUM_WORKERS, max_steps=args.max_steps,
            eval_freq=0, train_dir=d, log_every=1, steps_per_call=k,
            step_guard="on", compile_guard="raise", numerics_watch="on",
            wire_dtype=wire, shadow_round=args.shadow_round,
            **FAMILIES[family],
        )

    twin_key = (family, k)
    if twin_key not in f32_twins:
        cfg0 = mk("f32")
        f32_twins[twin_key] = _train(cfg0, mesh, ds)
        shutil.rmtree(cfg0.train_dir, ignore_errors=True)
    pv0, recs0, _dim0 = f32_twins[twin_key]

    cfg = mk(dtype)
    pv, recs, dim = _train(cfg, mesh, ds)
    shutil.rmtree(cfg.train_dir, ignore_errors=True)

    exact = family in ("cyclic", "maj_vote")
    tp = sum(r.get("det_tp", 0.0) for r in recs)
    adv = sum(r.get("det_adv", 0.0) for r in recs)
    flagged = sum(r.get("located_errors", 0.0) for r in recs)
    prec, rec = _fold_prec_recall(tp, flagged, adv)
    err = float(np.linalg.norm(pv - pv0)
                / max(np.linalg.norm(pv0), 1e-30))
    ledger = numerics_mod.wire_ledger(cfg, dim)
    phys = ledger["physical_bytes_per_worker"]
    ratio = phys / ledger["bytes_per_worker"]["f32"]
    row = {
        "mode": "real", "family": family, "dtype": dtype, "k": k,
        "steps": len(recs),
        "end_to_end_err": round(err, 6),
        "det_precision": round(prec, 6), "det_recall": round(rec, 6),
        "adv_total": adv,
        "decode_residual_max": round(
            max(r.get("decode_residual", 0.0) for r in recs), 6),
        "guard_trips_total": sum(r.get("guard_trips", 0.0) for r in recs),
        "loss_final": round(recs[-1]["loss"], 6),
        "loss_final_f32": round(recs0[-1]["loss"], 6),
        "wire": ledger,
        "physical_ratio": round(ratio, 6),
    }
    # the ledger honesty pin (ISSUE 15 satellite): the materialized bytes
    # ARE the logical candidate row, by construction
    row["physical_matches_ledger"] = bool(
        phys == ledger["bytes_per_worker"][dtype]
        and ledger["wire_dtype"] == dtype)
    row["det_preserved"] = bool(
        not exact or (prec == 1.0 and rec == 1.0 and adv > 0))
    row["ok"] = bool(
        row["det_preserved"] and row["physical_matches_ledger"]
        and row["guard_trips_total"] == 0.0
        and row["steps"] == args.max_steps
        and err <= REAL_ERR_MAX[dtype]
        and ratio <= REAL_RATIO_MAX[dtype])
    return row


# --------------------------------------------------------------------------
# locator-margin cells (ISSUE 15): the PR 10 n=32 s=3 blocker, replayed
# --------------------------------------------------------------------------


def locator_cell(n: int, s: int, dtype: str, lam: float) -> dict:
    """Measure the narrow-wire locator margins at (n, s): worst honest-row
    relative deviation with NO adversary (the rank-deficient quantization
    amplification — the blocker), and the honest-max / adversary-min
    margins with s live rev_grad-magnitude adversaries. ``usable`` = the
    committed per-shape threshold separates the no-adversary honest band
    from the adversary band — the PR 10 blocker's certificate, and ONLY
    that: ``honest_dev_max_adv`` is recorded (not folded into ``usable``)
    because at the blocker shape it EXCEEDS the threshold — honest rows
    extrapolated under a live adversary cross the flag line, so detection
    RECALL holds (adv_dev_min > threshold) while flag PRECISION degrades
    in the adversary regime at large (n, s). A measured limit, documented
    in PERF_HISTORY.md §17 and the WIRE_REL_TOL_TABLE comment, not silently
    absorbed into the certificate."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic as cyclic_mod
    from draco_tpu.obs import numerics as numerics_mod

    code = cyclic_mod.build_cyclic_code(n, s)
    block = 256

    def margins(adv_rows):
        hmax, amin = 0.0, float("inf")
        for t in range(LOCATOR_TRIALS):
            rs = np.random.RandomState(100 + t)
            g = rs.randn(n, LOCATOR_D).astype(np.float32) * 0.05
            enc_re, enc_im = cyclic_mod.encode_shared(code, jnp.asarray(g))
            adv = np.zeros(n, bool)
            if adv_rows:
                adv[rs.choice(n, adv_rows, replace=False)] = True
                m = jnp.asarray(adv)[:, None]
                enc_re = jnp.where(m, -100.0 * enc_re, enc_re)
                enc_im = jnp.where(m, -100.0 * enc_im, enc_im)
            buf_re = numerics_mod.narrow_wire_rows(enc_re, dtype, block)
            buf_im = numerics_mod.narrow_wire_rows(enc_im, dtype, block)
            enc_re = numerics_mod.widen_wire_rows(buf_re, dtype, block)
            enc_im = numerics_mod.widen_wire_rows(buf_im, dtype, block)
            f = jnp.asarray(rs.randn(LOCATOR_D).astype(np.float32))
            _, _, h = cyclic_mod.decode(code, enc_re, enc_im, f,
                                        with_health=True, rel_tol=1e9,
                                        lam=lam)
            dev = np.asarray(h["dev_rel"])
            if adv_rows:
                amin = min(amin, float(dev[adv].min()))
                hmax = max(hmax, float(dev[~adv].max()))
            else:
                hmax = max(hmax, float(dev.max()))
        return hmax, amin

    noadv_hmax, _ = margins(0)
    adv_hmax, adv_min = margins(s)
    tol = numerics_mod.wire_rel_tol(n, s, dtype)
    usable = bool(noadv_hmax < tol < adv_min)
    return {
        "mode": "locator", "n": n, "s": s, "dtype": dtype,
        "lam": lam, "regularized": bool(lam > 0.0),
        "trials": LOCATOR_TRIALS, "d": LOCATOR_D,
        "honest_dev_max_noadv": round(noadv_hmax, 6),
        "honest_dev_max_adv": round(adv_hmax, 6),
        "adv_dev_min": round(adv_min, 6),
        "threshold": tol,
        "usable": usable,
        # the regularized cell must solve the blocker; the λ=0 cell must
        # REPRODUCE it (a blocker that stops reproducing means the λ=0
        # path changed — which it never may: it is the bitwise f32 path)
        "ok": usable if lam > 0.0 else not usable,
    }


# --------------------------------------------------------------------------
# --check: jax-free artifact re-verification (tools/check_artifacts.py)
# --------------------------------------------------------------------------


def check_artifact(path: str) -> int:
    """Re-verify a committed wire_study.json: the roll-up, the per-row
    detection pins, the ledger arithmetic (bytes must match the recorded
    dim — a stale ledger would misreport the item-4 win), and — ISSUE 15 —
    the real-wire rows' P/R + physical-bytes pins and the locator cells'
    blocker-solved certificate. Exits nonzero naming the first failure."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"wire_study --check: cannot read {path}: {e}")
        return 1
    rows = data.get("rows", [])
    shadow = [r for r in rows if r.get("mode", "shadow") == "shadow"]
    real = [r for r in rows if r.get("mode") == "real"]
    locator = [r for r in rows if r.get("mode") == "locator"]
    want_cells = {(f, dt, k) for f in FAMILIES for dt in DTYPES for k in KS}
    for label, rset in (("shadow", shadow), ("real", real)):
        got = {(r.get("family"), r.get("dtype"), r.get("k")) for r in rset}
        if not want_cells <= got:
            print(f"wire_study --check: missing {label} cells "
                  f"{sorted(want_cells - got)}")
            return 1
    for r in shadow + real:
        cell = f"{r.get('mode', 'shadow')}.{r['family']}.{r['dtype']}" \
               f".k{r['k']}"
        w = r.get("wire") or {}
        rows_per = 2 if r["family"] == "cyclic" else 1
        dim = w.get("dim", 0)
        per = w.get("bytes_per_worker", {})
        if per.get("f32") != 4 * rows_per * dim \
                or per.get("bf16") != 2 * rows_per * dim:
            print(f"wire_study --check: {cell}: ledger bytes inconsistent "
                  f"with dim={dim} ({per})")
            return 1
        if not (per.get("int8", 0) < per.get("bf16", 0)
                < per.get("f32", 0)):
            print(f"wire_study --check: {cell}: dtype ordering broken "
                  f"({per})")
            return 1
        # ISSUE 16: the ledger's per-segment physical bytes must SUM to
        # the per-worker/per-step rows exactly — a segment boundary can
        # never create or destroy wire bytes
        seg = w.get("segments")
        if not isinstance(seg, dict):
            print(f"wire_study --check: {cell}: ledger carries no "
                  f"segments block — regenerate with the segmented "
                  f"wire_ledger (ISSUE 16)")
            return 1
        bounds = seg.get("bounds") or []
        if (sum(seg.get("physical_bytes_per_worker", []))
                != w.get("physical_bytes_per_worker")
                or sum(seg.get("physical_bytes_per_step", []))
                != w.get("physical_bytes_per_step")
                or seg.get("count") != len(bounds) - 1
                or bounds[:1] != [0] or bounds[-1:] != [dim]):
            print(f"wire_study --check: {cell}: per-segment bytes do not "
                  f"sum to the per-step ledger row (segments={seg})")
            return 1
        if r["dtype"] == "bf16" and not r.get("det_preserved"):
            print(f"wire_study --check: {cell}: bf16 wire lost "
                  f"detection (det_preserved false) — the ISSUE 10/15 "
                  f"acceptance pin")
            return 1
        if not r.get("ok"):
            print(f"wire_study --check: {cell}: row not ok")
            return 1
    for r in real:
        cell = f"real.{r['family']}.{r['dtype']}.k{r['k']}"
        w = r.get("wire") or {}
        dtype = r["dtype"]
        # the ledger-honesty pin: physical == the logical candidate row
        if w.get("wire_dtype") != dtype or \
                w.get("physical_bytes_per_worker") \
                != (w.get("bytes_per_worker") or {}).get(dtype):
            print(f"wire_study --check: {cell}: materialized wire bytes "
                  f"disagree with the logical candidate row "
                  f"(wire_dtype={w.get('wire_dtype')})")
            return 1
        ratio = (w.get("physical_bytes_per_worker", 0)
                 / max(w.get("bytes_per_worker", {}).get("f32", 1), 1))
        if ratio > REAL_RATIO_MAX[dtype]:
            print(f"wire_study --check: {cell}: physical bytes ratio "
                  f"{ratio:.4f} exceeds the {dtype} pin "
                  f"{REAL_RATIO_MAX[dtype]} — the wire is not narrow")
            return 1
        if r.get("end_to_end_err", 1.0) > REAL_ERR_MAX[dtype]:
            print(f"wire_study --check: {cell}: end-to-end error "
                  f"{r.get('end_to_end_err')} exceeds {REAL_ERR_MAX[dtype]}")
            return 1
        if r["family"] in ("cyclic", "maj_vote") and not (
                r.get("det_precision") == 1.0
                and r.get("det_recall") == 1.0):
            print(f"wire_study --check: {cell}: detection P/R "
                  f"{r.get('det_precision')}/{r.get('det_recall')} != 1.0 "
                  f"on the real narrow wire — the ISSUE 15 acceptance pin")
            return 1
    # locator cells: the blocker must REPRODUCE at λ=0 and be SOLVED at λ
    n32, s32 = LOCATOR_SHAPE
    for dtype in DTYPES:
        cells = {bool(r.get("regularized")): r for r in locator
                 if r.get("dtype") == dtype and r.get("n") == n32
                 and r.get("s") == s32}
        if set(cells) != {False, True}:
            print(f"wire_study --check: locator cells missing for {dtype} "
                  f"at n={n32} s={s32} (need λ=0 and λ>0)")
            return 1
        if cells[False].get("usable"):
            print(f"wire_study --check: locator {dtype} λ=0 row claims "
                  f"usable — the PR 10 blocker stopped reproducing, which "
                  f"means the exact path changed")
            return 1
        reg = cells[True]
        if not reg.get("usable"):
            print(f"wire_study --check: locator {dtype} regularized row "
                  f"not usable — the blocker is back")
            return 1
        thr = reg.get("threshold")
        tbl = (data.get("threshold_table") or {}).get(
            f"{n32}:{s32}:{dtype}")
        if thr != tbl:
            print(f"wire_study --check: locator {dtype} threshold {thr} "
                  f"!= committed table entry {tbl}")
            return 1
        if not (reg.get("honest_dev_max_noadv", 1e9) < thr
                < reg.get("adv_dev_min", 0.0)):
            print(f"wire_study --check: locator {dtype} threshold {thr} "
                  f"does not separate the measured margins "
                  f"({reg.get('honest_dev_max_noadv')} .. "
                  f"{reg.get('adv_dev_min')})")
            return 1
    for r in locator:
        if not r.get("ok"):
            print(f"wire_study --check: locator row not ok: {r}")
            return 1
    if not data.get("all_ok"):
        print("wire_study --check: all_ok is false")
        return 1
    print(f"wire_study --check: {len(shadow)} shadow + {len(real)} real + "
          f"{len(locator)} locator cells verified ({path})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=str,
                    default=os.path.join("baselines_out", "wire_study.json"))
    ap.add_argument("--max-steps", type=int, default=12)
    ap.add_argument("--shadow-round", type=str, default="nearest",
                    choices=["nearest", "stochastic"])
    ap.add_argument("--families", type=str, default="",
                    help="comma-separated subset (default: all)")
    ap.add_argument("--dtypes", type=str, default="",
                    help="comma-separated subset of bf16,int8")
    ap.add_argument("--ks", type=str, default="",
                    help="comma-separated subset of 1,4")
    ap.add_argument("--check", action="store_true",
                    help="re-verify a committed artifact (jax-free)")
    ap.add_argument("--artifact", type=str, default="",
                    help="artifact path for --check (default --out)")
    ap.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                    help="force an N-device virtual CPU mesh")
    args = ap.parse_args(argv)
    if args.check:
        return check_artifact(args.artifact or args.out)
    from draco_tpu.cli import maybe_force_cpu_mesh

    if args.cpu_mesh:
        maybe_force_cpu_mesh(args)

    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh

    families = [f for f in args.families.split(",") if f] or list(FAMILIES)
    dtypes = [d for d in args.dtypes.split(",") if d] or list(DTYPES)
    ks = [int(x) for x in args.ks.split(",") if x] or list(KS)
    ds = load_dataset("synthetic-mnist", synthetic_train=512,
                      synthetic_test=128)
    mesh = make_mesh(NUM_WORKERS)
    rows = []
    for family in families:
        for dtype in dtypes:
            for k in ks:
                row = run_cell(family, dtype, k, args, mesh, ds)
                row["mode"] = "shadow"
                rows.append(row)
                print(f"wire_study: shadow {family:8s} {dtype:4s} k={k} -> "
                      f"err_max={row['shadow_err_max']:.4g} "
                      f"agree_min={row['shadow_flag_agree_min']} "
                      f"det_shadow={row['det_precision_shadow']:.2f}/"
                      f"{row['det_recall_shadow']:.2f} ok={row['ok']}",
                      flush=True)

    # REAL-wire cells (ISSUE 15): wire_dtype set, f32 twin per (family, k)
    f32_twins: dict = {}
    for family in families:
        for dtype in dtypes:
            for k in ks:
                row = run_real_cell(family, dtype, k, args, mesh, ds,
                                    f32_twins)
                rows.append(row)
                print(f"wire_study: real   {family:8s} {dtype:4s} k={k} -> "
                      f"err={row['end_to_end_err']:.4g} "
                      f"det={row['det_precision']:.2f}/"
                      f"{row['det_recall']:.2f} "
                      f"bytes_ratio={row['physical_ratio']:.4f} "
                      f"ok={row['ok']}", flush=True)

    # locator-margin cells: the PR 10 blocker shape, λ=0 (must reproduce
    # the blocker) and the committed λ (must solve it)
    from draco_tpu.obs.numerics import (WIRE_LOCATOR_LAMBDA,
                                        WIRE_REL_TOL_TABLE)

    n32, s32 = LOCATOR_SHAPE
    for dtype in dtypes:
        for lam in (0.0, WIRE_LOCATOR_LAMBDA[dtype]):
            row = locator_cell(n32, s32, dtype, lam)
            rows.append(row)
            print(f"wire_study: locator n={n32} s={s32} {dtype:4s} "
                  f"lam={lam:g} -> noadv_hmax="
                  f"{row['honest_dev_max_noadv']:.4g} "
                  f"adv_min={row['adv_dev_min']:.4g} "
                  f"usable={row['usable']} ok={row['ok']}", flush=True)

    payload = {
        "schema": 2,
        "tool": "tools/wire_study.py",
        "num_workers": NUM_WORKERS,
        "max_steps": args.max_steps,
        "shadow_round": args.shadow_round,
        # the committed per-(n, s, dtype) flag-threshold table the narrow
        # wire decodes with (obs/numerics.WIRE_REL_TOL_TABLE) + the
        # locator λ per dtype — re-verified against the locator cells'
        # measured margins by --check
        "threshold_table": {f"{n}:{s}:{dt}": tol for (n, s, dt), tol
                            in sorted(WIRE_REL_TOL_TABLE.items())},
        "locator_lambda": dict(WIRE_LOCATOR_LAMBDA),
        "rows": rows,
        "all_ok": bool(rows) and all(r["ok"] for r in rows),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print(f"wire_study: {len(rows)} cells -> {args.out} "
          f"(all_ok={payload['all_ok']})")
    return 0 if payload["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
