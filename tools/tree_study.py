#!/usr/bin/env python
"""Tree-vs-flat aggregation study: the hierarchical CodedReduce evidence
(ISSUE 17).

The flat coded path decodes all n codewords at ONE logical aggregation
point — decode time and ingest bytes at that point grow with n (the
committed decode_study scaling rows: 1.8 ms at n=8 to 6.3 ms at n=32 on
the flagship d). The tree topology (coding/topology.py) caps per-node
fan-in at g: leaf nodes decode their OWN (g, d) block with the small
per-group code and parents combine decoded (d,) partials level by level.
This study measures that trade at the study d for every valid
(n, fanout) cell:

  * **flat decode ms** — the small-code decode at (n, d), the per-step
    cost of today's star aggregation point (chained-feedback timing,
    tools/_timing.py protocol);
  * **per-node critical path** — what ONE tree node pays per step: the
    leaf decode at (g, d) plus each combine level's fan-in-f partial sum.
    This is the deployment quantity CodedReduce optimises (every level
    runs in parallel across nodes), and the headline crossover column;
  * **sequential total** — the HONEST single-host number: all G leaf
    decodes plus the full combine run back to back, which is how this
    repo's one-process routes actually execute the tree. Flat can win
    this column (total work favors one big decode) and the artifact
    records it when it does;
  * **detection equality** — at cells whose per-group budget s_g >= 1,
    the tree's folded flagged mask must equal the flat decode's under the
    SAME live rev_grad adversary, and under a straggler drop the victim
    must never be accused — detection P/R identical to flat, pinned;
  * **per-level bytes** — the wire ledger's tree sub-block
    (obs/numerics.wire_ledger): leaf-level ingest bytes must SUM EXACTLY
    to the flat ledger's physical_bytes_per_step (same n codeword rows,
    partitioned), pinned tolerance-0 by tools/perf_watch.py.

The winning tree cell re-runs once under the span tracer + a jax
profiler capture and the host/device event streams merge onto one clock
(obs/device_attr.merge_timeline, the PR 9 machinery) — per-group decode
and per-level combine spans land in the committed merged-timeline block.

``--check`` re-verifies a committed artifact jax-free (byte sums, plan
algebra, detection pins, the crossover honesty columns) — wired into
tools/check_artifacts.py.

Usage (CPU, ~2-4 min):
  python tools/tree_study.py
  python tools/tree_study.py --check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NS = (8, 16, 32)
FANOUTS = (4, 8)
WORKER_FAIL = 1
D_DEFAULT = 1_048_576
D_DETECT = 4096
SEED = 1729


def _valid_tree(n: int, g: int) -> bool:
    return n % g == 0 and n // g >= 2


def _study_cfg(n: int, g: int, d: int):
    """The TrainConfig a tree cell names — the ONE source of the committed
    ledger and the per-group code shape (config.validate has the final
    word on the (n, g) cells the study may claim)."""
    from draco_tpu.config import TrainConfig

    kw = dict(network="LeNet", dataset="synthetic-mnist", batch_size=2,
              num_workers=n, approach="cyclic", redundancy="shared",
              worker_fail=WORKER_FAIL, adversary_count=0,
              err_mode="rev_grad", max_steps=2, eval_freq=0, train_dir="",
              log_every=10 ** 9)
    if g:
        kw.update(topology="tree", tree_fanout=g)
    return TrainConfig(**kw)


def _decode_ms(code, d: int, reps: int) -> float:
    """Chained-feedback decode cost of one cyclic code at (code.n, d)."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic as cyc
    from tools._timing import timeit_chained

    r = np.random.RandomState(SEED)
    g = jnp.asarray(r.randn(code.n, d).astype(np.float32) * 0.05)
    rf = jnp.asarray(r.randn(d).astype(np.float32))
    e_re, e_im = cyc.encode_shared(code, g)

    def dec_step(carry, rf):
        er, ei = carry
        dec, _honest = cyc.decode(code, er, ei, rf)
        return (er.at[0, 0].add(1e-30 * jnp.sum(dec ** 2)), ei)

    return timeit_chained(dec_step, (e_re, e_im), (rf,), reps=reps) * 1e3


def _combine_node_ms(fan_in: int, d: int, reps: int) -> float:
    """One combine node's per-step cost: the fan-in-f partial sum."""
    import jax.numpy as jnp
    import numpy as np

    from tools._timing import timeit_chained

    r = np.random.RandomState(SEED)
    parts = jnp.asarray(r.randn(fan_in, d).astype(np.float32))

    def node_step(pc):
        s = jnp.sum(pc, axis=0)
        return pc.at[0, 0].add(1e-30 * jnp.sum(s ** 2))

    return timeit_chained(node_step, parts, reps=reps) * 1e3


def _combine_full_ms(plan, d: int, reps: int) -> float:
    """The WHOLE level-structured fold (G, d) -> (d,) on one host — the
    sequential-total column's combine share."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import topology as topo
    from tools._timing import timeit_chained

    r = np.random.RandomState(SEED)
    parts = jnp.asarray(r.randn(plan.num_groups, d).astype(np.float32))

    def fold_step(pc):
        s = topo.combine_partials(plan, pc)
        return pc.at[0, 0].add(1e-30 * jnp.sum(s ** 2))

    return timeit_chained(fold_step, parts, reps=reps) * 1e3


def _pr(flagged, adv_mask):
    """Detection precision/recall of a flagged mask against truth."""
    import numpy as np

    flagged = np.asarray(flagged, bool)
    adv = np.asarray(adv_mask, bool)
    tp = int((flagged & adv).sum())
    fp = int((flagged & ~adv).sum())
    fn = int((~flagged & adv).sum())
    prec = tp / (tp + fp) if tp + fp else 1.0
    rec = tp / (tp + fn) if tp + fn else 1.0
    return round(prec, 4), round(rec, 4)


def detection_cell(n: int, g: int) -> dict:
    """Tree-vs-flat detection equality at (n, g): the SAME live rev_grad
    adversary decoded both ways must flag the SAME rows (P/R identical),
    and a straggler drop's victim must never be accused either way.
    Requires s_g >= 1 (the g=4 cells have no per-group error budget and
    skip — recorded, not hidden)."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic as cyc, topology as topo

    s_g = topo.group_worker_fail(g, WORKER_FAIL)
    if s_g < 1:
        return {"checked": False, "reason": f"s_g={s_g} (no per-group "
                                            f"error budget at g={g})"}
    d = D_DETECT
    cfg = _study_cfg(n, g, d)
    tcode = topo.build_tree_code(cfg)
    flat = cyc.build_cyclic_code(n, WORKER_FAIL)
    r = np.random.RandomState(SEED)
    grads = jnp.asarray(r.randn(n, d).astype(np.float32) * 0.05)
    rf = jnp.asarray(r.randn(d).astype(np.float32))
    adv_row = n - 2  # lives in the LAST leaf group — the fold must map it
    adv = jnp.zeros((n, 1), bool).at[adv_row, 0].set(True)

    # live adversary: rev_grad on the encoded rows, both topologies
    fr, fi = cyc.encode_shared(flat, grads)
    tr, ti = topo.encode_tree(tcode, grads)
    fr, fi = (jnp.where(adv, -100.0 * fr, fr),
              jnp.where(adv, -100.0 * fi, fi))
    tr, ti = (jnp.where(adv, -100.0 * tr, tr),
              jnp.where(adv, -100.0 * ti, ti))
    _dec_f, _hon_f, hl_f = cyc.decode(flat, fr, fi, rf, with_health=True)
    _dec_t, _hon_t, hl_t = topo.decode_tree_cyclic(tcode, tr, ti, rf)
    fl_f = np.asarray(hl_f["flagged"], bool)
    fl_t = np.asarray(hl_t["flagged"], bool)
    p_f, r_f = _pr(fl_f, np.asarray(adv).ravel())
    p_t, r_t = _pr(fl_t, np.asarray(adv).ravel())

    # straggler drop: one worker absent (erasure), nobody gets accused
    drop_row = 1
    present = jnp.ones((n,), bool).at[drop_row].set(False)
    fr2, fi2 = cyc.encode_shared(flat, grads)
    tr2, ti2 = topo.encode_tree(tcode, grads)
    dec_f2, _h, hl_f2 = cyc.decode(flat, fr2, fi2, rf, present=present,
                                   with_health=True)
    dec_t2, _h, hl_t2 = topo.decode_tree_cyclic(tcode, tr2, ti2, rf,
                                                present=present)
    dfl_f = np.asarray(hl_f2["flagged"], bool)
    dfl_t = np.asarray(hl_t2["flagged"], bool)
    true_mean = np.asarray(jnp.mean(grads, axis=0))
    err_f = float(np.max(np.abs(np.asarray(dec_f2) - true_mean)))
    err_t = float(np.max(np.abs(np.asarray(dec_t2) - true_mean)))
    return {
        "checked": True, "adv_row": adv_row, "drop_row": drop_row,
        "precision_flat": p_f, "recall_flat": r_f,
        "precision_tree": p_t, "recall_tree": r_t,
        "flags_equal": bool((fl_f == fl_t).all()),
        "drop_victim_accused_flat": bool(dfl_f[drop_row]),
        "drop_victim_accused_tree": bool(dfl_t[drop_row]),
        "drop_flags_equal": bool((dfl_f == dfl_t).all()),
        "drop_decode_err_flat": round(err_f, 7),
        "drop_decode_err_tree": round(err_t, 7),
        "ok": bool((fl_f == fl_t).all() and (dfl_f == dfl_t).all()
                   and p_t == p_f and r_t == r_f and r_t == 1.0
                   and not dfl_t[drop_row] and err_t < 1e-3),
    }


def run_tree_cell(n: int, g: int, d: int, flat_ms: float, reps: int) -> dict:
    from draco_tpu.coding import topology as topo
    from draco_tpu.obs import numerics as nx

    cfg = _study_cfg(n, g, d)
    flat_cfg = _study_cfg(n, 0, d)
    tcode = topo.build_tree_code(cfg)
    plan = tcode.plan

    leaf_ms = _decode_ms(tcode.group_code, d, reps)
    node_combine = [round(_combine_node_ms(f, d, reps), 3)
                    for f in plan.level_fanouts]
    combine_full_ms = _combine_full_ms(plan, d, reps)
    critical_ms = leaf_ms + sum(node_combine)
    sequential_ms = plan.num_groups * leaf_ms + combine_full_ms

    ledger = nx.wire_ledger(cfg, d)
    flat_ledger = nx.wire_ledger(flat_cfg, d)
    tree_block = ledger.get("tree") or {}
    level_bytes = tree_block.get("level_bytes_per_step") or []
    # the honesty pin: leaf-level ingest == the flat star's per-step bytes
    bytes_ok = bool(
        level_bytes
        and level_bytes[0] == flat_ledger["physical_bytes_per_step"]
        and level_bytes[0] == ledger["physical_bytes_per_step"]
        and tree_block.get("ingest_bytes_per_group", 0) * plan.num_groups
        == level_bytes[0])

    det = detection_cell(n, g)
    row = {
        "kind": "tree", "n": n, "fanout": g, "levels": plan.levels,
        "num_groups": plan.num_groups, "s_g": tcode.s, "d": d,
        "leaf_decode_ms": round(leaf_ms, 3),
        "node_combine_ms": node_combine,
        "critical_path_ms": round(critical_ms, 3),
        "sequential_total_ms": round(sequential_ms, 3),
        "flat_decode_ms": round(flat_ms, 3),
        "win": bool(critical_ms < flat_ms),
        "win_frac": round((flat_ms - critical_ms) / flat_ms, 4),
        "sequential_win": bool(sequential_ms < flat_ms),
        "ledger": {
            "flat_physical_bytes_per_step":
                flat_ledger["physical_bytes_per_step"],
            "tree": tree_block,
        },
        "bytes_ok": bytes_ok,
        "detection": det,
    }
    row["ok"] = bool(bytes_ok and (det["ok"] if det.get("checked")
                                   else True))
    return row


def capture_timeline(row: dict, reps: int, work_dir: str) -> dict:
    """Re-run the winning tree cell once under the span tracer + a jax
    profiler capture: per-group leaf decodes and per-level combines land
    as tree_* spans, merged onto one clock with any device events."""
    import gzip

    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic as cyc, topology as topo
    from draco_tpu.obs import device_attr
    from draco_tpu.obs.profiling import ANCHOR_FILE, ProfilerWindow
    from draco_tpu.obs.tracer import make_tracer

    n, g, d = row["n"], row["fanout"], row["d"]
    cfg = _study_cfg(n, g, d)
    tcode = topo.build_tree_code(cfg)
    plan = tcode.plan
    r = np.random.RandomState(SEED)
    grads = jnp.asarray(r.randn(n, d).astype(np.float32) * 0.05)
    rf = jnp.asarray(r.randn(d).astype(np.float32))
    e_re, e_im = topo.encode_tree(tcode, grads)
    dec = jax.jit(lambda er, ei, f: cyc.decode(tcode.group_code, er, ei, f))
    jax.block_until_ready(dec(e_re[: g], e_im[: g], rf))  # compile outside

    cell_dir = os.path.join(work_dir, "tree_decode")
    os.makedirs(cell_dir, exist_ok=True)
    tracer = make_tracer(cell_dir)
    win = ProfilerWindow(cell_dir, (0, 10 ** 9), tracer=tracer)
    win.maybe_start(0, first_step=0)
    try:
        parts = []
        for j, (lo, hi) in enumerate(plan.group_slices):
            with tracer.span(f"tree_leaf_decode_g{j}", fan_in=g):
                out, _ = dec(e_re[lo:hi], e_im[lo:hi], rf)
                jax.block_until_ready(out)
            parts.append(out)
        x = jnp.stack(parts)
        for l, f in enumerate(plan.level_fanouts):
            with tracer.span(f"tree_combine_l{l + 1}", fan_in=f):
                x = jax.block_until_ready(
                    x.reshape(-1, f, x.shape[-1]).sum(axis=1))
        jax.block_until_ready(x[0] / plan.num_groups)
    finally:
        win.stop()
        tracer.close()

    host = device_attr.load_json(os.path.join(cell_dir, "trace.json"))
    host_events = (host or {}).get("traceEvents") or []
    anchor = device_attr.load_json(os.path.join(cell_dir, ANCHOR_FILE))
    cap = device_attr.find_capture(cell_dir)
    dev_events = []
    if cap is not None:
        dev_events, _ = device_attr.load_trace(cap)
    merged = device_attr.merge_timeline(host_events, dev_events, None,
                                        anchor, max_device_events=50_000)
    out_path = os.path.join(cell_dir, "merged_timeline.json.gz")
    with gzip.open(out_path, "wt") as fh:
        json.dump(merged, fh)
    mt = merged["mergedTimeline"]
    tree_spans = sum(1 for e in host_events
                     if str(e.get("name", "")).startswith("tree_"))
    rel = os.path.join(os.path.basename(cell_dir.rstrip(os.sep)),
                       os.path.basename(out_path))
    return {"path": rel, "cell": f"n{n}.g{g}",
            "anchored": mt["anchored"], "anchor_kind": mt.get("anchor_kind"),
            "host_events": len(host_events), "tree_spans": tree_spans,
            "device_events": sum(1 for e in merged["traceEvents"]
                                 if e.get("cat") == "device")}


# --------------------------------------------------------------------------
# --check: jax-free artifact re-verification (tools/check_artifacts.py)
# --------------------------------------------------------------------------


def check_artifact(path: str) -> int:
    """Re-verify a committed tree_study.json: plan algebra, the per-level
    byte sums, the detection pins, and the crossover honesty columns.
    Exits nonzero naming the first failure."""
    from draco_tpu.coding.topology import tree_plan  # jax-free header

    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"tree_study --check: cannot read {path}: {e}")
        return 1
    rows = data.get("rows", [])
    flat = {r["n"]: r for r in rows if r.get("kind") == "flat"}
    trees = [r for r in rows if r.get("kind") == "tree"]
    want = {(n, g) for n in NS for g in FANOUTS if _valid_tree(n, g)}
    got = {(r.get("n"), r.get("fanout")) for r in trees}
    if not want <= got:
        print(f"tree_study --check: missing tree cells {sorted(want - got)}")
        return 1
    if set(flat) != set(NS):
        print(f"tree_study --check: flat rows cover {sorted(flat)}, "
              f"want {list(NS)}")
        return 1
    detect_checked = 0
    for r in trees:
        cell = f"n{r['n']}.g{r['fanout']}"
        plan = tree_plan(r["n"], r["fanout"], r.get("levels", 0))
        if (plan.levels != r["levels"]
                or plan.num_groups != r["num_groups"]):
            print(f"tree_study --check: {cell}: plan algebra disagrees "
                  f"(levels {r['levels']}, groups {r['num_groups']})")
            return 1
        led = r.get("ledger") or {}
        tb = led.get("tree") or {}
        lb = tb.get("level_bytes_per_step") or []
        if len(lb) != plan.levels:
            print(f"tree_study --check: {cell}: {len(lb)} byte levels for "
                  f"a {plan.levels}-level tree")
            return 1
        if lb[0] != led.get("flat_physical_bytes_per_step"):
            print(f"tree_study --check: {cell}: leaf-level bytes {lb[0]} "
                  f"!= flat per-step bytes "
                  f"{led.get('flat_physical_bytes_per_step')} — the "
                  f"partition must sum exactly")
            return 1
        if tb.get("ingest_bytes_per_group", 0) * plan.num_groups != lb[0]:
            print(f"tree_study --check: {cell}: per-group ingest bytes do "
                  f"not tile the leaf level")
            return 1
        if not r.get("bytes_ok"):
            print(f"tree_study --check: {cell}: bytes_ok is false")
            return 1
        base = flat.get(r["n"], {}).get("decode_ms")
        if base is None or abs(base - r.get("flat_decode_ms", -1)) > 1e-9:
            print(f"tree_study --check: {cell}: flat_decode_ms does not "
                  f"match the n={r['n']} flat row")
            return 1
        want_win = r["critical_path_ms"] < r["flat_decode_ms"]
        if bool(r.get("win")) != want_win:
            print(f"tree_study --check: {cell}: win column disagrees with "
                  f"its own timings")
            return 1
        det = r.get("detection") or {}
        if det.get("checked"):
            detect_checked += 1
            if not (det.get("flags_equal") and det.get("drop_flags_equal")
                    and det.get("precision_tree") == det.get(
                        "precision_flat")
                    and det.get("recall_tree") == det.get("recall_flat")
                    and det.get("recall_tree") == 1.0
                    and not det.get("drop_victim_accused_tree")
                    and det.get("ok")):
                print(f"tree_study --check: {cell}: detection parity pin "
                      f"failed ({det})")
                return 1
        if not r.get("ok"):
            print(f"tree_study --check: {cell}: row not ok")
            return 1
    if detect_checked == 0:
        print("tree_study --check: no cell ran the live-adversary "
              "detection parity check (need an s_g >= 1 cell)")
        return 1
    cx = data.get("crossover") or {}
    n_max = max(NS)
    best = [r for r in trees if r["n"] == n_max and r.get("win")]
    if not best:
        print(f"tree_study --check: no tree cell beats flat decode at "
              f"n={n_max} — the ISSUE 17 acceptance pin")
        return 1
    if cx.get("critical_path_n") not in [n for n, _g in sorted(got)]:
        print(f"tree_study --check: crossover block names no measured "
              f"cell ({cx})")
        return 1
    mt = data.get("merged_timeline") or {}
    if not mt.get("tree_spans", 0) > 0:
        print("tree_study --check: merged timeline carries no tree_* "
              "spans")
        return 1
    if not data.get("all_ok"):
        print("tree_study --check: all_ok is false")
        return 1
    print(f"tree_study --check: {len(rows)} rows verified ({path})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=str,
                    default=os.path.join("baselines_out", "tree_study.json"))
    ap.add_argument("--d", type=int, default=D_DEFAULT)
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--work-dir", type=str, default="",
                    help="dir for the merged-timeline artifact "
                         "(default: a temp dir, printed at exit)")
    ap.add_argument("--check", action="store_true",
                    help="re-verify a committed artifact (jax-free)")
    ap.add_argument("--artifact", type=str, default="",
                    help="artifact path for --check (default --out)")
    args = ap.parse_args(argv)
    if args.check:
        return check_artifact(args.artifact or args.out)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from draco_tpu.coding import cyclic as cyc

    dev = jax.devices()[0]
    d = args.d
    print(f"tree_study: d={d} worker_fail={WORKER_FAIL} on {dev.platform}",
          flush=True)
    rows = []
    flat_ms = {}
    for n in NS:
        t0 = time.time()
        flat = cyc.build_cyclic_code(n, WORKER_FAIL)
        ms = _decode_ms(flat, d, args.trials)
        flat_ms[n] = ms
        rows.append({"kind": "flat", "n": n, "s": WORKER_FAIL, "d": d,
                     "decode_ms": round(ms, 3),
                     "measure_s": round(time.time() - t0, 1)})
        print(f"tree_study: flat n={n} -> {ms:.3f} ms", flush=True)
    for n in NS:
        for g in FANOUTS:
            if not _valid_tree(n, g):
                continue
            t0 = time.time()
            row = run_tree_cell(n, g, d, flat_ms[n], args.trials)
            row["measure_s"] = round(time.time() - t0, 1)
            rows.append(row)
            det = row["detection"]
            print(f"tree_study: tree n={n} g={g} -> "
                  f"critical={row['critical_path_ms']:.3f} ms "
                  f"(leaf {row['leaf_decode_ms']:.3f}) "
                  f"sequential={row['sequential_total_ms']:.3f} ms "
                  f"flat={row['flat_decode_ms']:.3f} ms "
                  f"win={row['win']} bytes_ok={row['bytes_ok']} "
                  f"detect={'ok' if det.get('ok') else det.get('reason', 'FAIL')}",
                  flush=True)

    trees = [r for r in rows if r["kind"] == "tree"]
    # crossover honesty: the smallest n whose best tree cell wins each
    # column; sequential may have NO crossover on one host — recorded
    cp_wins = sorted({r["n"] for r in trees if r["win"]})
    sq_wins = sorted({r["n"] for r in trees if r["sequential_win"]})
    crossover = {
        "critical_path_n": cp_wins[0] if cp_wins else None,
        "sequential_n": sq_wins[0] if sq_wins else None,
        "flat_wins_sequential_at": sorted(
            {r["n"] for r in trees if not r["sequential_win"]}),
    }
    print(f"tree_study: crossover {crossover}", flush=True)

    best = None
    for r in trees:
        if r["win"] and (best is None
                         or r["win_frac"] > best["win_frac"]):
            best = r
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="tree_study_")
    merged = {}
    if best is not None:
        merged = capture_timeline(best, args.trials, work_dir)
        print(f"tree_study: merged timeline -> "
              f"{os.path.join(work_dir, merged['path'])} "
              f"(anchored={merged['anchored']}, "
              f"{merged['tree_spans']} tree spans)", flush=True)

    n_max = max(NS)
    payload = {
        "schema": 1,
        "tool": "tools/tree_study.py",
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "d": d, "worker_fail": WORKER_FAIL, "trials": args.trials,
        "rows": rows,
        "crossover": crossover,
        "merged_timeline": merged,
        "all_ok": bool(trees) and all(r["ok"] for r in trees)
        and any(r["n"] == n_max and r["win"] for r in trees)
        and any((r["detection"] or {}).get("checked") for r in trees)
        and merged.get("tree_spans", 0) > 0,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print(f"tree_study: {len(rows)} rows -> {args.out} "
          f"(all_ok={payload['all_ok']})")
    return 0 if payload["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
