#!/usr/bin/env python
"""Decode s-scaling study (VERDICT r2 item 7).

How do the isolated encode / decode costs scale with the Byzantine budget
s ∈ {1, 2, 3} and the worker count n ∈ {8, 16, 32} at the flagship gradient
dimension — against the Weiszfeld geometric-median cost at the same (n, d)?
(The "decode stays flat while Weiszfeld scales" claim.)

Writes after every point; a run cut short keeps completed points.

The study's second question — per-layer decode granularity against the
global one-locator decode, as a full train step — was timed by the
pre-ledger benchmark's scanned-steps harness, which PR 44 deleted with that
benchmark. The committed artifact keeps the cells it measured
(``granularity``: 98.8 ms global, 101.7 ms layer, ResNet-18 b32 on a v5e);
this tool no longer writes them. A full step's time is a benchmark cell's
to measure (benchmark/run.py).

ISSUE 17 additions:

  * ``--merge PATCH`` folds a partial re-run (e.g. the regenerated n=32
    rows measured after the PR 15 regularized locator landed) into the
    committed artifact: every (n, s) scaling row the patch carries
    WITHOUT an error replaces the main artifact's row, and the merge
    provenance is recorded in the artifact ("merged_from");
  * ``--tree-fanout G`` measures, next to every flat (n, s) scaling row,
    the tree topology's per-node critical path at the same d (leaf
    decode at the (G, s_g) group code + per-level combine,
    coding/topology.py) and records the tree-vs-flat crossover column —
    the light companion of tools/tree_study.py;
  * ``--check`` re-verifies a committed artifact jax-free: NO scaling
    row may carry an error, and every present tree column must agree
    with its own timings — wired into
    tools/check_artifacts.py.

Usage: python tools/decode_study.py [--out baselines_out/decode_study.json]
       [--d 11173962] [--cpu-mesh 8 for smoke]
       python tools/decode_study.py --merge baselines_out/decode_study_n32.json
       python tools/decode_study.py --check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def geomedian_ms(n, d, iters=80, reps=10):
    """Isolated Weiszfeld cost at (n, d) under the chained-feedback timing
    protocol (tools/_timing.py) — the PS-phase cost cyclic decode replaces."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu import aggregation
    from tools._timing import timeit_chained

    r = np.random.RandomState(0)
    g = jnp.asarray(r.randn(n, d).astype(np.float32))

    def step(gc):
        med = aggregation.geometric_median(gc, iters=iters)
        return gc.at[0, 0].add(1e-30 * jnp.sum(med**2))

    return timeit_chained(step, g, reps=reps) * 1e3


def tree_phase_times(n, d, s, fanout, reps=10):
    """Per-node critical path of the tree topology at (n, d): the leaf
    decode at the (fanout, s_g) group code plus each combine level's
    fan-in partial sum (coding/topology.py algebra). Returns
    ``(critical_ms, leaf_ms, s_g, levels)`` or None when (n, fanout) has
    no valid tree (n % g != 0 or fewer than 2 groups)."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic as cyc
    from draco_tpu.coding import topology as topo
    from tools._timing import timeit_chained

    if n % fanout != 0 or n // fanout < 2:
        return None
    plan = topo.tree_plan(n, fanout)
    s_g = topo.group_worker_fail(fanout, s)
    code = cyc.build_cyclic_code(fanout, s_g)
    r = np.random.RandomState(0)
    g = jnp.asarray(r.randn(fanout, d).astype(np.float32))
    rf = jnp.asarray(r.randn(d).astype(np.float32))
    e_re, e_im = cyc.encode_shared(code, g)

    def dec_step(carry, rf):
        er, ei = carry
        dec, _honest = cyc.decode(code, er, ei, rf)
        return (er.at[0, 0].add(1e-30 * jnp.sum(dec ** 2)), ei)

    leaf_ms = timeit_chained(dec_step, (e_re, e_im), (rf,), reps=reps) * 1e3
    combine_ms = 0.0
    for f in plan.level_fanouts:
        parts = jnp.asarray(r.randn(f, d).astype(np.float32))

        def node_step(pc):
            t = jnp.sum(pc, axis=0)
            return pc.at[0, 0].add(1e-30 * jnp.sum(t ** 2))

        combine_ms += timeit_chained(node_step, parts, reps=reps) * 1e3
    return leaf_ms + combine_ms, leaf_ms, s_g, plan.levels


def merge_artifact(out_path: str, patch_path: str) -> int:
    """Fold a partial re-run into the committed artifact: error-free
    (n, s) scaling rows from the patch replace the main artifact's rows
    (stale errors included). Jax-free; records provenance under
    ``merged_from``."""
    try:
        with open(out_path) as fh:
            main_doc = json.load(fh)
        with open(patch_path) as fh:
            patch = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"decode_study --merge: cannot read artifacts: {e}")
        return 1
    by_key = {(r.get("n"), r.get("s")): r
              for r in patch.get("scaling", []) if "error" not in r}
    replaced = []
    rows = []
    for row in main_doc.get("scaling", []):
        key = (row.get("n"), row.get("s"))
        if key in by_key:
            rows.append(by_key.pop(key))
            replaced.append(key)
        else:
            rows.append(row)
    rows.extend(by_key.values())  # patch rows the main artifact lacked
    replaced.extend(by_key)
    main_doc["scaling"] = sorted(rows, key=lambda r: (r["n"], r["s"]))
    main_doc["merged_from"] = {
        "patch": os.path.basename(patch_path),
        "replaced": sorted(f"n{n}s{s}" for n, s in replaced),
    }
    with open(out_path, "w") as fh:
        json.dump(main_doc, fh, indent=1)
    print(f"decode_study --merge: {len(replaced)} rows from {patch_path} "
          f"-> {out_path}")
    return 0


def check_artifact(path: str) -> int:
    """Re-verify a committed decode_study.json jax-free: no error rows
    anywhere (ISSUE 17 satellite — the stale n=32 failure rows must
    stay purged), and any tree crossover columns consistent with their
    own timings."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"decode_study --check: cannot read {path}: {e}")
        return 1
    rows = data.get("scaling", [])
    if not rows:
        print(f"decode_study --check: no scaling rows in {path}")
        return 1
    for r in rows:
        cell = f"n{r.get('n')}s{r.get('s')}"
        if "error" in r:
            print(f"decode_study --check: {cell}: error row committed "
                  f"({r['error'][:80]}) — re-measure and --merge")
            return 1
        if "skipped" in r:
            continue  # n <= 4s existence gaps are honest, not stale
        for col in ("encode_ms", "decode_ms", "geomedian_ms_same_n"):
            if not isinstance(r.get(col), (int, float)):
                print(f"decode_study --check: {cell}: non-numeric {col}")
                return 1
        if isinstance(r.get("tree_critical_ms"), (int, float)):
            want = bool(r["tree_critical_ms"] < r["decode_ms"])
            if bool(r.get("tree_win")) != want:
                print(f"decode_study --check: {cell}: tree_win disagrees "
                      f"with its own timings")
                return 1
    print(f"decode_study --check: {len(rows)} scaling rows clean ({path})")
    return 0


def phase_times(n, d, s, reps=20):
    """Isolated encode / decode costs at gradient dimension d.

    Timing and feedback discipline per tools/_timing.timeit_chained
    (non-linear full-output feedback, operands via consts)."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic as cyc
    from tools._timing import timeit_chained

    code = cyc.build_cyclic_code(n, s)
    r = np.random.RandomState(0)
    g = jnp.asarray(r.randn(n, d).astype(np.float32))
    rf = jnp.asarray(r.randn(d).astype(np.float32))

    def enc_step(gc):
        e_re, e_im = cyc.encode_shared(code, gc)
        return gc.at[0, 0].add(1e-30 * (jnp.sum(e_re**2) + jnp.sum(e_im**2)))

    enc_ms = timeit_chained(enc_step, g, reps=reps) * 1e3

    e_re, e_im = cyc.encode_shared(code, g)

    def dec_step(carry, rf):
        er, ei = carry
        dec, honest = cyc.decode(code, er, ei, rf)
        return (er.at[0, 0].add(1e-30 * jnp.sum(dec**2)), ei)

    dec_ms = timeit_chained(dec_step, (e_re, e_im), (rf,), reps=reps) * 1e3
    return enc_ms, dec_ms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/decode_study.json")
    ap.add_argument("--merge", type=str, default="",
                    help="fold a partial re-run artifact into --out "
                         "(jax-free)")
    ap.add_argument("--check", action="store_true",
                    help="re-verify a committed artifact (jax-free)")
    ap.add_argument("--tree-fanout", type=int, default=0,
                    help="also measure the tree per-node critical path at "
                         "this fan-in next to every scaling row (0 = off)")
    ap.add_argument("--d", type=int, default=0,
                    help="gradient dimension (0 = flagship ResNet-18 dim)")
    ap.add_argument("--ns", type=str, default="8,16,32")
    ap.add_argument("--ss", type=str, default="1,2,3")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args(argv)
    if args.merge:
        return merge_artifact(args.out, args.merge)
    if args.check:
        return check_artifact(args.out)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)

    import jax

    dev = jax.devices()[0]
    d = args.d
    if not d:
        # flagship dimension without building the model: ResNet-18/CIFAR-10
        # param count, pinned by tests (tests/test_models_optim_data.py)
        d = 11_173_962

    report = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "grad_dim": d,
        "geomedian_iters": 80,
        "scaling": [],
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def flush():
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)

    # ---- s / n scaling of isolated phases vs Weiszfeld --------------------
    for n in [int(x) for x in args.ns.split(",")]:
        gm = None
        for s in [int(x) for x in args.ss.split(",")]:
            if n <= 4 * s:  # cyclic existence condition
                report["scaling"].append({"n": n, "s": s,
                                          "skipped": "needs n > 4s"})
                flush()
                continue
            print(f"[decode_study] n={n} s={s} ...", file=sys.stderr,
                  flush=True)
            t0 = time.time()
            try:
                enc_ms, dec_ms = phase_times(n, d, s, reps=args.reps)
                if gm is None:
                    gm = geomedian_ms(n, d, reps=args.reps)
            except Exception as e:
                report["scaling"].append({"n": n, "s": s,
                                          "error": f"{type(e).__name__}: {e}"[:300]})
                flush()
                continue
            row = {
                "n": n, "s": s,
                "encode_ms": round(enc_ms, 3),
                "decode_ms": round(dec_ms, 3),
                "geomedian_ms_same_n": round(gm, 3),
                "decode_vs_geomedian": round(gm / dec_ms, 2),
                "measure_s": round(time.time() - t0, 1),
            }
            if args.tree_fanout:
                tp = tree_phase_times(n, d, s, args.tree_fanout,
                                      reps=args.reps)
                if tp is not None:
                    crit, leaf, s_g, levels = tp
                    row.update(
                        tree_fanout=args.tree_fanout, tree_s_g=s_g,
                        tree_levels=levels,
                        tree_leaf_ms=round(leaf, 3),
                        tree_critical_ms=round(crit, 3),
                        tree_win=bool(crit < dec_ms))
            report["scaling"].append(row)
            print(f"[decode_study] n={n} s={s}: enc {row['encode_ms']} ms, "
                  f"dec {row['decode_ms']} ms, geomed {row['geomedian_ms_same_n']} ms",
                  file=sys.stderr, flush=True)
            flush()

    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
