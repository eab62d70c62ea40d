#!/usr/bin/env python
"""Offline lowering audit + program-size evidence for scan_layers (round 5).

Every multi-variant attempt at the d≈159M LM point died in compilation at
~27 min (PERF_HISTORY.md §4) — the unrolled 12-layer remat program is ~12× the size
it needs to be, and the ceiling it hit is evidently program-size-shaped.
``scan_layers`` compiles
the layer stack as ONE nn.scan body over stacked weights (identical math:
tests/test_transformer_scan.py), shrinking the XLA program by ~layers×.

This tool proves, without a chip:
  1. the scan_layers variants of the exact lm_big rung shapes lower clean
     for platforms=["tpu"] (methodology: tools/tpu_lm_lowering_check.py,
     which pins the unrolled counterparts);
  2. the serialized StableHLO module is a fraction of the unrolled one —
     the quantity the compile chokes on. Both sizes are recorded
     per variant so the chip rung's compile-odds argument is numbers-backed;
  3. the PRODUCTION chunked token-loop program (train_token_many, K fused
     steps — parallel/common.py) lowers clean for platforms=["tpu"] AND its
     serialized module stays within ~2× of the eager single-step module:
     the token block and the adversary/straggler schedules enter as scan
     ARGUMENTS, so the 638 MB closed-over-constant regression (PERF_HISTORY.md §4)
     cannot reappear through them.

Configs come from tools/_lowering_common.py (build_lm_variants with
scan_layers=True) and the shapes from tools/tpu_lm_lowering_check.py
(LM_BIG), so both audits lower the same programs.

  python tools/tpu_lm_scan_lowering_check.py \
      [--out baselines_out/tpu_lm_scan_lowering.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def lower_variant(name, cfg_kw, steps=2):
    """Returns (ok-row dict) with serialized-module byte size."""
    import jax
    import jax.export

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.mesh import make_folded_wtp_mesh
    from draco_tpu.parallel.tp_step import build_tp_train_setup
    from tools._lowering_common import make_scan_loop, stage_scan_inputs

    cfg = TrainConfig(**cfg_kw)
    mesh = make_folded_wtp_mesh(cfg.num_workers)
    t0 = time.time()
    try:
        setup = build_tp_train_setup(cfg, mesh)
        xs, ms = stage_scan_inputs(cfg, steps)
        loop = make_scan_loop(setup)
        with mesh:
            exp = jax.export.export(jax.jit(loop), platforms=["tpu"])(
                setup.state, xs, ms)
        n_params = sum(x.size for x in jax.tree.leaves(setup.state.params))
        return {"variant": name, "ok": True, "params": int(n_params),
                "scan_layers": bool(cfg.scan_layers),
                "module_bytes": len(exp.mlir_module_serialized),
                "seconds": round(time.time() - t0, 1)}
    except Exception as e:
        return {"variant": name, "ok": False,
                "scan_layers": bool(cfg_kw.get("scan_layers", False)),
                "seconds": round(time.time() - t0, 1),
                "error": f"{type(e).__name__}: {str(e)[:400]}"}


CHUNK_RATIO_LIMIT = 2.0  # chunked module must stay within ~2x of eager step


def lower_chunked_variant(name, cfg_kw, k=4):
    """Export the eager single-step program AND the K-chunk
    ``train_token_many`` program for platforms=["tpu"]; ok requires both to
    lower clean and the chunked module to stay within CHUNK_RATIO_LIMIT of
    the eager step's serialized size (the closed-over-constant guard)."""
    import jax
    import jax.export
    import numpy as np

    from draco_tpu import rng as drng
    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.mesh import make_folded_wtp_mesh
    from draco_tpu.parallel.sp_step import synthetic_text
    from draco_tpu.parallel.tp_step import build_tp_train_setup

    cfg = TrainConfig(**dict(cfg_kw, steps_per_call=k))
    mesh = make_folded_wtp_mesh(cfg.num_workers)
    t0 = time.time()
    try:
        setup = build_tp_train_setup(cfg, mesh)
        adv = drng.adversary_schedule(cfg.seed, k + 1, cfg.num_workers,
                                      cfg.num_adversaries)
        toks1 = synthetic_text(cfg.seed, 1, cfg.num_workers, cfg.batch_size,
                               cfg.seq_len, cfg.vocab)
        blk = np.stack([
            synthetic_text(cfg.seed, s, cfg.num_workers, cfg.batch_size,
                           cfg.seq_len, cfg.vocab)
            for s in range(1, k + 1)
        ])
        with mesh:
            exp_step = jax.export.export(setup.train_step,
                                         platforms=["tpu"])(
                setup.state, toks1, np.asarray(adv[1]))
            exp_many = jax.export.export(setup.train_token_many,
                                         platforms=["tpu"])(
                setup.state, blk, np.asarray(adv[1 : k + 1]), None)
        step_bytes = len(exp_step.mlir_module_serialized)
        many_bytes = len(exp_many.mlir_module_serialized)
        ratio = many_bytes / max(step_bytes, 1)
        return {"variant": name, "ok": ratio <= CHUNK_RATIO_LIMIT,
                "steps_per_call": k,
                "scan_layers": bool(cfg.scan_layers),
                "eager_step_module_bytes": step_bytes,
                "chunked_module_bytes": many_bytes,
                "chunked_vs_eager_ratio": round(ratio, 3),
                "ratio_limit": CHUNK_RATIO_LIMIT,
                "seconds": round(time.time() - t0, 1)}
    except Exception as e:
        return {"variant": name, "ok": False, "steps_per_call": k,
                "seconds": round(time.time() - t0, 1),
                "error": f"{type(e).__name__}: {str(e)[:400]}"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/tpu_lm_scan_lowering.json")
    args = ap.parse_args(argv)

    from tools._lowering_common import run_rows, setup_cpu_host

    setup_cpu_host(1)  # the chip's folded 1-device layout

    from tools.tpu_lm_lowering_check import (
        LM_BIG, LM_BIG_VARIANTS_B1, LM_BIG_VARIANTS_B2,
    )
    from tools._lowering_common import build_lm_variants

    rows = []
    for scan in (True, False):
        v_b2 = build_lm_variants(batch_size=2, scan_layers=scan, **LM_BIG)
        v_b1 = build_lm_variants(batch_size=1, scan_layers=scan, **LM_BIG)
        tag = "scan" if scan else "unroll"
        rows += [(f"{n}_{tag}", (lambda n=n, v=v_b2: lower_variant(n, v[n])))
                 for n in LM_BIG_VARIANTS_B2]
        rows += [(f"{n}_{tag}", (lambda n=n, v=v_b1: lower_variant(n, v[n])))
                 for n in LM_BIG_VARIANTS_B1]
    # the production chunked token-loop program at the same rung shapes
    # (scan_layers, the chip layout): K=4 fused steps, token block and
    # schedules as arguments
    v_chunk = build_lm_variants(batch_size=2, scan_layers=True, **LM_BIG)
    rows += [(f"{n}_chunked_k4",
              (lambda n=n: lower_chunked_variant(n, v_chunk[n])))
             for n in ("lm_cyclic_s1_shared_bf16_flash", "lm_geomedian_bf16")]

    report = run_rows(
        args.out,
        "jax.export platforms=['tpu'] on the 1-virtual-device CPU host: "
        "d~159M lm_big rung shapes with scan_layers=True vs unrolled, plus "
        "the production chunked token-loop program (train_token_many, K=4) "
        "vs its eager single step; module_bytes = serialized StableHLO size "
        "(the compile-service pressure metric). Configs from "
        "tools/_lowering_common.py.",
        rows,
    )
    # headline ratio: shared-flash variant, scan vs unroll
    by = {r["variant"] + ("_scan" if r.get("scan_layers") else "_unroll"): r
          for r in report["rows"]
          if r.get("ok") and "chunked_module_bytes" not in r}
    k = "lm_cyclic_s1_shared_bf16_flash"
    if f"{k}_scan" in by and f"{k}_unroll" in by:
        ratio = by[f"{k}_unroll"]["module_bytes"] / by[f"{k}_scan"]["module_bytes"]
        report["flash_module_shrink_x"] = round(ratio, 2)
    # keyed on steps_per_call (present on success AND error rows) so a
    # crashed chunked export can't vanish from the guard's verdict
    chunk_rows = [r for r in report["rows"] if "steps_per_call" in r]
    if chunk_rows:
        report["chunked_within_ratio_limit"] = all(
            r["ok"] for r in chunk_rows
        )
        ratios = [r["chunked_vs_eager_ratio"] for r in chunk_rows
                  if "chunked_vs_eager_ratio" in r]
        if ratios:
            report["chunked_vs_eager_ratio_max"] = max(ratios)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"all_ok": report["all_ok"],
                      "flash_module_shrink_x": report.get(
                          "flash_module_shrink_x"),
                      "chunked_vs_eager_ratio_max": report.get(
                          "chunked_vs_eager_ratio_max")}))
    return 0 if report["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
