#!/usr/bin/env python
"""Offline TPU-lowering audit of the d≈159M LM chip programs (round 5).

The `lm_big` shapes (LM_BIG below) are the largest TransformerLM programs
in the repo: dim=1024/heads=16/layers=12 (d ≈ 159M params), T=2048, bf16,
remat, on the folded w×tp GSPMD mesh — cyclic shared + Pallas flash, cyclic
shared, geomedian, and cyclic simulate (r=3 redundant lanes). A Python-side
lowering bug there (Pallas tiling, sharding rule, remat/scan interaction)
would burn a chip call for nothing.

This tool cross-platform exports the full scanned train-step programs for
`platforms=["tpu"]` on the CPU host (`jax.export`), which runs the whole
StableHLO + Pallas TPU lowering stack without a chip (methodology +
negative control: tools/tpu_attn_lowering_check.py). The variant configs,
input staging, and scan loop are the shared ones of
tools/_lowering_common.py (build_lm_variants / stage_scan_inputs /
make_scan_loop), which the scan audit lowers too. The host runs with ONE
virtual device, so make_folded_wtp_mesh folds all 8 logical workers onto a
single device — the exact layout the single-chip run uses; an 8-device
layout would exercise different GSPMD shardings than the chip will.

What it cannot prove: Mosaic machine-code compilation and HBM fit — a
compile for the described chip (tests/test_chip_compile.py's method) or the
chip run closes those.

The scan_layers variants of these same shapes are audited by
the sibling tools/tpu_lm_scan_lowering_check.py, which also records the
serialized program-size comparison driving that flag.

  python tools/tpu_lm_lowering_check.py \
      [--out baselines_out/tpu_lm_big_lowering.json]

Builds ~159M-param states on host RAM (~1-2 min per variant); the report
is rewritten after every row, so an interrupt keeps finished rows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def lm_big_program(name, cfg_kw, steps=2):
    """Register one lm_big rung variant as a chip-tier LintProgram: the row
    now carries the full six-rule lint verdict on top of the lowering
    check, through the same machinery as the CI artifact
    (tools/_lowering_common.lint_row / draco_tpu/analysis).

    The audited program is unchanged: the exact scan loop the chip rung
    times (make_scan_loop over stage_scan_inputs — which deliberately does
    NOT donate its state, because the timing protocol re-runs the compiled
    loop on the same state; manifest.require_donated=None records that).
    Explicit-collective counts are also None: this is the GSPMD folded
    route, whose collectives exist only post-partitioner.
    """
    import jax

    from draco_tpu.analysis import BF16_DTYPES, BuiltProgram, LintProgram, Manifest

    def build():
        from draco_tpu.config import TrainConfig
        from draco_tpu.parallel.mesh import make_folded_wtp_mesh
        from draco_tpu.parallel.tp_step import build_tp_train_setup
        from tools._lowering_common import make_scan_loop, stage_scan_inputs

        cfg = TrainConfig(**cfg_kw)
        mesh = make_folded_wtp_mesh(cfg.num_workers)
        setup = build_tp_train_setup(cfg, mesh)
        xs, ms = stage_scan_inputs(cfg, steps)
        with mesh:
            loop = jax.jit(make_scan_loop(setup))
        n_params = sum(x.size for x in jax.tree.leaves(setup.state.params))
        manifest = Manifest(
            require_donated=None, collectives=None,
            allowed_dtypes=BF16_DTYPES,
            # a closed-over (d,) f32 adds 4d bytes (638 MB at this d — the
            # remote-compile ceiling, PERF_HISTORY.md §4); honest modules are ~1 MB
            max_module_bytes=2 * setup.dim, max_constant_bytes=1 << 20,
        )
        return BuiltProgram(name, loop, (setup.state, xs, ms), mesh,
                            manifest,
                            extra={"variant": name, "params": int(n_params),
                                   "devices_in_mesh":
                                       int(mesh.devices.size)},
                            # the lowering audit needs trace+export only; a
                            # CPU backend-compile of the d≈159M flagship
                            # costs real minutes per row
                            capture_memory=False)

    return LintProgram(name=name, build=build, route="lm_big", fast=False)


# The shapes the audit lowers: the b=2 variants and the b=1 simulate variant.
LM_BIG = dict(num_workers=8, seq_len=2048, vocab=8192, model_dim=1024,
              model_heads=16, model_layers=12, remat=True, max_steps=5)
LM_BIG_VARIANTS_B2 = ("lm_cyclic_s1_shared_bf16_flash",
                      "lm_cyclic_s1_shared_bf16", "lm_geomedian_bf16")
LM_BIG_VARIANTS_B1 = ("lm_cyclic_s1_simulate_bf16",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/tpu_lm_big_lowering.json")
    args = ap.parse_args(argv)

    # ONE virtual device: the chip folds all logical workers onto a single
    # device and the audit must lower that exact layout (docstring)
    from tools._lowering_common import (build_lm_variants, lint_row,
                                        run_rows, setup_cpu_host)

    setup_cpu_host(1)

    v_b2 = build_lm_variants(batch_size=2, **LM_BIG)
    v_b1 = build_lm_variants(batch_size=1, **LM_BIG)
    programs = ([lm_big_program(n, v_b2[n]) for n in LM_BIG_VARIANTS_B2]
                + [lm_big_program(n, v_b1[n]) for n in LM_BIG_VARIANTS_B1])
    named = [(p.name, (lambda p=p: lint_row(p))) for p in programs]
    report = run_rows(
        args.out,
        "jax.export cross-platform lowering, platforms=['tpu'], CPU host "
        "with ONE virtual device (the chip's folded layout), full scanned "
        "train-step programs at the lm_big shapes (LM_BIG), "
        "configs from tools/_lowering_common.py; each row "
        "carries the six-rule program-lint verdict (draco_tpu/analysis)",
        named,
    )
    print(json.dumps({"all_ok": report["all_ok"]}))
    return 0 if report["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
