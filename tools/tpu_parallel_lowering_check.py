#!/usr/bin/env python
"""Offline TPU-lowering audit of the multi-device parallel axes (round 5).

The multichip dryrun (`__graft_entry__.dryrun_multichip`) compiles and RUNS
every coded-DP × model-parallel composition — but against the XLA **CPU**
backend. This tool closes the other half offline: it cross-platform
exports the same jitted train steps for ``platforms=["tpu"]``
(`jax.export` on CPU host, methodology + negative control:
tools/tpu_attn_lowering_check.py), so the GSPMD partitioning, ppermute
ring schedules, cond-skipped hops, and the Pallas flash kernel inside the
ring are all validated against the TPU lowering stack — the stack an
actual multi-chip pod would compile with, which no single-chip rung can
exercise.

Axes (16 virtual devices, w=8 cyclic s=1 coded DP × axis2=2 — the cyclic
n > 4s row the dryrun can only afford at its larger mesh):
  sp_ring_dense   shard_map + ppermute ring attention
  sp_ring_flash   ring with the Pallas flash kernel per hop
                  (ring_flash_attention — the §2.3-SP/§5.7 long-context row)
  tp              Megatron tensor parallelism (GSPMD annotations)
  pp              GPipe microbatch pipeline (shard_map + ppermute schedule)
  ep              Switch-MoE expert parallelism

What it cannot prove: Mosaic machine-code compilation, HBM fit, and real
ICI behavior — those need a pod (SURVEY §7.4). Report rewritten per row.

  python tools/tpu_parallel_lowering_check.py \
      [--out baselines_out/tpu_parallel_lowering.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def axis_program(name, tag, overrides, collectives, w=8):
    """Register one parallel-axis row as a chip-tier LintProgram. The
    audited program is the route builder's own donated ``train_step`` (the
    old bespoke thunk re-wrapped it in a fresh jit, which dropped the
    donation attrs — the lint donation rule needs the real program), and
    the row carries the six-rule verdict including the axis's explicit
    collective budget: the ring/pipeline hop structure IS the row's claim,
    so count drift fails the audit even when lowering succeeds."""
    from draco_tpu.analysis import BuiltProgram, LintProgram, Manifest
    from draco_tpu.analysis.registry import lm_example_tokens

    def build():
        from draco_tpu.config import TrainConfig
        from draco_tpu.parallel import (
            make_mesh_2d, make_mesh_wep, make_mesh_wpp, make_mesh_wtp,
        )
        from draco_tpu.parallel.ep_step import build_ep_train_setup
        from draco_tpu.parallel.pp_step import build_pp_train_setup
        from draco_tpu.parallel.sp_step import build_sp_train_setup
        from draco_tpu.parallel.tp_step import build_tp_train_setup

        builders = {
            "sp": (build_sp_train_setup, make_mesh_2d),
            "tp": (build_tp_train_setup, make_mesh_wtp),
            "pp": (build_pp_train_setup, make_mesh_wpp),
            "ep": (build_ep_train_setup, make_mesh_wep),
        }
        builder, make_mesh_fn = builders[tag]
        cfg = TrainConfig(
            network="TransformerLM", dataset="synthetic-text", batch_size=2,
            num_workers=w, approach="cyclic", mode="normal", worker_fail=1,
            err_mode="rev_grad", seq_len=64, vocab=64, model_dim=64,
            model_heads=2, max_steps=2, eval_freq=0, train_dir="",
            log_every=1000, **overrides)
        mesh = make_mesh_fn(w, 2)
        setup = builder(cfg, mesh)
        toks, mask = lm_example_tokens(cfg)
        manifest = Manifest(collectives=collectives)
        return BuiltProgram(name, setup.train_step,
                            (setup.state, toks, mask), mesh, manifest,
                            extra={"devices_in_mesh":
                                       int(mesh.devices.size)})

    return LintProgram(name=name, build=build, route=f"parallel_{tag}",
                       fast=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/tpu_parallel_lowering.json")
    args = ap.parse_args(argv)

    from tools._lowering_common import lint_row, run_rows, setup_cpu_host

    setup_cpu_host(16)

    # explicit-collective budgets per axis (the hop structure is the row's
    # claim), imported from the owning route modules so a legitimate
    # schedule change is a ONE-file manifest edit (PERF_HISTORY.md §6): the sp ring
    # budget covers both attention inners (dense and flash — the hop
    # structure is inner-independent), the pipeline brings its tick
    # schedule + loss/grad psums, and tp/ep are pure GSPMD (collectives
    # post-partitioner = none explicit).
    from draco_tpu.parallel import pp_step, sp_step

    axes = [
        ("sp_ring_dense", "sp", dict(seq_shards=2, model_layers=1),
         sp_step.LINT_COLLECTIVES),
        ("sp_ring_flash", "sp", dict(seq_shards=2, model_layers=1,
                                     attn_impl="flash"),
         sp_step.LINT_COLLECTIVES),
        ("tp", "tp", dict(tensor_shards=2, model_layers=1), {}),
        ("pp", "pp", dict(pipeline_shards=2, pp_microbatches=2,
                          model_layers=2),
         pp_step.LINT_COLLECTIVES),
        ("ep", "ep", dict(moe_experts=4, expert_shards=2, model_layers=1),
         {}),
    ]
    programs = [axis_program(name, tag, ov, colls)
                for name, tag, ov, colls in axes]
    named = [(p.name, (lambda p=p: lint_row(p))) for p in programs]
    report = run_rows(
        args.out,
        "jax.export cross-platform lowering, platforms=['tpu'], 16 virtual "
        "CPU devices, w=8 cyclic s=1 coded DP x axis2=2, the route "
        "builders' own donated train_step programs; each row carries the "
        "six-rule program-lint verdict incl. the axis's explicit "
        "collective budget (draco_tpu/analysis)",
        named,
    )
    print(json.dumps({"all_ok": report["all_ok"]}))
    return 0 if report["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
