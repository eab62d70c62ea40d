#!/usr/bin/env python
"""Offline TPU-lowering audit of the flash-attention kernel (round 5).

Every recorded hardware failure of the kernel (PERF_HISTORY.md's long-T
table: rows all `ValueError: The Pallas TPU lowering currently requires that
the last two dimensions of your block shape are divisible by 8 and 128 ...`)
was raised by the *Python-side Pallas TPU lowering*, not by the Mosaic
machine-code compiler. That stage runs during cross-platform export
(`jax.export.export(..., platforms=["tpu"])`) on a CPU-only host, so the
fixed kernel can be audited against it with zero chip time:

  python tools/tpu_attn_lowering_check.py \
      [--out baselines_out/tpu_attn_lowering.json]

The audit covers fwd and fwd+bwd, causal (training path) and the
non-causal `flash_attention_with_lse` pair the ring hops use
(parallel/ring_attention.py), f32 and bf16, T in {256, 1024, 2048, 4096},
plus a NEGATIVE control: a deliberately mis-tiled pallas_call that must
raise the same ValueError the chip produced pre-fix — proving the harness
exercises the real check rather than silently skipping it.

What this cannot prove: the Mosaic -> machine-code stage (scoped-vmem
budgets, codegen bugs) still needs the one real chip; that is the
`attn_t256`/`attn_full` rungs of tools/chip_jobs_r5.sh. This audit bounds
the remaining hardware risk to exactly that stage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str,
                    default="baselines_out/tpu_attn_lowering.json")
    ap.add_argument("--seq-lens", type=str, default="256,1024,2048,4096")
    args = ap.parse_args(argv)

    from tools._lowering_common import lint_row, run_rows, setup_cpu_host

    setup_cpu_host(1)
    import jax
    import jax.export
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from draco_tpu.analysis import (
        BF16_DTYPES, BuiltProgram, LintProgram, Manifest,
    )
    from draco_tpu.ops import flash_attention as fa

    # kernel-level rows: no state carry to donate and no cross-device
    # collectives, so those rules are manifest-skipped; constant-bloat,
    # dtype, and host-traffic still apply (a kernel baking a T-sized table
    # or upcasting to f64 should fail here, not on chip). The kernel's MXU
    # matmuls accumulate f32 in-op (dot_general preferred_element_type —
    # "the kernel accumulates f32 regardless", ops/flash_attention.py), so
    # dot_general joins the promotion whitelist here; the LM route
    # manifests stay convert-only.
    kernel_manifest = Manifest(require_donated=None, collectives=None,
                               allowed_dtypes=BF16_DTYPES,
                               bf16_promotion_whitelist=(
                                   "convert_element_type", "dot_general"))

    def kernel_program(name, fn, T, B=4, H=12, Dh=64, dtype=jnp.float32,
                       grad=False):
        def build():
            q = jnp.zeros((B, T, H, Dh), dtype)
            if grad:
                f = jax.jit(lambda q, k, v: jax.grad(
                    lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))
                )(q, k, v))
            else:
                f = jax.jit(fn)
            # Pallas tpu_custom_call cannot compile for the CPU backend —
            # skip the memory capture instead of paying a guaranteed failure
            return BuiltProgram(name, f, (q, q, q), None, kernel_manifest,
                                capture_memory=False)

        return LintProgram(name=name, build=build, route="attn_kernel",
                           fast=False)

    fwd = lambda q, k, v: fa.flash_attention(q, k, v, force=True)  # noqa: E731
    ring = lambda q, k, v: fa.flash_attention_with_lse(  # noqa: E731
        q, k, v, causal=False, force=True)[0]

    named = []
    for t in [int(x) for x in args.seq_lens.split(",")]:
        for label, fn, kw in [
            ("causal_fwd_f32", fwd, {}),
            ("causal_fwdbwd_f32", fwd, {"grad": True}),
            ("causal_fwd_bf16", fwd, {"dtype": jnp.bfloat16}),
            ("ring_noncausal_fwdbwd_f32", ring, {"grad": True}),
        ]:
            p = kernel_program(f"T{t}_{label}", fn, t, **kw)
            named.append((p.name, (
                lambda p=p, t=t, label=label:
                    lint_row(p, extra_row={"seq_len": t, "variant": label}))))

    # negative control: this MUST fail with the historical ValueError
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((4, 12), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 12), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 48), jnp.float32),
        )(x)

    x = jnp.zeros((16, 48), jnp.float32)
    try:
        jax.export.export(jax.jit(bad), platforms=["tpu"])(x)
        control = {"raised": False, "matches_historical": False}
    except Exception as e:  # record ANY failure type: a non-ValueError means
        # the lowering check moved/changed and the control must fail via the
        # matches_historical gate below, with the report still written
        control = {"raised": True,
                   "type": type(e).__name__,
                   "error_head": str(e)[:160],
                   "matches_historical": "Pallas TPU lowering" in str(e)}

    report = run_rows(
        args.out,
        "jax.export cross-platform lowering, platforms=['tpu'], CPU host — "
        "exercises the Pallas TPU lowering stage that produced every "
        "pre-fix hardware failure; each row carries the program-lint "
        "verdict (draco_tpu/analysis; donation/collectives manifest-skipped "
        "for kernel-level programs)",
        named,
        extra={"negative_control_bad_tiling": control},
    )
    print(json.dumps({"all_ok": report["all_ok"],
                      "negative_control_ok":
                          control.get("matches_historical", False)}))
    return 0 if (report["all_ok"]
                 and control.get("matches_historical")) else 1


if __name__ == "__main__":
    sys.exit(main())
