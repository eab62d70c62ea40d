#!/usr/bin/env python
"""Microbench of the fused decode kernels against the historical XLA
decode across (n, s, d) rungs — the committed evidence behind ISSUE 12's
"decode got faster" claim, and the perf_watch gate that keeps it true.

Each rung times ONE decode call (the whole coded decode: projection →
locator chain → recombination for cyclic, weight solve → masked combine →
residual-vs-bound health for approx) under two ``decode_impl`` lowerings:

  xla     the historical path, bit-for-bit what the K∈{1,4} bitwise
          suites pin
  pallas  the fused path — the hand-tiled Pallas kernels on a TPU
          backend; on other backends their reference lowering (the same
          fused algorithm through XLA, ops/decode_kernels
          .resolve_decode_impl), which is what this container measures
          (recorded per rung as ``pallas_lowering``)

Methodology: both impls jitted and warmed, then timed in INTERLEAVED
rounds (impl A chunk, impl B chunk, repeat) so host-load drift hits both
equally; per impl the minimum round mean is recorded (the same
minimum-of-chunks discipline as tools/host_loop_overhead.py). Outputs are
block_until_ready'd per chunk.

Gating (tools/perf_watch.py): every rung's ``pallas_over_xla`` ratio rides
at the time tolerance, and rungs marked ``gate: true`` additionally pin
``kernel_not_slower`` (ratio ≤ 1) at tolerance 0 — the fused path
regressing below the XLA path at a committed rung fails the round
(flipped-row tests in tests/test_cli_tools.py prove the gate live). Two
cyclic rung classes are deliberately ungated on CPU fallbacks (PERF_HISTORY.md
§14): the GLOBAL rungs — two near-memory-floor (n, d) matvec passes with
the locator at ~3% of them, nothing for the CPU fallback to win — and the
n=32 LAYER rung, where the per-segment matvec cost dominates both impls
identically (measured ratio ≈ 1.01) and the locator fusion's win
disappears into it. The n=8 layer rung (the device-profile cell shape)
and both approx rungs are where the fused path must and does win on this
backend too; the kernels' TPU-side win (HBM round-trips removed) is what
the ungated rungs exist to measure once a chip round runs this tool.

  python tools/decode_kernel_bench.py [--out baselines_out/decode_kernel_bench.json]
      [--reps 6] [--inner 4] [--rungs cyclic_layer_n8, ...]
  python tools/decode_kernel_bench.py --check   # jax-free artifact check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARTIFACT_REL = os.path.join("baselines_out", "decode_kernel_bench.json")

# name -> rung spec. ``gate``: pin kernel_not_slower (ratio <= 1) at tol 0
# in perf_watch — only set where the fused path wins on EVERY backend
# (see module docstring). n=32 s=3 is the wire-study noise-amplification
# shape ROADMAP item 3 tracks; d≈0.4M is the linter-CI LM gradient size.
RUNGS = {
    "cyclic_global_n8": dict(family="cyclic", n=8, s=1, d=400_000,
                             granularity="global", layers=0, gate=False),
    "cyclic_global_n32s3": dict(family="cyclic", n=32, s=3, d=400_000,
                                granularity="global", layers=0, gate=False),
    "cyclic_layer_n8": dict(family="cyclic", n=8, s=1, d=400_000,
                            granularity="layer", layers=10, gate=True),
    "cyclic_layer_n32s3": dict(family="cyclic", n=32, s=3, d=400_000,
                               granularity="layer", layers=10, gate=False),
    "approx_n8": dict(family="approx", n=8, r=1.5, d=400_000, gate=True),
    "approx_n32": dict(family="approx", n=32, r=1.5, d=400_000, gate=True),
}


def _build_cyclic(spec):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.attacks import inject_cyclic
    from draco_tpu.coding import cyclic as cyclic_mod

    n, s, d = spec["n"], spec["s"], spec["d"]
    code = cyclic_mod.build_cyclic_code(n, s)
    rng = np.random.RandomState(0)
    bg = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic_mod.encode_shared(code, jnp.asarray(bg))
    adv = np.zeros(n, bool)
    adv[rng.choice(n, size=s, replace=False)] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv),
                                   "rev_grad")
    rf = jnp.asarray(rng.normal(loc=1.0, size=d).astype(np.float32))
    if spec["granularity"] == "layer":
        offs = tuple(int(x) for x in
                     np.linspace(0, d, spec["layers"] + 1).astype(int))

        def fn(impl):
            return jax.jit(lambda a, b: cyclic_mod.decode_layers(
                code, a, b, rf, offs, with_health=True, impl=impl))
    else:
        def fn(impl):
            return jax.jit(lambda a, b: cyclic_mod.decode(
                code, a, b, rf, with_health=True, impl=impl))

    return fn, (enc_re, enc_im)


def _build_approx(spec):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import approx as approx_mod

    n, d = spec["n"], spec["d"]
    code = approx_mod.build_approx_code(n, spec["r"])
    rng = np.random.RandomState(0)
    bg = jnp.asarray(rng.randn(n, d).astype(np.float32))
    rows = approx_mod.encode_shared(code, bg)
    pres = jnp.asarray(np.ones(n, bool))

    def fn(impl):
        return jax.jit(lambda r, g: approx_mod.decode(
            code, r, present=pres, with_health=True, batch_grads=g,
            impl=impl))

    return fn, (rows, bg)


def _time_interleaved(fns, args, reps, inner):
    """Per-impl minimum round mean (ms) over interleaved rounds."""
    import jax

    for f in fns:  # compile + warm
        jax.block_until_ready(f(*args))
    mins = [float("inf")] * len(fns)
    for _ in range(reps):
        for i, f in enumerate(fns):
            t0 = time.perf_counter()
            for _ in range(inner):
                out = f(*args)
            jax.block_until_ready(out)
            mins[i] = min(mins[i],
                          (time.perf_counter() - t0) / inner * 1e3)
    return mins


def run(args) -> dict:
    from draco_tpu.ops.decode_kernels import resolve_decode_impl, use_pallas

    on_tpu = use_pallas()
    pallas_impl = resolve_decode_impl("pallas")
    rows = []
    names = ([r.strip() for r in args.rungs.split(",") if r.strip()]
             or list(RUNGS))
    unknown = [r for r in names if r not in RUNGS]
    if unknown:
        raise SystemExit(f"unknown rungs {unknown}; known: {list(RUNGS)}")
    for name in names:
        spec = RUNGS[name]
        build = _build_cyclic if spec["family"] == "cyclic" else _build_approx
        fn, data = build(spec)
        xla_ms, pallas_ms = _time_interleaved(
            [fn("xla"), fn(pallas_impl)], data, args.reps, args.inner)
        ratio = pallas_ms / xla_ms
        row = {"rung": name, **{k: v for k, v in spec.items()},
               "xla_ms": round(xla_ms, 3), "pallas_ms": round(pallas_ms, 3),
               "pallas_over_xla": round(ratio, 4),
               "pallas_lowering": "kernel" if on_tpu else "fused_xla"}
        if spec["gate"]:
            row["kernel_not_slower"] = bool(ratio <= 1.0)
        rows.append(row)
        print(f"decode_kernel_bench: {name}: xla {xla_ms:.2f} ms, "
              f"pallas({row['pallas_lowering']}) {pallas_ms:.2f} ms "
              f"(ratio {ratio:.3f})", flush=True)
    return {
        "schema": 1,
        "tool": "tools/decode_kernel_bench.py",
        "method": ("interleaved min-of-round-means over jitted whole-decode "
                   "calls, both impls warmed; pallas rows record which "
                   "lowering actually ran (kernel on TPU backends, the "
                   "fused reference through XLA elsewhere)"),
        "backend_pallas": on_tpu,
        "reps": args.reps, "inner": args.inner,
        "all_ok": all(r.get("kernel_not_slower", True) for r in rows),
        "rows": rows,
    }


def check_artifact(path, out=None) -> int:
    """jax-free self-check of the committed artifact: ratio arithmetic,
    gated rungs not slower, roll-up consistent. Exit 1 naming each
    violation (CI gate; tests/test_cli_tools.py drives a flipped row)."""
    out = out if out is not None else sys.stdout
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"decode_kernel_bench --check: cannot read {path}: {e}",
              file=out)
        return 1
    bad = []
    for row in data.get("rows", []):
        name = row.get("rung")
        xla, pal = row.get("xla_ms"), row.get("pallas_ms")
        ratio = row.get("pallas_over_xla")
        if not (isinstance(xla, (int, float)) and xla > 0
                and isinstance(pal, (int, float)) and pal > 0):
            bad.append(f"{name}: missing/non-positive timings")
            continue
        if not isinstance(ratio, (int, float)):
            bad.append(f"{name}: missing/non-numeric pallas_over_xla")
            continue
        if abs(ratio - pal / xla) > 0.01:
            bad.append(f"{name}: ratio {ratio} != pallas_ms/xla_ms "
                       f"{pal / xla:.4f}")
        if row.get("gate"):
            if "kernel_not_slower" not in row:
                bad.append(f"{name}: gated rung missing kernel_not_slower")
            elif bool(row["kernel_not_slower"]) != (ratio <= 1.0):
                bad.append(f"{name}: kernel_not_slower inconsistent with "
                           f"ratio {ratio}")
            elif not row["kernel_not_slower"]:
                bad.append(f"{name}: fused decode slower than XLA at a "
                           f"gated rung (ratio {ratio})")
    if not data.get("rows"):
        bad.append("no rows")
    if bool(data.get("all_ok")) != all(
            r.get("kernel_not_slower", True) for r in data.get("rows", [])):
        bad.append("all_ok inconsistent with rows")
    if bad:
        for b in bad:
            print(f"decode_kernel_bench FAIL: {b}", file=out)
        return 1
    print(f"decode_kernel_bench --check: {len(data['rows'])} rungs "
          f"consistent", file=out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=ARTIFACT_REL)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--inner", type=int, default=4)
    ap.add_argument("--rungs", default="",
                    help="comma-separated subset (default: all)")
    ap.add_argument("--check", action="store_true",
                    help="jax-free self-check of the committed artifact")
    ap.add_argument("--artifact", default="",
                    help=f"artifact path for --check (default {ARTIFACT_REL})")
    args = ap.parse_args(argv)
    if args.check:
        return check_artifact(args.artifact or args.out)
    payload = run(args)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print(f"decode_kernel_bench: {len(payload['rows'])} rungs -> {args.out}")
    return 0 if payload["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
