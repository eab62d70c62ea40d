#!/usr/bin/env python
"""Prove the Pallas coded-ops kernels on real TPU hardware.

For each of the three fused kernels in draco_tpu/ops/coded.py
(complex_matmul / complex_project / complex_recombine — the O(n·d) work of a
cyclic encode/decode step, reference src/c_coding.cpp:15-84 re-homed to the
MXU):

  1. numerical parity vs the plain-jnp path on the same device,
  2. wall-clock microbench fused vs unfused at ResNet-18 gradient size
     (d ≈ 11.2M) and a smaller LeNet-ish size,
  3. optional TILE_D sweep (--sweep) to check the tile choice.

Writes one JSON report (default baselines_out/tpu_kernels.json) and prints it.
CPU fallback (--cpu-mesh) runs the same protocol in Pallas interpret mode so
the harness itself stays testable anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _loop_time(step, carry, consts=(), reps=20):
    """Chained in-jit per-iteration timing — see
    tools/_timing.timeit_chained for the protocol and its
    feedback-discipline requirements (non-linear full-output feedback,
    operands via consts, adaptive trip count)."""
    from tools._timing import timeit_chained

    return timeit_chained(step, carry, consts, reps=reps)


def check_kernels(d, n=8, interpret=False, reps=10):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.ops import coded

    r = np.random.RandomState(0)
    w_re = jnp.asarray(r.randn(n, n).astype(np.float32))
    w_im = jnp.asarray(r.randn(n, n).astype(np.float32))
    g = jnp.asarray(r.randn(n, d).astype(np.float32))
    # distinct imaginary-part matrix: passing the SAME array for re and im
    # lets XLA CSE the duplicate read in the transparent jnp path (one HBM
    # pass instead of two) while the opaque Pallas kernel still streams both
    # block inputs — which would bias the comparison
    g2 = jnp.asarray(r.randn(n, d).astype(np.float32))
    f = jnp.asarray(r.randn(d).astype(np.float32))
    v_re = jnp.asarray(r.randn(n).astype(np.float32))
    v_im = jnp.asarray(r.randn(n).astype(np.float32))
    jax.block_until_ready((w_re, w_im, g, g2, f, v_re, v_im))

    fused = dict(force=True, interpret=interpret) if interpret else dict(force=True)
    out = {"d": d, "n": n, "interpret": interpret, "kernels": {}}

    def bench_pair(fused_step, unfused_step, carry, consts):
        t_f = _loop_time(fused_step, carry, consts, reps=reps)
        t_u = _loop_time(unfused_step, carry, consts, reps=reps)
        return t_f, t_u

    # ---- complex_matmul (encode) ----
    a_re, a_im = coded.complex_matmul(w_re, w_im, g, **fused)
    b_re, b_im = coded.complex_matmul(w_re, w_im, g, force=False)
    err = max(
        float(jnp.max(jnp.abs(a_re - b_re))),
        float(jnp.max(jnp.abs(a_im - b_im))),
    )
    scale = float(jnp.max(jnp.abs(b_re))) or 1.0

    # Feedback discipline (timing.timeit_chained): carry the full output or
    # feed back a NON-LINEAR reduction of every output. Slice feedbacks get
    # the op partially dead-code-eliminated; plain sums of these *linear*
    # ops get reassociated and hoisted (sum(R@f) == colsum(R)·f — observed
    # as 0.0 ms unfused readings). Squared sums force the full computation
    # each iteration on the transparent XLA path, matching what the opaque
    # Pallas call is already forced to do.
    def _mm_step(kw):
        def step(gc, wr, wi):
            o_re, o_im = coded.complex_matmul(wr, wi, gc, **kw)
            return o_re + 1e-30 * o_im  # full outputs feed the next iter
        return step

    t_f, t_u = bench_pair(_mm_step(fused), _mm_step(dict(force=False)),
                          g, (w_re, w_im))
    out["kernels"]["complex_matmul"] = {
        "max_abs_err": err, "rel_err": err / scale,
        "fused_ms": round(t_f * 1e3, 4), "unfused_ms": round(t_u * 1e3, 4),
        "speedup": round(t_u / t_f, 3) if t_f > 0 else None,
    }

    # ---- complex_project (decode in) ----
    p_re, p_im = coded.complex_project(g, g2, f, **fused)
    q_re, q_im = coded.complex_project(g, g2, f, force=False)
    err = max(
        float(jnp.max(jnp.abs(p_re - q_re))),
        float(jnp.max(jnp.abs(p_im - q_im))),
    )
    scale = float(jnp.max(jnp.abs(q_re))) or 1.0

    def _pj_step(kw):
        def step(fv, g, g2):
            e_re, e_im = coded.complex_project(g, g2, fv, **kw)
            return fv + 1e-30 * (jnp.sum(e_re**2) + jnp.sum(e_im**2))
        return step

    t_f, t_u = bench_pair(_pj_step(fused), _pj_step(dict(force=False)),
                          f, (g, g2))
    out["kernels"]["complex_project"] = {
        "max_abs_err": err, "rel_err": err / scale,
        "fused_ms": round(t_f * 1e3, 4), "unfused_ms": round(t_u * 1e3, 4),
        "speedup": round(t_u / t_f, 3) if t_f > 0 else None,
    }

    # ---- complex_recombine (decode out) ----
    c = coded.complex_recombine(v_re, v_im, g, g2, **fused)
    e = coded.complex_recombine(v_re, v_im, g, g2, force=False)
    err = float(jnp.max(jnp.abs(c - e)))
    scale = float(jnp.max(jnp.abs(e))) or 1.0

    def _rc_step(kw):
        def step(cv, g, g2):
            vr, vi = cv
            s = jnp.sum(coded.complex_recombine(vr, vi, g, g2, **kw) ** 2)
            return (vr + 1e-30 * s, vi - 1e-30 * s)
        return step

    t_f, t_u = bench_pair(_rc_step(fused), _rc_step(dict(force=False)),
                          (v_re, v_im), (g, g2))
    out["kernels"]["complex_recombine"] = {
        "max_abs_err": err, "rel_err": err / scale,
        "fused_ms": round(t_f * 1e3, 4), "unfused_ms": round(t_u * 1e3, 4),
        "speedup": round(t_u / t_f, 3) if t_f > 0 else None,
    }
    return out


def sweep_tile(d, n=8, interpret=False, tiles=(1024, 2048, 4096, 8192, 16384)):
    import numpy as np
    import jax.numpy as jnp

    from draco_tpu.ops import coded

    r = np.random.RandomState(0)
    g = jnp.asarray(r.randn(n, d).astype(np.float32))
    g2 = jnp.asarray(r.randn(n, d).astype(np.float32))
    f = jnp.asarray(r.randn(d).astype(np.float32))
    rows = []
    orig = coded.TILE_D
    kw = dict(force=True, interpret=interpret) if interpret else dict(force=True)
    def step(fv, g, g2):
        e_re, e_im = coded.complex_project(g, g2, fv, **kw)
        return fv + 1e-30 * (jnp.sum(e_re**2) + jnp.sum(e_im**2))

    try:
        for tile in tiles:
            coded.TILE_D = tile
            # new tile -> new trace (jit caches key on static shapes only, so
            # clear to force re-trace with the module-level tile)
            coded._project_pallas.clear_cache()
            coded._matmul_pallas.clear_cache()
            try:
                t = _loop_time(step, f, (g, g2), reps=10)
                rows.append({"tile_d": tile, "project_ms": round(t * 1e3, 4)})
            except Exception as exc:  # a tile can fail compile (vmem limits)
                rows.append({"tile_d": tile, "error": repr(exc)[:200]})
    finally:
        coded.TILE_D = orig
        coded._project_pallas.clear_cache()
        coded._matmul_pallas.clear_cache()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="baselines_out/tpu_kernels.json")
    ap.add_argument("--cpu-mesh", type=int, default=0,
                    help="run in Pallas interpret mode on a CPU mesh")
    ap.add_argument("--sweep", action="store_true", help="TILE_D sweep")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--small-d", type=int, default=62006)   # LeNet-ish
    ap.add_argument("--large-d", type=int, default=11173962)  # ResNet-18
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)  # shared bootstrap: compile cache (+ cpu mesh)

    interpret = bool(args.cpu_mesh)

    import jax

    dev = jax.devices()[0]
    from draco_tpu.ops import coded

    report = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "pallas_supported": coded.use_pallas(),
        "pallas_interpret": interpret,
        "sizes": [],
    }
    small_d = args.small_d if not interpret else min(args.small_d, 20000)
    large_d = args.large_d if not interpret else min(args.large_d, 100000)
    for d in (small_d, large_d):
        report["sizes"].append(check_kernels(d, interpret=interpret, reps=args.reps))
    if args.sweep:
        report["tile_sweep_d"] = large_d
        report["tile_sweep"] = sweep_tile(large_d, interpret=interpret)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    # parity gate: fused and unfused must agree to float32 accumulation noise
    worst = max(
        k["rel_err"] for s in report["sizes"] for k in s["kernels"].values()
    )
    return 0 if worst < 1e-4 else 1


if __name__ == "__main__":
    raise SystemExit(main())
