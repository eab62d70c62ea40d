#!/usr/bin/env python
"""Flash-attention kernel on real hardware: parity + micro-bench vs dense.

For each T in --seq-lens: numerical parity of the Pallas kernel against the
dense streaming-softmax oracle (fwd and input grads), then chained-loop
timing (utils/timing.py discipline: non-linear full-output feedback, big
operands via consts) of forward and forward+backward for both paths.
Writes --out (default baselines_out/tpu_attn.json).

The expected shape of the result: dense materialises (T, T) scores per
head, so its HBM traffic grows ~T² while flash stays ~T·Dh — the kernel's
advantage compounds with sequence length, and beyond some T the dense path
simply OOMs (recorded as {"dense": "oom"}).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_one(t, b, h, dh, reps, interpret=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.ops.flash_attention import flash_attention
    from draco_tpu.parallel.ring_attention import dense_attention
    from draco_tpu.utils.timing import timeit_chained

    r = np.random.RandomState(0)
    shape = (b, t, h, dh)
    q = jnp.asarray(r.normal(size=shape).astype(np.float32))
    k = jnp.asarray(r.normal(size=shape).astype(np.float32))
    v = jnp.asarray(r.normal(size=shape).astype(np.float32))

    flash = lambda q, k, v: flash_attention(q, k, v, force=True,
                                            interpret=interpret)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)

    rec = {"seq_len": t, "batch": b, "heads": h, "head_dim": dh}

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    def fwd_step(attn):
        def step(qc, k, v):
            o = attn(qc, k, v)
            return qc + 1e-30 * jnp.sum(o * o, axis=None, keepdims=False)
        return step

    def fb_step(attn):
        g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v))),
                     argnums=0)

        def step(qc, k, v):
            return qc + 1e-30 * g(qc, k, v) ** 2
        return step

    # flash numbers first — they must survive a dense OOM at long T (the
    # regime the kernel exists for)
    o_f = jax.jit(flash)(q, k, v)
    g_f = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    rec["flash_fwd_ms"] = round(
        timeit_chained(fwd_step(flash), q, (k, v), reps=reps) * 1e3, 3)
    rec["flash_fwdbwd_ms"] = round(
        timeit_chained(fb_step(flash), q, (k, v), reps=reps) * 1e3, 3)

    try:
        o_d = jax.jit(dense)(q, k, v)
        rec["fwd_max_abs_err"] = float(jnp.max(jnp.abs(o_f - o_d)))
        g_d = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
        rec["grad_max_abs_err"] = float(
            max(jnp.max(jnp.abs(a - b)) for a, b in zip(g_f, g_d))
        )
        rec["dense_fwd_ms"] = round(
            timeit_chained(fwd_step(dense), q, (k, v), reps=reps) * 1e3, 3)
        rec["dense_fwdbwd_ms"] = round(
            timeit_chained(fb_step(dense), q, (k, v), reps=reps) * 1e3, 3)
        if rec["flash_fwd_ms"] > 0:
            rec["fwd_speedup"] = round(
                rec["dense_fwd_ms"] / rec["flash_fwd_ms"], 3)
        if rec["flash_fwdbwd_ms"] > 0:
            rec["fwdbwd_speedup"] = round(
                rec["dense_fwdbwd_ms"] / rec["flash_fwdbwd_ms"], 3)
    except Exception as e:  # keep the flash row either way
        msg = f"{type(e).__name__}: {e}"
        is_oom = ("RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()
                  or "OOM" in msg)
        rec["dense"] = "oom" if is_oom else "failed"
        rec["dense_error"] = msg[:2500]

    # optional third column: jax's bundled reference Pallas flash op (same
    # blockwise algorithm, upstream-tuned) — an external yardstick for the
    # in-repo kernel. Skipped silently where the bundled op can't run
    # (non-TPU backends, interpret smoke).
    if not interpret:
        try:
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as jax_flash,
            )

            # upstream op wants (B,H,T,D) and defaults sm_scale=1.0 — feed
            # its native layout (pre-transposed OUTSIDE the timed step, so
            # the yardstick isn't padded with layout copies) and the same
            # 1/sqrt(dh) temperature the in-repo kernel applies
            scale = 1.0 / (dh ** 0.5)
            qh, kh, vh = (jnp.moveaxis(x, 2, 1) for x in (q, k, v))

            def ref(q, k, v):
                return jax_flash(q, k, v, causal=True, sm_scale=scale)

            o_r = jnp.moveaxis(jax.jit(ref)(qh, kh, vh), 1, 2)
            rec["jaxref_fwd_max_abs_err"] = float(jnp.max(jnp.abs(o_f - o_r)))
            rec["jaxref_fwd_ms"] = round(
                timeit_chained(fwd_step(ref), qh, (kh, vh), reps=reps) * 1e3,
                3)
            rec["jaxref_fwdbwd_ms"] = round(
                timeit_chained(fb_step(ref), qh, (kh, vh), reps=reps) * 1e3,
                3)
        except Exception as e:
            rec["jaxref_error"] = f"{type(e).__name__}: {e}"[:2500]
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="baselines_out/tpu_attn.json")
    # T=256 first: the cheapest hardware compile of the kernel — separates
    # "Mosaic rejects the kernel at all" from long-T-specific failures
    ap.add_argument("--seq-lens", type=str, default="256,1024,2048,4096")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cpu-interpret", action="store_true",
                    help="smoke: run tiny shapes in interpret mode on CPU")
    args = ap.parse_args(argv)

    from draco_tpu.cli import maybe_force_cpu_mesh

    maybe_force_cpu_mesh(args)  # shared bootstrap: compile cache (+ cpu mesh)

    if args.cpu_interpret:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax

    dev = jax.devices()[0]
    report = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "rows": [],
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for t in [int(x) for x in args.seq_lens.split(",")]:
        print(f"[tpu_attn] T={t} ...", file=sys.stderr, flush=True)
        try:
            rec = check_one(t, args.batch, args.heads, args.head_dim,
                            args.reps, interpret=args.cpu_interpret)
        except Exception as e:
            # keep enough of a Mosaic/compile error to act on it from the
            # record alone (300 chars cut the tiling detail in r3)
            rec = {"seq_len": t, "error": f"{type(e).__name__}: {e}"[:2500]}
        print(f"[tpu_attn] {json.dumps(rec)}", file=sys.stderr, flush=True)
        report["rows"].append(rec)
        # rewrite after every row: a run cut short keeps finished rows
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
