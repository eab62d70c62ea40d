"""Benchmark harness — JSON lines for the driver, on a TPU or not at all.

Headline metric (BASELINE.json north star): per-step wall-clock of the
flagship config — ResNet-18 / CIFAR-10 shapes, n=8 coded workers, cyclic code
s=1 under reverse-gradient attack — on the attached accelerator.

``vs_baseline``: the reference repo publishes no numbers (BASELINE.md), so the
paper's headline comparison is reported instead: speedup of the cyclic-decode
step over the geometric-median robust-aggregation step at identical model /
batch / adversary schedule (Draco's core claim — reference README.md:2,
baseline_master.py:271-276). Values > 1 mean decode beats geo-median. The
geo-median cost is linear in ``geomedian_iters``; 80 iterations is pinned to
hdmedians-level accuracy by tests/test_repetition_and_aggregation.py
(TestWeiszfeldIterationBudget), so the ratio is apples-to-apples.

Failure discipline: ONE process initialises JAX once. If the first device is
not a TPU the run prints a structured ``no_tpu`` record (no value) and exits
non-zero — there is no CPU measurement under any metric name. Three legs run
in order (cyclic simulate, geometric median, cyclic shared); a record is
printed after each, so the last line is the most complete result, and a leg
that raises fails the run with the exception it raised. Exit code 0 means all
three legs were measured on the chip. (ROADMAP S0 rewrites this file into a
matrix of cells; until then it is one cell, honestly.)

MFU: FLOPs per train step come from XLA's static cost analysis of the
compiled step (an analytic model of the whole program — fwd/bwd, encode,
gather, decode, update), divided by wall-clock and the chip's bf16 peak.

Flags: --steps N --warmup N --reps N --batch-size B --network NAME
       --num-workers N --wire-segments S --ignore-lint
"""

import argparse
import json
import os
import sys
import time

# bf16 matrix-unit peak per chip, keyed by the EXACT ``device_kind`` jax
# reports, with the source of each figure. MFU is reported against the bf16
# peak even for f32 runs (stated in the record). A device that is not in the
# table is an error, never a default: add its row with its source.
_PEAK_BF16 = {
    "TPU v5 lite": (197e12, 'Google Cloud documentation, "TPU v5e": '
                            "197 TFLOP/s bf16 per chip"),
}


def _peak_flops(device_kind: str) -> float:
    if device_kind not in _PEAK_BF16:
        raise KeyError(
            f"bench.py has no bf16 peak for device_kind {device_kind!r} "
            f"(known: {sorted(_PEAK_BF16)}); add it to _PEAK_BF16 with its "
            f"source")
    return _PEAK_BF16[device_kind][0]


def _emit(record):
    """Print one complete JSON record (one line). Later emissions supersede
    earlier ones for a reader of the tail; earlier ones survive a kill."""
    print(json.dumps(record), flush=True)


def _lint_violations():
    """Chip-window gate against the program-lint artifact
    (tools/program_lint.py → baselines_out/program_lint.json, path
    overridable via DRACO_PROGRAM_LINT_PATH for tests).

    Returns a list of "program: rule" strings for any CNN-family program —
    the family this bench times — whose artifact row reports a
    constant_bloat or host_traffic violation: the two defect classes that
    don't just skew a number but burn the chip budget itself (a 638 MB
    module that compiled for 27 minutes, PERF_HISTORY.md §4; a host hop that
    serializes every scanned chunk). Negative-control rows
    (deliberately defective) are skipped. A missing or unreadable artifact
    gates nothing — the lint runs in CI, not here; this is a last line of
    defense, not the enforcement point.
    """
    path = os.environ.get("DRACO_PROGRAM_LINT_PATH",
                          "baselines_out/program_lint.json")
    try:
        with open(path) as fh:
            report = json.load(fh)
    except Exception:
        return []
    bad = []
    for row in report.get("rows", []):
        if row.get("control"):
            continue
        if row.get("route") != "cnn":  # lint_program stamps every row
            continue
        hits = set(row.get("failed_rules", [])) & {"constant_bloat",
                                                   "host_traffic"}
        for rule in sorted(hits):
            bad.append(f"{row['name']}: {rule}")
    return bad


def _compiled_flops(compiled):
    """Analytic FLOPs from XLA's cost analysis of the *optimized* program
    (the unoptimized-HLO figure over-counts ops the compiler fuses away,
    which would inflate MFU)."""
    flops = float((compiled.cost_analysis() or {}).get("flops", 0.0))
    return flops if flops > 0 else None


def run(cfg_kwargs, ds, mesh, steps, warmup=1, reps=2, want_flops=False,
        info=None):
    """Per-step wall-clock of the jitted train step, plus the compile cost.

    Returns ``(dt_per_step_s, loss, flops, compile_s)``. The first-call
    compile is excluded from ms/step by construction (the
    ``.lower().compile()`` below runs before any timed execution) and is
    MEASURED and returned so the record carries ``extra.compile_ms`` —
    compile-time drift is a real regression class (a program that doubles
    its compile time eats the chip budget even when ms/step holds) and
    tools/perf_watch.py tracks it round-over-round.

    The ``steps`` training steps are folded into ONE jitted ``lax.scan`` over
    batches pre-staged in HBM — the production ``train_many`` program — so
    per-step Python dispatch is off the timed path; synchronisation and the
    round-trip subtraction are utils/timing.time_scanned_steps (its docstring
    says what changes with ROADMAP S0). The metric is the training step
    (fwd/bwd + encode + gather + decode/aggregate + update), not the host
    link; the input pipeline overlaps the step via the native prefetcher
    (draco_tpu/data/prefetch.py).
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from draco_tpu.config import TrainConfig
    from draco_tpu.runtime import WORKER_AXIS, put_global
    from draco_tpu.training.trainer import Trainer
    from draco_tpu.utils.timing import time_scanned_steps

    cfg = TrainConfig(**cfg_kwargs)
    tr = Trainer(cfg, mesh=mesh, dataset=ds, quiet=True)
    if info is not None:
        # logical wire ledger at the program's registered shapes
        # (obs/numerics.wire_ledger, ISSUE 10) — the record's
        # extra.wire_bytes series perf_watch tracks round-over-round
        from draco_tpu.obs import numerics as numerics_mod

        info["wire_ledger"] = numerics_mod.wire_ledger(cfg, tr.setup.dim)
    state = tr.state
    host_x, host_y = [], []
    for step in range(1, steps + 1):
        x, y = tr._host_batch(step)
        host_x.append(np.asarray(x))
        host_y.append(np.asarray(y))
    xs = put_global(np.stack(host_x), NamedSharding(mesh, P(None, WORKER_AXIS)))
    ys = put_global(np.stack(host_y), NamedSharding(mesh, P(None, WORKER_AXIS)))
    ms = put_global(
        np.stack([np.asarray(tr._adv_schedule[s]) for s in range(1, steps + 1)]),
        NamedSharding(mesh, P()),
    )
    loss_col = tr.setup.metric_names.index("loss")

    # The timed program IS the production chunked loop: train_many is the
    # same jitted scan Trainer._run_chunked dispatches with
    # cfg.steps_per_call = steps — bench numbers measure the path users run,
    # not a parallel harness that could drift from it.
    tc0 = time.perf_counter()
    compiled = tr.setup.train_many.lower(state, xs, ys, ms, None).compile()
    compile_s = time.perf_counter() - tc0
    # XLA cost analysis counts a scan body ONCE regardless of trip count
    # (verified on this jax: scan(L=5) and scan(L=10) report identical
    # flops), so the loop's flops figure already IS the per-step figure.
    flops = _compiled_flops(compiled) if want_flops else None

    dt, blocks = time_scanned_steps(
        compiled, state, (xs, ys, ms, None), steps=steps, warmup=warmup,
        reps=reps
    )
    loss = float(np.asarray(jax.device_get(blocks))[-1, loss_col])
    tr.close()
    return dt, loss, flops, compile_s


def measure(args, metric_name, dev):
    """Run the three legs on the chip, printing a progressively more
    complete record after each. Nothing is caught: a leg that raises ends
    the run with that exception and a non-zero exit."""
    from draco_tpu.data.datasets import load_dataset
    from draco_tpu.runtime import make_mesh

    import jax

    ds = load_dataset("Cifar10", data_dir="./data")
    mesh = make_mesh(args.num_workers)
    peak = _peak_flops(dev.device_kind)  # an unknown device fails HERE

    common = dict(
        network=args.network,
        dataset="Cifar10",
        batch_size=args.batch_size,
        lr=0.01,
        momentum=0.9,
        num_workers=args.num_workers,
        worker_fail=1,
        err_mode="rev_grad",
        max_steps=args.steps + 1,
        eval_freq=0,
        train_dir="",
        log_every=10**9,
        wire_segments=args.wire_segments,
    )
    base_extra = {
        "network": args.network,
        "geomedian_iters": 80,
        "num_workers": args.num_workers,
        "batch_size_per_worker": args.batch_size,
        "dataset": ds.name,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "compute_dtype": "float32",
        # the reference-parity simulate leg is the basis, full stop
        "vs_baseline_basis": "simulate_redundancy",
        # the timed program is the production train_many scan with all
        # steps fused into one device program (run() docstring)
        "steps_per_call": args.steps,
    }

    def record(value_ms, vs_baseline, extra):
        return {
            "metric": metric_name,
            "value": value_ms,
            "unit": "ms/step",
            "vs_baseline": vs_baseline,
            "extra": dict(base_extra, **extra),
        }

    # the contender: cyclic code, r=2s+1 redundant compute like the reference
    cyc_info = {}
    t_cyclic, loss_c, flops_c, compile_c = run(
        dict(common, approach="cyclic", redundancy="simulate"),
        ds, mesh, args.steps, args.warmup, args.reps, want_flops=True,
        info=cyc_info,
    )
    ledger = cyc_info["wire_ledger"]
    # logical codeword bytes per step (all workers, f32 wire) — the series
    # the narrow wire halves/quarters (ISSUE 10)
    base_extra["wire_bytes"] = ledger["bytes_per_step"]["f32"]
    base_extra["wire_bytes_per_worker"] = ledger["bytes_per_worker"]["f32"]
    base_extra["wire_dim"] = ledger["dim"]
    # streaming segmented wire (ISSUE 16): the segment count the timed
    # program decoded with and the ledger's per-segment PHYSICAL bytes —
    # tools/segment_study.py --check and the wire_study checker pin that
    # these sum to the per-step row
    seg = ledger.get("segments") or {}
    base_extra["wire_segments"] = seg.get("count", 1)
    base_extra["wire_segment_bytes_per_step"] = \
        seg.get("physical_bytes_per_step")
    cyc_extra = {
        "loss_cyclic": round(loss_c, 4),
        "flops_per_step": flops_c,
        "peak_bf16_flops": peak,
        "peak_bf16_source": _PEAK_BF16[dev.device_kind][1],
        "mfu_vs_bf16_peak": (round(flops_c / t_cyclic / peak, 4)
                             if flops_c else None),
        # first-call compile wall of the timed program, excluded from
        # ms/step by construction and recorded so perf_watch can track
        # compile-time drift round-over-round (PERF_HISTORY.md §8)
        "compile_ms": round(compile_c * 1000.0, 1),
    }
    value_ms = round(t_cyclic * 1000.0, 3)
    _emit(record(value_ms, None,
                 dict(cyc_extra, partial="geomedian leg pending")))

    # the baseline robust aggregator Draco positions against
    t_geomed, loss_g, _, compile_g = run(
        dict(common, approach="baseline", mode="geometric_median"),
        ds, mesh, args.steps, args.warmup, args.reps,
    )
    full_extra = dict(
        cyc_extra,
        geomedian_step_ms=round(t_geomed * 1000.0, 3),
        loss_geomedian=round(loss_g, 4),
        geomedian_compile_ms=round(compile_g * 1000.0, 1),
    )
    ratio_sim = round(t_geomed / t_cyclic, 4)
    _emit(record(value_ms, ratio_sim,
                 dict(full_extra, partial="shared leg pending")))

    # TPU-native fast path: identical decode semantics, each batch gradient
    # computed once (valid because SPMD adversaries are simulated, not
    # mutually-untrusting processes — config.py `redundancy`); reported
    # alongside the reference-parity number, never in its place
    t_shared, _, _, _ = run(
        dict(common, approach="cyclic", redundancy="shared"),
        ds, mesh, args.steps, args.warmup, args.reps,
    )
    _emit(record(value_ms, ratio_sim, dict(
        full_extra,
        shared_redundancy_step_ms=round(t_shared * 1000.0, 3),
        shared_vs_geomedian=round(t_geomed / t_shared, 4),
    )))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=1,
                   help="un-timed settle executions of the steps-scan")
    p.add_argument("--reps", type=int, default=2,
                   help="timed executions of the steps-scan")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--network", type=str, default="ResNet18")
    p.add_argument("--num-workers", type=int, default=8)
    p.add_argument("--wire-segments", type=int, default=1,
                   help="wire segmentation S for the timed programs "
                        "(ISSUE 16); the record carries "
                        "extra.wire_segments + per-segment ledger bytes")
    p.add_argument("--ignore-lint", action="store_true",
                   help="time the chip even when baselines_out/"
                        "program_lint.json reports a constant-bloat/"
                        "host-traffic violation for the timed programs")
    args = p.parse_args()

    metric_name = (
        f"{args.network.lower()}_cifar10_cyclic_s1_revgrad_step_wallclock"
    )

    def refuse(error, detail):
        _emit({"metric": metric_name, "value": None, "unit": "ms/step",
               "vs_baseline": None, "error": error, "detail": detail[:500]})
        return 1

    if not args.ignore_lint:
        violations = _lint_violations()
        if violations:
            # these defect classes burn the chip budget itself, which is
            # worth far more than one data point (--ignore-lint overrides)
            return refuse("program_lint_violation",
                          "refusing chip run; fix or rerun "
                          "tools/program_lint.py (or --ignore-lint): "
                          + "; ".join(violations))

    from draco_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]  # the one backend initialisation of this process
    if dev.platform != "tpu":
        return refuse("no_tpu",
                      f"bench.py measures on a TPU only; jax.devices()[0] is "
                      f"platform={dev.platform!r} kind={dev.device_kind!r}")
    measure(args, metric_name, dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
