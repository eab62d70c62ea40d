"""One run of one cell: set-up, the window, the trace, the check, the line.

``main`` is the command. ``run_cell`` takes the cell's files as arguments so
that the tests can hand it a small cell and a CPU; the command never does.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import statistics
import sys
import time

from benchmark.harness import check, manifest, stats

CHECK_STEPS = 3  # the reference follows these
WARM_STEPS = 6  # then the rate is read from these
TRACED_STEPS = 16  # the profiler's window, after the timed one


def _fail(msg: str, code: int = 1):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        _fail(f"no accelerator: JAX found platform "
              f"{devices[0].platform!r}; the benchmark measures on a TPU "
              f"or not at all")
    if len(devices) < chips:
        _fail(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def _step_seconds(records) -> list:
    return [r["t_fetch"] + r["t_comp"] for r in records]


def _memory_peak(devices) -> dict:
    reserved, in_use = 0, 0
    for d in devices:
        ms = d.memory_stats() or {}
        reserved = max(reserved, int(ms.get("peak_bytes_reserved", 0)
                                     or ms.get("peak_bytes_in_use", 0)))
        in_use = max(in_use, int(ms.get("peak_bytes_in_use", 0)))
    return {"reserved": reserved, "in_use": in_use}


def reference_of(config: dict):
    """The plain reference's module, named by the configuration."""
    return importlib.import_module(
        f"benchmark.reference.{config['reference']['module']}")


def make_data(config: dict, seed: int):
    """The run's seeded inputs, by the generator the configuration names."""
    kind = importlib.import_module(
        f"benchmark.data.{config['data']['kind']}")
    return kind.make(config["data"], seed)


def drive_first_steps(route, config: dict, seed: int) -> dict:
    """Seeded weights into the route's program, then its first CHECK_STEPS
    steps through the production loop: what ``correct`` compares on the
    program's side (``rows`` are those steps' records; ``grad`` is the first
    gradient as the optimizer got it, copied, because the next step
    overwrites the optimizer's buffers)."""
    import jax

    from benchmark.harness import seeded, trees

    weights = seeded.make_weights(route.param_shapes(), config["weights"],
                                  seed, route.replicated())
    route.install_weights(weights)
    first, _, _ = route.run_to(1)
    grad = jax.jit(lambda t: [x + 0 for x in t])(route.first_gradient())
    rest, _, _ = route.run_to(CHECK_STEPS)
    return {"weights": weights, "rows": first + rest, "grad": grad,
            "grad_norms": trees.leaf_norms(grad),
            "delta_norms": trees.leaf_norms(route.params(), weights)}


def run_cell(cell: dict, config: dict, traffic: dict, limits: dict,
             metrics: list, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             scratch: str = "") -> dict:
    """Returns the result object of the last line. ``metrics``: the
    manifest's entries this run reports (the cell's ``end_to_end`` ones, or
    with ``trace`` its ``per_layer`` ones). Raises SystemExit where the
    contract wants a non-zero exit and no result."""
    from benchmark.harness import trees

    marks = [("process", t_process)]

    def mark(name):
        marks.append((name, time.time()))

    devices = _devices(cell["chips"], require_tpu)
    mark("jax_and_devices")
    kind = devices[0].device_kind
    if require_tpu:
        from benchmark.harness.peaks import peaks_of
        peaks = peaks_of(kind)
    else:
        peaks = None

    scratch = scratch or os.path.join(manifest.ROOT, ".bench_out",
                                      cell["name"])
    trace_dir = os.path.join(scratch, "host_trace") if trace else ""
    profile_dir = os.path.join(scratch, "profile")

    data = make_data(config, seed)
    mark("data")
    fields = dict(config["train_config"], **traffic["train_config"])
    route_mod = importlib.import_module(
        f"benchmark.routes.{traffic['route']}")
    route = route_mod.Route(fields, data, devices, trace_dir)
    try:
        mark("build_program")
        # the first steps, through the window's own call and feed
        head = drive_first_steps(route, config, seed)
        mark("first_steps")

        warm, _, _ = route.run_to(CHECK_STEPS + WARM_STEPS)
        est = statistics.median(_step_seconds(warm))
        n_steps = max(int(math.ceil(seconds / est)), 8)
        compiles_before = route.compiles()
        mark("warm_steps")
        setup_s = marks[-1][1] - t_process
        print("setup: " + " ".join(
            f"{name}={b - a:.2f}s" for (_, a), (name, b)
            in zip(marks, marks[1:])), flush=True)

        # ---- the window ----
        start = CHECK_STEPS + WARM_STEPS
        window, t0, t1 = route.run_to(start + n_steps)
        window_s = t1 - t0
        compiles_in_window = route.compiles() - compiles_before
        memory = _memory_peak(devices)

        traced = None
        if trace:
            from benchmark.harness import xplane
            traced = xplane.capture(
                profile_dir,
                lambda: route.run_to(start + n_steps + TRACED_STEPS),
                xplane.scope_map_from_hlo(route.step_hlo()))
        spans = route.host_spans()
        job = route.job()
        examples_per_step = route.examples_per_step
        adversaries = route.adversaries_per_step
        every = route.records.rows
    finally:
        route.close()
    del route

    # ---- the check: the plain reference, outside set-up and the window ----
    t_ref = time.perf_counter()
    reference = reference_of(config)
    rjob = reference.make_job(config, job)
    weights = head["weights"]
    followed = reference.follow(rjob, weights, data, steps=CHECK_STEPS)
    # the twin: the same plain job at the precision the configuration states
    # for its products; the program's first gradient has to be THIS one
    twin = reference.follow(rjob, weights, data, steps=1,
                            precision=config["products"])
    reference_s = time.perf_counter() - t_ref
    observed = {
        "losses": [r["loss"] for r in head["rows"]],
        "grad_norms": head["grad_norms"],
        "delta_norms": head["delta_norms"],
        "grad_diff": check.noise_units(
            trees.rel_diff(head["grad"], twin.grad),
            trees.rel_diff(twin.grad, followed.grad)),
        "unlocated_steps": check.unlocated_steps(every, adversaries),
        "nonfinite_steps": sum(1 for r in every
                               if not math.isfinite(r["loss"])),
    }
    rows = check.compare(observed, followed, limits)
    for name, value, limit, ok in rows:
        print(f"check {name}: value={value:.6g} limit={limit:.6g} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
    correct = all(ok for *_, ok in rows)

    step_s = _step_seconds(window)
    slow = max(range(len(step_s)), key=step_s.__getitem__)
    print(f"window: steps={len(window)} seconds={window_s:.4f} "
          f"step_samples={len(step_s)} "
          f"fastest_step_s={min(step_s):.5f} "
          f"slowest_step_s={step_s[slow]:.5f} (sample {slow}: "
          f"t_fetch={window[slow]['t_fetch']:.5f} "
          f"t_comp={window[slow]['t_comp']:.5f}) "
          f"compiles_in_window={compiles_in_window} "
          f"est_step_s={est:.5f} reference_s={reference_s:.2f} "
          f"peak_bytes_in_use={memory['in_use']} "
          f"peak_bytes_reserved={memory['reserved']}", flush=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": memory["reserved"]}
    result = {"correct": bool(correct), "attempted": len(every),
              "failed": observed["unlocated_steps"]
              + observed["nonfinite_steps"]}
    if not trace:
        values = {
            "examples_per_s": stats.examples_per_s(
                len(window), examples_per_step, window_s),
            "step_ms_p50": stats.step_ms(step_s, 50),
            "step_ms_p95": stats.step_ms(step_s, 95),
            "peak_hbm_gb": memory["reserved"] / 1e9,
            "setup_s": setup_s,
        }
    else:
        ctx = {"records": window, "spans": spans, "window": (t0, t1),
               "trace": traced, "job": job, "peaks": peaks,
               "counters": {"compiles_in_window": compiles_in_window},
               "chips": cell["chips"]}
        values = {}
        for entry in metrics:
            spec = manifest.load_json(os.path.join(
                manifest.BENCH, "layer_metrics", entry["name"] + ".json"))
            reader = importlib.import_module(
                f"benchmark.reductions.{spec['reduction']}")
            values[entry["name"]] = reader.read(spec, ctx)
        device["busy_s"] = traced.busy_s
        device["window_s"] = traced.window_s
        result["breakdown"] = traced.breakdown(spans)
    result["metrics"] = {
        x["name"]: {"value": values[x["name"]], "unit": x["unit"]}
        for x in metrics if values.get(x["name"]) is not None}
    result["device"] = device
    return result


def main(argv=None, t_process=None) -> int:
    t_process = time.time() if t_process is None else t_process
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    m = manifest.load_manifest()
    errors = manifest.check_manifest(m)
    if errors:
        _fail("BENCHMARK.json: " + "; ".join(errors))
    cell = manifest.cell_of(m, args.workload)
    try:
        from draco_tpu.runtime import enable_compile_cache
    except ImportError as e:
        _fail(f"the program is not in this directory: {e}")
    _devices(cell["chips"], require_tpu=True)
    # the persistent compile cache: where JAX_COMPILATION_CACHE_DIR points,
    # else the fixed <checkout>/.jax_cache the program keeps
    enable_compile_cache()
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = manifest.metrics_for(m, cell["name"], kind)
    result = run_cell(
        cell, manifest.config_of(m, cell), manifest.traffic_of(cell),
        manifest.limits_of(cell), metrics, args.seed, args.seconds,
        bool(args.trace), t_process)
    if not args.trace and len(result["metrics"]) != len(metrics):
        _fail(f"metrics {sorted(result['metrics'])} != manifest "
              f"{sorted(x['name'] for x in metrics)}")
    print(json.dumps(result), flush=True)
    return 0
