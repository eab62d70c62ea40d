"""Readings for a cell's limits (benchmark/limits/<cell>.json), taken on the
chip at the cell's own size, several seeds in one process:

  sound    the program as the configuration states
  control  the program with a lower-precision path of its own switched on
           (``--program``: TrainConfig fields laid over the cell's), where
           it has one
  lowered  the reference itself computed in ``control.reference_dtype``

each against the reference (float32, ``highest``) and, for ``grad_diff``,
against the twin (the reference at the configuration's ``products``).

For each seed and each of the three it prints the numbers ``correct``
compares. A limit goes above the sound runs' largest and below the controls'
smallest (PERF.md section 2). Training's readings need no measured window.

  python3 benchmark/tools/limits_study.py --workload resnet18.cyclic_s1 \
      --seeds 101,102,103 --program '{"compute_dtype": "bfloat16"}' \
      --out chiprun_out/limits_resnet18.cyclic_s1.json
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import check, manifest, runner  # noqa: E402

STEPS = runner.CHECK_STEPS


def program_readings(route_mod, fields, config, data, devices, seed):
    route = route_mod.Route(fields, data, devices)
    try:
        head = runner.drive_first_steps(route, config, seed)
        rows = head["rows"]
        observed = {
            "losses": [r["loss"] for r in rows],
            "grad_norms": head["grad_norms"],
            "delta_norms": head["delta_norms"], "grad": head["grad"],
            "unlocated_steps": check.unlocated_steps(
                rows, route.adversaries_per_step),
            "nonfinite_steps": sum(1 for r in rows
                                   if not math.isfinite(r["loss"])),
        }
        return observed, head["weights"], route.job()
    finally:
        route.close()


def gaps(observed, followed, twin) -> dict:
    from benchmark.harness import trees

    loose = {"loss_gap": math.inf, "grad_norm_gap": math.inf,
             "delta_norm_gap": math.inf, "grad_diff": math.inf}
    observed = dict(observed, grad_diff=check.noise_units(
        trees.rel_diff(observed.pop("grad"), twin.grad),
        trees.rel_diff(twin.grad, followed.grad)))
    return {name: value
            for name, value, _, _ in check.compare(observed, followed, loose)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--program", default="", help="JSON object of "
                    "TrainConfig fields: the program's own lower-precision "
                    "path, read as 'control'")
    ap.add_argument("--skip", default="", help="comma list of: lowered")
    args = ap.parse_args(argv)
    skip = set(filter(None, args.skip.split(",")))
    lower_fields = json.loads(args.program) if args.program else {}

    import jax
    from draco_tpu.runtime import enable_compile_cache

    from benchmark.harness import trees

    enable_compile_cache()
    m = manifest.load_manifest()
    cell = manifest.cell_of(m, args.workload)
    config = manifest.config_of(m, cell)
    traffic = manifest.traffic_of(cell)
    devices = jax.devices()[:cell["chips"]]
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    route_mod = importlib.import_module(
        f"benchmark.routes.{traffic['route']}")
    fields = dict(config["train_config"], **traffic["train_config"])
    control = config.get("control", {})
    reference = runner.reference_of(config)
    out = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        data = runner.make_data(config, seed)
        observed, weights, job = program_readings(route_mod, fields, config,
                                                  data, devices, seed)
        rjob = reference.make_job(config, job)
        followed = reference.follow(rjob, weights, data, STEPS)
        twin = reference.follow(rjob, weights, data, 1,
                                precision=config["products"])
        row = {"seed": seed, "sound": gaps(observed, followed, twin),
               "twin_vs_reference": trees.rel_diff(twin.grad,
                                                   followed.grad),
               "reference_losses": followed.losses}
        if lower_fields:
            lowered_prog, _, _ = program_readings(
                route_mod, dict(fields, **lower_fields), config, data,
                devices, seed)
            row["control"] = gaps(lowered_prog, followed, twin)
        if control.get("reference_dtype") and "lowered" not in skip:
            low = reference.follow(rjob, weights, data, STEPS,
                                   dtype=control["reference_dtype"])
            row["lowered"] = gaps(
                {"losses": low.losses, "grad_norms": low.grad_norms,
                 "delta_norms": low.delta_norms, "grad": low.grad,
                 "unlocated_steps": 0,
                 "nonfinite_steps": 0}, followed, twin)
        print(json.dumps(row), flush=True)
        out.append(row)
    for kind in ("sound", "control", "lowered"):
        have = [r[kind] for r in out if kind in r]
        if not have:
            continue
        for name in have[0]:
            vals = [h[name] for h in have]
            print(f"{kind:8s} {name:18s} min={min(vals):.4g} "
                  f"max={max(vals):.4g}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
