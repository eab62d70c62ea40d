"""One seed of a training cell, followed past the check's three steps: the
program through its production loop, then the plain reference (float32,
``highest``) and the twin (the reference at the configuration's ``products``)
from the same seeded weights over the same rows, the loss of each on every
step side by side.

What it answers: where a run stops training after step 3 (a loss that climbs,
a counter that blows up), is that the program's doing or the
configuration's? A reference that leaves at the same step sides with the
program; one that trains on where the program does not is a fault
``correct`` cannot see. ``--lr`` lays another learning rate over the cell's,
for all three alike: the second witness.

  python3 benchmark/tools/follow_seed.py --workload lfm2.maj_vote_r3 \
      --seed 3141592999 --steps 60 --out chiprun_out/follow.json
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import manifest, runner  # noqa: E402


def program_rows(route_mod, fields, config, data, devices, seed, steps):
    """The seeded weights, the program's records of ``steps`` steps from
    them, and the route's job."""
    from benchmark.harness import seeded

    route = route_mod.Route(fields, data, devices)
    try:
        weights = seeded.make_weights(route.param_shapes(),
                                      config["weights"], seed,
                                      route.replicated())
        route.install_weights(weights)
        rows, _, _ = route.run_to(steps)
        stats = tuple(getattr(route.setup.model, "stat_names", ()))
        return weights, rows, route.job(), stats
    finally:
        route.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--lr", type=float, default=None,
                    help="laid over the cell's, for all three alike")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from draco_tpu.runtime import enable_compile_cache

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
    if args.lr is not None:
        fields["lr"] = args.lr
    data = runner.make_data(config, args.seed)
    weights, rows, job, stats = program_rows(
        route_mod, fields, config, data, devices, args.seed, args.steps)
    reference = runner.reference_of(config)
    rjob = reference.make_job(config, job)
    followed = reference.follow(rjob, weights, data, args.steps).losses
    twin = reference.follow(rjob, weights, data, args.steps,
                            precision=config["products"]).losses
    out = {"cell": cell["name"], "seed": args.seed, "lr": job["lr"],
           "steps": []}
    print(f"follow: lr={job['lr']} seed={args.seed}", flush=True)
    for i, rec in enumerate(rows):
        line = {"step": i + 1, "program": rec["loss"],
                "reference": followed[i], "twin": twin[i]}
        line.update({k: rec[k] for k in stats if k in rec})
        out["steps"].append(line)
        print("follow: " + " ".join(f"{k}={v:.6g}" for k, v in line.items()),
              flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
