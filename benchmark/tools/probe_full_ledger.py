"""Chip probe: one traced run of a cell reporting EVERY per-layer reader the
manifest has (the old scope readers work on any program), for PERF.md's
device ledger of a cell that reports only its own metrics."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import json
    import time
    T0 = time.time()
    from benchmark.harness import manifest, runner
    from draco_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    m = manifest.load_manifest()
    cell = manifest.cell_of(m, sys.argv[1])
    out = runner.run_cell(cell, manifest.config_of(m, cell), manifest.traffic_of(cell),
                          manifest.limits_of(cell), m["per_layer"], int(sys.argv[2]), 30.0, True, T0)
    print(json.dumps({"metrics": out["metrics"], "device": out["device"], "correct": out["correct"],
                      "device_ops": out["breakdown"]["device_ops"]}))


if __name__ == "__main__":
    main()
