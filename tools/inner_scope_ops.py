#!/usr/bin/env python
"""Chip probe: one traced benchmark run of a cell, then the instructions of
one scope, or of several (``draco_route``, ``draco_decode,draco_pack``, ...)
by device self time.

  python3 tools/inner_scope_ops.py kanana2.maj_vote_r3 <seed> draco_route [tag]

The cell's per-layer metrics say how long a scope takes; this says which
instructions inside it do. It drives ``benchmark.harness.runner.run_cell``
as ``benchmark/run.py --trace 1`` does, keeps the capture and the route's
innermost-scope map, and writes ``chiprun_out/inner_scope_ops_<tag>.json``
(every scope's total, the scope's instructions in ms per traced step with
their result shapes, the run's metrics, and the largest and the smallest
value any step's record held of each of the model's counters — the
``ledger:`` lines print medians —, how a lane's row is laid into a tiled
stack (``sp_step.RowLayout``: lines, closing zeros, joined leaves), every
step's loss as float hex) and the compiled step program's text beside it
(``step_hlo_<tag>.txt.gz``: what shapes the program holds).
Edits nothing of the benchmark; a TPU or exit 1, as the benchmark.
"""

from __future__ import annotations

import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    t_process = time.time()
    cell_name, seed, scopes = argv[0], int(argv[1]), argv[2].split(",")
    tag = argv[3] if len(argv) > 3 else scopes[0]
    from benchmark.harness import manifest, runner, xplane
    from benchmark.routes import token
    from draco_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    kept = {}
    capture, inner_scopes = xplane.capture, token.inner_scopes

    def keep_capture(*args, **kw):
        kept["trace"] = capture(*args, **kw)
        return kept["trace"]

    def keep_scopes(text):
        kept["hlo"], kept["inner"] = text, inner_scopes(text)
        return kept["inner"]

    def keep_records(self):
        kept["records"], kept["counters"] = self.records, getattr(
            self.setup.model, "stat_names", ())
        layout = self.setup.row_layout
        kept["row_layout"] = layout and dict(
            layout._asdict(), pieces=len(layout.pieces))
        return close(self)

    close = token.Route.close
    xplane.capture, token.inner_scopes = keep_capture, keep_scopes
    token.Route.close = keep_records
    m = manifest.load_manifest()
    cell = manifest.cell_of(m, cell_name)
    result = runner.run_cell(
        cell, manifest.config_of(m, cell), manifest.traffic_of(cell),
        manifest.limits_of(cell),
        manifest.metrics_for(m, cell_name, "per_layer"), seed, 30.0, True,
        t_process)

    trace, inner = kept["trace"], kept["inner"]
    per_step = 1e-6 / trace.steps  # ns over the capture -> ms a step
    totals: dict = {}
    ops: dict = {scope: {} for scope in scopes}
    for ev, ns in xplane.self_times(trace.first()):
        name = inner.get(ev[0]) or ev[3] or "(none)"
        totals[name] = totals.get(name, 0.0) + ns * per_step
        if name in ops:
            ops[name][ev[4]] = ops[name].get(ev[4], 0.0) + ns * per_step
    out = {"cell": cell_name, "seed": seed, "scopes": scopes,
           "traced_steps": trace.steps, "scope_ms_per_step": totals,
           "ops_ms_per_step": {
               scope: sorted(of.items(), key=lambda kv: -kv[1])
               for scope, of in ops.items()},
           "counters_max": {k: max(r[k] for r in kept["records"].rows)
                            for k in kept["counters"]},
           "counters_min": {k: min(r[k] for r in kept["records"].rows)
                            for k in kept["counters"]},
           "steps": len(kept["records"].rows),
           "row_layout": kept["row_layout"],
           "loss_hex": [float(r["loss"]).hex()
                        for r in kept["records"].rows],
           "correct": result["correct"], "metrics": result["metrics"],
           "device": result["device"]}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"inner_scope_ops_{tag}.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    with gzip.open(os.path.join(ROOT, "chiprun_out",
                                f"step_hlo_{tag}.txt.gz"), "wt") as fh:
        fh.write(kept["hlo"])
    print(json.dumps({k: out[k] for k in (
        "correct", "metrics", "scope_ms_per_step", "counters_max",
        "counters_min", "steps", "row_layout")}))
    for scope in scopes:
        print(f"-- {scope}")
        for label, ms in out["ops_ms_per_step"][scope][:40]:
            print(f"{ms:9.3f} ms  {label}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
