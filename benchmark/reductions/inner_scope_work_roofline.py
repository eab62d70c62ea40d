"""Share of its roofline a nested scope reaches: the least time the chip
could take for the work the scope's ALGORITHM needs per step — the larger of
its operations over the peak bf16 rate and its bytes over the peak memory
rate — over the device self-time the scope took per step
(inner_scope_ms_per_step). Operations and bytes are functions of the route's
job in the costs module the metric's file names (``costs``, under
benchmark/harness/; ``flops`` and ``bytes`` name the functions): the work as
the algorithm is written, forward and backward of every lane that really
computes, whatever the implementation adds or recomputes — so the share is
a floor, it cannot pass 100 %, and a later kernel is read on the same
yardstick. None where the program has no such scope (a parent without it),
the route no nested map, or the job no model mapping."""

import importlib

from benchmark.reductions.inner_scope_ms_per_step import scope_seconds


def least_seconds(spec, job, peaks) -> float:
    costs = importlib.import_module(f"benchmark.harness.{spec['costs']}")
    return max(
        getattr(costs, spec["flops"])(job) / peaks["bf16_flops_per_s"],
        getattr(costs, spec["bytes"])(job) / peaks["hbm_bytes_per_s"])


def read(spec, ctx):
    job, peaks = ctx.get("job") or {}, ctx["peaks"]
    if not peaks or "model_spec" not in job:
        return None
    seconds = scope_seconds(ctx, spec["scopes"])
    if not seconds:
        return None
    return 100.0 * least_seconds(spec, job, peaks) / seconds
