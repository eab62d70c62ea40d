"""Share of the chip's peak bf16 rate a nested scope reaches: the
operations its products need per step (a function of harness/lm_costs.py
named by the metric's file, from the route's job) over the peak, over the
device self-time the scope took per step (inner_scope_ms_per_step). The
operations are the forward and backward passes' of every lane that really
computes; what the backward pass recomputes is not counted, so the share
is a floor."""

from benchmark.harness import lm_costs
from benchmark.reductions.inner_scope_ms_per_step import scope_seconds


def read(spec, ctx):
    job, peaks = ctx.get("job") or {}, ctx["peaks"]
    if not peaks or "model_spec" not in job:
        return None
    seconds = scope_seconds(ctx, spec["scopes"])
    if not seconds:
        return None
    flops = getattr(lm_costs, spec["flops"])(job)
    return 100.0 * flops / peaks["bf16_flops_per_s"] / seconds
