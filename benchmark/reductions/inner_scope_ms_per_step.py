"""Device self-time under NESTED named scopes, per traced step, on the first
chip, in milliseconds. harness/xplane's scope map keeps the first
``draco_*`` segment of an instruction's path (``draco_comp`` for the whole
forward and backward pass); the route's ``job()['inner_scopes']`` maps each
instruction to its innermost segment, which is what tells attention from
routing from the experts' products inside it. None where the route gives no
such map (a route, or a parent program, without nested scopes) or none of
its instructions ran."""

from benchmark.harness import xplane


def scope_seconds(ctx, scopes) -> float | None:
    """Self seconds per traced step of the first chip's ops whose innermost
    scope is in ``scopes``; None where there is nothing to read."""
    trace = ctx["trace"]
    inner = (ctx.get("job") or {}).get("inner_scopes")
    if trace is None or not inner or not trace.first() or not trace.steps:
        return None
    scopes = set(scopes)
    ns = sum(self_ns for ev, self_ns in xplane.self_times(trace.first())
             if inner.get(ev[0]) in scopes)
    return ns * 1e-9 / trace.steps if ns else None


def read(spec, ctx):
    seconds = scope_seconds(ctx, spec["scopes"])
    return None if seconds is None else 1e3 * seconds
