"""Device self-time under named scopes, per traced step, on the first
chip, in milliseconds."""


def read(spec, ctx):
    trace = ctx["trace"]
    if trace is None or not trace.first() or not trace.steps:
        return None
    seconds = trace.scope_seconds(set(spec["scopes"]))
    if not seconds:
        return None
    return 1e3 * seconds / trace.steps
