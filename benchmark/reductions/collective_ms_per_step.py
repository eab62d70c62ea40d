"""Device self-time of collective operations (all-reduce, all-gather,
all-to-all, collective-permute, reduce-scatter, their -start/-done halves
included) per traced step on the first chip, in milliseconds. None on a
trace with no such event: a one-chip program has none."""

from benchmark.harness import xplane


def read(spec, ctx):
    trace = ctx["trace"]
    if trace is None or not trace.first() or not trace.steps:
        return None
    ns = sum(self_ns for ev, self_ns in xplane.self_times(trace.first())
             if xplane.COLLECTIVE_RE.search(ev[0]))
    return 1e3 * ns * 1e-9 / trace.steps if ns else None
