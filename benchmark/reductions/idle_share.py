"""Share of a step in which no op ran on the device, in percent: one minus
the device's busy seconds per traced step (union of op intervals, averaged
over the chips) over the host seconds per step of the UNTRACED window of the
same run. The traced window's own length is not used: under the profiler a
step's host side ran some 60 ms longer (PR 23), which would read as idle. The
line's ``device.busy_s`` / ``device.window_s`` stay as traced."""


def read(spec, ctx):
    trace, records = ctx["trace"], ctx["records"]
    if trace is None or trace.busy_s <= 0 or not trace.steps or not records:
        return None
    t0, t1 = ctx["window"]
    step_s = (t1 - t0) / len(records)
    return 100.0 * (1.0 - (trace.busy_s / trace.steps) / step_s)
