"""Share of the HBM roofline the decode reaches: the least bytes one cyclic
decode must move (harness/costs.cyclic_decode_min_bytes) over the chip's
peak bandwidth, over the device time the decode scope took per step. The
decode is bound by bandwidth: it reads the (n, d) encoded stack once and
does O(n) operations per element."""

from benchmark.harness import costs


def read(spec, ctx):
    trace, job, peaks = ctx["trace"], ctx["job"], ctx["peaks"]
    if trace is None or not trace.first() or not trace.steps or not peaks:
        return None
    seconds = trace.scope_seconds(set(spec["scopes"]))
    if not seconds:
        return None
    seconds /= trace.steps
    floor = (costs.cyclic_decode_min_bytes(job["n"], job["dim"], job["wire"])
             / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
