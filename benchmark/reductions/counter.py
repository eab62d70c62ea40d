"""A counter's value over the window, as counted."""


def read(spec, ctx):
    value = ctx["counters"].get(spec["counter"])
    return None if value is None else float(value)
