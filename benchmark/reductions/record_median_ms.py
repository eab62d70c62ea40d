"""Median over the window's steps of a seconds-valued key of the loop's own
per-step record, in milliseconds."""

import statistics


def read(spec, ctx):
    vals = [r[spec["key"]] for r in ctx["records"] if spec["key"] in r]
    return 1e3 * statistics.median(vals) if vals else None
