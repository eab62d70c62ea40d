"""One module per way of turning a run's spans, counters or trace into a
per-layer number: ``read(spec, ctx) -> float | None``. A metric's own file
under benchmark/layer_metrics/ names its reduction and its parameters; a
reader that finds nothing to read returns None and the metric is left out
of the line. ``ctx`` holds ``records`` (the window's per-step records),
``spans`` ((name, start, end) on perf_counter), ``window`` (t0, t1),
``trace`` (harness/xplane.Trace), ``job``, ``peaks``, ``counters``,
``chips``."""
