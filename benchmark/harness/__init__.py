"""The harness: manifest, seeded inputs, the window, the trace reduction,
the comparison that decides ``correct``. Driven by the data files named in
BENCHMARK.json; no module here knows a model or a cell by name."""
