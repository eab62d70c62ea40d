"""One module per kind of seeded input. A configuration's ``data`` block
names its ``kind``; the harness imports ``benchmark.data.<kind>`` and calls
``make(spec, seed)`` with that block. What comes back is handed, unopened,
to the route (which feeds the program) and to the reference (which follows
it): the harness itself knows no shape of any data."""
