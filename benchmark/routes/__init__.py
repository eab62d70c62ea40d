"""One module per production loop of the program. A traffic file names its
route; the harness imports ``benchmark.routes.<route>`` and drives the
``Route`` class it finds there. A route touches the program only through
what it takes to run it: its config, its loop, its records, spans and
counters."""
