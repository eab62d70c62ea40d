"""The benchmark of draco_tpu: the yardstick later PRs are measured with.

Everything here belongs to the benchmark and to nothing else (BENCHMARK.json
``paths``). Start at README.md; the command is ``python3 benchmark/run.py``.
"""
