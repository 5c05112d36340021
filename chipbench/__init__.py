"""The chip benchmark: one cell of ``BENCHMARK.json`` per run of
``chipbench/run.py``. See ``PERF.md`` at the repository root."""
