"""The benchmark: one entry point (``bench/run.py``) driven by data.

See ``BENCHMARK.json`` at the root of the checkout for its cells and
metrics, and ``PERF.md`` for why each exists.
"""
