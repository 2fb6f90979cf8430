"""The chip benchmark: see ``bench/harness.py`` and ``BENCHMARK.json``."""
