"""On-chip benchmark of the PerLLM serving path.

`run.py` beside this package is the entry point. Everything that belongs
to one model configuration, traffic mix or per-layer metric lives in a
file of its own (`configs/`, `traffic/`, `metrics/`), found by the name
that `BENCHMARK.json` gives it; this package is the general machinery.
"""
