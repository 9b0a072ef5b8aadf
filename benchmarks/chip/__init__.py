"""On-chip training benchmark: one cell per entry of ``BENCHMARK.json``'s
``workloads``. ``run.py`` is the command; README.md says how to add a
configuration, a traffic mix or a per-layer metric as files."""
