"""Per-layer metric readers, one module per metric name in ``BENCHMARK.json``."""
