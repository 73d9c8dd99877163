"""Per-layer metric readers, one module per metric, named as the metric
in ``BENCHMARK.json``.  Each has ``read(ctx) -> float | None`` over a
``chipbench.harness.Context``; ``None`` where it finds nothing to read,
and the harness then leaves the metric out of the result line."""
