"""Per-layer metric readers, one module per metric name in
``BENCHMARK.json`` (``<name>.py``, loaded by path). Each defines
``read(run) -> float | None``; ``None`` means the run held nothing to
read, and the metric is left out of the result line."""
