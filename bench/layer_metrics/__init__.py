"""One reader per per-layer metric: ``read(ctx) -> float | None``, with
``ctx`` a ``bench.harness.Context``.  A reader that finds nothing to read
returns None and the metric is left out of the result line."""
