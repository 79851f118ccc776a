"""One reader per per-layer metric, found by the metric's name.

Each module has ``read(ctx) -> float | None`` (``ctx`` is a
``tracing.Context``).  A reader that finds nothing to read returns None
and the metric is left out of the run's line."""
