"""Per-layer metric readers: one module per metric, each a ``read(run)``
that returns the metric, or ``None`` where the run gives it nothing to
read (see ``bench.harness.RunRecord``)."""
