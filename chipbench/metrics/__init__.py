"""Per-layer metric readers: ``<name>.py`` holds ``read(ctx)``, which returns
the metric's value or None where the run gives it nothing to read."""
