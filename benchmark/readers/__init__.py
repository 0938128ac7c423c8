"""One small reader for each kind of per-layer metric.  ``read(ctx, **params)``
returns the number, or None where the run gave it nothing to read."""
