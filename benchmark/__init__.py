"""The benchmark: harness, yardstick and cells.  See PERF.md."""
