"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``. ``read(layer, spec)`` returns the number, or None where
the run holds nothing to read (the harness then leaves the metric out).
``UNIT`` is its unit."""
