"""One file per metric, found by the name in ``BENCHMARK.json``. Each exposes
``read(record)`` and returns a number, or ``None`` where it finds nothing to
read (the harness then leaves the metric out of the line)."""
