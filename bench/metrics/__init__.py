"""Per-layer metric readers, one file per metric of ``BENCHMARK.json``.

``bench/metrics/<metric>.py`` holds ``read(run)``, which takes a
``bench.harness.RunData`` and returns the metric's value, or None when the
run gave it nothing to read (the harness then leaves the metric out).
"""
