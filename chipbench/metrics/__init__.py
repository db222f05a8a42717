"""One reader per metric, found by the metric's name in BENCHMARK.json.

``read(run)`` takes the run's record (``run.RunRecord``) and returns the
number, or ``None`` where the run has nothing to read for it."""
