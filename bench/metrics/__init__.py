"""Per-layer metrics: one reader per file, named as in ``BENCHMARK.json``,
and the reduction of a profiler trace that they share (``xplane.py``)."""
