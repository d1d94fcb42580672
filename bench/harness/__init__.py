"""The benchmark's harness: what every cell shares.

``spec`` finds a cell's configuration, traffic mix, limits and per-layer
metric readers by the names in ``BENCHMARK.json``; ``program`` builds the
system under test from them and ``driver`` is the jitted call the window
drives; ``reference`` is the plain training reference that decides
``correct``; ``trace`` reduces a profiler trace to device intervals.
"""
