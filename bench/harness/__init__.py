"""The benchmark harness: loads the cell named in ``BENCHMARK.json``, runs
its warm-up, measured window and correctness check, and reduces traces
and counters to metrics.  Everything that belongs to one configuration,
traffic mix, generator, per-layer metric, kernel role, entry or
reference is a file of its own under ``bench/``, found by name."""
