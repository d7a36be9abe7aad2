"""The on-chip benchmark's harness.

``run.py`` (one directory up) is the entry point.  Everything a cell is
made of is found by name from ``BENCHMARK.json`` (``spec.py`` lists
where): its configuration, the runner that drives the system under test,
its traffic mix and the mix's generator, the limits of its correctness
check and one reader per per-layer metric.  This package holds what they
share: the measured window and the result line, the reduction from
profiler traces to device times, the FLOP counts and the table of peaks.
"""
