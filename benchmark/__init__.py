"""The benchmark of spark-rapids-tpu: one cell, once, on the chip.

Everything that decides a number lives here, under the paths that
BENCHMARK.json names: data generation, the traffic loop, the reduction
from the profiler's trace, the table of peaks, the byte counts, each
query's plain reference and the comparison that decides `correct`.
From the program it takes the session API and its counters.
"""
