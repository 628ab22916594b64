"""The benchmark's yardstick: everything a later PR may not move.

Copied arithmetic (FLOP count, peaks, arrival schedule), the plain
reference forward, the xplane reducer and the kernel cost functions live
here, under ``BENCHMARK.json``'s ``paths``, and read nothing from the
program but its spans, counters and kernel names.
"""
