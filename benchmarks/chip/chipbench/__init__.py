"""The on-chip benchmark's own code: the yardstick that program changes
cannot move.

Cells, configurations, traffic mixes, limits and per-layer metrics are
data or small files of their own under ``benchmarks/chip/``; this package
finds them by the names in the root ``BENCHMARK.json``.
"""
