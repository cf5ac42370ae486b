"""Chip benchmark of the serving engine: one data-driven harness.

Every cell of ``BENCHMARK.json`` names a configuration file
(``benchmarks/chip/configs/<config>.json``), a traffic file
(``benchmarks/chip/traffic/<traffic>.json``) and, through its metrics,
one reader per per-layer metric (``benchmarks/chip/metrics/<name>.py``).
The harness finds each by name, so a later cell or metric is a new file.
"""
