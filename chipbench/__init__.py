"""Chip benchmark of the noise-injection analyser: one process per run,
cells named in ``BENCHMARK.json`` at the root of the checkout."""
