"""Shared tooling of the benchmark: loading, traffic, trace reduction,
operation counts, peaks and the result line."""
