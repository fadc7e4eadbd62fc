"""Architectures for the benchmark's own tests only: none is a benchmark
configuration. A test adds this package to ``bench.arch.PACKAGES``."""
