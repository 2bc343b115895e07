"""Shared pieces of the benchmark: manifest, traffic, statistics, peaks, costs."""
