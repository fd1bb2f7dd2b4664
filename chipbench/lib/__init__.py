"""Shared pieces of the on-chip benchmark: traffic, serving loop, trace
reduction, peaks, operation counts and the correctness check."""
