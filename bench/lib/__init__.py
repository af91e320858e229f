"""Shared pieces of the benchmark: the yardstick that later changes to the
program cannot edit (peaks, FLOP and byte counts, trace reduction,
percentiles, the plain reference, the traffic generator)."""
