"""Benchmark of the relocatable-collections runtime on the chip."""
