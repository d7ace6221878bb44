"""Benchmark of the PC-stable program on the TPU (see run.py)."""
