"""Benchmark of the StoCFL engine on TPU chips; see run.py."""
