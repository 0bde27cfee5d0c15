"""The chip benchmark's harness: see ``benchmarks/chip/run.py``."""
