"""The chip benchmark's own code: loading cells, traffic, weights, the
plain reference, the trace reduction and the load harness.

Nothing here is imported by the program under test, and nothing here
imports the program at module level: ``run.py`` puts ``src`` on the path
and the harness imports the program when a run starts.
"""
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]  # benchmarks/chip
