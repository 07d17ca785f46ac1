import os
import sys

# the benchmark's package lives beside its entry point, run.py
BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "chip")
sys.path.insert(0, os.path.abspath(BENCH))
