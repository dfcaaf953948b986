import os
import sys

# the benchmark's tests run on the CPU at toy sizes; only run.py needs a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
