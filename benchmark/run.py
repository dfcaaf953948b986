"""Run one benchmark cell once on the chip:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (benchmark/harness.py).
Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# libtpu would log under /tmp/tpu_logs: a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
