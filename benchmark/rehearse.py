"""CPU rehearsal: every cell of BENCHMARK.json end to end at a toy size.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py [cell ...]

Runs the same harness as benchmark/run.py (set-up, window, reference
check) with the generator cut to a toy cluster. It prints each run's
result line, whose metrics are empty: a CPU run names no device metric.
run.py itself refuses the CPU.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def toy(cell: dict, spec: dict) -> dict:
    """A toy cut of the cell's configuration: 128 nodes, 640 pods, and
    pools of 32 nodes, so every pool still spans the 4 zones."""
    conf = harness._by_name(spec["configs"], cell["config"], "config")
    gen = harness._load_json(os.path.join(harness.ROOT, conf["file"]))["generator"]
    return {"generator": {"n_nodes": 128, "n_pods": 640,
                          "pools": 4 if gen.get("pools") else 0}}


def main(argv) -> int:
    spec = harness._load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = argv or [c["name"] for c in spec["workloads"]]
    rc = 0
    for name in cells:
        cell = harness._by_name(spec["workloads"], name, "workload")
        for trace in (0, 1):
            rc |= harness.main(["--workload", name, "--seed", "2147483659",
                                "--seconds", "1", "--trace", str(trace)],
                               time.perf_counter(), allow_cpu=True,
                               scale=toy(cell, spec))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
