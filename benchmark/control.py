"""Readings that set a cell's limits (PERF.md): the program's compared
numbers on many seeds, and the control's on a few, at the cell's own
size, all in one process.

    python benchmark/control.py --workload <cell> --seeds 1,2,... --control 1,2,3

Each seed: the cell's cluster and traffic, one call of the window's
entry point, then benchmark/check.py with control=True on the control
seeds. The control is the plain reference itself computed in bfloat16,
put in the program's place at the same pods and placements. Prints one
JSON line per seed, with ``correct`` (and ``control_correct``) judged by
the cell's limits as a run judges them. The benchmark's own runs never
run this.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, harness  # noqa: E402


def main(argv, allow_cpu: bool = False, scale=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmark/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--spec", default=os.path.join(harness.ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu" and not allow_cpu:
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from open_simulator_tpu.engine.exec_cache import enable_persistent_cache

    enable_persistent_cache()
    spec = harness._load_json(args.spec)
    _, conf, mix = harness.cell_files(spec, args.workload)
    ctl = {int(s) for s in args.control.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        b = harness.build(conf, mix, seed, scale)
        b["traffic"].warm()
        win = b["traffic"].window(0.0)
        got = check.run(b["dicts"], b["max_new"], mix["kind"], win["results"], seed,
                        mix["check"], control=seed in ctl)
        limits = check.limits_for(args.workload)
        got["correct"] = check.verdict(got, limits)[1]
        if seed in ctl:
            got["control_correct"] = check.verdict(got, limits, "control_")[1]
        got.update(seed=seed, control=seed in ctl, seconds=time.perf_counter() - t0,
                   answers=[r["plan"].best_count for r in win["results"]])
        print(json.dumps(got), flush=True)
        del b, win
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
