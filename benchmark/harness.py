"""One run of one cell (``python benchmark/run.py --workload <cell> ...``).

Set-up: start JAX on the chip, build the configuration's cluster from the
seed (``benchmark/generator.py``), encode it with the program's
``encode_cluster``, and warm the traffic's shapes. Window: the traffic
(``benchmark/drive.py``) back to back for ``--seconds``. Then the peak
device memory, the comparison with the plain reference
(``benchmark/check.py``), and one JSON line. Everything is found by name:
the cell in ``BENCHMARK.json``, its configuration's file, the traffic
mix at ``benchmark/traffic/<mix>.json``, each per-layer metric's reader
at ``benchmark/metrics/<metric>.py`` and the cell's limits at
``benchmark/limits/<cell>.json``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# a traced run traces the calls that start in this much of the window
# (one sweep, or two questions): the profiler took 184 s to write the
# 6.2 million events of two scan sweeps, and the per-layer metrics are
# means per call
TRACE_SECONDS = 1.0


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _by_name(entries, name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def reader(metric: str):
    """The per-layer metric's reader, benchmark/metrics/<metric>.py."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def build(conf: Dict, mix: Dict, seed: int, scale: Optional[Dict] = None) -> Dict:
    """The cell's cluster from the seed, encoded, and its traffic (not
    yet warmed)."""
    from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.k8s.objects import Node, Pod

    from benchmark import drive
    from benchmark.generator import cluster_dicts

    gen = dict(conf["generator"])
    max_new = int(conf["max_new"])
    if scale:
        gen.update(scale.get("generator", {}))
        max_new = int(scale.get("max_new", max_new))
    dicts = cluster_dicts(seed, **gen)
    nodes = [Node.from_dict(d) for d in dicts[0]]
    pods = [Pod.from_dict(d) for d in dicts[1]]
    template = Node.from_dict(dicts[2])
    t_enc = time.perf_counter()
    snap = encode_cluster(nodes, pods, EncodeOptions(max_new_nodes=max_new,
                                                     new_node_template=template))
    encode_s = time.perf_counter() - t_enc
    del nodes, pods
    cfg = make_config(snap)
    return {"dicts": dicts, "snap": snap, "max_new": max_new, "encode_s": encode_s,
            "traffic": drive.Traffic(mix, snap, cfg, dicts, max_new, seed)}


def wave_fraction(snap, cfg) -> float:
    """Percent of pods in the wave plan's batched segments (0 without a
    plan): a hit in the plan cache that the warm call filled."""
    from open_simulator_tpu.engine.exec_cache import bucket_shape
    from open_simulator_tpu.engine.waves import waves_for

    plan = waves_for(snap.arrays, cfg._replace(fail_reasons=False),
                     n_pods_total=bucket_shape(snap.n_nodes, snap.n_pods)[1])
    return 100.0 * plan.wave_fraction if plan is not None else 0.0


def cell_files(spec: Dict, workload: str):
    """(cell, configuration, traffic mix) of a workload, found by name."""
    cell = _by_name(spec["workloads"], workload, "workload")
    conf_entry = _by_name(spec["configs"], cell["config"], "config")
    conf = _load_json(os.path.join(ROOT, conf_entry["file"]))
    mix = _load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return cell, conf, mix


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the benchmark file (default: BENCHMARK.json at the root)")
    return ap.parse_args(argv)


def main(argv, t0: float, allow_cpu: bool = False, scale: Optional[Dict] = None) -> int:
    """`allow_cpu` and `scale` (generator and max_new overrides) serve the
    CPU rehearsal and the tests only."""
    args = parse(argv)
    spec = _load_json(args.spec)
    cell, conf, mix = cell_files(spec, args.workload)

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" and not allow_cpu:
        log(f"needs a TPU; JAX found {dev['platform']} ({dev['kind']}); nothing was run")
        return 2
    if dev["count"] < int(cell["chips"]):
        log(f"needs {cell['chips']} chip(s); JAX found {dev['count']}")
        return 2

    from open_simulator_tpu.engine.exec_cache import EXEC_CACHE, enable_persistent_cache

    from benchmark import check

    cache_dir = enable_persistent_cache()
    b = build(conf, mix, args.seed, scale)
    dicts, snap, max_new, traffic = b["dicts"], b["snap"], b["max_new"], b["traffic"]
    encode_s = b["encode_s"]
    traffic.warm()
    n_exec = len(EXEC_CACHE.debug_entries())
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s (encode {encode_s:.3f} s), cache {cache_dir}")
    waves_pct = wave_fraction(snap, traffic.cfg)

    trace_dir = None
    if args.trace:
        from benchmark.trace import MARK

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(MARK):
            perf_mark = time.perf_counter()
        win = traffic.window(min(args.seconds, TRACE_SECONDS),
                             annotate=jax.profiler.TraceAnnotation)
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"profiler stopped in {time.perf_counter() - t_stop:.2f} s")
    else:
        win = traffic.window(args.seconds)
    window_s = win["t_end"] - win["t_start"]
    from open_simulator_tpu.telemetry.ledger import plan_digest

    log(f"window {window_s:.3f} s, {len(win['calls'])} call(s), {win['done']} done; "
        f"answers {[r['plan'].best_count for r in win['results']]}, "
        f"first plan_digest {plan_digest(win['results'][0]['plan'])['digest']}")
    compiled_in_window = len(EXEC_CACHE.debug_entries()) - n_exec
    if compiled_in_window:
        raise RuntimeError(f"{compiled_in_window} executable(s) compiled inside the window")
    peak = None
    if dev["platform"] == "tpu":
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:int(cell["chips"])])
    compile_s = sum(float(r["cost"].get("compile_s") or 0.0)
                    for r in EXEC_CACHE.debug_entries())
    del traffic, snap, b

    ctx = {"cell": args.workload, "kind": mix["kind"], "encode_s": encode_s,
           "compile_s": compile_s, "calls": win["calls"], "window_s": window_s,
           "wave_fraction": waves_pct, "peak_bytes": peak, "trace": None}
    device = dict(dev, memory_peak_bytes=peak)
    breakdown = None
    if trace_dir is not None:
        from benchmark.trace import Trace

        t_tr = time.perf_counter()
        tr = Trace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_tr:.2f} s: " + ", ".join(
            f"{p} {len(d['ops'])} ops {len(d['modules'])} launches"
            for p, d in tr.devices.items()))
        ctx["trace"] = tr
        busy = tr.busy_s()
        device.update(busy_s=busy, window_s=window_s)
        off = tr.clock_offset(perf_mark)
        labels = [("driver: in the call, outside the program's sweep span", a, b)
                  for name, ivs in tr.host.items() if name != MARK for a, b in ivs]
        if off is not None:
            labels += [("program: sweep span (launch and hosting)", a + off, a + d + off)
                       for call in win["calls"] for a, d in call["spans"]]
        breakdown = {"device_ops": [[n, s] for n, s in tr.top_ops(10)],
                     "idle_gaps": [[n, s] for n, s in tr.idle_gaps(labels, 10)]}

    got = check.run(dicts, max_new, mix["kind"], win["results"], args.seed,
                    mix["check"], log=log)
    compared, correct = check.verdict(got, check.limits_for(args.workload))

    metrics = {}
    if not args.trace:
        values = {"setup_s": setup_s}
        if mix["kind"] == "sweep":
            values["scenarios_per_s"] = win["done"] / window_s
        else:
            values["question_s"] = window_s / win["done"]
        for m in spec["end_to_end"]:
            if _applies(m, args.workload) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            if _applies(m, args.workload):
                v = reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if dev["platform"] != "tpu":
        metrics = {}     # a CPU run names no device metric
    failed = sum(len(r["plan"].trial_errors) for r in win["results"])
    out = {"correct": correct, "attempted": win["done"], "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = compared
    for k, v in compared.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
