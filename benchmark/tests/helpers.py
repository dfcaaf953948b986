"""Toy clusters run through the program's capacity sweep on the CPU."""

import json
import os

import numpy as np

from benchmark.generator import cluster_dicts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")
# each configuration's generator, cut to a toy size
TOY = {"pools5k": dict(n_nodes=128, n_pods=640, pools=4),
       "spread5k": dict(n_nodes=96, n_pods=640)}


def toy_dicts(config: str, seed: int):
    with open(os.path.join(CONFIGS, f"{config}.json")) as f:
        gen = json.load(f)["generator"]
    return cluster_dicts(seed, **dict(gen, **TOY[config]))


def toy_sweep(config: str, seed: int, max_new: int = 8):
    """(dicts, plan) of a 0..max_new sweep of the configuration's toy cut."""
    from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.k8s.objects import Node, Pod
    from open_simulator_tpu.parallel.sweep import capacity_sweep

    dicts = toy_dicts(config, seed)
    nd, pd, td = dicts
    snap = encode_cluster([Node.from_dict(d) for d in nd], [Pod.from_dict(d) for d in pd],
                          EncodeOptions(max_new_nodes=max_new,
                                        new_node_template=Node.from_dict(td)))
    plan = capacity_sweep(snap, make_config(snap), list(range(max_new + 1)))
    return dicts, plan


def all_pods(row) -> np.ndarray:
    return np.arange(len(row))


def run_cell(capsys, monkeypatch, cell, fault=None, seed=2 ** 31 + 3):
    """One whole run of the cell through harness.main at the rehearsal's
    toy size, with `fault(traffic, results)` breaking the window's
    results where they are produced; returns the result line."""
    import time

    from benchmark import drive, harness
    from benchmark.rehearse import toy

    if fault is not None:
        real = drive.Traffic.window

        def window(self, seconds, annotate=None):
            win = real(self, seconds, annotate)
            fault(self, win["results"])
            return win

        monkeypatch.setattr(drive.Traffic, "window", window)
    spec = harness._load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    c = harness._by_name(spec["workloads"], cell, "workload")
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                       "--trace", "0"], time.perf_counter(), allow_cpu=True,
                      scale=toy(c, spec))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
