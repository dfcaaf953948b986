"""The benchmark's cluster generator: a copy of
``open_simulator_tpu/testing/synthetic.py`` (``synthetic_objects``) that
takes the seed as an argument and returns plain Kubernetes object dicts.

With ``seed=0`` it builds the same objects as the program's copy (a test
in ``benchmark/tests`` holds it to that), so ``allops5k --seed 0`` is the
north-star cluster PR 21 ran on the chip. The program converts the dicts
into its own objects; the plain reference reads the dicts themselves.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def rng_for(seed: int) -> np.random.RandomState:
    """numpy's legacy generator, as the original; seeds past 32 bits are
    folded through a SeedSequence (seed 0 stays RandomState(0))."""
    if 0 <= seed < 2 ** 32:
        return np.random.RandomState(seed)
    return np.random.RandomState(
        np.random.SeedSequence(seed).generate_state(1)[0])


def cluster_dicts(seed: int, n_nodes: int, n_pods: int, rich: bool = False,
                  pools: int = 0, bound: float = 0.0, host_skew: int = 0
                  ) -> Tuple[List[Dict], List[Dict], Dict]:
    """(node dicts, pod dicts, template node dict), the same objects for
    the same arguments. pools > 0 labels nodes into tenant pools and gives
    every pod its pool's nodeSelector and a per-pool app group; bound > 0
    pre-binds that fraction of pods via spec.nodeName; rich turns on every
    default filter and score plugin at fixed fractions of pods and nodes;
    host_skew > 0 puts a ScheduleAnyway hostname spread of that maxSkew
    ahead of every pod's zone spread (kube-scheduler's built-in default
    constraints are hostname 3 and zone 5). The original has no
    host_skew: with 0 the objects are its own."""
    rng = rng_for(seed)
    app_mod = pools if pools > 0 else 8

    def mk_node(name, i=0):
        labels = {"topology.kubernetes.io/zone": f"z{rng.randint(4)}"}
        spec = {}
        if pools > 0:
            labels["pool"] = f"p{i % pools}"
        if rich:
            if i % 2 == 0:
                labels["disk"] = "ssd"
            if i % 16 == 7:
                spec["taints"] = [{"key": "dedicated", "value": "infra",
                                   "effect": "NoSchedule"}]
            if i % 8 == 3:
                spec.setdefault("taints", []).append(
                    {"key": "degraded", "effect": "PreferNoSchedule"})
            if i % 64 == 33:
                spec["unschedulable"] = True
        return {
            "metadata": {"name": name, "labels": labels},
            "status": {"allocatable": {"cpu": "16", "memory": "64Gi", "pods": 110}},
            "spec": spec,
        }

    def mk_pod(i):
        labels = {"app": f"a{i % app_mod}"}
        spread = [{
            "maxSkew": host_skew,
            "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": f"a{i % app_mod}"}},
        }] if host_skew > 0 else []
        spread.append({
            "maxSkew": 5,
            "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": f"a{i % app_mod}"}},
        })
        spec = {
            "containers": [{
                "name": "c",
                "resources": {"requests": {
                    "cpu": f"{rng.randint(100, 2000)}m",
                    "memory": f"{rng.randint(64, 2048)}Mi",
                }},
            }],
            "topologySpreadConstraints": spread,
        }
        if pools > 0:
            spec["nodeSelector"] = {"pool": f"p{i % pools}"}
        if bound > 0.0 and (i * 7919) % 100 < int(bound * 100):
            spec["nodeName"] = f"n{(i * 31) % n_nodes}"
        if rich:
            labels["anti"] = f"g{i % 97}"
            if i % 17 == 0:
                spec["containers"][0]["ports"] = [{"hostPort": 8000 + i % 5}]
            if i % 9 == 0:
                spec["nodeSelector"] = {"disk": "ssd"}
            if i % 16 == 0:
                spec["tolerations"] = [{"key": "dedicated", "operator": "Equal",
                                        "value": "infra", "effect": "NoSchedule"}]
            if i % 7 == 0:
                spread.append({
                    "maxSkew": 3,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": f"a{i % app_mod}"}},
                })
            if i % 19 == 0:
                spread.append({
                    "maxSkew": 4,
                    "topologyKey": "kubernetes.io/hostname",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": f"a{i % app_mod}"}},
                })
            affinity = {}
            if i % 13 == 0:
                affinity["podAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": {"app": f"a{i % app_mod}"}},
                        "topologyKey": "topology.kubernetes.io/zone",
                    }],
                }
            if i % 11 == 0:
                affinity["podAntiAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": {"anti": f"g{i % 97}"}},
                        "topologyKey": "kubernetes.io/hostname",
                    }],
                }
            if i % 5 == 0:
                affinity.setdefault("podAffinity", {})[
                    "preferredDuringSchedulingIgnoredDuringExecution"] = [{
                        "weight": 10,
                        "podAffinityTerm": {
                            "labelSelector": {"matchLabels": {"app": f"a{(i + 1) % app_mod}"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        },
                    }]
            if i % 6 == 0:
                affinity["nodeAffinity"] = {
                    "preferredDuringSchedulingIgnoredDuringExecution": [{
                        "weight": 5,
                        "preference": {"matchExpressions": [
                            {"key": "disk", "operator": "In", "values": ["ssd"]},
                        ]},
                    }],
                }
            if affinity:
                spec["affinity"] = affinity
        return {
            "metadata": {"name": f"p{i}", "namespace": "default", "labels": labels},
            "spec": spec,
        }

    nodes = [mk_node(f"n{i}", i) for i in range(n_nodes)]
    pods = [mk_pod(i) for i in range(n_pods)]
    # drawn last, so nodes and pods never depend on it
    return nodes, pods, mk_node("template")
