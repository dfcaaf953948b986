"""Synthetic clusters (shared by `__graft_entry__.py`, `chip_smoke.py`,
tools/ and tests).

rich=False is the round-1..3-comparable workload (cpu/mem requests + one
soft zone spread — most feature gates autodetect OFF). rich=True is the
honest all-ops-on workload: fractions of pods carry host ports, required
pod-affinity, anti-affinity, hard and hostname spread, preferred pod/node
affinities, node selectors and tolerations, and fractions of nodes carry
taints / unschedulable marks — so make_config keeps every feature gate ON
and a run pays for the full op pipeline (VERDICT r3: gates must not
hide regressions).
"""

from __future__ import annotations

import numpy as np


def synthetic_snapshot(n_nodes: int = 64, n_pods: int = 256, max_new: int = 0,
                       rich: bool = False, pools: int = 0,
                       bound: float = 0.0):
    """The synthetic cluster of `synthetic_objects`, encoded, with
    `max_new` template node slots for the capacity sweep."""
    from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster

    nodes, pods, template = synthetic_objects(n_nodes, n_pods, rich=rich,
                                              pools=pools, bound=bound)
    opts = None
    if max_new:
        opts = EncodeOptions(max_new_nodes=max_new, new_node_template=template)
    return encode_cluster(nodes, pods, opts)


def synthetic_objects(n_nodes: int, n_pods: int, rich: bool = False,
                      pools: int = 0, bound: float = 0.0):
    """(nodes, pods, template node) as k8s objects, seeded: the same
    objects every call. pools > 0 labels nodes into `pools` tenant pools
    and gives every pod a matching nodeSelector (+ per-pool app groups) — the
    multi-tenant shape whose disjoint footprints the wave scheduler
    (engine/waves.py) batches. bound > 0 pre-binds that fraction of pods
    via spec.nodeName, interleaved through the sequence — the
    cluster-dump replay shape. Both default off and leave the rich /
    non-rich workloads byte-identical to those without the knobs."""
    from open_simulator_tpu.k8s.objects import Node, Pod

    rng = np.random.RandomState(0)
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
        return Node.from_dict({
            "metadata": {"name": name, "labels": labels},
            "status": {"allocatable": {"cpu": "16", "memory": "64Gi", "pods": 110}},
            "spec": spec,
        })

    def mk_pod(i):
        labels = {"app": f"a{i % app_mod}"}
        spread = [{
            "maxSkew": 5,
            "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": f"a{i % app_mod}"}},
        }]
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
            # deterministic interleave of already-bound pods (a recorded
            # cluster dump replays placed pods mid-sequence)
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
        return Pod.from_dict({
            "metadata": {"name": f"p{i}", "namespace": "default", "labels": labels},
            "spec": spec,
        })

    nodes = [mk_node(f"n{i}", i) for i in range(n_nodes)]
    pods = [mk_pod(i) for i in range(n_pods)]
    # drawn last, so nodes and pods never depend on it
    return nodes, pods, mk_node("template")
