#!/usr/bin/env python
"""Tune smoke: the policy-search path against a REAL server process
(`make tune-smoke`, also a tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ISSUE 13):

1. Grid round: POST /api/tune sweeps a coordinate grid as lanes of one
   executable and answers the (unplaced, cost, disruption) Pareto set.
2. Evolutionary round: a seeded cem search is deterministic — the same
   request reproduces the same point digest.
3. Cancellation: a lapsed deadline answers a structured 504
   (E_DEADLINE/E_CANCELLED), never a 500, and a malformed knob is a
   structured 400.
4. Fleet lanes: a same-bucket fleet campaign through POST /api/campaign
   finishes in FEWER device launches than clusters (the §13 bucket-map
   witness cashed in), with every cluster completed.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CLUSTER_YAML = """
apiVersion: v1
kind: Node
metadata: {name: t0, labels: {topology.kubernetes.io/zone: z0}}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
---
apiVersion: v1
kind: Node
metadata: {name: t1, labels: {topology.kubernetes.io/zone: z1}}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
---
apiVersion: v1
kind: Node
metadata: {name: t2, labels: {topology.kubernetes.io/zone: z0}}
status:
  allocatable: {cpu: "16", memory: 32Gi, pods: "110"}
---
apiVersion: apps/v1
kind: Deployment
metadata: {name: smoke, namespace: default}
spec:
  replicas: 6
  selector: {matchLabels: {app: smoke}}
  template:
    metadata: {labels: {app: smoke}}
    spec:
      topologySpreadConstraints:
        - maxSkew: 1
          topologyKey: topology.kubernetes.io/zone
          whenUnsatisfiable: ScheduleAnyway
          labelSelector: {matchLabels: {app: smoke}}
      containers:
        - name: c
          image: registry.local/t:1
          resources: {requests: {cpu: "2", memory: 2Gi}}
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _call(base, method, path, payload=None, timeout=300.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        base + path, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _start_server(port: int, env: dict):
    proc = subprocess.Popen(
        [sys.executable, "-m", "open_simulator_tpu.cli", "server",
         "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    base = f"http://127.0.0.1:{port}"
    deadline = time.time() + 60
    while True:
        try:
            status, _ = _call(base, "GET", "/test", timeout=1.0)
            if status == 200:
                return proc, base
        except OSError:
            pass
        if time.time() > deadline:
            proc.kill()
            raise SystemExit("server never came up")
        if proc.poll() is not None:
            raise SystemExit(f"server exited early rc={proc.returncode}")
        time.sleep(0.2)


def main() -> int:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc, base = _start_server(_free_port(), env)
    fleet_root = tempfile.mkdtemp(prefix="tunesmoke-fleet-")
    try:
        # ---- stage 1: grid round ---------------------------------------
        status, grid = _call(base, "POST", "/api/tune",
                             {"cluster": {"yaml": CLUSTER_YAML},
                              "mode": "grid", "variants": 4,
                              "grid_values": [0, 2]})
        assert status == 200, (status, grid)
        assert grid["pareto"], grid
        assert grid["objectives"] == ["unplaced", "cost", "disruption"]
        assert grid["baseline"]["disruption"] == 0
        print(f"tune-smoke stage 1 OK: grid evaluated "
              f"{grid['n_variants']} variant(s) over "
              f"{grid['rounds_run']} round(s) -> "
              f"{len(grid['pareto'])} Pareto point(s), "
              f"digest {grid['digest']}")

        # ---- stage 2: evolutionary round, deterministic ----------------
        body = {"cluster": {"yaml": CLUSTER_YAML}, "mode": "cem",
                "variants": 4, "rounds": 2, "seed": 11}
        status, cem_a = _call(base, "POST", "/api/tune", body)
        assert status == 200, (status, cem_a)
        assert cem_a["rounds_run"] == 2, cem_a
        status, cem_b = _call(base, "POST", "/api/tune", body)
        assert status == 200 and cem_b["digest"] == cem_a["digest"], (
            f"seeded cem not deterministic: {cem_a['digest']} "
            f"!= {cem_b['digest']}")
        print(f"tune-smoke stage 2 OK: cem {cem_a['n_variants']} "
              f"variant(s), seeded digest reproduced "
              f"({cem_a['digest']})")

        # ---- stage 3: cancellation + structured 400 --------------------
        status, dead = _call(base, "POST", "/api/tune",
                             {"cluster": {"yaml": CLUSTER_YAML},
                              "mode": "cem", "variants": 4,
                              "rounds": 64, "deadline_s": 1e-4})
        assert status == 504, (status, dead)
        assert dead["code"] in ("E_DEADLINE", "E_CANCELLED"), dead
        status, bad = _call(base, "POST", "/api/tune",
                            {"cluster": {"yaml": CLUSTER_YAML},
                             "weights": {"w_nope": 1}})
        assert status == 400 and bad["code"] == "E_SPEC", (status, bad)
        print(f"tune-smoke stage 3 OK: lapsed deadline answered 504 "
              f"{dead['code']}, bogus weight field answered 400 "
              f"{bad['code']}")

        # ---- stage 4: campaign fleet lanes -----------------------------
        # 6 dumps in 2 shape buckets (write_synthetic_fleet alternates
        # two sizes): the lane path must finish in 2 launches, not 6
        from open_simulator_tpu.campaign.fleet import (  # noqa: PLC0415
            write_synthetic_fleet,
        )

        paths = write_synthetic_fleet(fleet_root, n_clusters=6,
                                      nodes=8, pods=24)
        status, fleet = _call(base, "POST", "/api/campaign",
                              {"clusters": paths})
        assert status == 200, (status, fleet)
        t = fleet["totals"]
        assert t["completed"] == 6 and t["quarantined"] == 0, t
        assert fleet["launches"] < t["clusters"], (
            f"fleet lanes did not batch: {fleet['launches']} launches "
            f"for {t['clusters']} clusters")
        assert len(fleet["buckets"]) == 2, fleet["buckets"]
        print(f"tune-smoke stage 4 OK: {t['clusters']} same-bucket "
              f"cluster(s) in {len(fleet['buckets'])} bucket(s) ran as "
              f"{fleet['launches']} launch(es), report digest "
              f"{fleet['digest']}")

        print("tune-smoke OK")
        return 0
    finally:
        shutil.rmtree(fleet_root, ignore_errors=True)
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


if __name__ == "__main__":
    raise SystemExit(main())
