#!/usr/bin/env python
"""Device-fault-domain smoke: a REAL server under an injected fault plan
(`make fault-smoke`, also a tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ISSUE 14, ARCHITECTURE.md §18):

1. Healthy reference: a clean server admits the cluster and answers the
   singleton placement digest.
2. Poisoned launch: a server started with
   ``--fault-plan fn=serving_lanes,exc=numeric,launch=1,times=1;
               fn=serving_lanes,exc=oom,launch=4,times=2``
   must answer the poisoned request (launch #1) with a STRUCTURED 5xx
   (code E_NUMERIC, never a bare traceback body) while the sibling
   requests before/after it answer 200 with the HEALTHY digest.
3. Degradation ladder: the OOM pair at launches #4/#5 walks
   cache_drop -> resident_drop and the request still answers 200 with
   the healthy digest — the degraded path is the same answer, later.
4. ``simon_fault_*`` counters scraped from /metrics match the plan
   exactly (3 injected faults), and the rung counters show the ladder.
5. SIGTERM: the faulted server still drains and exits 0.
"""

from __future__ import annotations

import json
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

CLUSTER_YAML = """
apiVersion: v1
kind: Node
metadata: {name: f0}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
---
apiVersion: v1
kind: Node
metadata: {name: f1}
status:
  allocatable: {cpu: "4", memory: 8Gi, pods: "110"}
---
apiVersion: apps/v1
kind: Deployment
metadata: {name: smoke, namespace: default}
spec:
  replicas: 4
  selector: {matchLabels: {app: smoke}}
  template:
    metadata: {labels: {app: smoke}}
    spec:
      containers:
        - name: c
          image: registry.local/s:1
          resources: {requests: {cpu: "1", memory: 1Gi}}
"""

FAULT_PLAN = ("fn=serving_lanes,exc=numeric,launch=1,times=1;"
              "fn=serving_lanes,exc=oom,launch=4,times=2")
PLAN_INJECTIONS = 3  # 1 numeric + 2 oom — what the counters must show


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
            raw = r.read()
            return r.status, (json.loads(raw) if path != "/metrics"
                              else raw.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _start_server(env, *extra):
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "open_simulator_tpu.cli", "server",
         "--port", str(port), *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    base = f"http://127.0.0.1:{port}"
    deadline = time.time() + 60
    while True:
        try:
            status, _ = _call(base, "GET", "/healthz", timeout=1.0)
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


def _metric(text: str, name: str, **labels) -> float:
    want = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    total = 0.0
    hit = False
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        m = re.match(r"^%s\{([^}]*)\}\s+([0-9.eE+-]+)$" % re.escape(name),
                     line)
        if not m:
            continue
        have = ",".join(sorted(p.strip() for p in m.group(1).split(",")))
        if all(f'{k}="{v}"' in have for k, v in labels.items()) or not want:
            total += float(m.group(2))
            hit = True
    if not hit:
        raise AssertionError(f"metric {name}{labels} not found")
    return total


def _stop(proc) -> int:
    proc.send_signal(signal.SIGTERM)
    return proc.wait(60)


def main() -> int:
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    # ---- stage 1: healthy reference digest -----------------------------
    proc, base = _start_server(env)
    try:
        status, out = _call(base, "POST", "/api/simulate",
                            {"cluster": {"yaml": CLUSTER_YAML}})
        assert status == 200, (status, out)
        healthy_digest = out["digest"]
        snapshot = out["snapshot_digest"]
    finally:
        rc = _stop(proc)
    assert rc == 0, f"healthy server exited {rc}"
    print(f"fault-smoke stage 1 OK: healthy digest {healthy_digest}")

    # ---- stage 2+: the same server under an injected fault plan --------
    proc, base = _start_server(env, "--fault-plan", FAULT_PLAN)
    try:
        # launch #0: the admit — healthy, digest must reproduce
        status, out = _call(base, "POST", "/api/simulate",
                            {"cluster": {"yaml": CLUSTER_YAML}})
        assert status == 200 and out["digest"] == healthy_digest, (
            status, out)
        assert out["snapshot_digest"] == snapshot

        # launch #1: the poisoned request — structured 5xx, never a bare
        # traceback (the body carries the taxonomy code + message)
        status, bad = _call(base, "POST", "/api/simulate",
                            {"base": snapshot})
        assert status == 500 and bad.get("code") == "E_NUMERIC", (
            status, bad)
        assert "non-finite" in bad.get("error", ""), bad
        print(f"fault-smoke stage 2 OK: poisoned launch answered "
              f"structured 500 E_NUMERIC")

        # launches #2, #3: siblings after the fault answer 200 with the
        # healthy digest
        for _ in range(2):
            status, ok = _call(base, "POST", "/api/simulate",
                               {"base": snapshot})
            assert status == 200 and ok["digest"] == healthy_digest, (
                status, ok)

        # launches #4..#6: the OOM pair walks the ladder —
        # cache_drop (exec cache) then resident_drop (snapshots) — and
        # the request STILL answers the healthy digest
        status, degraded = _call(base, "POST", "/api/simulate",
                                 {"base": snapshot})
        assert status == 200 and degraded["digest"] == healthy_digest, (
            status, degraded)
        print(f"fault-smoke stage 3 OK: post-fault degraded path "
              f"returned the healthy digest {healthy_digest}")

        # ---- counters match the plan exactly ---------------------------
        status, metrics = _call(base, "GET", "/metrics")
        assert status == 200
        injected = _metric(metrics, "simon_fault_injected_total",
                           fn="serving_lanes")
        assert injected == PLAN_INJECTIONS, (injected, PLAN_INJECTIONS)
        for rung in ("cache_drop", "resident_drop"):
            n = _metric(metrics, "simon_fault_rungs_total",
                        fn="serving_lanes", rung=rung)
            assert n == 1, (rung, n)
        classified = _metric(metrics, "simon_fault_classified_total",
                             fn="serving_lanes")
        assert classified >= 2, classified  # numeric + the final oom
        print(f"fault-smoke stage 4 OK: simon_fault_injected_total == "
              f"{PLAN_INJECTIONS} (the plan), ladder rungs counted")

        # ---- SIGTERM: the faulted server still drains clean ------------
    finally:
        if proc.poll() is None:
            rc = _stop(proc)
        else:
            rc = proc.returncode
        out = proc.stdout.read() if proc.stdout else ""
        if out and "--verbose" in sys.argv:
            print("--- server output ---")
            print(out)
    assert rc == 0, f"faulted server exited {rc}"
    print("fault-smoke stage 5 OK: SIGTERM drain exited 0 under the plan")
    print("fault-smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
