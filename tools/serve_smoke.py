#!/usr/bin/env python
"""Serving smoke: the inference-grade path against a REAL server process
(`make serve-smoke`, also a tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ISSUE 12):

1. Admit once, probe many: a full POST to /api/simulate returns the
   snapshot digest; `{"base": digest}` probes answer with the SAME
   placement digest and the resident cache reports the entry.
2. Delta what-ifs: a `remove_nodes` delta probe digests bit-identically
   to a cold full re-encode of the shrunk cluster; a dangling node ref
   is a structured 400 (never a 500), cache state untouched.
3. Mixed coalesced/singleton load with ONE poisoned lane: concurrent
   base probes + an exhaustive /api/capacity sweep against the same
   snapshot, plus one member whose deadline expires in the queue — the
   poisoned lane answers its own 504 E_DEADLINE while every sibling
   returns 200 with the singleton placement digest.
4. SIGTERM drain: with a probe in flight, the server finishes it,
   rejects new work 503, and exits 0 (ARCHITECTURE.md §11).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

CLUSTER_YAML = """
apiVersion: v1
kind: Node
metadata: {name: s0, labels: {topology.kubernetes.io/zone: z0}}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
---
apiVersion: v1
kind: Node
metadata: {name: s1, labels: {topology.kubernetes.io/zone: z0}}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
---
apiVersion: v1
kind: Node
metadata: {name: s2, labels: {topology.kubernetes.io/zone: z1}}
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
         "--port", str(port), "--workers", "2"],
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
    try:
        # ---- stage 1: admit once, probe by digest ----------------------
        status, admitted = _call(base, "POST", "/api/simulate",
                                 {"cluster": {"yaml": CLUSTER_YAML}})
        assert status == 200, (status, admitted)
        digest = admitted["snapshot_digest"]
        singleton = admitted["digest"]
        status, probe = _call(base, "POST", "/api/simulate",
                              {"base": digest})
        assert status == 200 and probe["digest"] == singleton, (
            status, probe)
        status, stats = _call(base, "GET", "/debug/stats")
        resident = stats["resident_snapshots"]
        assert any(e["digest"] == digest
                   for e in resident["snapshots"]), resident
        print(f"serve-smoke stage 1 OK: snapshot {digest} resident, "
              f"base probe digest {singleton}")

        # ---- stage 2: delta probe == cold re-encode; bad ref = 400 -----
        status, hot = _call(base, "POST", "/api/simulate",
                            {"base": digest,
                             "delta": {"remove_nodes": ["s2"]}})
        assert status == 200, (status, hot)
        cold_yaml = "\n---\n".join(doc for doc in CLUSTER_YAML.split("---")
                                   if "name: s2" not in doc)
        status, cold = _call(base, "POST", "/api/simulate",
                             {"cluster": {"yaml": cold_yaml}})
        assert status == 200, (status, cold)
        assert hot["digest"] == cold["digest"], (
            f"delta digest {hot['digest']} != cold re-encode "
            f"{cold['digest']}")
        status, bad = _call(base, "POST", "/api/simulate",
                            {"base": digest,
                             "delta": {"remove_nodes": ["no-such-node"]}})
        assert status == 400 and bad["code"] == "E_BAD_REQUEST", (
            status, bad)
        print(f"serve-smoke stage 2 OK: delta == cold re-encode "
              f"({hot['digest']}), dangling ref answered 400")

        # ---- stage 3: coalesced load, one poisoned lane ----------------
        results = []
        lock = threading.Lock()

        def fire(path, payload):
            r = _call(base, "POST", path, payload)
            with lock:
                results.append((path, payload, r))

        threads = [threading.Thread(target=fire,
                                    args=("/api/simulate", {"base": digest}))
                   for _ in range(5)]
        threads.append(threading.Thread(
            target=fire, args=("/api/capacity",
                               {"base": digest,
                                "sweep_mode": "exhaustive"})))
        for t in threads:
            t.start()
        # the poisoned member: fired while siblings occupy the workers,
        # with a deadline no queued job can meet
        time.sleep(0.05)
        threads.append(threading.Thread(
            target=fire, args=("/api/simulate",
                               {"base": digest, "deadline_s": 0.01})))
        threads[-1].start()
        for t in threads:
            t.join(120.0)
        assert len(results) == 7, results
        poisoned = ok = 0
        for path, payload, (status, body) in results:
            assert status != 500, (path, payload, body)
            if payload.get("deadline_s"):
                assert status == 504 and body["code"] == "E_DEADLINE", (
                    status, body)
                poisoned += 1
            elif path == "/api/capacity":
                assert status == 200, (status, body)
                assert body["lane_digests"][0] == singleton, body
                ok += 1
            else:
                assert status == 200 and body["digest"] == singleton, (
                    status, body)
                ok += 1
        assert poisoned == 1 and ok == 6, results
        print("serve-smoke stage 3 OK: 6 coalesced/singleton siblings "
              "answered 200 with singleton digests; the poisoned lane "
              "got its own 504 E_DEADLINE")

        # ---- stage 4: SIGTERM drain finishes in-flight, exits 0 --------
        drain_result = {}

        def last_probe():
            drain_result["r"] = _call(base, "POST", "/api/simulate",
                                      {"base": digest}, timeout=60.0)

        t = threading.Thread(target=last_probe)
        t.start()
        time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        t.join(60.0)
        rc = proc.wait(60)
        status, body = drain_result.get("r", (None, None))
        # the in-flight probe either finished 200 before the listener
        # closed or was refused 503 while draining — never dropped/500
        assert status in (200, 503), (status, body)
        assert rc == 0, f"drained server exited {rc}"
        print(f"serve-smoke stage 4 OK: SIGTERM drain (in-flight probe "
              f"answered {status}), server exited 0")
    finally:
        if proc.poll() is None:
            proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        if out and "--verbose" in sys.argv:
            print("--- server output ---")
            print(out)

    print("serve-smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
