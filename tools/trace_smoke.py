#!/usr/bin/env python
"""Causal-tracing smoke: trace ids + the black box against a REAL server
process (`make trace-smoke`, also a tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ARCHITECTURE.md §20):

1. Client-supplied trace id: POST /api/simulate with `X-Simon-Trace-Id`
   — the response echoes the id, and GET /api/trace/<id> reconstructs
   the causal timeline: queue admission with measured wait, the
   (coalesced) launch, the final 200. An unknown id is a structured
   404 E_NO_TRACE.
2. Journal causality: a journaled session fed events under a trace id
   shows the durable appends in that request's timeline.
3. Cost profiles: /debug/executables lists the warmed executable with
   a nonzero compile-time cost; the simon_exec_cost_* /
   simon_trace_events_total families render on /metrics.
4. Fault narrative: a second server under a deterministic
   SIMON_FAULT_PLAN (persistent OOM on the serving launch) answers a
   structured 5xx whose timeline records the degradation rungs walked
   and the numbered attempts — and the black box auto-dumped a
   trace:dump event into the run ledger.
5. SIGTERM under load: in-flight traced probes answer 200/503 (never
   dropped), the server exits 0.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TRACE_HEADER = "X-Simon-Trace-Id"

CLUSTER_YAML = """
apiVersion: v1
kind: Node
metadata: {name: s0, labels: {topology.kubernetes.io/zone: z0}}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
---
apiVersion: v1
kind: Node
metadata: {name: s1, labels: {topology.kubernetes.io/zone: z1}}
status:
  allocatable: {cpu: "4", memory: 8Gi, pods: "110"}
---
apiVersion: apps/v1
kind: Deployment
metadata: {name: smoke, namespace: default}
spec:
  replicas: 3
  selector: {matchLabels: {app: smoke}}
  template:
    metadata: {labels: {app: smoke}}
    spec:
      containers:
        - name: c
          image: registry.local/s:1
          resources: {requests: {cpu: "1", memory: 1Gi}}
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _call(base, method, path, payload=None, timeout=300.0, trace=None):
    data = None if payload is None else json.dumps(payload).encode()
    headers = {"Content-Type": "application/json"}
    if trace:
        headers[TRACE_HEADER] = trace
    req = urllib.request.Request(
        base + path, data=data, method=method, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.headers.get(TRACE_HEADER), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get(TRACE_HEADER), json.loads(e.read())


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
            status, _, _ = _call(base, "GET", "/test", timeout=1.0)
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


def _workload():
    import yaml

    from open_simulator_tpu.replay import (
        synthetic_replay_cluster,
        synthetic_trace_dict,
    )

    td = synthetic_trace_dict(n_batches=2, batch_pods=3, depart_every=2,
                              max_new_nodes=2)
    cluster = synthetic_replay_cluster(n_nodes=3, n_initial_pods=3)
    docs = ([{"apiVersion": "v1", "kind": "Node", **n.raw}
             for n in cluster.nodes]
            + [{"apiVersion": "v1", "kind": "Pod", **p.raw}
               for p in cluster.pods])
    return yaml.safe_dump_all(docs), td


def _drain(proc):
    if proc.poll() is None:
        proc.kill()
    return proc.stdout.read() if proc.stdout else ""


def main() -> int:
    ckpt = tempfile.mkdtemp(prefix="simon-trace-smoke-")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SIMON_CHECKPOINT_DIR": ckpt,
           "SIMON_LEDGER_DIR": os.path.join(ckpt, "ledger")}
    proc, base = _start_server(_free_port(), env)
    out = ""
    try:
        # ---- stage 1: client trace id -> echoed -> causal timeline -----
        tid = "smoke-trace-1"
        status, echo, admitted = _call(base, "POST", "/api/simulate",
                                       {"cluster": {"yaml": CLUSTER_YAML}},
                                       trace=tid)
        assert status == 200, (status, admitted)
        assert echo == tid, f"response header echoed {echo!r}, not {tid!r}"
        digest = admitted["snapshot_digest"]
        status, _, tl = _call(base, "GET", f"/api/trace/{tid}")
        assert status == 200 and tl["trace_id"] == tid, (status, tl)
        kinds = [e["kind"] for e in tl["events"]]
        for want in ("enqueue", "dequeue", "launch", "response"):
            assert want in kinds, (want, kinds)
        s = tl["summary"]
        assert s["status"] == 200 and s["queue_wait_ms"] is not None, s
        assert s["launches"] >= 1, s
        status, _, body = _call(base, "GET", "/api/trace/not-a-trace")
        assert status == 404 and body["code"] == "E_NO_TRACE", (status, body)
        print(f"trace-smoke stage 1 OK: trace {tid} echoed, timeline has "
              f"queue wait {s['queue_wait_ms']}ms + {s['launches']} "
              f"launch(es); unknown id answered 404 E_NO_TRACE")

        # ---- stage 2: journal appends land in the feeding request ------
        cluster_yaml, td = _workload()
        status, _, sess = _call(base, "POST", "/api/session", {
            "cluster": {"yaml": cluster_yaml}, "name": "trace-smoke",
            "spec": {"max_new_nodes": td["max_new_nodes"],
                     "node_template": td["node_template"]},
        }, trace="smoke-session-create")
        assert status == 200, (status, sess)
        sid = sess["session_id"]
        jid = "smoke-journal"
        status, _, fed = _call(base, "POST", f"/api/session/{sid}/events",
                               {"events": td["events"]}, trace=jid)
        assert status == 200, (status, fed)
        status, _, tl = _call(base, "GET", f"/api/trace/{jid}")
        assert status == 200, (status, tl)
        appends = tl["summary"]["journal_appends"]
        assert appends >= 1, tl["summary"]
        print(f"trace-smoke stage 2 OK: feeding session {sid} under trace "
              f"{jid} recorded {appends} durable journal append(s)")

        # ---- stage 3: warmed executable shows a nonzero cost -----------
        status, _, dbg = _call(base, "GET", "/debug/executables")
        assert status == 200 and dbg["entries"], (status, dbg)
        costs = [row.get("cost", {}) for row in dbg["entries"]]
        assert any(c.get("compile_s", 0) > 0 or c.get("flops", 0) > 0
                   for c in costs), costs
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        assert "simon_trace_events_total" in metrics, "trace family missing"
        assert "simon_exec_cost_" in metrics, "cost families missing"
        print(f"trace-smoke stage 3 OK: {len(dbg['entries'])} cached "
              f"executable(s) with harvested costs; trace + cost families "
              f"render on /metrics")

        # ---- stage 4: deterministic fault -> rungs + auto-dump ---------
        fault_env = {**env,
                     "SIMON_LEDGER_DIR": os.path.join(ckpt, "fault-ledger"),
                     "SIMON_FAULT_PLAN": "fn=serving_lanes,exc=oom,times=99"}
        fproc, fbase = _start_server(_free_port(), fault_env)
        try:
            fid = "smoke-fault"
            status, _, body = _call(fbase, "POST", "/api/simulate",
                                    {"cluster": {"yaml": CLUSTER_YAML}},
                                    trace=fid)
            assert status == 503 and body["code"] == "E_DEVICE_OOM", (
                status, body)
            status, _, tl = _call(fbase, "GET", f"/api/trace/{fid}")
            assert status == 200, (status, tl)
            s = tl["summary"]
            assert s["error_code"] == "E_DEVICE_OOM" and s["status"] == 503, s
            rungs = [r["rung"] for r in s["rungs"]]
            assert "cache_drop" in rungs, s
            assert s["attempts"] >= 2, s  # initial + post-rung retries
            assert s["queue_wait_ms"] is not None and s["launches"] >= 1, s
            # the structured 5xx auto-dumped the black box to the ledger
            status, _, runs = _call(fbase, "GET",
                                    "/api/runs?surface=trace:dump")
            assert status == 200 and runs.get("runs"), (status, runs)
            print(f"trace-smoke stage 4 OK: persistent OOM answered a "
                  f"structured 503 whose timeline walked rungs {rungs} "
                  f"over {s['attempts']} attempts; trace:dump ledger "
                  f"event written")
        finally:
            fout = _drain(fproc)
            if fout and "--verbose" in sys.argv:
                print("--- fault server output ---")
                print(fout)

        # ---- stage 5: SIGTERM under traced load, exit 0 ----------------
        results = []
        lock = threading.Lock()

        def fire(i):
            r = _call(base, "POST", "/api/simulate", {"base": digest},
                      timeout=60.0, trace=f"smoke-drain-{i}")
            with lock:
                results.append(r)

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        for t in threads:
            t.join(60.0)
        rc = proc.wait(60)
        assert rc == 0, f"drained server exited {rc}"
        for status, _, body in results:
            assert status in (200, 503), (status, body)
        print(f"trace-smoke stage 5 OK: SIGTERM under {len(results)} "
              f"traced probes (statuses "
              f"{sorted(r[0] for r in results)}), server exited 0")
    finally:
        out = _drain(proc)
        if out and "--verbose" in sys.argv:
            print("--- server output ---")
            print(out)

    print("trace-smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
