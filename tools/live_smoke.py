#!/usr/bin/env python
"""Live-operations smoke: the streaming event feed, the device-memory
ledger, and `simon-tpu top` against a REAL server process
(`make live-smoke`, also a tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ARCHITECTURE.md §21):

1. Causal stream: an SSE subscriber on GET /api/events?follow=1 watches
   a traced POST /api/simulate happen live — enqueue through launch to
   response, every frame carrying the request's trace id — and
   GET /api/trace/<id> reconstructs the same causal sequence.
2. Slow subscriber: a follower with a 1-slot queue that stops reading
   loses events (counted in /debug/stats events_feed + the
   simon_events_dropped_total counter) while a burst of requests all
   answer 200 promptly — the feed never blocks a worker.
3. Devmem ledger: /debug/stats shows per-owner device bytes
   (resident snapshots + executables after the warmed launch), the
   simon_devmem_bytes / simon_devmem_peak_bytes /
   simon_launch_seconds families render on /metrics, and the owner
   total matches the gauge total.
4. top: `simon-tpu top --once` renders one snapshot frame (no curses,
   no TTY needed) showing the queue, devmem owners and launch
   latencies of the live server.
5. SIGTERM under follow: a live SSE stream ends cleanly when the
   server drains (its last event is the drain record), in-flight
   probes answer 200/503, the server exits 0.
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
         "--port", str(port), "--workers", "2",
         "--blackbox-events", "2048"],
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


class _SSEReader:
    """Follow /api/events on a raw socket, parsing frames into a list.

    urllib buffers too aggressively for an unbounded stream, so this
    speaks just enough HTTP: one GET, skip headers, split `\\n\\n`
    frames into (event, data-dict) pairs as they arrive.
    """

    def __init__(self, host, port, path):
        self.events = []
        self.lock = threading.Lock()
        self.ended = threading.Event()
        self.sock = socket.create_connection((host, port), timeout=120)
        req = (f"GET {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
               f"Accept: text/event-stream\r\n\r\n")
        self.sock.sendall(req.encode())
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        buf = b""
        headers_done = False
        try:
            while True:
                chunk = self.sock.recv(65536)
                if not chunk:
                    break
                buf += chunk
                if not headers_done:
                    idx = buf.find(b"\r\n\r\n")
                    if idx < 0:
                        continue
                    headers_done = True
                    buf = buf[idx + 4:]
                while b"\n\n" in buf:
                    frame, buf = buf.split(b"\n\n", 1)
                    self._frame(frame.decode("utf-8", "replace"))
        except OSError:
            pass
        finally:
            self.ended.set()

    def _frame(self, text):
        kind, data = None, None
        for line in text.splitlines():
            if line.startswith("event: "):
                kind = line[len("event: "):]
            elif line.startswith("data: "):
                data = line[len("data: "):]
        if kind is None and data is None:
            return  # comment/keepalive frame
        try:
            payload = json.loads(data) if data else {}
        except ValueError:
            payload = {"raw": data}
        with self.lock:
            self.events.append((kind, payload))

    def snapshot(self):
        with self.lock:
            return list(self.events)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass
        self.thread.join(10)


def _wait_for(pred, timeout=30.0, interval=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(interval)
    return None


def _drain(proc):
    if proc.poll() is None:
        proc.kill()
    return proc.stdout.read() if proc.stdout else ""


def main() -> int:
    ckpt = tempfile.mkdtemp(prefix="simon-live-smoke-")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SIMON_CHECKPOINT_DIR": ckpt,
           "SIMON_LEDGER_DIR": os.path.join(ckpt, "ledger")}
    port = _free_port()
    proc, base = _start_server(port, env)
    out = ""
    try:
        # ---- stage 1: SSE follower sees the causal sequence live -------
        reader = _SSEReader("127.0.0.1", port,
                            "/api/events?follow=1&replay=0")
        # the subscriber must be attached before the request fires
        assert _wait_for(lambda: _call(
            base, "GET", "/debug/stats")[2]["events_feed"]["subscribers"]
            >= 1, 15), "SSE subscriber never registered"
        tid = "live-smoke-1"
        status, echo, admitted = _call(base, "POST", "/api/simulate",
                                       {"cluster": {"yaml": CLUSTER_YAML}},
                                       trace=tid)
        assert status == 200 and echo == tid, (status, echo)
        digest = admitted["snapshot_digest"]

        def traced():
            evs = [(k, p) for k, p in reader.snapshot()
                   if tid in (p.get("traces") or [])]
            kinds = [k for k, _ in evs]
            if {"enqueue", "launch", "response"} <= set(kinds):
                return evs
            return None

        evs = _wait_for(traced, 30)
        assert evs, ("stream never showed the causal sequence",
                     reader.snapshot()[-10:])
        stream_kinds = [k for k, _ in evs]
        status, _, tl = _call(base, "GET", f"/api/trace/{tid}")
        assert status == 200, (status, tl)
        timeline_kinds = [e["kind"] for e in tl["events"]]
        for want in ("enqueue", "dequeue", "launch", "response"):
            assert want in timeline_kinds, (want, timeline_kinds)
        # the stream saw the same causal events the timeline reconstructs
        missing = [k for k in stream_kinds if k not in timeline_kinds]
        assert not missing, (missing, stream_kinds, timeline_kinds)
        reader.close()
        print(f"live-smoke stage 1 OK: SSE follower saw {stream_kinds} "
              f"live for trace {tid}; /api/trace/{tid} reconstructs the "
              f"same causal sequence ({timeline_kinds})")

        # ---- stage 2: slow subscriber drops, requests never stall ------
        slow = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # a tiny receive window (set BEFORE connect so the handshake
        # advertises it) makes the server-side writer block fast
        slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        slow.settimeout(120)
        slow.connect(("127.0.0.1", port))
        slow.sendall((f"GET /api/events?follow=1&replay=0&queue=1 "
                      f"HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n\r\n"
                      ).encode())
        slow.recv(1024)  # headers only — then stop reading forever
        assert _wait_for(lambda: _call(
            base, "GET", "/debug/stats")[2]["events_feed"]["subscribers"]
            >= 1, 15), "slow subscriber never registered"
        t0 = time.time()
        statuses = []
        for i in range(60):
            s, _, _ = _call(base, "POST", "/api/simulate",
                            {"base": digest}, timeout=60.0,
                            trace=f"live-burst-{i}")
            statuses.append(s)
        elapsed = time.time() - t0
        assert all(s == 200 for s in statuses), statuses
        feed = _wait_for(lambda: (
            lambda f: f if (f["dropped"] or f["subscriber_dropped"])
            else None)(_call(base, "GET", "/debug/stats")[2]["events_feed"]),
            20)
        assert feed, "slow subscriber never dropped an event"
        slow.close()
        print(f"live-smoke stage 2 OK: 60 requests answered 200 in "
              f"{elapsed:.1f}s while the stalled subscriber dropped "
              f"{feed['dropped']} event(s) (queue=1) — no worker blocked")

        # ---- stage 3: devmem owners on /debug/stats + /metrics ---------
        status, _, stats = _call(base, "GET", "/debug/stats")
        assert status == 200, status
        dm = stats["devmem"]
        owners = dm["owners"]
        assert owners.get("resident_snapshots", 0) > 0, dm
        assert "executables" in owners, dm
        assert dm["peak_total"] >= dm["total"] >= 0, dm
        assert stats["launches"], stats.get("launches")
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        for fam in ("simon_devmem_bytes", "simon_devmem_peak_bytes",
                    "simon_launch_seconds_bucket", "simon_events_"):
            assert fam in metrics, f"{fam} missing from /metrics"
        gauge_total = sum(
            float(line.rsplit(None, 1)[1])
            for line in metrics.splitlines()
            if line.startswith("simon_devmem_bytes{"))
        assert abs(gauge_total - dm["total"]) <= max(
            1 << 20, 0.25 * max(gauge_total, dm["total"])), (
            gauge_total, dm["total"])
        print(f"live-smoke stage 3 OK: devmem owners {sorted(owners)} "
              f"hold {dm['total']} byte(s) (peak {dm['peak_total']}); "
              f"devmem + launch-histogram + events families render on "
              f"/metrics and the gauge total matches the ledger")

        # ---- stage 4: `simon-tpu top --once` renders a frame -----------
        top = subprocess.run(
            [sys.executable, "-m", "open_simulator_tpu.cli", "top",
             "--server", base, "--once"],
            env=env, capture_output=True, text=True, timeout=120)
        assert top.returncode == 0, (top.returncode, top.stderr)
        frame = top.stdout
        for needle in ("queue", "devmem", "resident_snapshots"):
            assert needle in frame, (needle, frame)
        print(f"live-smoke stage 4 OK: `simon-tpu top --once` rendered a "
              f"{len(frame.splitlines())}-line frame (queue, devmem "
              f"owners, launch latencies)")

        # ---- stage 5: SIGTERM ends the stream cleanly, exit 0 ----------
        reader = _SSEReader("127.0.0.1", port,
                            "/api/events?follow=1&replay=0")
        assert _wait_for(lambda: _call(
            base, "GET", "/debug/stats")[2]["events_feed"]["subscribers"]
            >= 1, 15), "final subscriber never registered"
        results = []
        lock = threading.Lock()

        def fire(i):
            r = _call(base, "POST", "/api/simulate", {"base": digest},
                      timeout=60.0, trace=f"live-drain-{i}")
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
        assert reader.ended.wait(30), "stream never ended after SIGTERM"
        final = reader.snapshot()
        kinds = [k for k, _ in final]
        assert "drain" in kinds, kinds[-10:]
        reader.close()
        for status, _, body in results:
            assert status in (200, 503), (status, body)
        print(f"live-smoke stage 5 OK: SIGTERM under {len(results)} "
              f"probes (statuses {sorted(r[0] for r in results)}); the "
              f"follower's stream ended after a drain event, server "
              f"exited 0")
    finally:
        out = _drain(proc)
        if out and "--verbose" in sys.argv:
            print("--- server output ---")
            print(out)

    print("live-smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
