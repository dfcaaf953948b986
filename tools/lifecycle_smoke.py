"""Lifecycle smoke: graceful drain end to end against a REAL server
process (tools/smoke.sh stage, `make lifecycle-smoke`).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Scenario (ISSUE 6 satellite): start `simon-tpu server`, put one request
in flight, SIGTERM the process, then assert

  1. /readyz flips to 503 while /healthz still answers 200 (readiness
     and liveness diverge: out-of-rotation, not restart),
  2. new POSTs are rejected 503 E_BUSY ("draining"),
  3. the in-flight request still completes 200,
  4. the process exits 0 and its final ledger record
     (surface "server:drain") is on disk,
  5. (ISSUE 11) an open digital-twin session created before the SIGTERM
     is served by a RESTARTED server with its drained-through digest
     intact, and keeps settling events.
"""

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

CLUSTER_YAML = """
apiVersion: v1
kind: Node
metadata: {name: s0}
status:
  allocatable: {cpu: "8", memory: 16Gi, pods: "110"}
"""

APP_YAML = """
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
          resources: {requests: {cpu: "1", memory: 1Gi}}
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(url: str, payload: dict, timeout: float = 120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def main() -> int:
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    ledger_dir = tempfile.mkdtemp(prefix="simon-lifecycle-smoke-")
    ckpt_dir = tempfile.mkdtemp(prefix="simon-lifecycle-ckpt-")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SIMON_CHECKPOINT_DIR": ckpt_dir}
    proc = subprocess.Popen(
        [sys.executable, "-m", "open_simulator_tpu.cli", "server",
         "--port", str(port), "--ledger-dir", ledger_dir,
         "--drain-timeout", "60"],
        env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 60
        while True:
            try:
                status, _ = _get(base + "/test", timeout=1.0)
                if status == 200:
                    break
            except OSError:
                pass
            if time.time() > deadline:
                raise SystemExit("server never came up")
            if proc.poll() is not None:
                raise SystemExit(f"server exited early rc={proc.returncode}")
            time.sleep(0.2)

        status, ready = _get(base + "/readyz")
        assert status == 200 and ready == {"ready": True}, (status, ready)

        # an open digital-twin session that must survive the drain
        status, sess = _post(base + "/api/session", {
            "cluster": {"yaml": CLUSTER_YAML}, "name": "drain-smoke"})
        assert status == 200 and sess["steps"] == 1, (status, sess)
        sid = sess["session_id"]
        status, fed = _post(base + f"/api/session/{sid}/events", {
            "events": [{"t": 1, "kind": "arrive",
                        "app": {"name": "smoke", "yaml": APP_YAML}}]})
        assert status == 200, (status, fed)
        sess_digest = fed["digest"]
        print(f"lifecycle: session {sid} open with 2 settled steps")

        # one request in flight: the FIRST simulation in the process has
        # the XLA compile ahead of it — seconds of real work to drain over
        box = {}

        def inflight():
            box["resp"] = _post(base + "/api/deploy-apps", {
                "cluster": {"yaml": CLUSTER_YAML},
                "apps": [{"name": "smoke", "yaml": APP_YAML}],
            })

        t = threading.Thread(target=inflight)
        t.start()
        time.sleep(0.75)  # the POST is queued/compiling, nowhere near done
        assert t.is_alive(), "in-flight request finished too fast to test drain"
        proc.send_signal(signal.SIGTERM)

        # readyz flips during drain while healthz stays 200
        flipped_at = None
        deadline = time.time() + 10
        while time.time() < deadline:
            status, body = _get(base + "/readyz")
            if status == 503:
                flipped_at = body
                break
            time.sleep(0.05)
        assert flipped_at == {"ready": False, "draining": True}, flipped_at
        status, hz = _get(base + "/healthz")
        assert status == 200 and hz["status"] == "healthy" and hz["draining"], hz
        print("lifecycle: readyz flipped to 503 while healthz stayed 200")

        status, body = _post(base + "/api/deploy-apps",
                             {"cluster": {"yaml": CLUSTER_YAML}, "apps": []})
        assert status == 503 and body["code"] == "E_BUSY", (status, body)
        print("lifecycle: new request during drain rejected 503 E_BUSY")

        t.join(90)
        assert not t.is_alive(), "in-flight request never completed"
        status, resp = box["resp"]
        assert status == 200 and "placements" in resp, (status, resp)
        print("lifecycle: in-flight request completed 200 during drain")

        rc = proc.wait(timeout=90)
        assert rc == 0, f"server exited rc={rc}"
        with open(os.path.join(ledger_dir, "runs.jsonl"),
                  encoding="utf-8") as f:
            surfaces = [json.loads(ln).get("surface") for ln in f]
        assert "server:drain" in surfaces, surfaces
        print(f"lifecycle: drained clean, final ledger record written "
              f"({surfaces.count('server:drain')} drain record)")

        # restart over the same checkpoint dir: the drained session must
        # come back with its digest intact and keep settling events
        port2 = _free_port()
        base2 = f"http://127.0.0.1:{port2}"
        proc2 = subprocess.Popen(
            [sys.executable, "-m", "open_simulator_tpu.cli", "server",
             "--port", str(port2), "--ledger-dir", ledger_dir],
            env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            deadline = time.time() + 60
            while True:
                try:
                    status, _ = _get(base2 + "/test", timeout=1.0)
                    if status == 200:
                        break
                except OSError:
                    pass
                if time.time() > deadline:
                    raise SystemExit("restarted server never came up")
                if proc2.poll() is not None:
                    raise SystemExit(
                        f"restarted server exited early rc={proc2.returncode}")
                time.sleep(0.2)
            status, listing = _get(base2 + "/api/session")
            ids = [s["session_id"] for s in listing.get("sessions", [])]
            assert status == 200 and sid in ids, (status, listing)
            status, st = _get(base2 + f"/api/session/{sid}")
            assert status == 200 and st["digest"] == sess_digest, (
                status, st, sess_digest)
            status, more = _post(base2 + f"/api/session/{sid}/events", {
                "events": [{"t": 2, "kind": "depart", "app": "smoke"}]})
            assert status == 200 and more["status"]["steps"] == 3, (
                status, more)
            print("lifecycle smoke OK: restarted server resumed the open "
                  "session digest-identical and settled a new event")
        finally:
            if proc2.poll() is None:
                proc2.send_signal(signal.SIGTERM)
                try:
                    proc2.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc2.kill()
        return 0
    finally:
        if proc.poll() is None:
            proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        if out:
            print("--- server output ---")
            print(out)


if __name__ == "__main__":
    raise SystemExit(main())
