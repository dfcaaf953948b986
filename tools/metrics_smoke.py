"""Smoke stage: boot the REST server, simulate once over HTTP, scrape
/metrics, and assert the core series are present (tools/smoke.sh).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Runs the real ThreadingHTTPServer on a loopback port (not handler calls
in-process) so the scrape exercises exactly what an operator's Prometheus
would: request accounting, the scheduling-phase histogram, simulation
counters, the admission family, and the explain endpoint over the last
result.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import urllib.request
from http.server import ThreadingHTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from open_simulator_tpu.server.rest import SimulationServer, _make_handler  # noqa: E402

CLUSTER_YAML = """
apiVersion: v1
kind: Node
metadata: {name: smoke-0}
status:
  allocatable: {cpu: '4', memory: 8Gi, pods: '110'}
"""

APP_YAML = """
apiVersion: v1
kind: Pod
metadata: {name: smoke-pod, namespace: default}
spec:
  containers:
    - name: c
      resources: {requests: {cpu: 100m}}
---
apiVersion: v1
kind: Pod
metadata: {name: smoke-too-big, namespace: default}
spec:
  containers:
    - name: c
      resources: {requests: {cpu: '64'}}
"""

REQUIRED_SERIES = [
    "simon_http_requests_total",        # request accounting
    "simon_http_request_seconds",       # request latency histogram
    "simon_phase_seconds",              # encode/schedule/decode spans
    "simon_simulations_total",          # scheduling counters
    "simon_pods_scheduled_total",
    "simon_pods_unscheduled_total",
    "simon_compile_cache_total",        # jit cache accounting
    "simon_admission_rejections_total", # admission family
    "simon_jax_devices",                # runtime gauges
]


def main() -> int:
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(SimulationServer()))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        with urllib.request.urlopen(url + "/healthz") as resp:
            assert json.loads(resp.read())["status"] == "healthy"

        body = json.dumps({
            "cluster": {"yaml": CLUSTER_YAML},
            "apps": [{"name": "smoke", "yaml": APP_YAML}],
        }).encode()
        req = urllib.request.Request(url + "/api/deploy-apps", data=body)
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        if len(out["unscheduled_pods"]) != 1:
            print(f"unexpected deploy result: {out}", file=sys.stderr)
            return 1

        with urllib.request.urlopen(url + "/metrics") as resp:
            text = resp.read().decode()
        missing = [s for s in REQUIRED_SERIES if s not in text]
        if missing:
            print(f"missing series on /metrics: {missing}", file=sys.stderr)
            print(text, file=sys.stderr)
            return 1

        with urllib.request.urlopen(url + "/api/explain?top_k=2") as resp:
            report = json.loads(resp.read())
        unsched = [p for p in report["pods"] if p["status"] == "unscheduled"]
        if not unsched or not unsched[0].get("first_failing_op"):
            print(f"explain did not decode the failure: {report}", file=sys.stderr)
            return 1
        sched = [p for p in report["pods"] if p["status"] == "scheduled"]
        if not sched or not sched[0].get("candidates"):
            print(f"explain has no candidate breakdown: {report}", file=sys.stderr)
            return 1
        print("telemetry smoke OK: "
              f"{len(REQUIRED_SERIES)} series present, explain decoded "
              f"{unsched[0]['first_failing_op']!r} and "
              f"{len(sched[0]['candidates'])} candidate(s) for "
              f"{sched[0]['pod']}")
        return 0
    finally:
        httpd.shutdown()


if __name__ == "__main__":
    raise SystemExit(main())
