#!/usr/bin/env python
"""Digital-twin session smoke: the crash-safety contract against a REAL
server process (`make session-smoke`, also a tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ISSUE 11):

1. Create a journaled session on a live server (synthetic cluster +
   autoscaler), feed the first event batch, record the digest.
2. SIGKILL the server process — a real uncatchable kill. Restart a new
   server over the same checkpoint dir: the session must be listed open
   with a BIT-IDENTICAL digest, and the remaining events must settle.
3. Bit-identity: a fresh reference session on the restarted server fed
   ALL events at once must land on the same trajectory digest (the
   journal + batching-invariant row canonicalization at work).
4. Fork isolation: a chaos what-if fork completes and returns its own
   digest while the mainline digest is untouched; a poisoned fork
   (unknown node target) is quarantined with a structured error; the
   mainline keeps settling events after both.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPLIT = 3  # events fed before the SIGKILL


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


def _workload():
    import yaml

    from open_simulator_tpu.replay import (
        synthetic_replay_cluster,
        synthetic_trace_dict,
    )

    td = synthetic_trace_dict(n_batches=4, batch_pods=4, depart_every=2,
                              max_new_nodes=4)
    cluster = synthetic_replay_cluster(n_nodes=3, n_initial_pods=3)
    docs = ([{"apiVersion": "v1", "kind": "Node", **n.raw}
             for n in cluster.nodes]
            + [{"apiVersion": "v1", "kind": "Pod", **p.raw}
               for p in cluster.pods])
    return yaml.safe_dump_all(docs), td


def main() -> int:
    ckpt = tempfile.mkdtemp(prefix="simon-session-smoke-")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SIMON_CHECKPOINT_DIR": ckpt}
    cluster_yaml, td = _workload()
    create_body = {
        "cluster": {"yaml": cluster_yaml},
        "name": "smoke",
        "spec": {"max_new_nodes": td["max_new_nodes"],
                 "node_template": td["node_template"]},
        "controllers": [{"kind": "autoscaler", "scale_step": 2}],
    }
    events = td["events"]

    # ---- stage 1: create + feed, then SIGKILL --------------------------
    proc, base = _start_server(_free_port(), env)
    try:
        status, sess = _call(base, "POST", "/api/session", create_body)
        assert status == 200 and sess["steps"] == 1, (status, sess)
        sid = sess["session_id"]
        status, fed = _call(base, "POST", f"/api/session/{sid}/events",
                            {"events": events[:SPLIT]})
        assert status == 200, (status, fed)
        digest_killed = fed["digest"]
        print(f"session-smoke stage 1 OK: session {sid} fed {SPLIT} "
              f"events, digest {digest_killed}")
    finally:
        proc.kill()  # SIGKILL: no drain, no flush — the journal is all
        proc.wait(30)

    # ---- stage 2: restart, resume, continue ----------------------------
    proc, base = _start_server(_free_port(), env)
    try:
        status, listing = _call(base, "GET", "/api/session")
        ids = [s["session_id"] for s in listing.get("sessions", [])]
        assert status == 200 and sid in ids, (status, listing)
        status, st = _call(base, "GET", f"/api/session/{sid}")
        assert status == 200 and st["digest"] == digest_killed, (
            f"resumed digest {st.get('digest')} != pre-kill "
            f"{digest_killed}")
        status, fed = _call(base, "POST", f"/api/session/{sid}/events",
                            {"events": events[SPLIT:]})
        assert status == 200, (status, fed)
        digest_resumed = fed["digest"]
        print(f"session-smoke stage 2 OK: SIGKILL'd server restarted, "
              f"session resumed digest-identical, {len(events) - SPLIT} "
              f"more events settled")

        # ---- stage 3: bit-identity vs an uninterrupted reference -------
        status, ref = _call(base, "POST", "/api/session",
                            {**create_body, "name": "reference"})
        assert status == 200, (status, ref)
        rid = ref["session_id"]
        status, reffed = _call(base, "POST", f"/api/session/{rid}/events",
                               {"events": events})
        assert status == 200, (status, reffed)
        assert reffed["digest"] == digest_resumed, (
            f"resumed trajectory digest {digest_resumed} != "
            f"uninterrupted reference {reffed['digest']}")
        print(f"session-smoke stage 3 OK: resumed digest bit-identical "
              f"to an uninterrupted run ({digest_resumed})")

        # ---- stage 4: fork isolation ------------------------------------
        t_next = events[-1]["t"] + 10
        status, fork = _call(base, "POST", f"/api/session/{sid}/fork", {
            "name": "chaos", "events": [
                {"t": t_next, "kind": "kill_node", "target": "rn-1"}]})
        assert status == 200 and fork["status"] == "completed", (
            status, fork)
        assert fork["mainline_digest"] == digest_resumed
        status, st = _call(base, "GET", f"/api/session/{sid}")
        assert st["digest"] == digest_resumed, (
            "the fork disturbed the mainline digest")
        status, poison = _call(base, "POST", f"/api/session/{sid}/fork", {
            "name": "poison", "events": [
                {"t": t_next, "kind": "node_remove",
                 "target": "no-such-node"}]})
        assert status == 200 and poison["status"] == "quarantined", (
            status, poison)
        assert poison["error"]["code"], poison
        status, more = _call(base, "POST", f"/api/session/{sid}/events",
                             {"events": [{"t": t_next + 1,
                                          "kind": "kill_node",
                                          "target": "rn-0"}]})
        assert status == 200, (status, more)
        assert more["status"]["steps"] == st["steps"] + 1
        status, _ = _call(base, "DELETE", f"/api/session/{sid}")
        assert status == 200
        print("session-smoke stage 4 OK: chaos fork completed and the "
              "poisoned fork quarantined while the mainline advanced")
    finally:
        if proc.poll() is None:
            proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        if out and "--verbose" in sys.argv:
            print("--- server output ---")
            print(out)

    import shutil

    shutil.rmtree(ckpt, ignore_errors=True)
    print("session-smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
