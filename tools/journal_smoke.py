#!/usr/bin/env python
"""Durable-state fault-domain smoke: the framed-journal integrity
contract against a REAL server process (`make journal-smoke`, also a
tools/smoke.sh stage).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (ISSUE 16, ARCHITECTURE.md §19):

1. Create TWO journaled sessions on a live server, feed events, record
   their digests — then SIGKILL the server (no drain, no flush).
2. Damage the journals the two ways the taxonomy distinguishes: a
   partial FINAL line (the torn tail a crash mid-append leaves) on
   session A, a flipped byte MID-file on session B. The restarted
   server must resume A digest-identically and keep settling events,
   while B answers a structured 409 E_CORRUPT (kind/index/offset in the
   body, never a traceback) and shows up flagged in the session list —
   the sibling is never harmed by the quarantine.
3. A server under ``--fault-plan fn=journal_append,exc=enospc,...``
   walks the shared checkpointing_disabled rung: the session still
   answers 200 (the run continues, crash-safety stops), the status
   carries the degraded journal integrity, and the ``simon_journal_*``
   /metrics counters match the plan.
4. SIGTERM: the degraded server still drains and exits 0.
"""

from __future__ import annotations

import json
import os
import re
import signal
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
SESSION_JOURNAL_SUFFIX = ".session.jsonl"
ENOSPC_PLAN = "fn=journal_append,exc=enospc,launch=2,times=99"


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


def _journal_path(ckpt: str, sid: str) -> str:
    return os.path.join(ckpt, sid + SESSION_JOURNAL_SUFFIX)


def main() -> int:
    ckpt = tempfile.mkdtemp(prefix="simon-journal-smoke-")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SIMON_CHECKPOINT_DIR": ckpt}
    cluster_yaml, td = _workload()
    create_body = {
        "cluster": {"yaml": cluster_yaml},
        "spec": {"max_new_nodes": td["max_new_nodes"],
                 "node_template": td["node_template"]},
        "controllers": [{"kind": "autoscaler", "scale_step": 2}],
    }
    events = td["events"]

    # ---- stage 1: two sessions, then SIGKILL ---------------------------
    proc, base = _start_server(env)
    try:
        status, sa = _call(base, "POST", "/api/session",
                           {**create_body, "name": "torn-tail"})
        assert status == 200, (status, sa)
        sid_a = sa["session_id"]
        status, fed = _call(base, "POST", f"/api/session/{sid_a}/events",
                            {"events": events[:SPLIT]})
        assert status == 200, (status, fed)
        digest_a = fed["digest"]

        status, sb = _call(base, "POST", "/api/session",
                           {**create_body, "name": "mid-file"})
        assert status == 200, (status, sb)
        sid_b = sb["session_id"]
        status, _ = _call(base, "POST", f"/api/session/{sid_b}/events",
                          {"events": events[:SPLIT]})
        assert status == 200
        print(f"journal-smoke stage 1 OK: sessions {sid_a} (digest "
              f"{digest_a}) and {sid_b} journaled; SIGKILLing the server")
    finally:
        proc.kill()  # SIGKILL: the journals are all that survives
        proc.wait(30)

    # ---- stage 2: torn tail vs mid-file corruption ---------------------
    # A: a partial final line — exactly what a crash mid-append leaves
    with open(_journal_path(ckpt, sid_a), "ab") as f:
        f.write(b'J1 deadbeef 99 {"kind": "step", "tor')
    # B: one flipped byte mid-file — damage no torn write can explain
    pb = _journal_path(ckpt, sid_b)
    with open(pb, "rb") as f:
        lines = f.read().split(b"\n")
    buf = bytearray(lines[1])
    buf[len(buf) // 2] ^= 0x10
    lines[1] = bytes(buf)
    with open(pb, "wb") as f:
        f.write(b"\n".join(lines))

    proc, base = _start_server(env)
    try:
        # the quarantine is visible in the listing, structured
        status, listing = _call(base, "GET", "/api/session")
        assert status == 200, (status, listing)
        by_sid = {s["session_id"]: s for s in listing["sessions"]}
        assert sid_a in by_sid and not by_sid[sid_a].get("corrupt"), by_sid
        assert by_sid[sid_b].get("corrupt") is True, by_sid
        assert by_sid[sid_b]["error"]["code"] == "E_CORRUPT", by_sid

        # the torn tail resumes digest-identically and keeps settling
        status, st = _call(base, "GET", f"/api/session/{sid_a}")
        assert status == 200 and st["digest"] == digest_a, (
            f"torn-tail resume digest {st.get('digest')} != pre-kill "
            f"{digest_a}")
        status, fed = _call(base, "POST", f"/api/session/{sid_a}/events",
                            {"events": events[SPLIT:]})
        assert status == 200, (status, fed)

        # the mid-file corruption is a structured 409, never a traceback
        status, bad = _call(base, "GET", f"/api/session/{sid_b}")
        assert status == 409 and bad.get("code") == "E_CORRUPT", (
            status, bad)
        j = bad.get("journal") or {}
        assert j.get("kind") == "session" and j.get("index") == 1, bad
        assert j.get("offset", -1) >= 0, bad
        print(f"journal-smoke stage 2 OK: torn tail resumed "
              f"digest-identical ({digest_a}) and kept settling; "
              f"mid-file corruption answered structured 409 E_CORRUPT "
              f"(record #{j['index']}, byte {j['offset']}) with the "
              f"sibling unharmed")
    finally:
        rc = _stop(proc)
    assert rc == 0, f"quarantining server exited {rc}"

    # ---- stage 3: ENOSPC plan walks the disable rung -------------------
    ckpt2 = tempfile.mkdtemp(prefix="simon-journal-smoke-enospc-")
    env2 = {**env, "SIMON_CHECKPOINT_DIR": ckpt2}
    proc, base = _start_server(env2, "--fault-plan", ENOSPC_PLAN)
    try:
        # header is append #0, the baseline step #1; the disk "fills"
        # on append #2 — the event still settles (200), journaling stops
        status, sess = _call(base, "POST", "/api/session", create_body)
        assert status == 200, (status, sess)
        sid = sess["session_id"]
        status, fed = _call(base, "POST", f"/api/session/{sid}/events",
                            {"events": events[:SPLIT]})
        assert status == 200, (status, fed)

        status, st = _call(base, "GET", f"/api/session/{sid}")
        assert status == 200, (status, st)
        integ = st.get("journal") or {}
        assert integ.get("checkpointing_disabled") is True, st
        assert integ.get("storage_fault") == "E_STORAGE_FULL", st

        status, metrics = _call(base, "GET", "/metrics")
        assert status == 200
        disabled = _metric(metrics, "simon_journal_disabled_total",
                           kind="session", code="E_STORAGE_FULL")
        assert disabled == 1, disabled
        rung = _metric(metrics, "simon_fault_rungs_total",
                       fn="journal_append", rung="checkpointing_disabled")
        assert rung == 1, rung
        injected = _metric(metrics, "simon_fault_injected_total",
                           fn="journal_append")
        assert injected == 1, injected  # the latch stops further appends
        appends = _metric(metrics, "simon_journal_appends_total",
                          kind="session")
        assert appends == 2, appends    # header + baseline, pre-ENOSPC
        print(f"journal-smoke stage 3 OK: ENOSPC on append #2 took the "
              f"checkpointing_disabled rung (counters: disabled=1, "
              f"rung=1, injected=1, durable appends=2) and the session "
              f"kept answering 200")

        # ---- stage 4: SIGTERM drains clean under the plan --------------
    finally:
        if proc.poll() is None:
            rc = _stop(proc)
        else:
            rc = proc.returncode
        out = proc.stdout.read() if proc.stdout else ""
        if out and "--verbose" in sys.argv:
            print("--- server output ---")
            print(out)
    assert rc == 0, f"degraded server exited {rc}"
    print("journal-smoke stage 4 OK: SIGTERM drain exited 0 with "
          "checkpointing disabled")
    print("journal-smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
