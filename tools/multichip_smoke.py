"""Multi-chip digest-equality + recompile gate (`make multichip-smoke`).

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Runs `batched_schedule` over an 8-virtual-CPU-device "scenario" mesh
and asserts the node assignments — and their ledger result
digest — are IDENTICAL to the single-device run of the same workload.
The MULTICHIP_r01–r05 records all silently carried the same pre-PR-1
scan-arity crash because nothing gated the sharded path between rounds;
this tool is that gate, fast enough for tools/smoke.sh.

Three workloads, chosen to exercise the paths that can rot
independently:

* the easy preset (most feature gates off — the fit fast path),
* the all-ops rich preset (every gate on: slot paint, affinity,
  anti-affinity, spread, ports),
* a multi-tenant pools preset, where the wave scheduler
  (engine/waves.py) batches the whole sequence — so the gate covers
  GSPMD-sharded wave execution, not just the sequential scan.

Two more gates ride the same process (ISSUE 19):

* **recompile gate** — two same-bucket mesh launches plus a
  donated-carry round-2 must show EXACTLY ONE
  `simon_compile_cache_total{fn=mesh_schedule}` miss, so the old
  fresh-`jit(vmap(lambda ...))`-per-call shape (a full recompile per
  bisect round) can never silently return; the donated round's digest
  must equal the fresh rounds' (the §9 x*0 reset contract, under the
  mesh);
* **perf record** — a timed donated-carry loop on the 8-device mesh
  lands one tagged "bench" RunRecord (preset=multichip, scenarios/sec,
  mesh split, digest) in SIMON_LEDGER_DIR (or a temp ledger when
  unset): the enforced, regressable replacement for the rotted
  MULTICHIP_r01–r05 snapshots.

Exit 0 = all digests equal and the gates hold; any mismatch, miss-count
drift, or crash exits nonzero.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8


def _mesh_misses() -> float:
    from open_simulator_tpu.telemetry import counter

    return counter("simon_compile_cache_total", "",
                   labelnames=("fn", "event")).value(
                       fn="mesh_schedule", event="miss")


def main() -> int:
    import __graft_entry__ as ge

    devices = ge._cpu_devices(N_DEVICES)
    import jax.numpy as jnp
    import numpy as np

    from open_simulator_tpu.engine.scheduler import (
        device_arrays,
        make_config,
    )
    from open_simulator_tpu.engine.waves import waves_for
    from open_simulator_tpu.parallel.sweep import (
        active_masks_for_counts,
        batched_schedule,
        make_mesh,
    )
    from open_simulator_tpu.telemetry import ledger
    from open_simulator_tpu.telemetry.ledger import array_result_digest

    mesh = make_mesh(n_scenario=N_DEVICES, devices=devices)
    failures = 0
    for name, kw in (
        ("easy", {}),
        ("rich", {"rich": True}),
        ("pools", {"pools": 8}),
    ):
        max_new = 0 if kw.get("pools") else 8
        snap = ge._synthetic_snapshot(n_nodes=8, n_pods=64, max_new=max_new,
                                      **kw)
        cfg = make_config(snap)._replace(fail_reasons=False)
        plan = waves_for(snap.arrays, cfg)
        counts = [min(c, max_new) for c in range(N_DEVICES)]
        masks = jnp.asarray(active_masks_for_counts(snap, counts))

        arrs_single = device_arrays(snap)
        out_single = batched_schedule(arrs_single, masks, cfg, mesh=None,
                                      waves=plan)
        nodes_single = np.asarray(out_single.node)

        arrs_mesh = device_arrays(snap)
        out_mesh = batched_schedule(arrs_mesh, masks, cfg, mesh=mesh,
                                    waves=plan)
        nodes_mesh = np.asarray(out_mesh.node)

        d_single = array_result_digest(nodes_single)
        d_mesh = array_result_digest(nodes_mesh)
        same = d_single["digest"] == d_mesh["digest"]
        wave_note = (f", waves={plan.stats()['n_waves']}"
                     if plan is not None else ", waves=off")
        print(f"multichip {name}: mesh={mesh.shape} lanes={len(counts)} "
              f"digest single={d_single['digest']} mesh={d_mesh['digest']} "
              f"equal={same}{wave_note}")
        if not same:
            diff = np.nonzero(nodes_single != nodes_mesh)
            print(f"  MISMATCH at (lane, pod) = "
                  f"{list(zip(*[d[:5] for d in diff]))}", file=sys.stderr)
            failures += 1

    # ---- recompile + donation gate (fresh shape: its cache key must not
    # collide with the workloads above, so launch 1 is a genuine miss)
    snap = ge._synthetic_snapshot(n_nodes=8, n_pods=48, max_new=8)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = waves_for(snap.arrays, cfg)
    masks = jnp.asarray(active_masks_for_counts(
        snap, [min(c, 8) for c in range(N_DEVICES)]))
    arrs = device_arrays(snap)
    m0 = _mesh_misses()
    out1 = batched_schedule(arrs, masks, cfg, mesh=mesh, waves=plan)
    out2 = batched_schedule(arrs, masks, cfg, mesh=mesh, waves=plan)
    d1 = array_result_digest(np.asarray(out1.node))["digest"]
    d2 = array_result_digest(np.asarray(out2.node))["digest"]
    # round 3 donates round 2's state — out2.state is DEAD after this
    out3 = batched_schedule(arrs, masks, cfg, mesh=mesh, waves=plan,
                            carry=out2.state)
    d3 = array_result_digest(np.asarray(out3.node))["digest"]
    miss_delta = int(_mesh_misses() - m0)
    print(f"multichip recompile gate: 3 same-bucket launches "
          f"(round 3 donated-carry), mesh_schedule miss delta={miss_delta}, "
          f"digests {d1}/{d2}/{d3}")
    if miss_delta != 1:
        print(f"  RECOMPILE REGRESSION: expected exactly 1 mesh_schedule "
              f"cache miss across same-bucket launches, got {miss_delta} "
              f"(the per-call jit(vmap(...)) shape is back?)",
              file=sys.stderr)
        failures += 1
    if not (d1 == d2 == d3):
        print(f"  DONATION DRIFT: donated-carry round digest {d3} != "
              f"fresh rounds {d1}/{d2} (the x*0 reset contract broke "
              f"under the mesh)", file=sys.stderr)
        failures += 1

    # ---- tagged perf record: a timed donated-carry loop on the mesh
    # (pure cache hits — compiled above), recorded like a bench preset so
    # `simon-tpu runs` / bench_regress can read the multichip series
    if not ledger.enabled():
        ledger.configure(tempfile.mkdtemp(prefix="multichip-ledger-"))
    rounds = 3
    carry = None
    t0 = time.perf_counter()
    for _ in range(rounds):
        out = batched_schedule(arrs, masks, cfg, mesh=mesh, waves=plan,
                               carry=carry)
        carry = out.state
    dt = time.perf_counter() - t0
    lanes = int(masks.shape[0])
    per_sec = lanes * rounds / dt
    n_chips = int(mesh.devices.size)
    split = "x".join(str(s) for s in mesh.shape.values())
    with ledger.run_capture("bench") as cap:
        cap.set_config(cfg, snapshot=snap, arrs=arrs)
        cap.set_result_info(**array_result_digest(np.asarray(out.node)))
        cap.tag("preset", "multichip")
        cap.tag("shape", f"{snap.n_nodes}n-{snap.n_pods}p-{lanes}s-{split}")
        cap.tag("devices", n_chips)
        cap.tag("mesh", split)
        cap.tag("lanes", lanes)
        cap.tag("seconds", round(dt, 6))
        cap.tag("value", round(per_sec, 3))
        cap.tag("scenarios_per_sec_per_chip", round(per_sec / n_chips, 3))
    print(f"multichip perf: {per_sec:.1f} scenarios/sec on {n_chips} "
          f"virtual devices (mesh {split}, {rounds} donated rounds) -> "
          f"ledger dir {ledger.ledger_dir()}")

    if failures:
        print(f"multichip-smoke FAILED: {failures} gate(s) failed",
              file=sys.stderr)
        return 1
    print("multichip-smoke OK: 8-device mesh digests equal single-device; "
          "1 compile across same-bucket + donated launches")
    return 0


if __name__ == "__main__":
    sys.exit(main())
