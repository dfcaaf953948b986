# graftlint: disable-file=GL6 bench times raw launch+sync latency; the fault-domain wrapper would add its own retries/backoff to the measurement
"""Benchmark: batched capacity-planning throughput on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Workload (BASELINE.md config #2/#4 shape): synthetic cluster of --nodes
nodes, --pods pods with mixed requests + a zone spread constraint, and a
--scenarios-lane batched sweep (what-if node counts) vmapped on device.

`vs_baseline` compares against the stand-in for the reference's CPU
engine: the same scan run single-scenario on one XLA:CPU thread-pool
(measured in-process on jax.devices("cpu")[0], smaller pod count, rate
extrapolated per pod).
The reference publishes no numbers (BASELINE.md), so the CPU rate is the
baseline this repo tracks round over round.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build(n_nodes: int, n_pods: int, max_new: int, rich: bool = False,
          pools: int = 0, bound: float = 0.0):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import __graft_entry__ as ge

    return ge._synthetic_snapshot(
        n_nodes=n_nodes, n_pods=n_pods, max_new=max_new, rich=rich,
        pools=pools, bound=bound)


BENCH_SECONDS = "simon_bench_seconds"


def _bench_gauge():
    from open_simulator_tpu.telemetry import gauge

    return gauge(
        BENCH_SECONDS,
        "best-of-5 batched sweep wall time per workload shape (bench.py)",
        labelnames=("shape",))


def shape_label(nodes: int, pods: int, scenarios: int, rich: bool = False) -> str:
    return f"{nodes}n_x{pods}p_x{scenarios}s" + ("_allops" if rich else "")


def exec_costs() -> dict:
    """Per-executable XLA cost profile for the tracked bench line:
    {fn: {flops, bytes_accessed, peak_hbm_bytes, compile_s}} as harvested
    at compile time by the executable cache. Empty on backends whose
    cost_analysis() yields nothing — the key still rides along so the
    regression gate sees the same shape everywhere."""
    from open_simulator_tpu.engine.exec_cache import EXEC_CACHE

    out = {}
    for fn, cost in EXEC_CACHE.cost_snapshot().items():
        out[fn] = {k: cost[k] for k in
                   ("flops", "bytes_accessed", "peak_hbm_bytes",
                    "compile_s") if k in cost}
    return out


def devmem_peak() -> int:
    """High-watermark of devmem-ledger-registered device bytes so far in
    this process (telemetry/live.py) — rides every bench JSON line and
    tagged RunRecord so the bench trajectory records memory alongside
    scenarios/sec (ROADMAP item 1's remaining-HBM-lever work reads this
    series)."""
    from open_simulator_tpu.telemetry import live

    return int(live.DEVMEM.peak_total())


def run_batched(snapshot, n_scenarios: int, fail_reasons: bool = False,
                shape: str = "", preset: str = ""):
    """Time the capacity-sweep product path: what-if lanes run with
    fail_reasons off (the applier re-runs only the decoded lane with
    reasons on — not part of the per-lane sweep cost; parallel/sweep.py).

    Returns (best_seconds, wave_stats): the wave scheduler's plan for
    the shape (engine/waves.py; SIMON_WAVES=0 forces the pure scan) is
    part of the measured program, and its n_waves / max_wave_width /
    wave_fraction land in the JSON line and the per-shape ledger record
    so `make bench-regress` history shows whether a regression is
    engine-side or partition-side.

    The measured best lands in the simon_bench_seconds{shape} gauge and
    is read BACK from the registry by main() — the BENCH json line and a
    /metrics scrape of this process report one source of truth. With a
    ledger configured (--ledger-dir / SIMON_LEDGER_DIR), each timed shape
    also appends one "bench" RunRecord tagged with its preset/shape/value
    — the series tools/bench_regress.py gates on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from open_simulator_tpu.engine.scheduler import device_arrays, make_config, schedule_pods
    from open_simulator_tpu.engine.waves import waves_for
    from open_simulator_tpu.parallel.sweep import active_masks_for_counts
    from open_simulator_tpu.telemetry import ledger

    with ledger.run_capture("bench") as lcap:
        cfg = make_config(snapshot)._replace(fail_reasons=fail_reasons)
        arrs = device_arrays(snapshot)
        max_new = snapshot.n_nodes - snapshot.n_real_nodes
        counts = [min(i % (max_new + 1), max_new) for i in range(n_scenarios)]
        masks = jnp.asarray(active_masks_for_counts(snapshot, counts))
        wave_plan = waves_for(snapshot.arrays, cfg)
        wave_stats = (wave_plan.stats() if wave_plan is not None
                      else {"n_waves": 0, "max_wave_width": 0,
                            "wave_fraction": 0.0, "n_segments": 1})

        fn = jax.jit(jax.vmap(
            lambda a: schedule_pods(arrs, a, cfg, waves=wave_plan)))
        out = fn(masks)  # compile + warm
        jax.block_until_ready(out.node)

        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            out = fn(masks)
            jax.block_until_ready(out.node)
            best = min(best, time.perf_counter() - t0)
        label = shape or shape_label(snapshot.n_real_nodes, snapshot.n_pods,
                                     n_scenarios)
        _bench_gauge().labels(shape=label).set(best)
        # arrs carries the shapes this run actually compiled at (bench uses
        # the raw unbucketed arrays), so the fingerprint's bucket is honest
        lcap.set_config(cfg, snapshot=snapshot, arrs=arrs)
        lcap.set_result_info(**ledger.array_result_digest(np.asarray(out.node)))
        lcap.tag("preset", preset)
        lcap.tag("shape", label)
        lcap.tag("lanes", n_scenarios)
        lcap.tag("seconds", round(best, 6))
        # wave-partition provenance per shape: a bench regression with
        # unchanged wave stats is engine-side; with changed stats it is
        # partition-side (the plan moved)
        for wk, wv in wave_stats.items():
            lcap.tag(wk, wv)
        # higher-is-better throughput: the number bench_regress.py compares
        # against the trailing median of this shape's prior records
        lcap.tag("value", round(snapshot.n_pods * n_scenarios / best, 3))
        lcap.tag("devmem_peak_bytes", devmem_peak())
    return best, wave_stats


def run_mesh_bench(snapshot, n_scenarios: int, mesh_scenario=None,
                   shape: str = "",
                   preset: str = "northstar-mesh"):
    """Time the mesh-sharded north-star path (the multi-chip number).

    One single-device reference launch pins the digest; the mesh warm
    launch must equal it bit-for-bit (GSPMD sharding must never change a
    placement), and the timed loop donates each round's carry back into
    the next (ARCHITECTURE §9 x*0 reset — zero realloc per round). The
    run asserts EXACTLY ONE simon_compile_cache_total{fn=mesh_schedule}
    miss across the warm launch plus all timed rounds: a recompile per
    round would be the old per-call jit(vmap(...)) shape returning.
    Reported as scenarios/sec/chip with device count and mesh split in
    the tagged ledger record, so `make bench-regress` gates the
    multi-chip number per mesh shape like every other series."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from open_simulator_tpu.engine.exec_cache import (
        run_batched_cached,
        run_mesh_cached,
    )
    from open_simulator_tpu.engine.scheduler import device_arrays, make_config
    from open_simulator_tpu.engine.waves import waves_for
    from open_simulator_tpu.parallel.sweep import (
        active_masks_for_counts,
        make_mesh,
    )
    from open_simulator_tpu.telemetry import counter, ledger

    mesh = make_mesh(n_scenario=mesh_scenario)
    n_chips = int(mesh.devices.size)
    split = "x".join(str(s) for s in mesh.shape.values())
    scen_axis = int(mesh.shape["scenario"])
    if n_scenarios % scen_axis:
        raise SystemExit(
            f"bench: --scenarios {n_scenarios} is not divisible by the mesh "
            f"scenario axis ({scen_axis}); pick sizes that divide")

    with ledger.run_capture("bench") as lcap:
        cfg = make_config(snapshot)._replace(fail_reasons=False)
        arrs = device_arrays(snapshot)
        max_new = snapshot.n_nodes - snapshot.n_real_nodes
        counts = [min(i % (max_new + 1), max_new) for i in range(n_scenarios)]
        masks = jnp.asarray(active_masks_for_counts(snapshot, counts))
        wave_plan = waves_for(snapshot.arrays, cfg)
        wave_stats = (wave_plan.stats() if wave_plan is not None
                      else {"n_waves": 0, "max_wave_width": 0,
                            "wave_fraction": 0.0, "n_segments": 1})

        # single-device reference: the mesh number only counts if GSPMD
        # sharding did not move a single placement
        ref = run_batched_cached(arrs, masks, cfg, waves=wave_plan)
        ref_digest = ledger.array_result_digest(np.asarray(ref.node))

        misses = counter("simon_compile_cache_total", "",
                         labelnames=("fn", "event"))
        m0 = misses.value(fn="mesh_schedule", event="miss")
        out = run_mesh_cached(arrs, masks, cfg, mesh,
                              waves=wave_plan)  # compile + warm
        jax.block_until_ready(out.node)
        warm_digest = ledger.array_result_digest(np.asarray(out.node))
        if warm_digest["digest"] != ref_digest["digest"]:
            raise SystemExit(
                f"bench: mesh digest {warm_digest['digest']} != "
                f"single-device {ref_digest['digest']} — the sharded path "
                f"changed placement")

        best = float("inf")
        carry = out.state  # donated into round 1 (DEAD after the call)
        for _ in range(5):
            t0 = time.perf_counter()
            out = run_mesh_cached(arrs, masks, cfg, mesh, carry=carry,
                                  waves=wave_plan)
            jax.block_until_ready(out.node)
            best = min(best, time.perf_counter() - t0)
            carry = out.state
        miss_delta = int(misses.value(fn="mesh_schedule", event="miss") - m0)
        if miss_delta != 1:
            raise SystemExit(
                f"bench: {miss_delta} mesh_schedule cache misses across the "
                f"warm + 5 donated rounds (expected exactly 1)")
        last_digest = ledger.array_result_digest(np.asarray(out.node))
        if last_digest["digest"] != ref_digest["digest"]:
            raise SystemExit(
                f"bench: donated-carry round digest {last_digest['digest']} "
                f"!= single-device {ref_digest['digest']} — the §9 x*0 "
                f"reset contract broke under the mesh")

        label = shape or (shape_label(snapshot.n_real_nodes, snapshot.n_pods,
                                      n_scenarios) + f"_mesh{split}")
        _bench_gauge().labels(shape=label).set(best)
        lcap.set_config(cfg, snapshot=snapshot, arrs=arrs)
        lcap.set_result_info(**last_digest)
        lcap.tag("preset", preset)
        lcap.tag("shape", label)
        lcap.tag("lanes", n_scenarios)
        lcap.tag("devices", n_chips)
        lcap.tag("mesh", split)
        lcap.tag("seconds", round(best, 6))
        for wk, wv in wave_stats.items():
            lcap.tag(wk, wv)
        # same higher-is-better unit as run_batched so the per-shape
        # bench_regress gate reads one convention everywhere
        lcap.tag("value", round(snapshot.n_pods * n_scenarios / best, 3))
        lcap.tag("scenarios_per_sec_per_chip",
                 round(n_scenarios / best / n_chips, 3))
        lcap.tag("devmem_peak_bytes", devmem_peak())
    return dict(best=best, wave_stats=wave_stats,
                digest=ref_digest["digest"], devices=n_chips, mesh=split,
                label=label, miss_delta=miss_delta)


def cpu_baseline_rate(n_nodes: int, rich: bool = False):
    """Single-scenario pods/sec of the same scan on XLA:CPU, run in this
    process with its inputs committed to ``jax.devices("cpu")[0]`` — a
    child process could not share the chip this one already holds.

    Returns (rate, error): rate 0.0 with a non-None error when the run
    failed — a crashed baseline must NOT masquerade as a skipped one
    (the error string lands in the JSON line as "baseline_error")."""
    import jax

    from open_simulator_tpu.engine.scheduler import (
        device_arrays,
        make_config,
        schedule_pods,
    )

    try:
        snap = build(n_nodes, 512, 0, rich=rich)
        cfg = make_config(snap)
        arrs = jax.device_put(device_arrays(snap), jax.devices("cpu")[0])
        jax.block_until_ready(schedule_pods(arrs, arrs.active, cfg).node)
        t0 = time.perf_counter()
        jax.block_until_ready(schedule_pods(arrs, arrs.active, cfg).node)
        return 512 / (time.perf_counter() - t0), None
    except Exception as e:  # noqa: BLE001 — reported in the JSON line
        print(f"bench: CPU baseline failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 0.0, f"{type(e).__name__}: {e}"


# BASELINE.md config presets (the reference publishes no numbers; these are
# the shapes the repo tracks round over round).
#
# Workload honesty (VERDICT r3): `rich=True` presets use the all-ops-on
# synthetic workload (ports, required pod-affinity, anti-affinity, hard +
# hostname spread, preferred affinities, taints/selectors) so every
# make_config feature gate stays ON — a gate can never hide a regression in
# the tracked number. `gated` keeps the old easy workload to show the
# gating win separately. `northstar` also keeps the easy workload so its
# scenarios/s/chip stays directly comparable to the rounds 1-3 series
# (BENCH_r0*.json / VERDICT r3's 65/s); `northstar-rich` is the all-ops-on
# variant of the same shape.
PRESETS = {
    "demo": dict(nodes=10, pods=128, scenarios=8, max_new=8),          # config 1 analog
    "fit1k": dict(nodes=1024, pods=10240, scenarios=64, max_new=64),   # config 2
    "affinity1k": dict(nodes=1024, pods=10240, scenarios=64, max_new=64, rich=True),  # config 3
    "sweep": dict(nodes=1024, pods=2048, scenarios=512, max_new=512),  # config 4
    "northstar": dict(nodes=5120, pods=51200, scenarios=64, max_new=64),  # BASELINE.md north star shape (single chip)
    # 256 lanes amortize the per-step cost further — the honest per-chip
    # ceiling at the north-star shape (compare to the r2/r3 256-lane
    # figures, not to the 64-lane series)
    "northstar-wide": dict(nodes=5120, pods=51200, scenarios=256, max_new=64),
    "northstar-rich": dict(nodes=5120, pods=51200, scenarios=64, max_new=64, rich=True),
    # the multi-chip north star: the SAME northstar shape, lanes sharded
    # over the "scenario" axis of a GSPMD mesh via the AOT executable cache
    # (engine/exec_cache.py run_mesh_cached) — scenarios/sec/CHIP with
    # the digest asserted identical to the single-device path and exactly
    # ONE mesh_schedule compile across the warm + donated-carry rounds.
    # Mesh size via --mesh-scenario (default: all local devices).
    "northstar-mesh": dict(nodes=5120, pods=51200, scenarios=64, max_new=64),
    "gated": dict(nodes=1024, pods=2048, scenarios=256, max_new=64),
    "default": dict(nodes=1024, pods=2048, scenarios=256, max_new=64, rich=True),
    # multi-tenant pools: per-pool nodeSelectors make consecutive pods'
    # footprints disjoint — the workload shape the wave scheduler
    # (engine/waves.py) batches end to end (wave_fraction 1.0); compare
    # its scenarios/s against `sweep`-class shapes to see the wave win
    "pools": dict(nodes=1024, pods=10240, scenarios=64, max_new=0, pools=32),
    # fleet campaign throughput (campaign/): a synthetic fleet of
    # recorded dumps streamed through the per-cluster fault boundary —
    # clusters/sec + quarantine count, gated by bench-regress like every
    # other shape (the fleet path is covered from day one)
    "campaign": dict(clusters=12, nodes=16, pods=64),
    # trace-replay throughput (replay/): a synthetic day-in-the-cluster
    # (arrival waves, departures, one mid-trace fault, autoscaler loop)
    # through the step engine — steps/sec + events/sec, gated by
    # bench-regress like every other shape (the time axis is covered
    # from day one)
    "replay": dict(nodes=16, batches=10, batch_pods=24),
    # digital-twin session throughput (replay/session.py): a fixed pool
    # of resident sessions fed timed events round-robin, one settle per
    # event — events/sec at a fixed session-reuse ratio (every session
    # encodes once, then settles `batches x events` steps against the
    # shared bucketed executable), gated by bench-regress like every
    # other shape
    "session": dict(sessions=4, nodes=16, batches=6, batch_pods=16),
    # inference-grade serving (server/serving.py): an in-process server
    # admits ONE snapshot, then a client pool hammers it with base-digest
    # probes (the POST-once-probe-millions loop) — requests/sec at a
    # fixed snapshot-reuse ratio, coalesced launches counted, the shared
    # placement digest tagged so a regression in EITHER throughput or
    # determinism shows in the tracked line
    "serve": dict(nodes=12, requests=96, clients=6),
    # scheduler-policy tuning (tune/): the whole weight-space search as
    # lanes of ONE executable — variants/sec through the traced-weights
    # engine at a fixed lane width, Pareto size + point digest tagged so
    # a regression in EITHER search throughput or determinism shows in
    # the tracked line
    "tune": dict(nodes=16, pods=48, variants=8, rounds=8),
}


def run_campaign_bench(n_clusters: int, nodes: int, pods: int):
    """Time the fleet path: write a synthetic fleet once, stream it
    through the campaign runner (fault boundary + audit + report, no
    checkpointing — disk must not be part of the measured loop), and
    report clusters/sec. One warm-up pass compiles the shape buckets;
    the timed pass measures the compile-once-run-many fleet rate."""
    import shutil
    import tempfile

    from open_simulator_tpu.campaign import (
        CampaignOptions,
        run_campaign,
        write_synthetic_fleet,
    )
    from open_simulator_tpu.telemetry import ledger

    root = tempfile.mkdtemp(prefix="simbenchfleet-")
    try:
        write_synthetic_fleet(root, n_clusters=n_clusters, nodes=nodes,
                              pods=pods)
        opts = CampaignOptions(fleet=root, checkpoint=False, audit=True)
        with ledger.run_capture("bench") as lcap:
            run_campaign(opts)  # warm-up: compiles the fleet's buckets
            t0 = time.perf_counter()
            report = run_campaign(opts)
            dt = time.perf_counter() - t0
            label = f"campaign{n_clusters}c_{nodes}n_x{pods}p"
            _bench_gauge().labels(shape=label).set(dt)
            lcap.tag("preset", "campaign")
            lcap.tag("shape", label)
            lcap.tag("seconds", round(dt, 6))
            lcap.tag("value", round(n_clusters / dt, 3))
            lcap.tag("quarantined", report["totals"]["quarantined"])
            lcap.tag("report_digest", report["digest"])
            lcap.tag("devmem_peak_bytes", devmem_peak())
        return dt, report, label
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_replay_bench(n_nodes: int, n_batches: int, batch_pods: int):
    """Time the replay path: a deterministic synthetic trace (arrivals,
    departures, one kill_node, autoscaler) through the step engine.
    One warm-up trajectory compiles the step executables; the timed
    trajectory measures the compile-once-run-many step rate. No
    checkpointing — disk must not be part of the measured loop."""
    from open_simulator_tpu.replay import (
        AutoscalerPolicy,
        ReplayOptions,
        ReplayTrace,
        run_replay,
        synthetic_replay_cluster,
        synthetic_trace_dict,
    )
    from open_simulator_tpu.telemetry import ledger

    trace_dict = synthetic_trace_dict(n_batches=n_batches,
                                      batch_pods=batch_pods,
                                      max_new_nodes=max(4, n_nodes // 2))

    def one_run():
        return run_replay(
            synthetic_replay_cluster(n_nodes=n_nodes,
                                     n_initial_pods=n_nodes),
            ReplayTrace.from_dict(trace_dict),
            ReplayOptions(controllers=[AutoscalerPolicy(scale_step=2)],
                          checkpoint=False))

    with ledger.run_capture("bench") as lcap:
        one_run()  # warm-up: compiles the trajectory's executables
        t0 = time.perf_counter()
        report = one_run()
        dt = time.perf_counter() - t0
        steps = report["totals"]["steps"]
        events = report["totals"]["events"]
        label = f"replay{steps}st_{n_nodes}n_x{batch_pods}bp"
        _bench_gauge().labels(shape=label).set(dt)
        lcap.tag("preset", "replay")
        lcap.tag("shape", label)
        lcap.tag("seconds", round(dt, 6))
        lcap.tag("value", round(steps / dt, 3))
        lcap.tag("events_per_sec", round(events / dt, 3))
        lcap.tag("report_digest", report["digest"])
        lcap.tag("devmem_peak_bytes", devmem_peak())
    return dt, report, label


def run_session_bench(n_sessions: int, n_nodes: int, n_batches: int,
                      batch_pods: int):
    """Time the digital-twin path: ``n_sessions`` resident sessions
    (created once — the reuse: no re-encode inside the measured loop)
    fed the same synthetic event sequence round-robin, ONE event per
    apply, every settle through the controller loop. Reported as
    events/sec at a fixed session-reuse ratio (events settled per
    create). No journaling — disk must not be part of the measured
    loop."""
    from open_simulator_tpu.replay import (
        ReplaySession,
        SessionSpec,
        synthetic_replay_cluster,
        synthetic_trace_dict,
    )
    from open_simulator_tpu.telemetry import ledger

    td = synthetic_trace_dict(n_batches=n_batches, batch_pods=batch_pods,
                              max_new_nodes=max(4, n_nodes // 2))
    spec = SessionSpec(max_new_nodes=td["max_new_nodes"],
                       node_template=td["node_template"])

    def mk():
        return ReplaySession.create(
            synthetic_replay_cluster(n_nodes=n_nodes,
                                     n_initial_pods=n_nodes),
            spec, controllers=[{"kind": "autoscaler", "scale_step": 2}],
            checkpoint=False)

    with ledger.run_capture("bench") as lcap:
        warm = mk()
        warm.apply_events(td["events"])  # warm-up: compiles the shape
        sessions = [mk() for _ in range(n_sessions)]
        t0 = time.perf_counter()
        for ev in td["events"]:
            for s in sessions:
                s.apply_events([ev])
        dt = time.perf_counter() - t0
        n_events = len(td["events"]) * n_sessions
        label = f"session{n_sessions}s_{n_nodes}n_x{batch_pods}bp"
        _bench_gauge().labels(shape=label).set(dt)
        lcap.tag("preset", "session")
        lcap.tag("shape", label)
        lcap.tag("seconds", round(dt, 6))
        lcap.tag("value", round(n_events / dt, 3))
        lcap.tag("reuse_ratio", len(td["events"]))
        lcap.tag("trajectory_digest", sessions[0].digest)
        lcap.tag("devmem_peak_bytes", devmem_peak())
    assert all(s.digest == sessions[0].digest for s in sessions), (
        "identical sessions fed identical events diverged")
    return dt, n_events, sessions[0].digest, label


def run_tune_bench(n_nodes: int, n_pods: int, variants: int, rounds: int):
    """Time the policy-search path: one synthetic workload, a seeded cem
    search of ``variants`` lanes x ``rounds`` rounds through the
    traced-weights executable (tune/search.py). The warm-up run compiles
    the single batched program; the timed run measures the
    compile-once-search-many rate in variants/sec. The Pareto size and
    the point digest ride the tagged record so a regression in either
    throughput or determinism shows in the tracked line."""
    from open_simulator_tpu.replay import synthetic_replay_cluster
    from open_simulator_tpu.telemetry import ledger
    from open_simulator_tpu.tune import TuneOptions, tune_search

    cluster = synthetic_replay_cluster(n_nodes=n_nodes,
                                       n_initial_pods=n_pods)

    def one_run(seed):
        return tune_search(cluster, [], TuneOptions(
            mode="cem", variants=variants, rounds=rounds, seed=seed))

    with ledger.run_capture("bench") as lcap:
        one_run(seed=1)  # warm-up: compiles the lane executable
        t0 = time.perf_counter()
        report = one_run(seed=0)
        dt = time.perf_counter() - t0
        n_variants = report["n_variants"]
        label = f"tune{variants}w_x{rounds}r_{n_nodes}n"
        _bench_gauge().labels(shape=label).set(dt)
        lcap.tag("preset", "tune")
        lcap.tag("shape", label)
        lcap.tag("seconds", round(dt, 6))
        lcap.tag("value", round(n_variants / dt, 3))
        lcap.tag("pareto", len(report["pareto"]))
        lcap.tag("tune_digest", report["digest"])
        lcap.tag("devmem_peak_bytes", devmem_peak())
    return dt, report, label


def run_serve_bench(n_nodes: int, n_requests: int, n_clients: int):
    """Time the inference-grade serving path: an in-process server admits
    ONE snapshot (the only encode), then ``n_clients`` threads hammer it
    with ``{"base": digest}`` probes — the POST-once-probe-millions loop
    of server/serving.py. Probes queued behind an in-flight launch merge
    into coalesced batches, so the measured rate covers the whole
    admission-queue + resident-cache + batched-launch path, not just the
    device. Every response's placement digest must equal the admitting
    POST's (a coalesced lane is bit-identical to its singleton run)."""
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    import yaml as _yaml

    from open_simulator_tpu import telemetry
    from open_simulator_tpu.replay import synthetic_replay_cluster
    from open_simulator_tpu.server.rest import SimulationServer, _make_handler
    from open_simulator_tpu.telemetry import ledger

    cluster = synthetic_replay_cluster(n_nodes=n_nodes,
                                       n_initial_pods=n_nodes * 2)
    cluster_yaml = _yaml.safe_dump_all(
        [{"apiVersion": "v1", "kind": "Node", **n.raw}
         for n in cluster.nodes]
        + [{"apiVersion": "v1", "kind": "Pod", **p.raw}
           for p in cluster.pods])

    srv = SimulationServer(queue_depth=max(16, n_clients * 2), workers=2)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(srv))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/api/simulate"

    def post(payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300.0) as r:
            return json.loads(r.read())

    launches = telemetry.counter("simon_coalesced_launches_total",
                                 labelnames=("kind",))
    per_client = max(1, n_requests // n_clients)
    n_probes = per_client * n_clients
    try:
        with ledger.run_capture("bench") as lcap:
            admitted = post({"cluster": {"yaml": cluster_yaml}})
            digest = admitted["snapshot_digest"]
            post({"base": digest})  # warm-up: arrays resident, AOT hot
            l0 = (launches.value(kind="coalesced")
                  + launches.value(kind="singleton"))
            results = []
            lock = threading.Lock()

            def client():
                mine = [post({"base": digest}) for _ in range(per_client)]
                with lock:
                    results.extend(mine)

            threads = [threading.Thread(target=client)
                       for _ in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            n_launches = int(launches.value(kind="coalesced")
                             + launches.value(kind="singleton") - l0)
            label = f"serve{n_probes}r_{n_nodes}n_x{n_clients}c"
            _bench_gauge().labels(shape=label).set(dt)
            lcap.tag("preset", "serve")
            lcap.tag("shape", label)
            lcap.tag("seconds", round(dt, 6))
            lcap.tag("value", round(n_probes / dt, 3))
            lcap.tag("launches", n_launches)
            lcap.tag("reuse_ratio", n_probes)
            lcap.tag("placement_digest", admitted["digest"])
            lcap.tag("devmem_peak_bytes", devmem_peak())
        assert len(results) == n_probes, (len(results), n_probes)
        assert all(r["digest"] == admitted["digest"] for r in results), (
            "a coalesced probe diverged from the admitting run's digest")
    finally:
        httpd.shutdown()
    return dt, n_probes, n_launches, admitted["digest"], label


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="bench.py",
        description="Batched capacity-planning throughput benchmark: one "
                    "JSON line per run, appended to the run ledger and "
                    "gated round over round by tools/bench_regress.py.")
    ap.add_argument("--preset", choices=sorted(PRESETS), default="default")
    ap.add_argument("--nodes", type=int)
    ap.add_argument("--pods", type=int)
    ap.add_argument("--scenarios", type=int)
    ap.add_argument("--max-new", type=int)
    ap.add_argument("--skip-baseline", action="store_true")
    ap.add_argument(
        "--compile-cache-dir", default="",
        help="persistent XLA compile cache (default <checkout>/.jax_cache; "
             "JAX_COMPILATION_CACHE_DIR, when set, wins): repeat bench "
             "runs skip the cold compile")
    ap.add_argument(
        "--ledger-dir", default="",
        help="run-ledger directory: each timed shape appends one bench "
             "RunRecord (also honors SIMON_LEDGER_DIR); gate the series "
             "with tools/bench_regress.py")
    ap.add_argument(
        "--fail-reasons", action="store_true",
        help="time the simulate() path (per-op failure accounting in every "
             "lane) instead of the default sweep path",
    )
    ap.add_argument(
        "--mesh-scenario", type=int,
        help="northstar-mesh: devices on the mesh's scenario axis "
             "(default: all local devices); --scenarios must be divisible "
             "by it")
    return ap


def main():
    args = build_parser().parse_args()
    from open_simulator_tpu.engine.exec_cache import enable_persistent_cache

    enable_persistent_cache(args.compile_cache_dir)
    if args.ledger_dir:
        from open_simulator_tpu.telemetry import ledger

        ledger.configure(args.ledger_dir)
    preset = PRESETS[args.preset]
    if args.preset == "campaign":
        # fleet-path bench: clusters/sec through the campaign runner's
        # fault boundary (quarantine count rides along so a regression
        # in EITHER speed or isolation shows in the tracked line)
        dt, report, label = run_campaign_bench(
            preset["clusters"], args.nodes or preset["nodes"],
            args.pods or preset["pods"])
        print(json.dumps({
            "metric": f"clusters_per_sec@{label}",
            "value": round(preset["clusters"] / dt, 3),
            "unit": "clusters/s",
            "vs_baseline": 0.0,
            "baseline": "none_fleet_path",
            "preset": "campaign",
            "quarantined": report["totals"]["quarantined"],
            "completed": report["totals"]["completed"],
            "report_digest": report["digest"],
            "exec_costs": exec_costs(),
            "devmem_peak_bytes": devmem_peak(),
        }))
        return
    if args.preset == "replay":
        # time-axis bench: steps/sec + events/sec through the replay
        # step engine (one executable per trajectory after warm-up);
        # the digest rides along so a regression in EITHER speed or
        # determinism shows in the tracked line
        dt, report, label = run_replay_bench(
            args.nodes or preset["nodes"], preset["batches"],
            args.pods or preset["batch_pods"])
        steps = report["totals"]["steps"]
        print(json.dumps({
            "metric": f"replay_steps_per_sec@{label}",
            "value": round(steps / dt, 3),
            "unit": "steps/s",
            "vs_baseline": 0.0,
            "baseline": "none_replay_path",
            "preset": "replay",
            "events_per_sec": round(report["totals"]["events"] / dt, 3),
            "steps": steps,
            "pending_final": report["totals"]["pending"],
            "report_digest": report["digest"],
            "exec_costs": exec_costs(),
            "devmem_peak_bytes": devmem_peak(),
        }))
        return
    if args.preset == "session":
        # digital-twin bench: events/sec across a resident session pool
        # at a fixed session-reuse ratio; the shared trajectory digest
        # rides along so a regression in EITHER speed or determinism
        # shows in the tracked line
        dt, n_events, digest, label = run_session_bench(
            preset["sessions"], args.nodes or preset["nodes"],
            preset["batches"], args.pods or preset["batch_pods"])
        print(json.dumps({
            "metric": f"session_events_per_sec@{label}",
            "value": round(n_events / dt, 3),
            "unit": "events/s",
            "vs_baseline": 0.0,
            "baseline": "none_session_path",
            "preset": "session",
            "sessions": preset["sessions"],
            "events": n_events,
            "reuse_ratio": n_events // preset["sessions"],
            "trajectory_digest": digest,
            "exec_costs": exec_costs(),
            "devmem_peak_bytes": devmem_peak(),
        }))
        return
    if args.preset == "tune":
        # policy-search bench: variants/sec through the traced-weights
        # lane executable; the Pareto size and point digest ride along
        # so a regression in EITHER search throughput or determinism
        # shows in the tracked line
        dt, report, label = run_tune_bench(
            args.nodes or preset["nodes"], args.pods or preset["pods"],
            preset["variants"], preset["rounds"])
        print(json.dumps({
            "metric": f"tune_variants_per_sec@{label}",
            "value": round(report["n_variants"] / dt, 3),
            "unit": "variants/s",
            "vs_baseline": 0.0,
            "baseline": "none_tune_path",
            "preset": "tune",
            "variants": report["n_variants"],
            "rounds": report["rounds_run"],
            "pareto_points": len(report["pareto"]),
            "tune_digest": report["digest"],
            "exec_costs": exec_costs(),
            "devmem_peak_bytes": devmem_peak(),
        }))
        return
    if args.preset == "serve":
        # serving bench: requests/sec through the resident-snapshot +
        # coalescing path at a fixed snapshot-reuse ratio; the shared
        # placement digest rides along so a regression in EITHER
        # throughput or determinism shows in the tracked line
        dt, n_probes, n_launches, digest, label = run_serve_bench(
            args.nodes or preset["nodes"], preset["requests"],
            preset["clients"])
        print(json.dumps({
            "metric": f"serve_requests_per_sec@{label}",
            "value": round(n_probes / dt, 3),
            "unit": "requests/s",
            "vs_baseline": 0.0,
            "baseline": "none_serving_path",
            "preset": "serve",
            "requests": n_probes,
            "launches": n_launches,
            "reuse_ratio": n_probes,
            "placement_digest": digest,
            "exec_costs": exec_costs(),
            "devmem_peak_bytes": devmem_peak(),
        }))
        return
    for k in ("nodes", "pods", "scenarios", "max_new"):
        if getattr(args, k) is None:
            setattr(args, k, preset[k])
    rich = preset.get("rich", False)

    if args.preset == "northstar-mesh":
        # multi-chip north star: the same engine, lanes sharded over the
        # GSPMD mesh through the AOT executable cache — digest asserted
        # identical to the single-device path, exactly one compile
        snapshot = build(args.nodes, args.pods, args.max_new)
        res = run_mesh_bench(snapshot, args.scenarios,
                             mesh_scenario=args.mesh_scenario,
                             preset=args.preset)
        print(json.dumps({
            "metric": f"mesh_scenarios_per_sec_per_chip@{res['label']}",
            "value": round(args.scenarios / res["best"] / res["devices"], 2),
            "unit": "scenarios/s/chip",
            "vs_baseline": 0.0,
            # the digest-checked single-device path IS the baseline here;
            # compare this line's per-chip rate to the `northstar` series
            "baseline": "single_device_same_engine_digest_checked",
            "preset": args.preset,
            "devices": res["devices"],
            "mesh": res["mesh"],
            "lanes": args.scenarios,
            "scenarios_per_sec": round(args.scenarios / res["best"], 2),
            "pods_per_sec": round(args.pods * args.scenarios / res["best"], 1),
            "digest": res["digest"],
            "mesh_miss_delta": res["miss_delta"],
            "n_waves": res["wave_stats"]["n_waves"],
            "max_wave_width": res["wave_stats"]["max_wave_width"],
            "wave_fraction": res["wave_stats"]["wave_fraction"],
            "exec_costs": exec_costs(),
            "devmem_peak_bytes": devmem_peak(),
        }))
        return

    snapshot = build(args.nodes, args.pods, args.max_new, rich=rich,
                     pools=preset.get("pools", 0), bound=preset.get("bound", 0.0))
    label = shape_label(args.nodes, args.pods, args.scenarios, rich)
    if preset.get("pools"):
        label += f"_pools{preset['pools']}"
    # run_batched sets simon_bench_seconds{shape=label} to the same value
    # it returns, so the JSON below and a /metrics scrape of this process
    # report one source of truth
    dt, wave_stats = run_batched(snapshot, args.scenarios,
                                 fail_reasons=args.fail_reasons,
                                 shape=label, preset=args.preset)
    pods_per_sec = args.pods * args.scenarios / dt
    scenarios_per_sec = args.scenarios / dt

    baseline_error = None
    if args.skip_baseline:
        base_rate = 0.0
    else:
        base_rate, baseline_error = cpu_baseline_rate(args.nodes, rich=rich)
    vs = pods_per_sec / base_rate if base_rate > 0 else 0.0

    out = {
        "metric": f"pods_scheduled_per_sec@{label}",
        "value": round(pods_per_sec, 1),
        "unit": "pods/s",
        "vs_baseline": round(vs, 2),
        # the baseline is this same engine single-lane on one XLA:CPU
        # thread-pool (the reference publishes no numbers, BASELINE.md) —
        # vs_baseline is a round-over-round tracking ratio, NOT "x the Go
        # reference"
        "baseline": "xla_cpu_single_lane_same_engine",
        "scenarios_per_sec": round(scenarios_per_sec, 2),
        "preset": args.preset,
        # wave-scheduling partition stats for the timed shape
        # (engine/waves.py): 0/0/0.0 = pure scan (nothing provably
        # independent); a regression with unchanged stats is engine-side
        "n_waves": wave_stats["n_waves"],
        "max_wave_width": wave_stats["max_wave_width"],
        "wave_fraction": wave_stats["wave_fraction"],
    }
    if baseline_error:
        # vs_baseline 0.0 with this key present means the baseline CRASHED
        # (stderr tail above), not that it was skipped
        out["baseline_error"] = baseline_error
    if args.preset == "default":
        # the driver runs bench.py bare: record the BASELINE.md north-star
        # numbers (scenarios/s/chip at 5120n x 51200p, rounds-1..3-comparable
        # workload) in the same JSON line every round. Both keys are NEW in
        # round 4 (BENCH_r01-03 hold only the default-preset line); the
        # 64-lane point continues the judge-measured 63/65 series, the
        # 256-lane point records the per-chip ceiling (lane amortization).
        ns = PRESETS["northstar"]
        ns_snap = build(ns["nodes"], ns["pods"], ns["max_new"])
        ns_label = shape_label(ns["nodes"], ns["pods"], ns["scenarios"])
        ns_dt, _ = run_batched(ns_snap, ns["scenarios"],
                               fail_reasons=args.fail_reasons, shape=ns_label,
                               preset="northstar")
        out["northstar_scenarios_per_sec_per_chip"] = round(ns["scenarios"] / ns_dt, 1)
        out["northstar_shape"] = f"{ns['nodes']}n_x{ns['pods']}p_x{ns['scenarios']}s"
        # wide = the SAME snapshot at more lanes (assert the preset table
        # hasn't drifted from that identity)
        wide = PRESETS["northstar-wide"]
        assert all(wide[k] == ns[k] for k in ("nodes", "pods", "max_new")), (
            "northstar-wide must differ from northstar only in lane count")
        wide_label = shape_label(wide["nodes"], wide["pods"], wide["scenarios"])
        wide_dt, _ = run_batched(ns_snap, wide["scenarios"],
                                 fail_reasons=args.fail_reasons,
                                 shape=wide_label, preset="northstar-wide")
        out["northstar_wide_scenarios_per_sec_per_chip"] = round(
            wide["scenarios"] / wide_dt, 1)
        out["northstar_wide_lanes"] = wide["scenarios"]
        # the all-ops variant of the north-star shape (every gate on) —
        # the series the round-5 latency lead is defined on (ROADMAP)
        nr = PRESETS["northstar-rich"]
        assert all(nr[k] == ns[k] for k in ("nodes", "pods", "max_new", "scenarios")), (
            "northstar-rich must differ from northstar only in workload")
        nr_snap = build(nr["nodes"], nr["pods"], nr["max_new"], rich=True)
        nr_label = shape_label(nr["nodes"], nr["pods"], nr["scenarios"], rich=True)
        nr_dt, _ = run_batched(nr_snap, nr["scenarios"],
                               fail_reasons=args.fail_reasons, shape=nr_label,
                               preset="northstar-rich")
        out["northstar_rich_scenarios_per_sec_per_chip"] = round(
            nr["scenarios"] / nr_dt, 2)
        # the wave-showcase shape: multi-tenant pools whose disjoint
        # footprints the wave scheduler batches (wave_fraction 1.0) —
        # NEW in round 7, recorded alongside the north-star series
        pl = PRESETS["pools"]
        pl_snap = build(pl["nodes"], pl["pods"], pl["max_new"],
                        pools=pl["pools"])
        pl_label = (shape_label(pl["nodes"], pl["pods"], pl["scenarios"])
                    + f"_pools{pl['pools']}")
        pl_dt, pl_stats = run_batched(pl_snap, pl["scenarios"],
                                      fail_reasons=args.fail_reasons,
                                      shape=pl_label, preset="pools")
        out["pools_scenarios_per_sec_per_chip"] = round(
            pl["scenarios"] / pl_dt, 2)
        out["pools_wave_stats"] = pl_stats
    out["exec_costs"] = exec_costs()
    out["devmem_peak_bytes"] = devmem_peak()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
