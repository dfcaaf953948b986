"""Chip smoke: drive the capacity planner's main path once on a TPU.

    python chip_smoke.py          # one chip, the phases below
    python chip_smoke.py --mesh   # four chips: the mesh-sharded sweep only

One process holds the chip and runs every phase through the entry
points a user calls:

1. **plan** — the north-star cluster (5,120 nodes x 51,200 all-ops pods,
   64 template slots) through ``capacity_bisect``, the path
   ``simon-tpu apply`` takes, and a 64-lane ``capacity_sweep``; both
   answers and every shared lane's placements must agree.
2. **placement** — 1,024 nodes x 10,240 all-ops pods, where per-domain
   counts pass 256 (the bf16 integer limit), run once on the chip and
   once with the inputs committed to ``jax.devices("cpu")[0]``; node
   assignments, per-op fail counts and the ledger digest must be
   identical.
3. **serve** — ``SimulationServer`` on a thread: a ~1,000-node cluster
   admission, base probes and a capacity question, each answering 200
   with placements equal to ``core.simulate`` of the same cluster.
4. **faults** — no degradation-ladder rung and no classified compile
   fault fired: on the chip a rung would turn a failure into a slow run.

``--mesh`` runs the north-star sweep through ``run_mesh_cached`` on a
4x1 ("scenario", "node") mesh and compares it with the single-device
run on chip 0: same placements and digest, exactly one compile miss
across a warm and two donated-carry rounds, and shards on all four
chips. It also checks that a 2x2 mesh, which would split the nodes
over chips, is refused (ROADMAP B3).

Earlier lines report compile and phase seconds, peak device bytes,
answers and digests; the last line is the contract
``{"ok": true, "device": {...}}``. Without a TPU the script exits
nonzero and prints no result. Tests run the phases at toy size on the
CPU through ``run_phases``/``run_mesh`` (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Tuple


@dataclass(frozen=True)
class Sizes:
    nodes: int            # north-star plan (and the --mesh sweep)
    pods: int
    max_new: int
    sweep_lanes: int
    check_nodes: int      # chip-vs-CPU placement check
    check_pods: int
    check_counts: Tuple[int, ...]
    serve_nodes: int      # in-process server
    serve_pods: int
    serve_probes: int
    serve_max_new: int


FULL = Sizes(nodes=5120, pods=51200, max_new=64, sweep_lanes=64,
             check_nodes=1024, check_pods=10240, check_counts=(0, 1, 4, 8),
             serve_nodes=1000, serve_pods=3000, serve_probes=3,
             serve_max_new=8)
TOY = Sizes(nodes=24, pods=96, max_new=8, sweep_lanes=8,
            check_nodes=16, check_pods=64, check_counts=(0, 2),
            serve_nodes=12, serve_pods=24, serve_probes=2, serve_max_new=2)


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print(f"chip_smoke {phase}: {json.dumps(fields, default=str)}",
          flush=True)


def device_info() -> Dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(dev=None):
    import jax

    stats = (dev or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _compiles(seen: set) -> List[Dict]:
    """Executables compiled since the last call: fn, compile seconds and
    the compiler's peak-HBM estimate."""
    from open_simulator_tpu.engine.exec_cache import EXEC_CACHE

    out = []
    for row in EXEC_CACHE.debug_entries():
        if row["key"] in seen:
            continue
        seen.add(row["key"])
        cost = row["cost"]
        out.append({"fn": row["fn"], "compile_s": cost.get("compile_s"),
                    "peak_hbm_bytes": cost.get("peak_hbm_bytes")})
    return out


def _padded_masks(snap, counts, n_pad: int):
    import numpy as np

    from open_simulator_tpu.parallel.sweep import active_masks_for_counts

    m = active_masks_for_counts(snap, list(counts))
    out = np.zeros((m.shape[0], n_pad), dtype=bool)
    out[:, :m.shape[1]] = m
    return out


def _cpu_threshold(snap, max_new: int):
    """A CPU-occupancy limit that puts the answer near 0.64 * max_new,
    so the bisection narrows a bracket instead of answering in round
    one: the limit is the occupancy all pods give at that count."""
    import numpy as np

    from open_simulator_tpu.parallel.sweep import SweepThresholds

    a, cpu = snap.arrays, snap.resources.index("cpu")
    alloc = np.asarray(a.alloc)[:, cpu].astype(np.float64)
    n_real = snap.n_real_nodes
    target = int(0.64 * max_new) + 0.5
    cap = float(alloc[:n_real].sum()) + target * float(alloc[n_real])
    req = float(np.asarray(a.req)[:, cpu].astype(np.float64).sum())
    return SweepThresholds(max_cpu_pct=100.0 * req / cap)


def phase_plan(sz: Sizes, seen: set) -> None:
    import numpy as np

    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.parallel.sweep import (
        capacity_bisect,
        capacity_sweep,
    )
    from open_simulator_tpu.telemetry.ledger import plan_digest
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    t0 = time.perf_counter()
    snap = synthetic_snapshot(sz.nodes, sz.pods, max_new=sz.max_new,
                              rich=True)
    encode_s = time.perf_counter() - t0
    cfg = make_config(snap)
    th = _cpu_threshold(snap, sz.max_new)

    t0 = time.perf_counter()
    bis = capacity_bisect(snap, cfg, sz.max_new, th)
    bisect_s = time.perf_counter() - t0
    counts = list(range(sz.sweep_lanes))
    t0 = time.perf_counter()
    swp = capacity_sweep(snap, cfg, counts, th)
    sweep_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = capacity_sweep(snap, cfg, counts, th)
    sweep_warm_s = time.perf_counter() - t0

    _check(not bis.trial_errors and not swp.trial_errors,
           f"failed lanes: bisect {bis.trial_errors} sweep {swp.trial_errors}")
    _check(bis.best_count == swp.best_count,
           f"bisect answered {bis.best_count}, sweep {swp.best_count}")
    shared = [c for c in bis.counts if c in counts]
    for c in shared:
        _check(np.array_equal(bis.nodes_per_scenario[bis.counts.index(c)],
                              swp.nodes_per_scenario[c]),
               f"bisect and sweep placements differ at count {c}")
    _check(plan_digest(swp) == plan_digest(again),
           "a warm re-run of the sweep changed its digest")
    report("plan", nodes=snap.n_real_nodes, pods=snap.n_pods,
           max_new=sz.max_new, max_cpu_pct=th.max_cpu_pct,
           best_count=bis.best_count, bisect_probes=bis.counts,
           shared_lanes_equal=len(shared),
           scheduled_at_best=(None if swp.best_count is None else int(
               np.sum(swp.nodes_per_scenario[swp.best_count] >= 0))),
           sweep_digest=plan_digest(swp)["digest"],
           bisect_digest=plan_digest(bis)["digest"],
           encode_s=encode_s, bisect_s=bisect_s, sweep_cold_s=sweep_cold_s,
           sweep_warm_s=sweep_warm_s, compiles=_compiles(seen),
           peak_bytes_in_use=peak_bytes())


def phase_placement(sz: Sizes, seen: set) -> None:
    import jax
    import numpy as np

    from open_simulator_tpu.engine.exec_cache import (
        bucketed_device_arrays,
        run_batched_cached,
    )
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.engine.waves import waves_for
    from open_simulator_tpu.telemetry.ledger import array_result_digest
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    snap = synthetic_snapshot(sz.check_nodes, sz.check_pods,
                              max_new=max(sz.check_counts), rich=True)
    cfg = make_config(snap)._replace(fail_reasons=True)
    arrs, _, n_pods = bucketed_device_arrays(snap.arrays)
    waves = waves_for(snap.arrays, cfg, n_pods_total=int(arrs.req.shape[0]))
    masks = _padded_masks(snap, sz.check_counts, arrs.alloc.shape[0])
    cpu = jax.devices("cpu")[0]

    runs = {}
    for name, placed in (("chip", arrs), ("cpu", jax.device_put(arrs, cpu))):
        t0 = time.perf_counter()
        out = run_batched_cached(placed, masks, cfg, waves=waves)
        runs[name] = dict(
            device=str(next(iter(out.node.devices()))),
            seconds=time.perf_counter() - t0,
            node=np.asarray(out.node)[:, :n_pods],
            fail=np.asarray(out.fail_counts)[:, :n_pods],
            headroom=np.asarray(out.state.headroom))
    chip, ref = runs["chip"], runs["cpu"]
    digests = {k: array_result_digest(r["node"])["digest"]
               for k, r in runs.items()}
    mismatched = int(np.sum(chip["node"] != ref["node"]))
    _check(mismatched == 0,
           f"{mismatched} pod placements differ between chip and CPU")
    _check(np.array_equal(chip["fail"], ref["fail"]),
           "per-op fail counts differ between chip and CPU")
    _check(digests["chip"] == digests["cpu"], f"digests differ: {digests}")
    report("placement", nodes=sz.check_nodes, pods=sz.check_pods,
           counts=list(sz.check_counts),
           devices={k: r["device"] for k, r in runs.items()},
           digest=digests["chip"],
           placed_per_lane=[int(v) for v in np.sum(chip["node"] >= 0, axis=1)],
           headroom_equal=bool(np.array_equal(chip["headroom"],
                                              ref["headroom"])),
           chip_s=chip["seconds"], cpu_s=ref["seconds"],
           compiles=_compiles(seen), peak_bytes_in_use=peak_bytes())


def _placement_digest(pairs) -> str:
    """Order-free digest of (pod key, node name or "!") pairs."""
    h = hashlib.sha256()
    for key, node in sorted(pairs):
        h.update(f"{key}->{node};".encode())
    return h.hexdigest()[:16]


def phase_serve(sz: Sizes, seen: set) -> None:
    import urllib.error
    import urllib.request
    from http.server import ThreadingHTTPServer

    import yaml

    from open_simulator_tpu.core import simulate
    from open_simulator_tpu.k8s.loader import ClusterResources
    from open_simulator_tpu.server.rest import SimulationServer, _make_handler
    from open_simulator_tpu.testing.synthetic import synthetic_objects

    nodes, pods, template = synthetic_objects(sz.serve_nodes, sz.serve_pods,
                                              rich=True)
    cluster_yaml = yaml.safe_dump_all(
        [{"apiVersion": "v1", "kind": "Node", **n.raw} for n in nodes]
        + [{"apiVersion": "v1", "kind": "Pod", **p.raw} for p in pods])
    template_yaml = yaml.safe_dump(
        {"apiVersion": "v1", "kind": "Node", **template.raw})

    srv = SimulationServer(workers=2)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(srv))
    serving = threading.Thread(target=httpd.serve_forever, daemon=True)
    serving.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"

    def post(path: str, payload: Dict) -> Dict:
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=600.0) as r:
                status, body = r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            status, body = e.code, e.read().decode(errors="replace")
        _check(status == 200, f"POST {path} answered {status}: {body}")
        seconds.append(round(time.perf_counter() - t0, 6))
        return body

    def served_pairs(resp: Dict):
        pairs = [(k, n) for n, keys in resp["placements"].items()
                 for k in keys]
        return pairs + [(k, "!") for k in resp["unscheduled_pods"]]

    seconds: List[float] = []
    try:
        admitted = post("/api/simulate", {
            "cluster": {"yaml": cluster_yaml},
            "new_node": {"spec_yaml": template_yaml},
            "max_new_nodes": sz.serve_max_new, "placements": True})
        snap_digest = admitted["snapshot_digest"]
        probes = [post("/api/simulate", {"base": snap_digest,
                                         "placements": True})
                  for _ in range(sz.serve_probes)]
        cap = post("/api/capacity", {"base": snap_digest})
    finally:
        srv.begin_drain()
        httpd.shutdown()
        httpd.server_close()
        serving.join(timeout=30.0)

    served = _placement_digest(served_pairs(admitted))
    _check(all(_placement_digest(served_pairs(p)) == served for p in probes),
           "a base probe's placements differ from the admission's")
    _check(all(p["digest"] == admitted["digest"] for p in probes),
           "a base probe's digest differs from the admission's")
    _check(not cap["trial_errors"],
           f"capacity lanes failed: {cap['trial_errors']}")
    t0 = time.perf_counter()
    result = simulate(ClusterResources(nodes=nodes, pods=pods), [])
    simulate_s = time.perf_counter() - t0
    direct = _placement_digest(
        [(sp.pod.key, sp.node_name) for sp in result.scheduled_pods]
        + [(up.pod.key, "!") for up in result.unscheduled_pods])
    _check(served == direct,
           f"served placements {served} != core.simulate {direct}")
    report("serve", nodes=sz.serve_nodes, pods=sz.serve_pods,
           placement_digest=served, placed=admitted["placed"],
           unplaced=admitted["unplaced"], capacity_best=cap["best_count"],
           capacity_probes=cap["counts"], request_s=seconds,
           simulate_s=simulate_s, compiles=_compiles(seen),
           peak_bytes_in_use=peak_bytes())


def phase_faults() -> None:
    from open_simulator_tpu import telemetry

    rungs = telemetry.counter("simon_fault_rungs_total",
                              labelnames=("fn", "rung")).collect_values()
    classified = telemetry.counter(
        "simon_fault_classified_total",
        labelnames=("fn", "code", "disposition")).collect_values()
    fired = {"/".join(k): v for k, v in rungs.items() if v}
    compile_faults = sum(v for k, v in classified.items()
                         if k[1] == "E_COMPILE")
    report("faults", rungs=fired, compile_faults=compile_faults,
           classified={"/".join(k): v for k, v in classified.items() if v})
    _check(not fired, f"degradation rungs fired: {fired}")
    _check(compile_faults == 0, f"{compile_faults} classified compile faults")


def run_phases(sz: Sizes) -> None:
    seen: set = set()
    phase_plan(sz, seen)
    phase_placement(sz, seen)
    phase_serve(sz, seen)
    phase_faults()


def run_mesh(sz: Sizes, devices) -> None:
    """The north-star sweep on a 4x1 mesh of `devices` against
    the single-device run on devices[0]."""
    import jax
    import numpy as np

    from open_simulator_tpu import telemetry
    from open_simulator_tpu.engine.exec_cache import (
        bucketed_device_arrays,
        run_batched_cached,
        run_mesh_cached,
    )
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.engine.waves import waves_for
    from open_simulator_tpu.parallel.sweep import make_mesh
    from open_simulator_tpu.telemetry.ledger import array_result_digest
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    _check(len(devices) == 4, f"the mesh needs 4 devices, got {len(devices)}")
    seen: set = set()
    snap = synthetic_snapshot(sz.nodes, sz.pods, max_new=sz.max_new,
                              rich=True)
    cfg = make_config(snap)._replace(fail_reasons=False)
    arrs, _, n_pods = bucketed_device_arrays(snap.arrays)
    arrs = jax.device_put(arrs, devices[0])
    waves = waves_for(snap.arrays, cfg, n_pods_total=int(arrs.req.shape[0]))
    masks = _padded_masks(snap, range(sz.sweep_lanes), arrs.alloc.shape[0])
    misses = telemetry.counter("simon_compile_cache_total",
                               labelnames=("fn", "event"))

    t0 = time.perf_counter()
    single = run_batched_cached(arrs, masks, cfg, waves=waves)
    ref = np.asarray(single.node)[:, :n_pods]
    ref_digest = array_result_digest(ref)["digest"]
    report("mesh-reference", device=str(devices[0]), lanes=sz.sweep_lanes,
           digest=ref_digest, seconds=time.perf_counter() - t0,
           compiles=_compiles(seen))
    del single

    # a node split is refused (ROADMAP B3): the 2x2 must fail loudly
    try:
        make_mesh(n_scenario=2, n_node=2, devices=devices)
    except ValueError as e:
        report("mesh-2x2", refused=str(e))
    else:
        raise SmokeFailure("a 2x2 ('scenario', 'node') mesh was accepted")
    mesh = make_mesh(n_scenario=4, devices=devices)
    m0 = misses.value(fn="mesh_schedule", event="miss")
    carry, digests, seconds, shard_devs = None, [], [], set()
    for _ in range(3):    # warm round, then two donated-carry rounds
        t0 = time.perf_counter()
        out = run_mesh_cached(arrs, masks, cfg, mesh, carry=carry,
                              waves=waves)
        nodes = np.asarray(out.node)[:, :n_pods]
        seconds.append(time.perf_counter() - t0)
        _check(np.array_equal(nodes, ref),
               "4x1 mesh placements differ from chip 0")
        digests.append(array_result_digest(nodes)["digest"])
        shard_devs = {s.device for s in out.state.headroom.addressable_shards}
        carry = out.state
    n_miss = misses.value(fn="mesh_schedule", event="miss") - m0
    _check(n_miss == 1, f"4x1 mesh compiled {n_miss} times")
    _check(set(digests) == {ref_digest}, f"mesh digests {digests}")
    _check(shard_devs == set(devices),
           f"carry shards on {sorted(map(str, shard_devs))}")
    report("mesh", split="4x1", digests=digests,
           compile_misses=n_miss, seconds=seconds,
           shard_devices=sorted(str(d) for d in shard_devs),
           shard_shape=list(out.state.headroom.addressable_shards[0]
                            .data.shape),
           peak_bytes_in_use=[peak_bytes(d) for d in devices],
           compiles=_compiles(seen))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="chip_smoke.py",
        description="Run the capacity planner's main path once on a TPU "
                    "and check its answers.")
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the north-star sweep on 4x1 and 2x2 "
                         "meshes against the single-device run, nothing else")
    args = ap.parse_args(argv)

    import jax

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev['platform']} "
              f"({dev['kind']}), so nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.mesh else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} chip(s), JAX found {dev['count']}",
              file=sys.stderr)
        return 2

    from open_simulator_tpu.engine.exec_cache import enable_persistent_cache

    report("start", device=dev, jax=jax.__version__,
           compile_cache=enable_persistent_cache())
    t0 = time.perf_counter()
    if args.mesh:
        run_mesh(FULL, jax.devices()[:4])
    else:
        run_phases(FULL)
    report("done", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
