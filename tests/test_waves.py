"""Wave scheduling (engine/waves.py + the scheduler's wave execution).

Two layers:

* **partitioner units** — hand-built conflict graphs (chain, star,
  all-independent, all-conflicting, forced runs, pad tails) asserting
  the host-side analysis draws exactly the wave boundaries the
  independence criterion demands;
* **equivalence properties** — seeded snapshots (multi-tenant pools,
  interleaved forced binds, the all-ops rich workload, GPU share, host
  ports) asserting the wave engine's assignments, fail_counts, every
  carry leaf of the final state, and the ledger result digest are
  BIT-IDENTICAL to the pure scan (`SIMON_WAVES=0` / waves=None) — the
  exactness contract waves are allowed to exist under.
"""

from __future__ import annotations

import numpy as np
import pytest

from open_simulator_tpu.encode.snapshot import encode_cluster
from open_simulator_tpu.engine import waves as W
from open_simulator_tpu.engine.scheduler import (
    device_arrays,
    make_config,
    schedule_pods,
)
from open_simulator_tpu.testing.builders import make_fake_node, make_fake_pod
from open_simulator_tpu.testing.synthetic import synthetic_snapshot


# ---- helpers -------------------------------------------------------------


def _pool_nodes(n, pools, **kw):
    return [make_fake_node(f"n{i}", labels={"pool": f"p{i % pools}"}, **kw)
            for i in range(n)]


def _run_both(snap, overrides=None):
    """Run the scan engine and the wave engine on one snapshot; assert
    bit-identical outputs + state; return the plan."""
    cfg = make_config(snap, **(overrides or {}))
    arrs = device_arrays(snap)
    plan = W.waves_for(snap.arrays, cfg)
    out_scan = schedule_pods(arrs, arrs.active, cfg)
    out_wave = schedule_pods(arrs, arrs.active, cfg, waves=plan)
    for name in ("node", "fail_counts", "feasible", "gpu_pick", "vol_pick",
                 "topk_node", "topk_score", "topk_parts"):
        a = np.asarray(getattr(out_scan, name))
        b = np.asarray(getattr(out_wave, name))
        assert np.array_equal(a, b), f"{name} diverged"
    for name, a in out_scan.state._asdict().items():
        b = getattr(out_wave.state, name)
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"state.{name} diverged")
    from open_simulator_tpu.telemetry.ledger import array_result_digest

    assert (array_result_digest(np.asarray(out_scan.node))
            == array_result_digest(np.asarray(out_wave.node)))
    return plan


# ---- partitioner units ---------------------------------------------------


def test_all_conflicting_is_pure_scan():
    # identical unconstrained pods: every pod reads headroom across the
    # shared footprint every earlier pod writes — nothing batches
    nodes = [make_fake_node(f"n{i}") for i in range(4)]
    pods = [make_fake_pod(f"p{i}") for i in range(16)]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg)
    assert all(seg[2] == W.SCAN for seg in plan.segments)
    assert W.waves_for(snap.arrays, cfg) is None  # degenerate -> None


def test_all_independent_pools_grid():
    # 8 tenant pools, pods round-robin across them with per-pool spread
    # groups: consecutive runs of 8 are pairwise independent -> one
    # uniform GRID of width 8 covering the whole sequence
    snap = synthetic_snapshot(16, 64, 0, pools=8)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg)
    assert plan.segments == ((0, 64, W.GRID, 8),)
    assert plan.max_wave_width == 8
    assert plan.n_waves == 8
    assert plan.wave_fraction == 1.0


def test_chain_conflicts_serialize():
    # pod i's spread selector reads the group pod i-1's label writes —
    # a dependency chain: every wave closes after one pod
    nodes = _pool_nodes(16, 16)
    pods = [
        make_fake_pod(
            f"p{i}", labels={"app": f"a{i}"},
            node_selector={"pool": f"p{i}"},
            topology_spread=[{
                "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": f"a{max(i - 1, 0)}"}},
            }])
        for i in range(16)
    ]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg)
    assert all(seg[2] == W.SCAN for seg in plan.segments)


def test_star_hub_then_spoke_wave():
    # pod 0 (hub) writes the group every spoke reads; the 16 spokes are
    # pairwise independent (disjoint pools, distinct groups) -> segments
    # [hub: scan] + [spokes: one batched wave]
    nodes = _pool_nodes(17, 17)
    pods = [make_fake_pod("hub", labels={"app": "hub"},
                          node_selector={"pool": "p0"})]
    for i in range(1, 17):
        pods.append(make_fake_pod(
            f"s{i}", labels={"app": f"spoke{i}"},
            node_selector={"pool": f"p{i}"},
            topology_spread=[{
                "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": "hub"}},
            }]))
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg)
    assert plan.segments == ((0, 1, W.SCAN, 0), (1, 17, W.BATCH, 0))


def test_forced_run_merges():
    # a run of already-bound pods reads nothing (no failure accounting):
    # one FORCED merge segment, no matter how the nodes repeat
    nodes = [make_fake_node(f"n{i}") for i in range(4)]
    pods = [make_fake_pod(f"b{i}", node_name=f"n{i % 4}")
            for i in range(12)]
    pods += [make_fake_pod(f"p{i}") for i in range(4)]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)._replace(fail_reasons=False, forced_prefix=0)
    plan = W.compute_wave_plan(snap.arrays, cfg)
    # the first free pod reads the footprint the bound run wrote, so
    # the merge wave is exactly the 12 bound pods
    assert plan.segments[0] == (0, 12, W.FORCED, 0)


def test_pad_tail_is_sentinel_segment():
    nodes = [make_fake_node(f"n{i}") for i in range(4)]
    pods = [make_fake_pod(f"p{i}") for i in range(6)]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg, n_pods_total=16)
    assert plan.segments[-1] == (6, 16, W.SENTINEL, 0)
    assert plan.n_pods == 16


def test_fail_reasons_keeps_prefix_and_reads_footprints():
    # with per-op failure accounting on, every pod observes its class
    # footprint, so the leading bound run rides the hoist (plan.start)
    # and interleaved forced pods cannot batch
    nodes = [make_fake_node(f"n{i}") for i in range(4)]
    pods = [make_fake_pod(f"b{i}", node_name=f"n{i % 4}") for i in range(8)]
    pods += [make_fake_pod("free")]
    pods += [make_fake_pod(f"b2{i}", node_name=f"n{i % 4}") for i in range(6)]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)  # fail_reasons=True default; forced_prefix=8
    plan = W.compute_wave_plan(snap.arrays, cfg)
    assert plan.start == 8
    assert all(seg[2] == W.SCAN for seg in plan.segments)


def test_pod_waves_decode():
    snap = synthetic_snapshot(16, 64, 0, pools=8)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg)
    wid, batched = plan.pod_waves()
    assert wid.shape == (64,) and batched.all()
    # 8 grid waves of 8 pods, in sequence order
    assert list(wid[:8]) == [0] * 8 and list(wid[-8:]) == [7] * 8


def test_plan_cache_hits():
    snap = synthetic_snapshot(16, 64, 0, pools=8)
    cfg = make_config(snap)._replace(fail_reasons=False)
    a = W.waves_for(snap.arrays, cfg)
    b = W.waves_for(snap.arrays, cfg)
    assert a is b  # digest-keyed LRU returns the cached plan object


def test_plan_cache_keyed_on_all_analysis_inputs():
    # regression: the ledger workload digest does NOT cover node
    # schedulability (or class masks / selector arrays), but the plan
    # depends on them — cordoning a node must never serve the uncordoned
    # cluster's cached plan
    def snap_for(cordoned):
        nodes = [make_fake_node(f"n{i}", labels={"pool": f"p{i % 8}"},
                                unschedulable=(cordoned and i == 0))
                 for i in range(8)]
        pods = [make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % 8}"})
                for i in range(32)]
        return encode_cluster(nodes, pods)

    from open_simulator_tpu.telemetry.ledger import workload_digest

    a, b = snap_for(False), snap_for(True)
    # the premise of the regression: the cheap workload digest collides
    assert workload_digest(a.arrays) == workload_digest(b.arrays)
    cfg_a = make_config(a)._replace(fail_reasons=False)
    cfg_b = make_config(b)._replace(fail_reasons=False)
    plan_a = W.waves_for(a.arrays, cfg_a)
    plan_b = W.waves_for(b.arrays, cfg_b)
    assert plan_a is not plan_b  # separate cache entries, no stale reuse
    _run_both(b, {"fail_reasons": False})  # and the cordoned plan is exact


def test_class_cap_returns_pure_scan():
    # pathological per-pod-distinct tolerations blow up the compat-class
    # count; past MAX_CLASSES the analysis must bail to all-SCAN instead
    # of building an O(C^2 N) overlap table
    nodes = [make_fake_node(f"n{i}") for i in range(2)]
    pods = [make_fake_pod(
        f"p{i}", tolerations=[{"key": f"t{i}", "operator": "Exists"}])
        for i in range(12)]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg, max_segments=24)
    import open_simulator_tpu.engine.waves as waves_mod

    orig = waves_mod.MAX_CLASSES
    try:
        waves_mod.MAX_CLASSES = 4
        capped = W.compute_wave_plan(snap.arrays, cfg)
        assert capped.segments == ((0, 12, W.SCAN, 0),)
    finally:
        waves_mod.MAX_CLASSES = orig
    assert plan.n_pods == 12  # uncapped analysis still runs below the cap


def test_simon_waves_env_disables(monkeypatch):
    snap = synthetic_snapshot(16, 64, 0, pools=8)
    monkeypatch.setenv("SIMON_WAVES", "0")
    cfg = make_config(snap)._replace(fail_reasons=False)
    assert not cfg.wave_scheduling
    assert W.waves_for(snap.arrays, cfg) is None


# ---- equivalence properties ---------------------------------------------


def test_equiv_pools_grid():
    plan = _run_both(synthetic_snapshot(16, 96, 0, pools=8),
                     {"fail_reasons": False})
    assert plan is not None and plan.wave_fraction == 1.0


def test_equiv_pools_fail_reasons_on():
    plan = _run_both(synthetic_snapshot(16, 96, 0, pools=8))
    assert plan is not None  # footprint-disjoint pods wave even with
    #                          failure accounting on


def test_equiv_rich_pools():
    # the all-ops workload: affinity, anti-affinity, hard+hostname
    # spread, ports, taints — whatever the analysis batches (possibly
    # nothing) must stay bit-identical
    _run_both(synthetic_snapshot(16, 96, 0, rich=True, pools=4),
              {"fail_reasons": False})
    _run_both(synthetic_snapshot(16, 96, 0, rich=True))


def test_equiv_interleaved_forced():
    plan = _run_both(synthetic_snapshot(16, 128, 0, bound=0.6),
                     {"fail_reasons": False, "forced_prefix": 0})
    assert plan is not None


def test_equiv_star_and_explain_topk():
    nodes = _pool_nodes(17, 17)
    pods = [make_fake_pod("hub", labels={"app": "hub"},
                          node_selector={"pool": "p0"})]
    for i in range(1, 17):
        pods.append(make_fake_pod(
            f"s{i}", labels={"app": f"spoke{i}"},
            node_selector={"pool": f"p{i}"},
            topology_spread=[{
                "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": "hub"}},
            }]))
    snap = encode_cluster(nodes, pods)
    plan = _run_both(snap, {"fail_reasons": False})
    assert any(seg[2] == W.BATCH for seg in plan.segments)
    # explain recording rides the batched path bit-identically too
    _run_both(snap, {"fail_reasons": True, "explain_topk": 3})


def test_equiv_gpu_share_in_waves():
    # gpu-share pods inside batched waves: picks computed against the
    # wave-start state and merged — identical to the sequential picks
    nodes = [make_fake_node(
        f"n{i}", labels={"pool": f"p{i % 8}"},
        extra_allocatable={"alibabacloud.com/gpu-count": "4",
                           "alibabacloud.com/gpu-mem": "32"})
        for i in range(8)]
    pods = [make_fake_pod(
        f"g{i}", labels={"app": f"a{i % 8}"},
        node_selector={"pool": f"p{i % 8}"},
        annotations={"alibabacloud.com/gpu-mem": "2",
                     "alibabacloud.com/gpu-count": "1"})
        for i in range(32)]
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)
    assert cfg.enable_gpu
    plan = _run_both(snap, {"fail_reasons": False})
    assert plan is not None and plan.max_wave_width >= 8


def test_equiv_group_anti_pref_merges_in_waves():
    # every group-carrier write path inside ONE batched wave: each pod
    # spreads on its OWN app group under the hostname key (group_count +
    # dom writes), owns an anti-affinity term on its own unique label
    # (term_block paint), and prefers its own group (pref_paint) — all
    # self-referential, so pods stay pairwise independent across pools
    # and the wave MERGE must reproduce the sequential carry bit-for-bit
    nodes = _pool_nodes(16, 16)
    pods = []
    for i in range(16):
        aff = {
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"anti": f"g{i}"}},
                    "topologyKey": "kubernetes.io/hostname",
                }],
            },
            "podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": 7,
                    "podAffinityTerm": {
                        "labelSelector": {"matchLabels": {"app": f"a{i}"}},
                        "topologyKey": "kubernetes.io/hostname",
                    },
                }],
            },
        }
        pods.append(make_fake_pod(
            f"p{i}", labels={"app": f"a{i}", "anti": f"g{i}"},
            node_selector={"pool": f"p{i}"}, affinity=aff,
            topology_spread=[{
                "maxSkew": 2, "topologyKey": "kubernetes.io/hostname",
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": f"a{i}"}},
            }]))
    snap = encode_cluster(nodes, pods)
    cfg = make_config(snap)
    assert cfg.needs_group_count and cfg.enable_anti_affinity
    assert cfg.enable_pref
    plan = _run_both(snap, {"fail_reasons": False})
    assert plan is not None
    assert any(seg[2] in (W.BATCH, W.GRID) for seg in plan.segments)


def test_equiv_host_ports_across_pools():
    # the same hostPort in every pool: the port channel is per-node, so
    # disjoint footprints still batch — and stay exact
    nodes = _pool_nodes(8, 8)
    pods = [make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % 8}"},
                          host_ports=[8080])
            for i in range(32)]
    snap = encode_cluster(nodes, pods)
    plan = _run_both(snap, {"fail_reasons": False})
    assert plan is not None


@pytest.mark.parametrize("lanes", [4, 64])
def test_equiv_sweep_digest(monkeypatch, lanes):
    # the product sweep path with waves on vs off must produce
    # bit-identical placements and plan digests (the acceptance
    # criterion's ledger-digest form): a 4-lane capacity_bisect over
    # waves of 8, and a 64-lane capacity_sweep over GRID waves of 32,
    # the wave step at the lane count and width of a pools sweep
    from open_simulator_tpu.parallel.sweep import capacity_bisect, capacity_sweep
    from open_simulator_tpu.telemetry.ledger import plan_digest

    monkeypatch.delenv("SIMON_LEDGER_DIR", raising=False)
    monkeypatch.delenv("SIMON_CHECKPOINT_DIR", raising=False)
    if lanes == 4:
        snap = synthetic_snapshot(16, 96, 8, pools=8)

        def run(cfg):
            return capacity_bisect(snap, cfg, max_new=8, lanes=4)
    else:
        snap = synthetic_snapshot(64, 128, 64, pools=32)
        grid = W.waves_for(snap.arrays, make_config(snap)._replace(
            fail_reasons=False))
        assert grid.segments == ((0, 128, W.GRID, 32),)

        def run(cfg):
            return capacity_sweep(snap, cfg, counts=list(range(lanes)))
    plans = {}
    for env in ("1", "0"):
        monkeypatch.setenv("SIMON_WAVES", env)
        cfg = make_config(snap)
        assert cfg.wave_scheduling == (env == "1")
        plans[env] = run(cfg)
    assert np.array_equal(plans["1"].nodes_per_scenario,
                          plans["0"].nodes_per_scenario)
    assert (plan_digest(plans["1"])["digest"]
            == plan_digest(plans["0"])["digest"])


def test_simulate_reports_waves():
    from open_simulator_tpu.core import AppResource, simulate
    from open_simulator_tpu.k8s.loader import ClusterResources
    from open_simulator_tpu.telemetry.explain import explain_result

    cluster = ClusterResources()
    cluster.nodes = _pool_nodes(8, 8)
    app = ClusterResources()
    app.pods = [make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % 8}"})
                for i in range(24)]
    res = simulate(cluster, [AppResource(name="a", resources=app)])
    assert res.wave_id is not None and res.wave_batched is not None
    assert res.wave_batched.any()
    report = explain_result(res)
    assert report["waves"]["batched_pods"] > 0
    entry = report["pods"][0]
    assert "wave" in entry and entry["wave_path"] in ("batched", "scan")


def test_equiv_simulate_result_digest(monkeypatch):
    # end-to-end simulate(): identical result digest with waves on/off
    from open_simulator_tpu.core import AppResource, simulate
    from open_simulator_tpu.k8s.loader import ClusterResources
    from open_simulator_tpu.telemetry.ledger import result_digest

    digests = {}
    for env in ("1", "0"):
        monkeypatch.setenv("SIMON_WAVES", env)
        cluster = ClusterResources()
        cluster.nodes = _pool_nodes(8, 8)
        app = ClusterResources()
        app.pods = [
            make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % 8}"})
            for i in range(24)]
        res = simulate(cluster, [AppResource(name="a", resources=app)])
        digests[env] = result_digest(res)["digest"]
    assert digests["1"] == digests["0"]


# ---- satellite: disabled-ledger sweeps never fingerprint -----------------


def test_sweep_disabled_ledger_computes_no_digests(monkeypatch):
    """With no ledger configured, the sweep wrappers must not hash the
    snapshot or the plan (the documented one-dict-lookup no-op): patch
    every record-building digest to raise and run both sweep modes."""
    from open_simulator_tpu.parallel import sweep as sweep_mod
    from open_simulator_tpu.telemetry import ledger

    monkeypatch.delenv("SIMON_LEDGER_DIR", raising=False)
    monkeypatch.delenv("SIMON_CHECKPOINT_DIR", raising=False)
    ledger.configure(None)

    def boom(*a, **kw):  # pragma: no cover - the assertion is "not called"
        raise AssertionError("digest computed on the disabled-ledger path")

    monkeypatch.setattr(ledger, "config_fingerprint", boom)
    monkeypatch.setattr(ledger, "plan_digest", boom)
    monkeypatch.setattr(ledger, "result_digest", boom)

    snap = synthetic_snapshot(8, 32, 4)
    cfg = make_config(snap)
    plan = sweep_mod.capacity_bisect(snap, cfg, max_new=4, lanes=2)
    assert plan.best_count is not None or plan.counts
    plan2 = sweep_mod.capacity_sweep(snap, cfg, counts=[0, 2, 4])
    assert plan2.counts == [0, 2, 4]
