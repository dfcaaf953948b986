"""Capacity sweep + sharded scenario batch on the 8-device virtual mesh."""

import jax
import pytest
import numpy as np

from open_simulator_tpu.core import build_pod_sequence, AppResource
from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster
from open_simulator_tpu.engine.scheduler import make_config
from open_simulator_tpu.k8s.loader import ClusterResources, make_valid_node
from open_simulator_tpu.parallel import capacity_sweep, make_mesh, SweepThresholds
from tests.conftest import make_node, make_pod


def _snapshot(n_pods=12, pod_cpu="1500m", max_new=8):
    cluster = ClusterResources()
    cluster.nodes = [make_node("real-0", cpu_m=4000, mem_mib=8192)]
    app = ClusterResources()
    app.pods = [make_pod(f"p{i}", cpu=pod_cpu, mem="512Mi") for i in range(n_pods)]
    pods = build_pod_sequence(cluster, [AppResource(name="a", resources=app)])
    template = make_node("template", cpu_m=4000, mem_mib=8192)
    snap = encode_cluster(
        [make_valid_node(n) for n in cluster.nodes],
        pods,
        EncodeOptions(max_new_nodes=max_new, new_node_template=template),
    )
    return snap


def test_capacity_sweep_finds_min_count():
    snap = _snapshot()
    cfg = make_config(snap)
    plan = capacity_sweep(snap, cfg, counts=list(range(9)))
    # 12 pods x 1500m = 18000m; each node fits floor(4000/1500)=2 pods.
    # 12 pods need 6 nodes total -> 5 new nodes.
    assert plan.best_count == 5
    assert plan.all_scheduled == [c >= 5 for c in range(9)]
    # monotone: more nodes never decreases scheduled pods
    scheduled_counts = [(plan.nodes_per_scenario[s] >= 0).sum() for s in range(9)]
    assert scheduled_counts == sorted(scheduled_counts)


def test_capacity_sweep_occupancy_threshold():
    snap = _snapshot()
    cfg = make_config(snap)
    # Tight CPU occupancy cap forces more headroom than bare fit.
    plan = capacity_sweep(
        snap, cfg, counts=list(range(9)), thresholds=SweepThresholds(max_cpu_pct=60.0)
    )
    # 18000m total request; need total alloc >= 30000m -> 8 nodes -> 7 new.
    assert plan.best_count == 7


def test_sweep_on_device_mesh_matches_single_device():
    snap = _snapshot()
    cfg = make_config(snap)
    counts = list(range(8))
    mesh = make_mesh()  # 8 virtual CPU devices on the scenario axis
    assert mesh.devices.size == len(jax.devices())
    plan_mesh = capacity_sweep(snap, cfg, counts=counts, mesh=mesh)
    plan_single = capacity_sweep(snap, cfg, counts=counts)
    assert plan_mesh.best_count == plan_single.best_count
    np.testing.assert_array_equal(plan_mesh.nodes_per_scenario, plan_single.nodes_per_scenario)


def test_mesh_bisect_donated_carry_digest_matches_single_device():
    """ISSUE 19: the bisection threads its donated carry through the
    CACHED mesh path — every round after the first reuses round one's
    sharded executable (`mesh_schedule` miss delta == 1 across the whole
    bisect), and the resulting plan is ledger-digest-identical to the
    single-device bisect's."""
    from open_simulator_tpu.parallel import capacity_bisect
    from open_simulator_tpu.telemetry import counter, ledger

    snap = _snapshot()
    cfg = make_config(snap)
    # the scenario axis must divide the lane count (4 lanes below)
    mesh = make_mesh(n_scenario=4)

    def miss():
        return counter("simon_compile_cache_total", "",
                       labelnames=("fn", "event")).value(
                           fn="mesh_schedule", event="miss")

    # lanes=4 keys a mask shape no other mesh test compiles, so the
    # delta below counts THIS bisect's compiles only
    m0 = miss()
    plan_mesh = capacity_bisect(snap, cfg, max_new=8, mesh=mesh, lanes=4)
    assert miss() - m0 == 1
    plan_single = capacity_bisect(snap, cfg, max_new=8, lanes=4)
    assert plan_mesh.best_count == plan_single.best_count
    assert (ledger.plan_digest(plan_mesh)["digest"]
            == ledger.plan_digest(plan_single)["digest"])


def test_scenario_meshes_bit_equal_across_splits():
    """The same snapshot swept over 1, 4 and 8 chips on the scenario axis
    must produce bit-identical picks, fail counts and headroom."""
    from open_simulator_tpu.engine.scheduler import device_arrays
    from open_simulator_tpu.parallel.sweep import (
        active_masks_for_counts,
        batched_schedule,
    )
    import jax.numpy as jnp

    snap = _snapshot(n_pods=16, max_new=7)  # 8 total nodes: divisible by 2 and 4
    cfg = make_config(snap)
    counts = [0, 2, 4, 7] * 2               # 8 lanes
    masks = jnp.asarray(active_masks_for_counts(snap, counts))

    results = []
    for n_scen in (1, 4, 8):
        mesh = make_mesh(n_scenario=n_scen)
        out = batched_schedule(device_arrays(snap), masks, cfg, mesh=mesh)
        results.append((np.asarray(out.node), np.asarray(out.fail_counts),
                        np.asarray(out.state.headroom)))
    base = results[0]
    for got in results[1:]:
        np.testing.assert_array_equal(got[0], base[0])
        np.testing.assert_array_equal(got[1], base[1])
        np.testing.assert_allclose(got[2], base[2], rtol=0, atol=0)


def test_scenario_meshes_with_spread_constraints():
    """Mesh lanes with zone spread: the dom_count carry and hoisted domain
    stats must come out bit-for-bit whatever the scenario split."""
    from open_simulator_tpu.engine.scheduler import device_arrays
    from open_simulator_tpu.parallel.sweep import (
        active_masks_for_counts,
        batched_schedule,
    )
    import jax.numpy as jnp

    cluster = ClusterResources()
    cluster.nodes = [
        make_node(f"real-{i}", cpu_m=4000, mem_mib=8192,
                  labels={"topology.kubernetes.io/zone": f"z{i % 2}"})
        for i in range(4)
    ]
    app = ClusterResources()
    spread = [{
        "maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
        "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"app": "a0"}},
    }]
    app.pods = [
        make_pod(f"p{i}", cpu="900m", mem="256Mi", labels={"app": "a0"},
                 spread=spread)
        for i in range(10)
    ]
    pods = build_pod_sequence(cluster, [AppResource(name="a", resources=app)])
    template = make_node("template", cpu_m=4000, mem_mib=8192,
                         labels={"topology.kubernetes.io/zone": "z0"})
    snap = encode_cluster(
        [make_valid_node(n) for n in cluster.nodes], pods,
        EncodeOptions(max_new_nodes=4, new_node_template=template),
    )
    cfg = make_config(snap)
    assert cfg.enable_spread_hard
    counts = [0, 1, 2, 4]
    masks = jnp.asarray(active_masks_for_counts(snap, counts))

    results = []
    for n_scen in (1, 2, 4):
        mesh = make_mesh(n_scenario=n_scen)
        out = batched_schedule(device_arrays(snap), masks, cfg, mesh=mesh)
        results.append(np.asarray(out.node))
    np.testing.assert_array_equal(results[1], results[0])
    np.testing.assert_array_equal(results[2], results[0])


def test_make_mesh_require_all_rejects_partial_use():
    """require_all: multi-host callers must not silently drop a host's
    devices (a host with no addressable shard hangs instead of erroring)."""
    import pytest

    n = len(jax.devices())
    assert n == 8
    # 6 of 8 devices: fine by default, rejected with require_all
    mesh = make_mesh(n_scenario=6)
    assert mesh.devices.size == 6
    with pytest.raises(ValueError, match="uses 6 of 8 devices"):
        make_mesh(n_scenario=6, require_all=True)
    # an oversubscribed mesh always errors
    with pytest.raises(ValueError, match="needs 16 devices"):
        make_mesh(n_scenario=16)


@pytest.mark.parametrize("how", ["make_mesh", "hand_built_mesh",
                                 "capacity_sweep"])
def test_node_axis_is_refused_naming_b3(how):
    """A node split placed pods wrongly on a 2x2 v5e mesh (PR 21): a
    "node" axis above 1 fails loudly — from make_mesh, and from the mesh
    executable for a Mesh built by hand — instead of running."""
    from jax.sharding import Mesh

    from open_simulator_tpu.engine.scheduler import device_arrays
    from open_simulator_tpu.parallel.sweep import (
        active_masks_for_counts,
        batched_schedule,
    )

    with pytest.raises(ValueError, match="ROADMAP B3"):
        if how == "make_mesh":
            make_mesh(n_scenario=2, n_node=2)
        snap = _snapshot(n_pods=4, max_new=3)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("scenario", "node"))
        if how == "hand_built_mesh":
            batched_schedule(device_arrays(snap),
                             active_masks_for_counts(snap, [0, 1]),
                             make_config(snap), mesh=mesh)
        # the sweep refuses before its fault ladder could isolate lanes
        capacity_sweep(snap, make_config(snap), [0, 1], mesh=mesh)


def test_isolated_lane_pick_shape_mismatch_is_recorded(monkeypatch):
    """Satellite: the isolated-lane fallback used to silently keep zero
    gpu/vol picks when the lane's output width drifted from the batch
    layout; it must now record the lane in trial_errors."""
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.parallel import sweep as sweep_mod

    snap = _snapshot(n_pods=4, pod_cpu="500m", max_new=1)
    cfg = make_config(snap)._replace(enable_gpu=True)
    n_real = snap.n_real_nodes
    real_batched = sweep_mod.batched_schedule

    def drifted(arrs, masks, cfg_, mesh=None, **kw):
        if masks.shape[0] > 1:
            raise RuntimeError("injected: force the isolated fallback")
        out = real_batched(arrs, masks, cfg_, mesh=mesh, **kw)
        if int(np.asarray(masks[0]).sum()) - n_real == 0:
            # lane for count=0: gpu_pick width drifted from the batch
            return out._replace(
                gpu_pick=np.zeros((1, np.asarray(out.node).shape[1], 99),
                                  dtype=np.int32))
        return out

    monkeypatch.setattr(sweep_mod, "batched_schedule", drifted)
    plan = sweep_mod.capacity_sweep(snap, cfg, [0, 1], backoff_s=0.0)
    assert list(plan.trial_errors) == [0]
    assert "gpu_pick shape" in plan.trial_errors[0]
    assert not plan.satisfied[0]
    assert plan.all_scheduled[1]


def test_all_lanes_failed_message_survives_any_lane_numbering(monkeypatch):
    """Satellite: the all-lanes-failed diagnostic reads SOME recorded
    error (next(iter(...))) instead of hard-indexing trial_errors[0]."""
    import pytest

    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.parallel import sweep as sweep_mod

    snap = _snapshot(n_pods=4, pod_cpu="500m", max_new=1)
    cfg = make_config(snap)

    def dead(*a, **kw):
        raise RuntimeError("device gone")

    monkeypatch.setattr(sweep_mod, "batched_schedule", dead)
    with pytest.raises(RuntimeError,
                       match="all 2 sweep trials failed; first: .*device gone"):
        sweep_mod.capacity_sweep(snap, cfg, [0, 1], backoff_s=0.0)


def test_scenario_meshes_bit_equal_all_ops():
    """Same mesh-split equality as above, but on the all-ops workload —
    the sparse-slot column updates (dynamic-update-slice on the sharded
    carries), affinity/anti-affinity/spread ops, and ports must come out
    bit-for-bit too."""
    import __graft_entry__ as ge
    import jax.numpy as jnp
    from open_simulator_tpu.engine.scheduler import device_arrays
    from open_simulator_tpu.parallel.sweep import (
        active_masks_for_counts,
        batched_schedule,
    )

    snap = ge._synthetic_snapshot(n_nodes=8, n_pods=48, max_new=8, rich=True)
    cfg = make_config(snap)
    assert cfg.slot_paint and cfg.enable_anti_affinity and cfg.enable_spread
    counts = [0, 2, 5, 8] * 2               # 8 lanes; 16 total nodes
    masks = jnp.asarray(active_masks_for_counts(snap, counts))

    results = []
    for n_scen in (1, 4, 8):
        mesh = make_mesh(n_scenario=n_scen)
        out = batched_schedule(device_arrays(snap), masks, cfg, mesh=mesh)
        results.append((np.asarray(out.node), np.asarray(out.fail_counts),
                        np.asarray(out.state.headroom),
                        np.asarray(out.state.term_block),
                        np.asarray(out.state.group_count)))
    base = results[0]
    for got in results[1:]:
        for a, b in zip(got, base):
            np.testing.assert_array_equal(a, b)
