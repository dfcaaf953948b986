"""Footprint-narrowed GRID waves (engine/waves.py "Footprint narrowing",
scheduler._narrowed_step): a GRID segment whose config is
narrowing-exact scores each pod over its class's footprint, padded to
the plan's static width K, instead of over every node slot. Placements
must be bit-identical to the same plan run over the full node axis:
equal nodes, feasible counts and final carry for one launch, and an
equal plan digest for the product sweep. Where the config or the
footprint sizes rule narrowing out, the plan must say so (no width)."""

from __future__ import annotations

import numpy as np
import pytest

from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster
from open_simulator_tpu.engine import waves as W
from open_simulator_tpu.engine.sched_config import weight_overrides_from_doc
from open_simulator_tpu.engine.scheduler import (
    device_arrays,
    make_config,
    schedule_pods,
)
from open_simulator_tpu.testing.builders import make_fake_node, make_fake_pod
from open_simulator_tpu.testing.synthetic import synthetic_snapshot

N_NODES = 512  # a node axis on which a 128-slot footprint narrows


def _dense(plan):
    return None if plan is None else plan._replace(
        narrow=((0, ()),) * len(plan.segments))


def _width(plan):
    return max(k for k, _ in plan.narrow)


def _pools(pools=8, max_new=16, n_pods=256):
    return synthetic_snapshot(N_NODES, n_pods, max_new, pools=pools)


def _ties(pools=8, max_new=16, host_ports=None):
    """Identical nodes and identical pods, each pinned to a pool: every
    node of a pool scores the same, so every pick breaks a tie."""
    nodes = [make_fake_node(f"n{i}", labels={"pool": f"p{i % pools}"})
             for i in range(N_NODES)]
    pods = [make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % pools}"},
                          host_ports=host_ports)
            for i in range(8 * pools)]
    template = make_fake_node("t", labels={"pool": "p0"})
    return encode_cluster(nodes, pods, EncodeOptions(
        max_new_nodes=max_new, new_node_template=template))


def _hostname_spread():
    """Pools whose pods spread by hostname: the [N, S] group-count carry
    is live and its domain sums read every node (the gcr_seg path)."""
    nodes = [make_fake_node(f"n{i}", labels={"pool": f"p{i % 8}"})
             for i in range(N_NODES)]
    pods = [make_fake_pod(
        f"p{i}", labels={"app": f"a{i % 8}"},
        node_selector={"pool": f"p{i % 8}"},
        topology_spread=[{
            "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": f"a{i % 8}"}}}])
        for i in range(64)]
    template = make_fake_node("t", labels={"pool": "p0"})
    return encode_cluster(nodes, pods, EncodeOptions(
        max_new_nodes=16, new_node_template=template))


def _forced_in_waves():
    """Every fourth wave's pool-p0 pod is already bound (spec.nodeName)
    to the p0 node the next p0 pod would take if the bind left the node's
    slot in the p0 row unchanged (p0's pods fill its nodes in id order).
    The bound pods tolerate a taint no node has, so their compat class
    has no row among the segment's scheduled classes."""
    nodes = [make_fake_node(f"n{i}", labels={"pool": f"p{i % 8}"})
             for i in range(N_NODES)]
    spare = [{"key": "spare", "operator": "Exists"}]
    pods = [make_fake_pod(
        f"p{i}", cpu="1", node_selector={"pool": f"p{i % 8}"},
        node_name=f"n{8 * (i // 8)}" if i % 32 == 16 else "",
        tolerations=spare if i % 32 == 16 else None)
        for i in range(128)]
    template = make_fake_node("t", labels={"pool": "p0"})
    return encode_cluster(nodes, pods, EncodeOptions(
        max_new_nodes=16, new_node_template=template))


def _shared_footprint():
    """Pool p0's pods alternate between two compat classes (one
    tolerates a taint no node has) with the same footprint: waves stay
    one pod a pool, but two classes of the segment share their nodes."""
    nodes = [make_fake_node(f"n{i}", labels={"pool": f"p{i % 8}"})
             for i in range(N_NODES)]
    pods = []
    for i in range(128):
        tol = ([{"key": "spare", "operator": "Exists"}]
               if i % 16 == 8 else None)
        pods.append(make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % 8}"},
                                  tolerations=tol))
    template = make_fake_node("t", labels={"pool": "p0"})
    return encode_cluster(nodes, pods, EncodeOptions(
        max_new_nodes=16, new_node_template=template))


def _filters_off():
    """The lowest two node ids of every pool are tainted or cordoned, so
    with the TaintToleration and NodeUnschedulable filters disabled by a
    KubeSchedulerConfiguration each pool's first picks land on them (the
    pools' nodes tie), and the footprints must keep them."""
    nodes = [make_fake_node(
        f"n{i}", labels={"pool": f"p{i % 8}"},
        taints=[{"key": "gpu", "effect": "NoSchedule"}] if i < 8 else None,
        unschedulable=8 <= i < 16) for i in range(N_NODES)]
    pods = [make_fake_pod(f"p{i}", node_selector={"pool": f"p{i % 8}"})
            for i in range(64)]
    template = make_fake_node("t", labels={"pool": "p0"})
    return encode_cluster(nodes, pods, EncodeOptions(
        max_new_nodes=16, new_node_template=template))


FILTERS_OFF = weight_overrides_from_doc({
    "kind": "KubeSchedulerConfiguration",
    "profiles": [{"plugins": {"filter": {"disabled": [
        {"name": "TaintToleration"}, {"name": "NodeUnschedulable"}]}}}]})


def _one_node_classes():
    """Each pod selects its own node; pods alternate between two apps
    with a zone spread, so a wave holds two pods: 64 classes of 128
    slots would outgrow one wave's [2, N] rows."""
    nodes = [make_fake_node(f"n{i}", labels={
        "pool": f"p{i}", "topology.kubernetes.io/zone": f"z{i % 4}"})
        for i in range(N_NODES)]
    pods = [make_fake_pod(
        f"p{i}", labels={"app": f"a{i % 2}"}, node_selector={"pool": f"p{i}"},
        topology_spread=[{
            "maxSkew": 5, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": f"a{i % 2}"}}}])
        for i in range(64)]
    template = make_fake_node("t", labels={
        "pool": "p0", "topology.kubernetes.io/zone": "z0"})
    return encode_cluster(nodes, pods, EncodeOptions(
        max_new_nodes=16, new_node_template=template))


# (case id, snapshot builder, config overrides, narrowed width expected)
CASES = [
    ("pools", _pools, {}, 128),
    ("p0-wider", lambda: _pools(max_new=48), {}, 128),
    ("ties", _ties, {}, 128),
    # the same host port on every pod: the port carry rides the step too
    ("host-ports", lambda: _ties(host_ports=[8080]), {}, 128),
    # bound pods inside narrowed waves: their binds land in a class row
    ("forced-in-waves", _forced_in_waves, {}, 128),
    ("shared-footprint", _shared_footprint, {}, 0),
    # filters a scheduler config disables admit every node
    ("filters-off", _filters_off, FILTERS_OFF, 128),
    ("tables-over-a-wave", _one_node_classes, {}, 0),
    ("footprint-over-half", lambda: _pools(pools=2, n_pods=128), {}, 0),
    ("fail-reasons", _pools, {"fail_reasons": True}, 0),
    ("tie-break-seed", _pools, {"tie_break_seed": 7}, 0),
    ("gcr-seg", _hostname_spread, {}, 0),
]


@pytest.mark.parametrize("name,build,overrides,width", CASES,
                         ids=[c[0] for c in CASES])
def test_narrowed_grid_equals_dense(monkeypatch, name, build, overrides,
                                    width):
    from open_simulator_tpu.parallel.sweep import capacity_sweep
    from open_simulator_tpu.telemetry.ledger import plan_digest

    snap = build()
    cfg = make_config(snap, **{"fail_reasons": False, **overrides})
    plan = W.waves_for(snap.arrays, cfg)
    assert plan is not None
    assert any(seg[2] == W.GRID for seg in plan.segments)
    assert _width(plan) == width
    assert (plan.narrowed_fraction > 0.5) if width else (
        plan.narrowed_fraction == 0.0)
    assert plan.stats()["narrowed_fraction"] == plan.narrowed_fraction
    if width:
        # K covers every scheduled pod's footprint with a pad slot left
        fp = W.footprints(snap.arrays, cfg).sum(axis=1)
        assert fp[snap.arrays.class_id].max() < width
    if name == "shared-footprint":
        assert len(np.unique(snap.arrays.class_id)) == 9
    if name == "forced-in-waves":
        # the bound pods' class has no table row
        n_classes = snap.arrays.class_affinity.shape[0]
        assert max(len(c) for _, c in plan.narrow) < n_classes
    if name == "tables-over-a-wave":
        assert max(seg[3] for seg in plan.segments) == 2

    arrs = device_arrays(snap)
    real = np.asarray(arrs.active)
    # template lanes off (the snapshot's own activation) and on
    for active in (real, np.ones_like(real)):
        narrowed = schedule_pods(arrs, active, cfg, waves=plan)
        dense = schedule_pods(arrs, active, cfg, waves=_dense(plan))
        for field in ("node", "feasible", "fail_counts"):
            assert np.array_equal(np.asarray(getattr(narrowed, field)),
                                  np.asarray(getattr(dense, field))), field
        for field, a in dense.state._asdict().items():
            assert np.array_equal(np.asarray(a), np.asarray(
                getattr(narrowed.state, field))), f"state.{field}"
        nodes = np.asarray(narrowed.node)
        assert (nodes >= 0).all()
        if name == "forced-in-waves":
            forced = np.asarray(snap.arrays.forced_node)
            assert np.array_equal(nodes[forced >= 0], forced[forced >= 0])
        if name == "filters-off":
            # the pools' first picks: their tainted, then cordoned node
            assert list(nodes[:16]) == list(range(16))
        if name == "ties":
            # each pool's first pod takes the pool's lowest node id, the
            # second the next one (templates, in p0, have higher ids)
            assert list(nodes[:16]) == list(range(16))

    # the product sweep: narrowed against the same plan made dense
    counts = [0, 5, 16]
    got = capacity_sweep(snap, make_config(snap, **overrides), counts=counts)
    real_waves_for = W.waves_for
    monkeypatch.setattr(W, "waves_for",
                        lambda *a, **kw: _dense(real_waves_for(*a, **kw)))
    want = capacity_sweep(snap, make_config(snap, **overrides), counts=counts)
    assert np.array_equal(got.nodes_per_scenario, want.nodes_per_scenario)
    assert plan_digest(got)["digest"] == plan_digest(want)["digest"]


def test_narrowing_needs_an_exact_config():
    """Every config gate that puts an all-node reduction or a per-node
    carry the narrowed step does not gather into _step turns narrowing
    off; the sweep's own config turns it on."""
    cfg = make_config(_pools())._replace(fail_reasons=False)
    assert W.narrowing_exact(cfg)
    for gate in ("fail_reasons", "tie_break_seed", "explain_topk",
                 "enable_gpu", "enable_storage", "enable_vol_static",
                 "enable_pv_match", "enable_vol_limits",
                 "enable_pod_affinity"):
        on = 3 if gate in ("tie_break_seed", "explain_topk") else True
        assert not W.narrowing_exact(cfg._replace(**{gate: on})), gate


def test_narrow_width_is_per_grid_segment(monkeypatch):
    """The GRID segment narrows, with tables for its scheduled classes;
    the bucketing pad tail after it (a SENTINEL segment) keeps width 0.
    Every plan holds one entry per segment."""
    snap = synthetic_snapshot(N_NODES, 256, 16, pools=8, bound=0.0)
    cfg = make_config(snap)._replace(fail_reasons=False)
    plan = W.compute_wave_plan(snap.arrays, cfg, n_pods_total=320)
    assert [s[2] for s in plan.segments] == [W.GRID, W.SENTINEL]
    classes = tuple(int(c) for c in np.unique(snap.arrays.class_id))
    assert plan.narrow == ((128, classes), (0, ()))
    assert plan.narrowed_fraction == 256 / 320
    # the planner's all-SCAN answer past its class cap
    monkeypatch.setattr(W, "MAX_CLASSES", 0)
    capped = W.compute_wave_plan(snap.arrays, cfg, n_pods_total=320)
    assert capped.narrow == ((0, ()),) and capped.narrowed_fraction == 0.0
