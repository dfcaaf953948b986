"""Compile-amortization layer: bucketing, padding equivalence, the AOT
executable LRU, donated carries, and the bisection sweep's parity with
the exhaustive sweep (ISSUE 4 acceptance criteria)."""

import dataclasses
import os

import numpy as np
import pytest

from open_simulator_tpu.core import AppResource, build_pod_sequence, simulate
from open_simulator_tpu.encode.snapshot import (
    NODE_AXIS_FIRST,
    NODE_AXIS_SECOND,
    POD_AXIS_FIRST,
    EncodeOptions,
    SnapshotArrays,
    encode_cluster,
)
from open_simulator_tpu.engine import exec_cache
from open_simulator_tpu.engine.exec_cache import (
    ExecutableCache,
    bucket_dim,
    bucket_shape,
    pad_snapshot_arrays,
    pad_vector,
    run_batched_cached,
)
from open_simulator_tpu.engine.scheduler import _pod_xs, make_config
from open_simulator_tpu.k8s.loader import ClusterResources, make_valid_node
from open_simulator_tpu.parallel.sweep import (
    active_masks_for_counts,
    capacity_bisect,
    capacity_sweep,
)
from tests.conftest import make_node, make_pod


def _counter(name, **labels):
    from open_simulator_tpu.telemetry import counter

    return counter(name, "", labelnames=tuple(labels)).value(**labels)


def _cluster(n_nodes, n_pods, cpu="500m"):
    cluster = ClusterResources()
    cluster.nodes = [make_node(f"n{i}", cpu_m=4000, mem_mib=8192)
                     for i in range(n_nodes)]
    app = ClusterResources()
    app.pods = [make_pod(f"p{i}", cpu=cpu, mem="256Mi") for i in range(n_pods)]
    return cluster, [AppResource(name="a", resources=app)]


def _snapshot(n_pods=12, pod_cpu="1500m", max_new=12):
    cluster, apps = _cluster(1, n_pods, cpu=pod_cpu)
    pods = build_pod_sequence(cluster, apps)
    template = make_node("template", cpu_m=4000, mem_mib=8192)
    return encode_cluster(
        [make_valid_node(n) for n in cluster.nodes], pods,
        EncodeOptions(max_new_nodes=max_new, new_node_template=template))


# ---- bucket boundaries --------------------------------------------------

def test_bucket_dim_pow2_then_linear_tail():
    assert [bucket_dim(n, 16, 16) for n in (1, 2, 3, 5, 9, 16)] == \
        [1, 2, 4, 8, 16, 16]
    # linear tail: multiples of the step beyond the pow2 region
    assert bucket_dim(17, 16, 16) == 32
    assert bucket_dim(33, 16, 16) == 48
    assert bucket_dim(48, 16, 16) == 48
    assert bucket_dim(0, 16, 16) == 0


@pytest.mark.parametrize("shape,bucket", [
    # 5,120 x 51,200 sits ON a boundary (no pad)
    ((5120, 51200), (5120, 51200)),
    # the benchmark cells: 5,120 nodes + 64 template slots
    ((5184, 51200), (6144, 51200)),
    # the doubling region of both axes
    ((13, 37), (16, 64)),
    # one past each doubling region: the first linear step
    ((1025, 2049), (2048, 4096)),
])
def test_bucket_shape_keeps_northstar_exact(shape, bucket):
    assert bucket_shape(*shape) == bucket


def test_bucket_shape_ignores_the_retired_env_switch():
    """Bucketing is decided from the shape alone: the environment
    variable that once turned it off changes nothing, even when it is
    set before the module is imported."""
    import subprocess
    import sys

    env = dict(os.environ, SIMON_BUCKETING="0", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from open_simulator_tpu.engine.exec_cache import bucket_shape; "
         "print(bucket_shape(13, 37))"],
        env=env, capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.stdout.strip() == "(16, 64)"


def _unbucketed(monkeypatch):
    """Run every entry point on its unpadded shape (the reference that
    bucketing must equal)."""
    monkeypatch.setattr(exec_cache, "bucket_shape", lambda n, p: (n, p))


def test_axis_declarations_cover_every_field():
    """Adding a SnapshotArrays field must classify its axis exactly once
    (padding or sharding a misdeclared field corrupts results silently)."""
    all_fields = {f.name for f in dataclasses.fields(SnapshotArrays)}
    declared = NODE_AXIS_FIRST | NODE_AXIS_SECOND | POD_AXIS_FIRST
    assert declared <= all_fields
    for a, b in [(NODE_AXIS_FIRST, NODE_AXIS_SECOND),
                 (NODE_AXIS_FIRST, POD_AXIS_FIRST),
                 (NODE_AXIS_SECOND, POD_AXIS_FIRST)]:
        assert not (a & b)
    # the scan's xs leaves ARE the pod axis (minus the synthesized index)
    snap = _snapshot(n_pods=2, max_new=0)
    xs_names = set(_pod_xs(snap.arrays)) - {"_pod_index"}
    assert xs_names == POD_AXIS_FIRST
    # undeclared fields are the vocab-axis arrays — pin the roster so a
    # new node/pod-axis field cannot hide there
    assert all_fields - declared == {
        "spec_alloc", "term_key", "pref_term_key", "pv_cand", "svol_key"}


def test_pad_snapshot_arrays_shapes_and_sentinels():
    snap = _snapshot(n_pods=10, max_new=2)
    a = snap.arrays
    n, p = a.alloc.shape[0], a.req.shape[0]
    padded = pad_snapshot_arrays(a, n + 5, p + 3)
    assert padded.alloc.shape[0] == n + 5
    assert padded.topo_onehot.shape[1] == n + 5
    assert padded.req.shape[0] == p + 3
    # padded nodes can never activate or host anything
    assert not padded.active[n:].any()
    assert padded.unschedulable[n:].all()
    # padded pods are bind-nothing sentinels with empty slot rows
    assert (padded.forced_node[p:] == -4).all()
    assert (padded.req[p:] == 0).all()
    assert (padded.match_gid[p:] == -1).all()
    # vocab arrays untouched
    np.testing.assert_array_equal(padded.term_key, a.term_key)


def test_pad_vector():
    v = np.array([1, 2, 3], dtype=np.int32)
    out = pad_vector(v, 5, -1)
    np.testing.assert_array_equal(out, [1, 2, 3, -1, -1])
    assert pad_vector(None, 5, -1) is None
    assert pad_vector(v, 3, -1) is v


def test_bucketed_simulate_matches_unbucketed(monkeypatch):
    """Bucketing is a pure compile-amortization move: placements, reasons
    and gpu picks must be bit-identical with the padding off."""
    cluster, apps = _cluster(5, 11)
    res_pad = simulate(cluster, apps)
    _unbucketed(monkeypatch)
    cluster2, apps2 = _cluster(5, 11)
    res_raw = simulate(cluster2, apps2)
    assert res_pad.placements() == res_raw.placements()
    assert [u.reason for u in res_pad.unscheduled_pods] == \
        [u.reason for u in res_raw.unscheduled_pods]
    np.testing.assert_array_equal(res_pad.fail_counts, res_raw.fail_counts)
    assert res_pad.n_active_nodes == res_raw.n_active_nodes == 5


@pytest.mark.parametrize("pools", [0, 4])
def test_bucketed_sweep_matches_unbucketed(monkeypatch, pools):
    """The capacity sweep on its padded bucket (15 -> 16 nodes, 37 -> 64
    pods) gives the plan of the unpadded run: same counts, verdicts and
    every lane's node assignments. pools=4 runs the wave path, whose
    plan sees the padded pod tail."""
    from open_simulator_tpu.telemetry.ledger import plan_digest
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    snap = synthetic_snapshot(10, 37, 5, pools=pools)
    cfg = make_config(snap)
    counts = list(range(6))
    padded = capacity_sweep(snap, cfg, counts=counts)
    _unbucketed(monkeypatch)
    raw = capacity_sweep(snap, cfg, counts=counts)
    assert padded.counts == raw.counts == counts
    np.testing.assert_array_equal(padded.nodes_per_scenario,
                                  raw.nodes_per_scenario)
    assert plan_digest(padded)["digest"] == plan_digest(raw)["digest"]


def test_same_bucket_simulate_zero_recompiles():
    """ISSUE 4 acceptance: two consecutive simulate() calls on snapshots
    in the same bucket perform zero recompiles, observed through the
    jit-cache hit/miss counters."""
    miss = lambda: _counter("simon_compile_cache_total",  # noqa: E731
                            fn="schedule_pods", event="miss")
    hit = lambda: _counter("simon_compile_cache_total",  # noqa: E731
                           fn="schedule_pods", event="hit")

    cluster_a, apps_a = _cluster(5, 10)
    simulate(cluster_a, apps_a)          # may or may not compile (suite order)
    m0, h0 = miss(), hit()
    # one node and two pods bigger — same [8, 16] bucket
    cluster_b, apps_b = _cluster(6, 12)
    res = simulate(cluster_b, apps_b)
    assert len(res.scheduled_pods) == 12
    assert miss() == m0, "same-bucket simulate() recompiled the scan"
    assert hit() == h0 + 1


# ---- AOT executable LRU -------------------------------------------------

def test_executable_cache_lru_hit_miss_eviction():
    ev = lambda e: _counter("simon_compile_cache_total",  # noqa: E731
                            fn="lru-test", event=e)
    base = {e: ev(e) for e in ("hit", "miss", "eviction")}
    cache = ExecutableCache(capacity=2)
    built = []

    def make(tag):
        def build():
            built.append(tag)
            return tag
        return build

    assert cache.get_or_compile(("a",), "lru-test", make("a")) == "a"
    assert cache.get_or_compile(("a",), "lru-test", make("a2")) == "a"  # hit
    assert cache.get_or_compile(("b",), "lru-test", make("b")) == "b"
    assert cache.get_or_compile(("c",), "lru-test", make("c")) == "c"  # evicts a
    assert built == ["a", "b", "c"]
    assert len(cache) == 2
    assert cache.get_or_compile(("a",), "lru-test", make("a3")) == "a3"  # rebuilt
    assert ev("hit") - base["hit"] == 1
    assert ev("miss") - base["miss"] == 4
    assert ev("eviction") - base["eviction"] == 2


def test_batched_exec_cache_reuse_and_donation():
    snap = _snapshot(n_pods=8, max_new=3)
    cfg = make_config(snap)
    arrs, _, n_pods = exec_cache.bucketed_device_arrays(snap.arrays)
    lane_masks = np.zeros((2, arrs.alloc.shape[0]), dtype=bool)
    lane_masks[:, :snap.n_nodes] = active_masks_for_counts(snap, [0, 3])

    miss = lambda: _counter("simon_compile_cache_total",  # noqa: E731
                            fn="batched_schedule", event="miss")
    m0 = miss()
    out1 = run_batched_cached(arrs, lane_masks, cfg)
    m1 = miss()
    nodes1 = np.asarray(out1.node)
    # round 2 donates round 1's carry; results identical, zero new compiles
    out2 = run_batched_cached(arrs, lane_masks, cfg, carry=out1.state)
    assert miss() == m1
    np.testing.assert_array_equal(np.asarray(out2.node), nodes1)
    assert m1 - m0 <= 1  # at most one compile for this shape in the suite
    # the donated carry is dead — reading it must fail loudly
    with pytest.raises(Exception, match="deleted|donated"):
        np.asarray(out1.state.headroom)


def test_mesh_exec_cache_reuse_and_donation():
    """ISSUE 19: two same-bucket MESH launches compile exactly once
    (`simon_compile_cache_total{fn=mesh_schedule}` miss delta == 1), the
    donated-carry round is bit-identical to a fresh round (the §9 x*0
    reset, now sharded), and [S, K] traced weight lanes run under the
    mesh — digest-identical to constant mode."""
    import jax

    from open_simulator_tpu.engine.exec_cache import run_mesh_cached
    from open_simulator_tpu.engine.scheduler import weight_vector
    from open_simulator_tpu.parallel.sweep import make_mesh

    assert len(jax.devices()) >= 2  # conftest forces 8 virtual devices
    mesh = make_mesh(n_scenario=2, n_node=1, devices=jax.devices()[:2])
    snap = _snapshot(n_pods=8, max_new=3)
    cfg = make_config(snap)
    arrs, _, _ = exec_cache.bucketed_device_arrays(snap.arrays)
    lane_masks = np.zeros((2, arrs.alloc.shape[0]), dtype=bool)
    lane_masks[:, :snap.n_nodes] = active_masks_for_counts(snap, [0, 3])

    miss = lambda: _counter("simon_compile_cache_total",  # noqa: E731
                            fn="mesh_schedule", event="miss")
    m0 = miss()
    out1 = run_mesh_cached(arrs, lane_masks, cfg, mesh)
    assert miss() - m0 == 1
    nodes1 = np.asarray(out1.node)
    # same bucket -> pure cache hit, zero recompiles
    out2 = run_mesh_cached(arrs, lane_masks, cfg, mesh)
    assert miss() - m0 == 1
    np.testing.assert_array_equal(np.asarray(out2.node), nodes1)
    # round 3 donates round 2's sharded state; identical results, still
    # the one executable
    out3 = run_mesh_cached(arrs, lane_masks, cfg, mesh, carry=out2.state)
    assert miss() - m0 == 1
    np.testing.assert_array_equal(np.asarray(out3.node), nodes1)
    # the donated carry is dead — reading it must fail loudly
    with pytest.raises(Exception, match="deleted|donated"):
        np.asarray(out2.state.headroom)

    # [S, K] traced weight lanes under the mesh: every lane at the
    # config's own vector must reproduce the constant-mode digest
    cfg_t = cfg._replace(traced_weights=True)
    w = np.tile(weight_vector(cfg_t), (2, 1))
    out_w = run_mesh_cached(arrs, lane_masks, cfg_t, mesh, weights=w)
    np.testing.assert_array_equal(np.asarray(out_w.node), nodes1)


@pytest.fixture
def cache_state():
    """Hand later tests back the suite-wide cache conftest configures."""
    import jax

    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)
    exec_cache._persistent_dir = None


@pytest.mark.parametrize("env_set,flag_set", [(True, True), (True, False),
                                              (False, True), (False, False)])
def test_compile_cache_placement(tmp_path, monkeypatch, cache_state,
                                 env_set, flag_set):
    """JAX_COMPILATION_CACHE_DIR, when set, IS the cache — a
    --compile-cache-dir flag never overrides it — after every entry point
    starts (CLI main, the server); unset, the flag's path is used, else
    one fixed path inside the checkout."""
    import importlib

    import jax

    from open_simulator_tpu.server.rest import SimulationServer

    cli = importlib.import_module("open_simulator_tpu.cli.main")

    env_dir, flag_dir = str(tmp_path / "env"), str(tmp_path / "flag")
    if env_set:
        monkeypatch.setenv(exec_cache.CACHE_ENV, env_dir)
    else:
        monkeypatch.delenv(exec_cache.CACHE_ENV, raising=False)
    flag = flag_dir if flag_set else ""
    want = env_dir if env_set else flag_dir if flag_set else (
        exec_cache.DEFAULT_CACHE_DIR)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert exec_cache.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")

    monkeypatch.setattr(cli, "_replay_main", lambda args: 0)
    argv = ["replay", "--cluster-config", ".", "--trace", "t.json"]
    assert cli.main(argv + (["--compile-cache-dir", flag] if flag else [])) == 0
    assert jax.config.jax_compilation_cache_dir == want
    exec_cache._persistent_dir = None
    jax.config.update("jax_compilation_cache_dir", None)
    SimulationServer(compile_cache_dir=flag)
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)


def test_persistent_cache_writes_executables(tmp_path, monkeypatch,
                                            cache_state):
    """The placed cache must actually persist compiles: jax freezes its
    on-disk cache as "disabled" on the first (import-time) compile, so
    enable_persistent_cache has to reset that state or restarts stay
    cold. A fresh-shaped simulate after enabling must write entries."""
    monkeypatch.setenv(exec_cache.CACHE_ENV, str(tmp_path))
    exec_cache.enable_persistent_cache("")
    cluster, apps = _cluster(3, 7)
    # a weight no other test uses -> unique jit signature, so an
    # earlier in-memory cache hit cannot mask the persistent write
    simulate(cluster, apps, config_overrides={"w_least": 0.875})
    names = os.listdir(tmp_path)
    assert any("schedule_pods" in n for n in names), names[:5]


# ---- bisection sweep ----------------------------------------------------

def test_removed_compile_cache_override_is_a_structured_error():
    """The old config_overrides={"compile_cache_dir": ...} key fails with
    E_SPEC pointing at enable_persistent_cache, instead of a TypeError or
    a silently cold cache."""
    import pytest

    from open_simulator_tpu.errors import SimulationError

    cluster, apps = _cluster(3, 2)
    with pytest.raises(SimulationError, match="enable_persistent_cache") as e:
        simulate(cluster, apps, config_overrides={"compile_cache_dir": "/x"})
    assert e.value.code == "E_SPEC"


def test_bisect_matches_exhaustive_and_dispatches_fewer_trials():
    """ISSUE 4 acceptance: capacity_bisect returns the exhaustive sweep's
    best_count while dispatching fewer device executions (observed via
    simon_sweep_trials_total)."""
    trials = lambda: _counter("simon_sweep_trials_total",  # noqa: E731
                              outcome="ok")
    snap = _snapshot(n_pods=12, pod_cpu="1500m", max_new=12)
    cfg = make_config(snap)
    t0 = trials()
    plan_ex = capacity_sweep(snap, cfg, counts=list(range(13)))
    t1 = trials()
    plan_bi = capacity_bisect(snap, cfg, max_new=12, lanes=4)
    t2 = trials()
    assert plan_ex.best_count == plan_bi.best_count == 5
    assert t1 - t0 == 13
    assert t2 - t1 < t1 - t0, (t2 - t1, t1 - t0)
    # the probed lanes agree with the exhaustive lanes where they overlap
    for i, c in enumerate(plan_bi.counts):
        assert plan_bi.satisfied[i] == plan_ex.satisfied[plan_ex.counts.index(c)]


def test_bisect_respects_thresholds():
    from open_simulator_tpu.parallel.sweep import SweepThresholds

    snap = _snapshot(n_pods=12, pod_cpu="1500m", max_new=12)
    cfg = make_config(snap)
    th = SweepThresholds(max_cpu_pct=60.0)
    plan_ex = capacity_sweep(snap, cfg, counts=list(range(13)), thresholds=th)
    plan_bi = capacity_bisect(snap, cfg, max_new=12, lanes=4, thresholds=th)
    assert plan_ex.best_count == plan_bi.best_count == 7


def test_bisect_endpoints():
    # impossible: max_new probed in round one -> one-round None verdict
    snap = _snapshot(n_pods=12, pod_cpu="1500m", max_new=2)
    cfg = make_config(snap)
    plan = capacity_bisect(snap, cfg, max_new=2, lanes=4)
    assert plan.best_count is None
    assert max(plan.counts) == 2
    # fits already: count 0 probed in round one -> one-round 0 verdict
    snap2 = _snapshot(n_pods=2, pod_cpu="100m", max_new=12)
    cfg2 = make_config(snap2)
    plan2 = capacity_bisect(snap2, cfg2, max_new=12, lanes=4)
    assert plan2.best_count == 0


def test_bisect_plan_decodes_through_applier_path():
    """The applier indexes plan.counts / nodes_per_scenario — the bisect
    plan must satisfy the same contract over its probed counts."""
    from open_simulator_tpu.core import decode_result

    snap = _snapshot(n_pods=12, pod_cpu="1500m", max_new=12)
    cfg = make_config(snap)
    plan = capacity_bisect(snap, cfg, max_new=12, lanes=4)
    idx = plan.counts.index(plan.best_count)
    masks = active_masks_for_counts(snap, plan.counts)
    result = decode_result(snap, plan.nodes_per_scenario[idx],
                           plan.fail_counts[idx], masks[idx])
    assert len(result.unscheduled_pods) == 0
    assert len(result.scheduled_pods) == snap.n_pods
