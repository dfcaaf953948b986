"""The program's stage spans: span() events on the profiler's clock, the
capacity sweep's upload / fetch / lane-statistics spans, the wave-plan
build and the encode sections."""

import glob
import os
import threading
import time
from collections import OrderedDict

import jax

from open_simulator_tpu import telemetry
from open_simulator_tpu.engine import waves as W
from open_simulator_tpu.engine.scheduler import make_config
from open_simulator_tpu.parallel.sweep import capacity_bisect, capacity_sweep
from open_simulator_tpu.telemetry.spans import RECORDER, span
from open_simulator_tpu.testing.synthetic import synthetic_snapshot

STAGES = ("sweep.upload", "sweep", "sweep.fetch", "sweep.lane_stats")


def _records_since(mark, names=None):
    """This thread's records since `mark`, in start order."""
    tid = threading.get_ident()
    return sorted((r for r in RECORDER.records_since(mark)
                   if r.tid == tid and (names is None or r.name in names)),
                  key=lambda r: r.t0)


def _inside(inner, outer):
    return outer.t0 <= inner.t0 and inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    mark = RECORDER.mark()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe.stage", lanes=64, mode="bisect"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    [rec] = _records_since(mark, {"probe.stage"})
    [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = [(plane.name, ev) for plane in data.planes for line in plane.lines
             for ev in line.events if ev.name == "simon.probe.stage"]
    assert len(found) == 1
    plane, ev = found[0]
    assert plane.startswith("/host:")
    stats = dict(ev.stats)
    assert stats["lanes"] == 64 and stats["mode"] == "bisect"
    assert abs(ev.duration_ns * 1e-9 - rec.dur) < 1e-3


def test_span_records_with_no_profiler_session():
    mark = RECORDER.mark()
    with span("probe.quiet", lanes=8) as info:
        pass
    [rec] = _records_since(mark, {"probe.quiet"})
    assert rec.args == {"lanes": "8"} and info["dur"] == rec.dur


def test_capacity_sweep_records_its_stages_in_order():
    snap = synthetic_snapshot(n_nodes=2, n_pods=40, max_new=6)
    cfg = make_config(snap)
    mark = RECORDER.mark()
    capacity_sweep(snap, cfg, counts=list(range(7)))
    recs = _records_since(mark, STAGES)
    assert [r.name for r in recs] == list(STAGES)
    upload, sweep, fetch, stats = recs
    assert _inside(fetch, sweep)
    tops = [upload, sweep, stats]
    for a, b in zip(tops, tops[1:]):
        assert a.t0 + a.dur <= b.t0


def test_capacity_bisect_records_one_upload_and_stages_per_round(tmp_path, monkeypatch):
    from open_simulator_tpu.resilience import lifecycle

    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV, str(tmp_path))
    snap = synthetic_snapshot(n_nodes=2, n_pods=40, max_new=6)
    cfg = make_config(snap)
    mark = RECORDER.mark()
    plan = capacity_bisect(snap, cfg, 6, lanes=2, checkpoint=True)
    recs = _records_since(mark, STAGES)
    names = [r.name for r in recs]
    assert names[0] == "sweep.upload" and names.count("sweep.upload") == 1
    rounds = names.count("sweep")
    assert rounds >= 2
    assert names[1:] == ["sweep", "sweep.fetch", "sweep.lane_stats"] * rounds
    for sweep_rec, fetch in zip(recs[1::3], recs[2::3]):
        assert _inside(fetch, sweep_rec)

    # every round replayed from the journal: the upload, and no round
    mark = RECORDER.mark()
    resumed = capacity_bisect(snap, cfg, 6, lanes=2, resume="last")
    assert resumed.resumed_rounds == rounds and resumed.best_count == plan.best_count
    assert [r.name for r in _records_since(mark, STAGES)] == ["sweep.upload"]


def test_waves_for_records_wave_plan_on_a_miss_only(monkeypatch):
    monkeypatch.setattr(W, "_PLAN_CACHE", OrderedDict())
    snap = synthetic_snapshot(16, 64, 0, pools=8)
    cfg = make_config(snap)._replace(fail_reasons=False)
    mark = RECORDER.mark()
    plan = W.waves_for(snap.arrays, cfg)
    assert plan is not None
    assert [r.name for r in _records_since(mark, {"wave_plan"})] == ["wave_plan"]
    mark = RECORDER.mark()
    assert W.waves_for(snap.arrays, cfg) is plan
    assert _records_since(mark, {"wave_plan"}) == []


def test_encode_cluster_records_encode_and_its_sections():
    mark = RECORDER.mark()
    synthetic_snapshot(n_nodes=4, n_pods=16, max_new=2)
    recs = _records_since(mark)
    [enc] = [r for r in recs if r.name == "encode"]
    children = [r for r in recs if r.name.startswith("encode.")]
    assert [r.name for r in children] == [
        "encode.topology", "encode.groups", "encode.classes", "encode.pods",
        "encode.terms"]
    for c in children:
        assert _inside(c, enc) and c.depth == enc.depth + 1


def test_simulate_records_one_encode(node_factory, pod_factory):
    from open_simulator_tpu.core import AppResource, simulate
    from open_simulator_tpu.k8s.loader import ClusterResources

    cluster = ClusterResources()
    cluster.nodes = [node_factory("e0")]
    apps = ClusterResources()
    apps.pods = [pod_factory("e-pod")]
    mark = RECORDER.mark()
    simulate(cluster, [AppResource("a", apps)])
    assert [r.name for r in _records_since(mark, {"encode"})] == ["encode"]


def test_sweep_exports_trial_outcomes_and_no_trial_histogram():
    trials = telemetry.counter("simon_sweep_trials_total",
                               "capacity-sweep lane outcomes",
                               labelnames=("outcome",))
    before = trials.value(outcome="ok")
    snap = synthetic_snapshot(n_nodes=2, n_pods=40, max_new=6)
    capacity_sweep(snap, make_config(snap), counts=[0, 3, 6])
    assert trials.value(outcome="ok") == before + 3
    text = telemetry.render_prometheus()
    assert "simon_sweep_trials_total" in text
    assert "simon_sweep_trial_seconds" not in text
