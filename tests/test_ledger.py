"""Flight recorder: ledger round-trip, capture wiring, diffing, rotation
and the runs CLI (ARCHITECTURE.md §10)."""

import json
import os

import pytest

from open_simulator_tpu.core import AppResource, simulate
from open_simulator_tpu.k8s.loader import ClusterResources
from open_simulator_tpu.telemetry import ledger
from open_simulator_tpu.testing.builders import make_fake_node, make_fake_pod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def led(tmp_path, monkeypatch):
    """A fresh process-wide ledger rooted in tmp_path; reset afterwards so
    other tests run with recording off."""
    monkeypatch.delenv(ledger.LEDGER_DIR_ENV, raising=False)
    ledger.configure(str(tmp_path))
    yield ledger.default_ledger()
    ledger.configure(None)


def _small_cluster():
    cluster = ClusterResources()
    cluster.nodes = [make_fake_node(f"n{i}") for i in range(3)]
    app = ClusterResources()
    app.pods = [make_fake_pod(f"p{i}") for i in range(4)]
    return cluster, [AppResource(name="a", resources=app)]


def _record(run_id="r0", ts=1000.0, surface="apply", digest="d0",
            engine="e0", workload="w0", phases=None):
    rec = {
        "schema": 1, "run_id": run_id, "ts": ts, "surface": surface,
        "wall_s": 1.0,
        "fingerprint": {"engine": engine, "bucket": [4, 4],
                        "workload": workload},
        "phases": phases or {"encode": 0.01, "schedule": 0.5,
                             "decode": 0.002},
        "metrics": {}, "env": {},
        "result": {"placed": 4, "unplaced": 0, "digest": digest},
        "tags": {},
    }
    return rec


# ---- storage round-trip --------------------------------------------------


def test_append_list_find_round_trip(led):
    led.append(_record("aaa111", ts=1.0))
    led.append(_record("bbb222", ts=2.0, surface="chaos"))
    recs = led.records()
    assert [r["run_id"] for r in recs] == ["aaa111", "bbb222"]
    assert [r["run_id"] for r in led.records(surface="chaos")] == ["bbb222"]
    assert led.find("aaa")["run_id"] == "aaa111"
    assert led.find("last")["run_id"] == "bbb222"
    assert led.find("prev")["run_id"] == "aaa111"
    with pytest.raises(ledger.LedgerError):
        led.find("zzz")
    # ambiguous prefix
    led.append(_record("aaa999", ts=3.0))
    with pytest.raises(ledger.LedgerError):
        led.find("aaa")


def test_corrupt_lines_are_skipped(led):
    led.append(_record("good01"))
    with open(led.path, "a", encoding="utf-8") as f:
        f.write("{truncated json\n")
    led.append(_record("good02", ts=2000.0))
    assert [r["run_id"] for r in led.records()] == ["good01", "good02"]


def test_rotation_at_size_cap(tmp_path):
    small = ledger.Ledger(str(tmp_path), max_bytes=4096)
    for i in range(40):
        small.append(_record(f"run{i:04d}", ts=float(i)))
    # the cap rotated the file at least once, kept ONE prior generation
    assert os.path.exists(small.path + ".1")
    assert os.path.getsize(small.path) <= 4096
    recs = small.records()
    # newest record always survives; total bounded by ~2 generations
    assert recs[-1]["run_id"] == "run0039"
    assert 0 < len(recs) < 40
    # ids stay ordered and unique across the generation boundary
    ids = [r["run_id"] for r in recs]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)


def test_disabled_ledger_is_null_capture(monkeypatch):
    monkeypatch.delenv(ledger.LEDGER_DIR_ENV, raising=False)
    ledger.configure(None)
    with ledger.run_capture("apply") as cap:
        assert cap is ledger.NULL_CAPTURE
        cap.tag("k", "v")  # all methods are no-ops


# ---- capture wiring ------------------------------------------------------


def test_simulate_records_and_is_deterministic(led):
    cluster, apps = _small_cluster()
    simulate(cluster, apps)
    cluster, apps = _small_cluster()
    simulate(cluster, apps)
    recs = led.records(surface="simulate")
    assert len(recs) == 2
    a, b = recs
    # identical inputs: identical fingerprints AND identical digests
    assert a["fingerprint"] == b["fingerprint"]
    assert a["result"]["digest"] == b["result"]["digest"]
    assert a["result"]["placed"] == 4 and a["result"]["unplaced"] == 0
    assert a["run_id"] != b["run_id"]
    # the span harvest captured the pipeline phases
    for phase in ("encode", "transfer", "schedule", "decode"):
        assert phase in a["phases"], a["phases"]
    assert a["env"].get("backend")


def test_nested_captures_yield_one_record(led):
    """An outer capture claims the run: the simulate() inside must not
    write a second record (one record per logical run)."""
    cluster, apps = _small_cluster()
    with ledger.run_capture("apply") as cap:
        result = simulate(cluster, apps)
        cap.set_result(result)
    recs = led.records()
    assert [r["surface"] for r in recs] == ["apply"]


def test_surface_override_names_the_entry_point(led):
    cluster, apps = _small_cluster()
    with ledger.surface_override("server:/api/deploy-apps"):
        simulate(cluster, apps)
    assert led.records()[-1]["surface"] == "server:/api/deploy-apps"


def test_failed_run_writes_no_record(led):
    cluster, apps = _small_cluster()
    cluster.nodes[0].allocatable["cpu"] = -5  # admission rejects
    with pytest.raises(Exception):
        simulate(cluster, apps)
    assert led.records() == []


def test_sweep_records_both_modes(led):
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.parallel.sweep import capacity_bisect, capacity_sweep
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    snap = synthetic_snapshot(n_nodes=4, n_pods=8, max_new=4)
    cfg = make_config(snap)
    capacity_bisect(snap, cfg, 4)
    capacity_sweep(snap, cfg, list(range(5)))
    recs = led.records(surface="sweep")
    assert len(recs) == 2
    for rec in recs:
        assert rec["fingerprint"]["workload"]
        assert rec["result"]["digest"]
        assert "best_count" in rec["tags"]
    # both modes answered the same question about the same workload
    assert (recs[0]["fingerprint"]["workload"]
            == recs[1]["fingerprint"]["workload"])


def test_chaos_records_one_run(led):
    from open_simulator_tpu.resilience.chaos import ChaosPlan, FaultEvent, run_chaos

    cluster, apps = _small_cluster()
    plan = ChaosPlan(events=[FaultEvent("kill_node", "n0")])
    report = run_chaos(cluster, plan, apps)
    recs = led.records()
    assert [r["surface"] for r in recs] == ["chaos"]
    assert recs[0]["result"]["digest"] == ledger.report_digest(report)["digest"]
    assert recs[0]["tags"]["events"] == 1


def test_compile_cache_metric_delta_flips_to_hit(led):
    """The metric-delta harvest: a repeat run in the same bucket must
    record a cache HIT and no miss (the compile-once contract, now
    visible run-over-run instead of process-locally)."""
    cluster, apps = _small_cluster()
    simulate(cluster, apps)
    cluster, apps = _small_cluster()
    simulate(cluster, apps)
    a, b = led.records(surface="simulate")
    key_hit = "simon_compile_cache_total{event=hit,fn=schedule_pods}"
    key_miss = "simon_compile_cache_total{event=miss,fn=schedule_pods}"
    assert b["metrics"].get(key_hit, 0) >= 1
    assert key_miss not in b["metrics"]
    # run 1 either missed (cold process) or hit (suite already warmed the
    # jit cache) — but it cannot have done neither
    assert (key_hit in a["metrics"]) or (key_miss in a["metrics"])


# ---- fingerprints --------------------------------------------------------


def test_fingerprint_tracks_config_and_workload():
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.testing.synthetic import synthetic_snapshot

    snap = synthetic_snapshot(n_nodes=4, n_pods=8, max_new=0)
    cfg = make_config(snap)
    fp1 = ledger.config_fingerprint(cfg, snapshot=snap)
    fp2 = ledger.config_fingerprint(cfg, snapshot=snap)
    assert fp1 == fp2
    # a config knob flips the engine hash, not the workload digest
    fp3 = ledger.config_fingerprint(
        cfg._replace(fail_reasons=False), snapshot=snap)
    assert fp3["engine"] != fp1["engine"]
    assert fp3["workload"] == fp1["workload"]
    # a different workload flips the workload digest
    snap2 = synthetic_snapshot(n_nodes=4, n_pods=9, max_new=0)
    fp4 = ledger.config_fingerprint(make_config(snap2), snapshot=snap2)
    assert fp4["workload"] != fp1["workload"]


# ---- diffing -------------------------------------------------------------


def test_diff_identical_runs():
    a = _record("run000000000a", ts=1.0)
    b = _record("run000000000b", ts=2.0)
    d = ledger.diff_records(a, b)
    assert d["fingerprint"]["match"] and not d["fingerprint"]["drift"]
    assert d["result"]["identical"] and not d["result"]["nondeterministic"]
    text = ledger.format_diff(d)
    assert "MATCH" in text and "IDENTICAL" in text
    assert "schedule" in text


def test_diff_flags_nondeterminism_and_drift():
    a = _record("runa", ts=1.0, digest="d0")
    # same fingerprint, different digest -> nondeterminism
    b = _record("runb", ts=2.0, digest="d1")
    d = ledger.diff_records(a, b)
    assert d["result"]["nondeterministic"]
    assert "NONDETERMINISM" in ledger.format_diff(d)
    # drifted engine config explains a digest change: NOT nondeterminism
    c = _record("runc", ts=3.0, digest="d1", engine="e9")
    d2 = ledger.diff_records(a, c)
    assert d2["fingerprint"]["drift"] == ["engine"]
    assert not d2["result"]["nondeterministic"]
    text = ledger.format_diff(d2)
    assert "DRIFT" in text and "engine config changed" in text


def test_diff_phase_rows_percentages():
    a = _record("runa", phases={"encode": 0.10, "schedule": 1.0})
    b = _record("runb", ts=2000.0,
                phases={"encode": 0.05, "schedule": 2.0, "compile": 1.5})
    rows = {r["phase"]: r for r in ledger.diff_records(a, b)["phases"]}
    assert rows["encode"]["pct"] == -50.0
    assert rows["schedule"]["pct"] == 100.0
    assert rows["compile"]["a_s"] is None  # present only in run b


# ---- runs CLI ------------------------------------------------------------


def test_runs_cli_list_show_diff(led, capsys):
    from open_simulator_tpu.cli.main import main

    led.append(_record("aaa111", ts=1.0))
    led.append(_record("bbb222", ts=2.0))
    root = led.root

    assert main(["runs", "--ledger-dir", root, "list"]) == 0
    out = capsys.readouterr().out
    assert "aaa111" in out and "bbb222" in out

    assert main(["runs", "--ledger-dir", root, "list", "--json",
                 "--surface", "apply", "-n", "1"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert [r["run_id"] for r in rows] == ["bbb222"]

    assert main(["runs", "--ledger-dir", root, "show", "aaa"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["run_id"] == "aaa111"

    assert main(["runs", "--ledger-dir", root, "diff", "prev", "last"]) == 0
    out = capsys.readouterr().out
    assert "IDENTICAL" in out and "phases" in out

    assert main(["runs", "--ledger-dir", root, "show", "zzz"]) == 1
    assert "no run id matches" in capsys.readouterr().err


def test_runs_cli_without_ledger_errors(monkeypatch, capsys):
    from open_simulator_tpu.cli.main import main

    monkeypatch.delenv(ledger.LEDGER_DIR_ENV, raising=False)
    ledger.configure(None)
    assert main(["runs", "list"]) == 1
    assert "no run ledger configured" in capsys.readouterr().err


def test_apply_cli_two_runs_identical_digests(led, capsys):
    """The acceptance scenario: two consecutive `simon-tpu apply` runs of
    the demo config against one ledger -> two RunRecords with identical
    result digests and matching config fingerprints, and `runs diff`
    renders per-phase deltas without error."""
    from open_simulator_tpu.cli.main import main

    cfg_path = os.path.join(REPO, "examples/config.yaml")
    for _ in range(2):
        assert main(["apply", "-f", cfg_path, "--max-new-nodes", "4",
                     "--output-file", os.devnull]) == 0
    capsys.readouterr()
    a, b = led.records(surface="apply")
    assert a["result"]["digest"] == b["result"]["digest"]
    assert a["fingerprint"] == b["fingerprint"]
    assert a["tags"]["sweep_mode"] == "bisect"
    assert main(["runs", "--ledger-dir", led.root, "diff", "prev", "last"]) == 0
    out = capsys.readouterr().out
    assert "IDENTICAL" in out and "sweep" in out
