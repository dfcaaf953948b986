"""Fleet campaigns (ISSUE 8): per-cluster fault isolation + quarantine,
campaign checkpoint/resume (SIGKILL subprocess acceptance), the placement
invariant auditor, fleet analytics, degrade-to-disabled ledger and
checkpoint dirs, and the fuzzed admission boundary."""

import json
import logging
import os
import random
import signal
import subprocess
import sys

import numpy as np
import pytest

from open_simulator_tpu.campaign import (
    AuditError,
    CampaignOptions,
    audit_result,
    discover_fleet,
    format_audit,
    format_report,
    load_and_admit,
    report_from_journal,
    resolve_campaign,
    run_campaign,
    write_synthetic_fleet,
)
from open_simulator_tpu.core import AppResource, simulate
from open_simulator_tpu.errors import SimulationError
from open_simulator_tpu.k8s.cluster_source import ClusterSourceError
from open_simulator_tpu.k8s.loader import ClusterResources
from open_simulator_tpu.resilience import lifecycle
from open_simulator_tpu.resilience.journal import unframe_line
from tests.conftest import make_node, make_pod

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "api_dump.json")


@pytest.fixture
def fleet_dir(tmp_path):
    d = tmp_path / "fleet"
    write_synthetic_fleet(str(d), n_clusters=3, nodes=4, pods=12,
                          malformed=1)
    return str(d)


@pytest.fixture
def no_checkpoint(monkeypatch):
    monkeypatch.delenv(lifecycle.CHECKPOINT_DIR_ENV, raising=False)
    monkeypatch.delenv("SIMON_LEDGER_DIR", raising=False)
    from open_simulator_tpu.telemetry import ledger

    ledger.configure(None)


# ---- fault isolation -----------------------------------------------------


def test_poisoned_cluster_quarantined_campaign_continues(fleet_dir,
                                                         no_checkpoint):
    report = run_campaign(CampaignOptions(fleet=fleet_dir,
                                          checkpoint=False))
    t = report["totals"]
    assert t == {"clusters": 3, "completed": 2, "quarantined": 1,
                 "placed": t["placed"], "unplaced": t["unplaced"]}
    assert t["placed"] > 0
    [quar] = report["quarantined"]
    assert quar["cluster"] == "cluster-02"
    assert quar["error"]["code"] == "E_SOURCE"
    assert "line" in quar["error"]["field"]
    assert quar["attempts"] == 1  # E_SOURCE is deterministic: no retries
    # every OTHER cluster completed, audit-clean
    assert [r["cluster"] for r in report["clusters"]] == [
        "cluster-00", "cluster-01"]
    assert all(r["audit_ok"] for r in report["clusters"])
    assert report["quarantine_summary"] == {"E_SOURCE": 1}
    # heterogeneous fleet, shared executables: two shape buckets
    assert len(report["buckets"]) == 2
    # the renderer holds together
    text = format_report(report)
    assert "QUARANTINED [E_SOURCE]" in text and "cluster-00" in text


def test_audit_violation_quarantines_with_e_audit(fleet_dir, no_checkpoint,
                                                  monkeypatch):
    """A corrupted result (engine-bug stand-in) must quarantine THAT
    cluster with E_AUDIT while the rest of the fleet completes."""
    real_simulate = simulate

    def corrupting(cluster, apps, **kw):
        result = real_simulate(cluster, apps, **kw)
        if result.scheduled_pods and \
                cluster.nodes[0].name.startswith("cluster-00"):
            # bind a pod to a node that does not exist in the snapshot
            result.scheduled_pods[0].node_name = "ghost-node"
        return result

    monkeypatch.setattr("open_simulator_tpu.core.simulate", corrupting)
    report = run_campaign(CampaignOptions(fleet=fleet_dir,
                                          checkpoint=False))
    codes = {q["cluster"]: q["error"]["code"]
             for q in report["quarantined"]}
    assert codes == {"cluster-00": "E_AUDIT", "cluster-02": "E_SOURCE"}
    assert [r["cluster"] for r in report["clusters"]] == ["cluster-01"]
    audit_err = next(q for q in report["quarantined"]
                     if q["cluster"] == "cluster-00")["error"]
    assert "audit" in audit_err and not audit_err["audit"]["ok"]


def test_transient_failures_retry_with_history(fleet_dir, no_checkpoint,
                                               monkeypatch):
    """Only classifier-transient failures (resilience/faults.py) spend
    the retry budget; persistent transients quarantine with the attempt
    count, and a deterministic-classed fault quarantines on attempt 1
    instead of being retried like a transient."""
    calls = {"n": 0}
    real_simulate = simulate

    def flaky(cluster, apps, **kw):
        if cluster.nodes[0].name.startswith("cluster-00"):
            calls["n"] += 1
            if calls["n"] == 1:
                # E_TRANSFER-classed: the retry-worthy class
                raise OSError("DATA_LOSS: failed to transfer buffer")
        return real_simulate(cluster, apps, **kw)

    monkeypatch.setattr("open_simulator_tpu.core.simulate", flaky)
    report = run_campaign(CampaignOptions(fleet=fleet_dir,
                                          checkpoint=False,
                                          backoff_s=0.0))
    assert report["totals"]["completed"] == 2  # the flake recovered
    assert calls["n"] == 2

    def always_down(cluster, apps, **kw):
        if cluster.nodes[0].name.startswith("cluster-00"):
            raise OSError("connection reset by peer")
        return real_simulate(cluster, apps, **kw)

    monkeypatch.setattr("open_simulator_tpu.core.simulate", always_down)
    report = run_campaign(CampaignOptions(fleet=fleet_dir,
                                          checkpoint=False, retries=2,
                                          backoff_s=0.0))
    quar = next(q for q in report["quarantined"]
                if q["cluster"] == "cluster-00")
    assert quar["error"]["code"] == "E_INTERNAL"
    assert quar["attempts"] == 3 and quar["transient_retries"] == 2

    # the satellite's point: a deterministic fault (an OOM) must NOT
    # burn the retry budget reproducing itself — one attempt, quarantined
    det_calls = {"n": 0}

    def oom(cluster, apps, **kw):
        if cluster.nodes[0].name.startswith("cluster-00"):
            det_calls["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real_simulate(cluster, apps, **kw)

    monkeypatch.setattr("open_simulator_tpu.core.simulate", oom)
    report = run_campaign(CampaignOptions(fleet=fleet_dir,
                                          checkpoint=False, retries=2,
                                          backoff_s=0.0))
    quar = next(q for q in report["quarantined"]
                if q["cluster"] == "cluster-00")
    assert det_calls["n"] == 1
    assert quar["attempts"] == 1 and quar["transient_retries"] == 0


def test_cancellation_observed_at_cluster_boundary(fleet_dir,
                                                   no_checkpoint):
    token = lifecycle.CancelToken()
    token.cancel("drain")
    with lifecycle.cancel_scope(token):
        with pytest.raises(lifecycle.CancelledError) as ei:
            run_campaign(CampaignOptions(fleet=fleet_dir,
                                         checkpoint=False))
    assert "campaign cluster boundary" in str(ei.value)
    assert "clusters_settled" in ei.value.partial


# ---- checkpoint / resume -------------------------------------------------


def _campaign_child():
    """Subprocess entry: SIGKILL self after the first settled cluster's
    journal line lands (test_sigkill_mid_campaign...)."""
    from open_simulator_tpu.campaign import runner as campaign_runner

    real_append = campaign_runner.CampaignJournal._append

    def kamikaze(self, rec):
        real_append(self, rec)
        if rec.get("kind") in ("cluster", "quarantine"):
            os.kill(os.getpid(), signal.SIGKILL)

    campaign_runner.CampaignJournal._append = kamikaze
    # serial boundary on purpose: this test pins the SERIAL settlement
    # order (first journal line = first cluster's row); the fleet-lane
    # path settles prepass quarantines before batched rows and has its
    # own journal/report coverage in test_tune.py. The PARENT resume
    # below runs the default (lane) mode, so serial-journal -> lane-mode
    # resume compatibility is exactly what this test now also proves.
    run_campaign(CampaignOptions(fleet=os.environ["TEST_FLEET"],
                                 fleet_lanes=False))
    raise SystemExit("unreachable")


def test_sigkill_mid_campaign_then_resume_bit_identical(fleet_dir,
                                                        tmp_path,
                                                        no_checkpoint):
    """ISSUE 8 acceptance: SIGKILL mid-campaign, parent resumes via
    --resume, fleet report digest bit-identical, quarantined clusters
    reported once (not re-run, not lost)."""
    reference = run_campaign(CampaignOptions(fleet=fleet_dir,
                                             checkpoint=False))

    ckpt = tmp_path / "ckpt"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "TEST_FLEET": fleet_dir,
           lifecycle.CHECKPOINT_DIR_ENV: str(ckpt)}
    proc = subprocess.run(
        [sys.executable, "-c",
         "from tests.test_campaign import _campaign_child; "
         "_campaign_child()"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, (
        f"child should die by SIGKILL, got rc={proc.returncode}\n"
        f"stdout: {proc.stdout}\nstderr: {proc.stderr}")

    [name] = [n for n in os.listdir(ckpt) if n.endswith(".campaign.jsonl")]
    with open(ckpt / name, encoding="utf-8") as f:
        kinds = [json.loads(unframe_line(ln))["kind"] for ln in f
                 if ln.strip()]
    assert kinds == ["header", "cluster"]  # torn mid-campaign

    os.environ[lifecycle.CHECKPOINT_DIR_ENV] = str(ckpt)
    try:
        resumed = run_campaign(CampaignOptions(fleet=fleet_dir,
                                               resume="last"))
        # the journal is the report's source of truth either way
        journal = resolve_campaign("last")
    finally:
        del os.environ[lifecycle.CHECKPOINT_DIR_ENV]
    assert resumed["resumed_clusters"] == 1
    assert resumed["digest"] == reference["digest"]
    assert resumed["totals"] == reference["totals"]
    # quarantined exactly once: in the report AND in the journal
    assert [q["cluster"] for q in resumed["quarantined"]] == ["cluster-02"]
    assert journal.done is not None
    assert journal.done["digest"] == reference["digest"]
    assert report_from_journal(journal)["digest"] == reference["digest"]
    quar_lines = [r for r in journal.records if r["kind"] == "quarantine"]
    assert len(quar_lines) == 1


def test_resume_fleet_drift_is_structured(fleet_dir, tmp_path,
                                          no_checkpoint, monkeypatch):
    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV, str(tmp_path / "ck"))
    run_campaign(CampaignOptions(fleet=fleet_dir))
    # mutate one dump: the fleet digest drifts, resume must refuse
    target = os.path.join(fleet_dir, "cluster-00.json")
    with open(target, "a", encoding="utf-8") as f:
        f.write("\n")
    with pytest.raises(lifecycle.ResumeError, match="fleet drifted"):
        run_campaign(CampaignOptions(fleet=fleet_dir, resume="last"))


def test_resume_unknown_id_and_no_dir(fleet_dir, no_checkpoint, tmp_path,
                                      monkeypatch):
    with pytest.raises(lifecycle.ResumeError, match="no checkpoint"):
        run_campaign(CampaignOptions(fleet=fleet_dir, resume="last"))
    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV, str(tmp_path))
    with pytest.raises(lifecycle.ResumeError, match="no campaign"):
        run_campaign(CampaignOptions(fleet=fleet_dir, resume="last"))


# ---- the auditor ---------------------------------------------------------


def _small_result():
    cluster = ClusterResources()
    cluster.nodes = [make_node("n0", cpu_m=4000, mem_mib=8192),
                     make_node("n1", cpu_m=4000, mem_mib=8192)]
    cluster.pods = [make_pod("bound", cpu="500m", node_name="n0")]
    app = ClusterResources()
    app.pods = [make_pod(f"p{i}", cpu="500m", mem="256Mi")
                for i in range(4)]
    return simulate(cluster, [AppResource(name="a", resources=app)])


def test_audit_clean_result_passes(no_checkpoint):
    result = _small_result()
    rep = audit_result(result)
    assert rep.ok and rep.n_violations == 0
    assert rep.n_bound == 5 and rep.n_active_nodes == 2
    assert rep.cpu_pct > 0
    assert "binding" in rep.checks and "forced" in rep.checks
    assert "PASS" in format_audit(rep, name="small")


def _free_scheduled(result):
    """A scheduled pod WITHOUT a forced bind (doctoring a forced pod
    would trip the forced_bind check too — also correct, but these
    tests want one violation kind at a time)."""
    import numpy as np

    forced = np.asarray(result.snapshot.arrays.forced_node)
    for sp in result.scheduled_pods:
        pi = next(i for i, p in enumerate(result.snapshot.pods)
                  if p is sp.pod)
        if forced[pi] < 0:
            return sp
    raise AssertionError("no free scheduled pod in the fixture")


def test_audit_flags_unknown_and_inactive_node(no_checkpoint):
    result = _small_result()
    _free_scheduled(result).node_name = "ghost"
    rep = audit_result(result)
    assert not rep.ok
    assert {v.kind for v in rep.violations} == {"unknown_node"}

    result = _small_result()
    # drop n1 from node_status: pods bound there become inactive-node binds
    dropped = [ns for ns in result.node_status if ns.node.name != "n1"]
    had_on_n1 = any(sp.node_name == "n1" for sp in result.scheduled_pods)
    result.node_status = dropped
    rep = audit_result(result)
    if had_on_n1:
        assert {v.kind for v in rep.violations} == {"inactive_node"}
    else:
        assert rep.ok


def test_audit_flags_overcommit_and_forced_drift(no_checkpoint):
    result = _small_result()
    arrs = result.snapshot.arrays
    # inflate every request 100x post-hoc: consumption > allocatable
    result.snapshot.arrays = arrs.replace(req=np.asarray(arrs.req) * 100.0)
    rep = audit_result(result)
    assert not rep.ok
    assert "overcommit" in {v.kind for v in rep.violations}

    result = _small_result()
    arrs = result.snapshot.arrays
    forced = np.asarray(arrs.forced_node).copy()
    # claim pod 0 was pinned to the OTHER node than it landed on
    placed_on = result.scheduled_pods[0].node_name
    other = 1 if placed_on == result.snapshot.node_names[0] else 0
    pi = result.snapshot.pods.index(result.scheduled_pods[0].pod)
    forced[pi] = other
    result.snapshot.arrays = arrs.replace(forced_node=forced)
    rep = audit_result(result)
    assert any(v.kind == "forced_bind" for v in rep.violations)


def test_audit_error_payload_is_structured(no_checkpoint):
    result = _small_result()
    _free_scheduled(result).node_name = "ghost"
    rep = audit_result(result)
    err = AuditError(rep, ref="cluster/x")
    assert err.code == "E_AUDIT"
    d = err.to_dict()
    assert d["audit"]["n_violations"] == 1
    assert d["audit"]["violations"][0]["kind"] == "unknown_node"


def test_audit_cli_standalone(no_checkpoint, capsys):
    from open_simulator_tpu.cli.main import main

    rc = main(["campaign", "audit", FIXTURE])
    assert rc == 0
    assert "PASS" in capsys.readouterr().out


# ---- fuzzed admission boundary (satellite) -------------------------------


def _mutate(doc, rng):
    """One seeded random mutation: dropped keys, wrong types, negative
    quantities, bogus kinds — the ISSUE 8 fuzz families."""
    doc = json.loads(json.dumps(doc))  # deep copy
    items = doc.get("items", [])
    kind = rng.randrange(6)
    if kind == 0 and items:       # drop a random key somewhere
        obj = rng.choice(items)
        if obj:
            obj.pop(rng.choice(sorted(obj)), None)
    elif kind == 1 and items:     # wrong type for a random field
        obj = rng.choice(items)
        key = rng.choice(sorted(obj)) if obj else None
        if key:
            obj[key] = rng.choice([42, ["x"], "zzz", None])
    elif kind == 2:               # negative / malformed quantity
        for obj in items:
            if obj.get("kind") == "Pod":
                c = obj.setdefault("spec", {}).setdefault(
                    "containers", [{}])[0]
                c.setdefault("resources", {})["requests"] = {
                    "cpu": rng.choice(["-1", "2x", "--", "1e999m"]),
                    "memory": "-5Gi"}
                break
    elif kind == 3 and items:     # bogus kind
        rng.choice(items)["kind"] = rng.choice(
            ["Frobnicator", 7, "", None])
    elif kind == 4 and items:     # metadata mangled to a scalar
        rng.choice(items)["metadata"] = rng.choice([3, "meta", ["x"]])
    else:                         # nested status/spec mangled
        if items:
            obj = rng.choice(items)
            obj[rng.choice(["status", "spec"])] = rng.choice(
                [17, "nope", [1, 2]])
    return doc


def test_fuzzed_dumps_yield_structured_errors_only(tmp_path,
                                                   no_checkpoint):
    """~50 seeded mutations of a valid dump: the campaign admission
    boundary must answer each with success or a structured
    SimulationError — never an uncaught traceback."""
    with open(FIXTURE, encoding="utf-8") as f:
        base = json.load(f)
    rng = random.Random(1208)
    outcomes = {"ok": 0, "structured": 0}
    for i in range(50):
        doc = _mutate(base, rng)
        path = tmp_path / f"mutant-{i:02d}.json"
        text = json.dumps(doc)
        if i % 10 == 9:  # every 10th: truncate mid-stream instead
            text = text[:rng.randrange(1, max(2, len(text) - 1))]
        path.write_text(text)
        try:
            load_and_admit(str(path))
            outcomes["ok"] += 1
        except SimulationError as e:
            assert e.code, f"mutant {i}: structured error without a code"
            assert isinstance(e.to_dict(), dict)
            outcomes["structured"] += 1
        # anything else propagates and fails the test — by design
    assert outcomes["structured"] > 0, outcomes
    assert sum(outcomes.values()) == 50


# ---- degrade-to-disabled dirs (satellite) --------------------------------


def test_unwritable_ledger_degrades_with_one_warning(tmp_path, caplog,
                                                     no_checkpoint):
    """A readonly/unwritable ledger dir must cost exactly ONE warning and
    disable recording — never crash a campaign. (Under root a chmod-0
    dir is still writable, so the unwritable parent is a regular file —
    the same OSError class a full disk raises.)"""
    from open_simulator_tpu.telemetry import ledger

    blocker = tmp_path / "blocker"
    blocker.write_text("not a dir")
    bad_dir = str(blocker / "ledger")
    ledger.configure(bad_dir)
    try:
        assert ledger.enabled()
        with caplog.at_level(logging.WARNING,
                             logger="open_simulator_tpu.telemetry.ledger"):
            with ledger.run_capture("simulate") as cap:
                assert cap.recording
            assert not ledger.enabled()          # latched off
            with ledger.run_capture("simulate") as cap2:
                assert not cap2.recording        # second run: free no-op
            assert ledger.append_event("x") is None
        warnings = [r for r in caplog.records if "unwritable" in r.message]
        assert len(warnings) == 1, [r.message for r in caplog.records]
        # reconfiguring clears the latch
        good = tmp_path / "ledger-ok"
        ledger.configure(str(good))
        assert ledger.enabled()
        with ledger.run_capture("simulate") as cap3:
            assert cap3.recording
    finally:
        ledger.configure(None)


def test_unwritable_checkpoint_dir_campaign_still_runs(fleet_dir, tmp_path,
                                                       no_checkpoint,
                                                       monkeypatch,
                                                       caplog):
    blocker = tmp_path / "blocker"
    blocker.write_text("not a dir")
    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV,
                       str(blocker / "ckpt"))
    with caplog.at_level(logging.WARNING):
        report = run_campaign(CampaignOptions(fleet=fleet_dir))
    assert report["totals"]["completed"] == 2
    assert any("checkpointing disabled" in r.message
               for r in caplog.records)


def test_sweep_journal_append_degrades_once(tmp_path, caplog):
    journal = lifecycle.SweepJournal.create(
        str(tmp_path), {"engine": "x"}, 4, 2, (100.0, 100.0, 100.0))
    blocker = tmp_path / "f"
    blocker.write_text("file")
    journal.path = str(blocker / "nope.sweep.jsonl")  # now unwritable
    with caplog.at_level(logging.WARNING):
        journal.append_round([1], {1: {"nodes": [0]}})
        journal.append_round([2], {2: {"nodes": [0]}})
        journal.finish(1, "d")
    assert journal.broken
    warnings = [r for r in caplog.records if "unwritable" in r.message]
    assert len(warnings) == 1


def test_sweep_checkpoint_create_degrades(tmp_path, monkeypatch,
                                          no_checkpoint, caplog):
    """An unwritable checkpoint dir must not kill a capacity bisection."""
    from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.k8s.loader import make_valid_node
    from open_simulator_tpu.parallel.sweep import capacity_bisect

    cluster = ClusterResources()
    cluster.nodes = [make_node("r0", cpu_m=2000, mem_mib=4096)]
    pods = [make_pod(f"p{i}", cpu="1500m") for i in range(3)]
    template = make_node("t", cpu_m=4000, mem_mib=8192)
    snap = encode_cluster(
        [make_valid_node(n) for n in cluster.nodes], pods,
        EncodeOptions(max_new_nodes=2, new_node_template=template))
    blocker = tmp_path / "f"
    blocker.write_text("file")
    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV, str(blocker / "ck"))
    with caplog.at_level(logging.WARNING):
        plan = capacity_bisect(snap, make_config(snap), 2, lanes=2)
    assert plan.best_count is not None
    assert plan.sweep_id is None  # checkpointing degraded to off
    assert any("checkpointing disabled" in r.message
               for r in caplog.records)


# ---- surfaces ------------------------------------------------------------


def test_campaign_cli_run_and_report(fleet_dir, tmp_path, no_checkpoint,
                                     monkeypatch, capsys):
    from open_simulator_tpu.cli.main import main

    ledger_dir = tmp_path / "ledger"
    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV, str(tmp_path / "ck"))
    rc = main(["campaign", "run", "--fleet", fleet_dir,
               "--ledger-dir", str(ledger_dir), "--json"])
    out = capsys.readouterr().out
    assert rc == 0  # a quarantined cluster must NOT fail the fleet
    report = json.loads(out)
    assert report["totals"] == {"clusters": 3, "completed": 2,
                                "quarantined": 1,
                                "placed": report["totals"]["placed"],
                                "unplaced": report["totals"]["unplaced"]}

    # per-(cluster, scenario) RunRecords tagged with the campaign id:
    # one per completed cluster plus the campaign summary event
    rc = main(["runs", "--ledger-dir", str(ledger_dir), "list",
               "--campaign", report["campaign_id"], "--json"])
    assert rc == 0
    runs = json.loads(capsys.readouterr().out)
    assert len(runs) == 3
    assert sum(1 for r in runs if r["digest"]) == 2  # the cluster records

    rc = main(["campaign", "report", report["campaign_id"][:6], "--json"])
    assert rc == 0
    rep2 = json.loads(capsys.readouterr().out)
    assert rep2["digest"] == report["digest"]

    from open_simulator_tpu.telemetry import ledger as ledger_mod

    ledger_mod.configure(None)


def test_campaign_rest_route(fleet_dir, no_checkpoint):
    from open_simulator_tpu.server.rest import SimulationServer

    srv = SimulationServer()
    report = srv.campaign({"fleet": fleet_dir, "audit": True})
    assert report["totals"]["completed"] == 2
    assert report["totals"]["quarantined"] == 1

    with pytest.raises(SimulationError) as ei:
        srv.campaign({})
    assert ei.value.code == "E_BAD_REQUEST"

    paths = [os.path.join(fleet_dir, "cluster-00.json")]
    report = srv.campaign({"clusters": paths})
    assert report["totals"] == {"clusters": 1, "completed": 1,
                                "quarantined": 0,
                                "placed": report["totals"]["placed"],
                                "unplaced": 0}


def test_fleet_manifest_and_errors(tmp_path, fleet_dir):
    manifest = tmp_path / "fleet.yaml"
    manifest.write_text(
        "clusters:\n"
        f"  - {os.path.join(fleet_dir, 'cluster-00.json')}\n"
        f"  - name: second\n"
        f"    path: {os.path.join(fleet_dir, 'cluster-01.json')}\n")
    entries = discover_fleet(str(manifest))
    assert [e.name for e in entries] == ["cluster-00", "second"]
    assert all(e.digest for e in entries)

    with pytest.raises(ClusterSourceError, match="does not exist"):
        discover_fleet(str(tmp_path / "nope"))
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(ClusterSourceError, match="no cluster dumps"):
        discover_fleet(str(empty))
    bad = tmp_path / "bad.yaml"
    bad.write_text("just a string")
    with pytest.raises(ClusterSourceError, match="manifest"):
        discover_fleet(str(bad))


def test_vanished_dump_quarantines_not_aborts(fleet_dir, tmp_path,
                                              no_checkpoint):
    """A dump that is missing/unreadable at DISCOVERY time (deleted
    between listdir and open, stale mount) must quarantine that cluster,
    not abort the campaign — fault isolation is per cluster."""
    manifest = tmp_path / "fleet.yaml"
    manifest.write_text(
        "clusters:\n"
        f"  - {os.path.join(fleet_dir, 'cluster-00.json')}\n"
        f"  - {os.path.join(fleet_dir, 'vanished.json')}\n")
    report = run_campaign(CampaignOptions(fleet=str(manifest),
                                          checkpoint=False))
    assert report["totals"]["completed"] == 1
    [quar] = report["quarantined"]
    assert quar["cluster"] == "vanished"
    assert quar["error"]["code"] == "E_SOURCE"
    assert quar["source"].startswith("unreadable-")
