"""Durable-state fault domain tests (resilience/journal.py, ISSUE 16).

Covers the framed-journal integrity contract across all four journal
kinds: the ~50-seed mutation fuzz (bit-flips, truncations, duplicated
lines, reordered sequence numbers -> either a bit-identical prefix
resume or a structured E_CORRUPT, never a traceback and never a
wrong-prefix resume), strict torn-tail-only recovery, legacy unframed
compatibility, the storage fault taxonomy (ENOSPC deterministic / EIO
transient) with the shared checkpointing_disabled degradation rung, the
ENOSPC-mid-run regression (the run finishes, the report says so, resume
from the surviving prefix is digest-identical), SessionStore startup
quarantine, and the ledger's skipped_corrupt surfacing."""

import errno
import json
import os
import random

import pytest

from open_simulator_tpu import telemetry
from open_simulator_tpu.errors import SimulationError
from open_simulator_tpu.resilience import faults, lifecycle
from open_simulator_tpu.resilience import journal as journal_mod
from open_simulator_tpu.resilience.journal import (
    DurableJournal,
    JournalCorrupt,
    frame_record,
    read_journal,
    scan_integrity,
    unframe_line,
)

# ---- builders: one real journal per kind ---------------------------------


def _build_sweep(root):
    j = lifecycle.SweepJournal.create(
        str(root), {"engine": "x", "cfg": 1}, 4, 2, (100.0, 99.5, 99.0))
    for r in range(3):
        j.append_round([r + 1], {r + 1: {"nodes": [r], "error": None}})
    j.finish(3, "digest-sweep")
    return j.path, lambda: lifecycle.SweepJournal.load(str(root), "last")


def _build_campaign(root):
    from open_simulator_tpu.campaign.runner import CampaignJournal

    j = CampaignJournal.create(str(root), "fleetdig", "scale", 3)
    for i in range(3):
        j.append_cluster(f"c{i}", {"source": f"s{i}"},
                         {"cluster": f"c{i}", "ok": True})
    j.finish("digest-campaign", 3, 0)
    return j.path, lambda: CampaignJournal.load(str(root), "last")


def _build_replay(root):
    from open_simulator_tpu.replay.engine import ReplayJournal

    j = ReplayJournal.create(str(root), {"trace": "t"}, 3,
                             [{"kind": "autoscaler"}])
    for i in range(3):
        j.append_step({"t": i, "event": {"kind": "arrival"}, "placed": i})
    j.finish("digest-replay", 3)
    return j.path, lambda: ReplayJournal.load(str(root), "last")


def _build_session(root):
    from open_simulator_tpu.replay.session import SessionJournal, SessionSpec

    j = SessionJournal.create(str(root), "sid0fuzz", "fuzz", {"f": 1},
                              [{"kind": "Node"}], SessionSpec(), [])
    for i in range(3):
        j.append_step({"t": i, "kind": "arrival"}, {"t": i, "placed": i})
    j.close("digest-session", 3)
    return j.path, lambda: SessionJournal.load(j.path)


_BUILDERS = {
    "sweep": _build_sweep,
    "campaign": _build_campaign,
    "replay": _build_replay,
    "session": _build_session,
}


# ---- the ~50-seed mutation fuzz (satellite 1) ----------------------------


def _mutate_journal(data: bytes, rng: random.Random) -> bytes:
    lines = data.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    op = rng.choice(["bit_flip", "truncate", "dup_line", "swap_lines",
                     "drop_line", "garbage_tail", "blank_line"])
    if op == "bit_flip":
        buf = bytearray(data)
        i = rng.randrange(len(buf))
        buf[i] ^= 1 << rng.randrange(8)
        return bytes(buf)
    if op == "truncate":
        return data[: rng.randrange(1, len(data))]
    if op == "dup_line":
        i = rng.randrange(len(lines))
        lines.insert(i, lines[i])
    elif op == "swap_lines":
        i = rng.randrange(len(lines) - 1)
        lines[i], lines[i + 1] = lines[i + 1], lines[i]
    elif op == "drop_line":
        del lines[rng.randrange(len(lines))]
    elif op == "garbage_tail":
        lines.append(bytes(rng.randrange(256) for _ in range(20)))
    elif op == "blank_line":
        lines.insert(rng.randrange(len(lines) + 1), b"")
    return b"\n".join(lines) + b"\n"


@pytest.mark.parametrize("seed", range(50))
def test_journal_mutation_fuzz(tmp_path, seed):
    """The strict-reader contract under 50 seeded mutations, cycling
    through all four journal kinds: every read either returns an EXACT
    prefix of the pristine records (the only damage a torn tail may
    cost) or raises a structured E_CORRUPT naming kind/index/offset —
    never a traceback, never a resumed wrong prefix."""
    kind = list(_BUILDERS)[seed % len(_BUILDERS)]
    rng = random.Random(seed)
    path, load = _BUILDERS[kind](tmp_path)
    truth = read_journal(path, kind).records
    assert len(truth) == 5  # header + 3 + done

    data = open(path, "rb").read()
    mutated = _mutate_journal(data, rng)
    with open(path, "wb") as f:
        f.write(mutated)
    if mutated == data:
        return  # the mutation was a no-op for this seed

    try:
        scan = read_journal(path, kind)
    except JournalCorrupt as e:
        assert e.code == "E_CORRUPT"
        assert e.kind == kind and e.index >= 0 and e.offset >= 0
        d = e.to_dict()
        assert d["journal"]["kind"] == kind
        # the kind-specific load path must agree (same strict reader)
        with pytest.raises(JournalCorrupt):
            load()
        return
    # accepted: the surviving records must be an exact, bit-identical
    # prefix of the pristine history — NEVER a subsequence with a hole
    assert scan.records == truth[: len(scan.records)], (kind, seed)


# ---- torn tail: the one forgiven damage ----------------------------------


@pytest.mark.parametrize("kind", sorted(_BUILDERS))
def test_torn_tail_resumes_from_prefix_and_heals(tmp_path, kind):
    path, load = _BUILDERS[kind](tmp_path)
    truth = read_journal(path, kind).records
    with open(path, "ab") as f:
        f.write(b'J1 deadbeef 5 {"kind": "torn')  # partial final write

    scan = read_journal(path, kind)
    assert scan.torn_tail and scan.records == truth
    assert scan.integrity() == {"format": "framed", "torn_tail": True}
    j = load()  # the kind-specific load tolerates it too
    assert j.torn_tail

    # resuming appends must first DROP the partial bytes: appending
    # after them would turn the forgiven tail into mid-file corruption
    j._append({"kind": "extra", "n": 1})
    healed = read_journal(path, kind)
    assert not healed.torn_tail
    assert healed.records == truth + [{"kind": "extra", "n": 1}]


def test_mid_file_corruption_is_structured(tmp_path):
    """A flipped byte anywhere but the final line is E_CORRUPT with the
    kind, record index, and byte offset of the damage."""
    path, load = _BUILDERS["sweep"](tmp_path)
    lines = open(path, "rb").read().split(b"\n")
    buf = bytearray(lines[1])
    buf[len(buf) // 2] ^= 0x10
    lines[1] = bytes(buf)
    with open(path, "wb") as f:
        f.write(b"\n".join(lines))
    with pytest.raises(JournalCorrupt) as ei:
        load()
    e = ei.value
    assert e.code == "E_CORRUPT" and e.kind == "sweep" and e.index == 1
    assert e.offset == len(lines[0]) + 1
    assert "crc mismatch" in e.message
    verdict = scan_integrity(path, "sweep")
    assert verdict is not None and verdict.index == 1


def test_sequence_gap_and_duplicate_are_corrupt(tmp_path):
    """Intact lines at the wrong position keep their CRC but break
    monotonicity: a dropped or duplicated mid-file record can never be a
    torn write, so both are refused."""
    path, _ = _BUILDERS["replay"](tmp_path)
    pristine = open(path, "rb").read().split(b"\n")

    with open(path, "wb") as f:  # drop record #2: a gap
        f.write(b"\n".join(pristine[:2] + pristine[3:]))
    with pytest.raises(JournalCorrupt) as ei:
        read_journal(path, "replay")
    assert "sequence break" in ei.value.message and ei.value.index == 2

    with open(path, "wb") as f:  # duplicate record #1
        f.write(b"\n".join(pristine[:2] + pristine[1:]))
    with pytest.raises(JournalCorrupt):
        read_journal(path, "replay")


def test_legacy_unframed_journal_still_loads(tmp_path):
    """Journals written before the frame format stay readable, are
    flagged legacy, and keep their format on append (mixing framed lines
    into an unframed file would make BOTH readers reject it)."""
    recs = [{"kind": "header", "sweep_id": "legacy01", "fingerprint": {},
             "max_new": 4, "lanes": 2, "thresholds": [100.0]},
            {"kind": "round", "round": 1, "counts": [1],
             "lanes": {"1": {"nodes": [0]}}}]
    path = tmp_path / ("legacy01" + lifecycle.SWEEP_JOURNAL_SUFFIX)
    with open(path, "w", encoding="utf-8") as f:
        for r in recs:
            f.write(json.dumps(r, sort_keys=True) + "\n")

    j = lifecycle.SweepJournal.load(str(tmp_path), "legacy01")
    assert j.legacy and len(j.rounds) == 1
    assert j.integrity()["format"] == "legacy"
    j.append_round([2], {2: {"nodes": [0]}})
    raw = open(path, "rb").read()
    assert not any(ln.startswith(b"J1 ")
                   for ln in raw.split(b"\n") if ln)
    again = lifecycle.SweepJournal.load(str(tmp_path), "legacy01")
    assert len(again.rounds) == 2 and again.legacy


def test_unframe_line_round_trip(tmp_path):
    framed = frame_record(7, {"kind": "x", "v": 1}).decode()
    assert json.loads(unframe_line(framed)) == {"kind": "x", "v": 1}
    legacy = json.dumps({"kind": "y"}) + "\n"
    assert json.loads(unframe_line(legacy)) == {"kind": "y"}


# ---- storage fault taxonomy (the PR-14 discipline for disks) -------------


def test_classify_storage_errnos():
    full = faults.classify(OSError(errno.ENOSPC, "No space left on device"))
    assert full.code == "E_STORAGE_FULL" and not full.transient
    assert faults.classify(
        OSError(errno.EDQUOT, "Disk quota exceeded")).code == "E_STORAGE_FULL"
    assert faults.classify(
        OSError(errno.EROFS, "Read-only file system")).code == "E_STORAGE_FULL"
    eio = faults.classify(OSError(errno.EIO, "Input/output error"))
    assert eio.code == "E_STORAGE_IO" and eio.transient
    # message-only classification (a wrapped OSError without errno)
    assert faults.classify(
        OSError("No space left on device")).code == "E_STORAGE_FULL"
    # a bare OSError stays in the transfer bucket (transient, retried)
    assert faults.classify(OSError("weird")).code == "E_TRANSFER"


def test_enospc_on_append_takes_disable_rung(tmp_path, caplog):
    """A full disk mid-run latches the shared checkpointing_disabled
    rung ONCE: counted per kind+code, ledger-evented, warn-once — and
    the surviving prefix stays loadable."""
    disabled = telemetry.counter("simon_journal_disabled_total",
                                 labelnames=("kind", "code"))
    rungs = telemetry.counter("simon_fault_rungs_total",
                              labelnames=("fn", "rung"))
    b_dis = disabled.value(kind="sweep", code="E_STORAGE_FULL")
    b_rung = rungs.value(fn="journal_append", rung="checkpointing_disabled")

    j = lifecycle.SweepJournal.create(str(tmp_path), {"f": 1}, 4, 2, (100.0,))
    with faults.injected("fn=journal_append,exc=ENOSPC,launch=0,times=9"):
        j.append_round([1], {1: {"nodes": [0]}})   # hits the full disk
        j.append_round([2], {2: {"nodes": [0]}})   # silently skipped
        j.finish(2, "d")
    assert j.broken and j.broken_code == "E_STORAGE_FULL"
    assert j.integrity()["checkpointing_disabled"] is True
    assert j.integrity()["storage_fault"] == "E_STORAGE_FULL"
    assert disabled.value(kind="sweep", code="E_STORAGE_FULL") == b_dis + 1
    assert rungs.value(fn="journal_append",
                       rung="checkpointing_disabled") == b_rung + 1
    # the prefix on disk is intact: header only (round 1 never landed)
    scan = read_journal(j.path, "sweep")
    assert [r["kind"] for r in scan.records] == ["header"]


def test_eio_is_transient_and_retried(tmp_path):
    """One EIO is absorbed by the run_io retry schedule: the append
    lands on the retry and journaling stays enabled."""
    j = lifecycle.SweepJournal.create(str(tmp_path), {"f": 1}, 4, 2, (100.0,))
    with faults.injected("fn=journal_append,exc=eio,launch=0,times=1"):
        j.append_round([1], {1: {"nodes": [0]}})
        stats = faults.injection_stats()
    assert not j.broken
    assert stats["injected"]["journal_append"] == 1
    assert stats["launches"]["journal_append"] == 2  # the EIO + the retry
    scan = read_journal(j.path, "sweep")
    assert [r["kind"] for r in scan.records] == ["header", "round"]
    assert not scan.torn_tail  # the failed attempt left no partial line


def test_storage_plan_round_trips_and_counts_match():
    """Satellite 4: the I/O-site grammar round-trips through canonical()
    and the injected counters match the plan exactly."""
    plan = faults.FaultPlan.parse("fn=journal_append,exc=ENOSPC,launch=2;"
                                  "fn=ledger_append,exc=eio")
    assert plan.canonical() == ("fn=journal_append,exc=enospc,launch=2,"
                                "times=1;fn=ledger_append,exc=eio,launch=0,"
                                "times=1")
    assert faults.FaultPlan.parse(plan.canonical()) == plan

    with faults.injected("fn=journal_append,exc=enospc,launch=1,times=2"):
        for _ in range(4):
            try:
                faults.run_io("journal_append", lambda: None, backoff_s=0.0)
            except faults.DeviceFault as e:
                assert e.code == "E_STORAGE_FULL"
        stats = faults.injection_stats()
    assert stats["injected"] == {"journal_append": 2}
    assert stats["launches"] == {"journal_append": 4}


@pytest.mark.parametrize("text,field", [
    ("fn=journal_append,exc=enospc,launch=-1", "rules[0].launch"),
    ("fn=journal_append,exc=ENOSPC!", "rules[0].exc"),
    ("fn=journal_append", "rules[0].exc"),
    ("fn=ledger_append,exc=eio,times=0", "rules[0].times"),
    ("fn=journal_rotate,exc=enospc", "rules[0].fn"),
])
def test_malformed_storage_rules_are_e_spec(text, field):
    with pytest.raises(SimulationError) as ei:
        faults.FaultPlan.parse(text)
    assert ei.value.code == "E_SPEC" and ei.value.field == field


# ---- the ENOSPC-mid-run regression (satellite 2) -------------------------


def _bisect_fixture():
    from tests.test_lifecycle import _snapshot
    from open_simulator_tpu.engine.scheduler import make_config

    snap = _snapshot()
    return snap, make_config(snap)


def test_enospc_mid_sweep_finishes_and_resumes_identically(
        tmp_path, monkeypatch):
    """The disk fills on round-2's append: the sweep FINISHES with the
    rung counted and the plan saying so, and resuming from the journal's
    surviving prefix is digest-identical to the uninterrupted run."""
    from open_simulator_tpu.parallel.sweep import capacity_bisect
    from open_simulator_tpu.telemetry.ledger import plan_digest

    monkeypatch.setenv(lifecycle.CHECKPOINT_DIR_ENV, str(tmp_path))
    snap, cfg = _bisect_fixture()
    reference = capacity_bisect(snap, cfg, 8, lanes=2)
    assert not reference.checkpointing_disabled
    for n in os.listdir(tmp_path):
        os.unlink(tmp_path / n)

    # header is append #0; round 1 lands; round 2's append hits ENOSPC
    with faults.injected("fn=journal_append,exc=enospc,launch=2,times=99"):
        degraded = capacity_bisect(snap, cfg, 8, lanes=2)
    assert degraded.checkpointing_disabled
    assert degraded.best_count == reference.best_count
    assert plan_digest(degraded)["digest"] == plan_digest(reference)["digest"]

    # the surviving prefix (header + round 1) resumes digest-identically
    [name] = [n for n in os.listdir(tmp_path)
              if n.endswith(lifecycle.SWEEP_JOURNAL_SUFFIX)]
    j = lifecycle.SweepJournal.load(str(tmp_path), "last")
    assert [r["round"] for r in j.rounds] == [1] and j.done is None
    resumed = capacity_bisect(snap, cfg, 8, lanes=2,
                              resume=name.split(".")[0])
    assert resumed.resumed_rounds == 1
    assert not resumed.checkpointing_disabled
    assert plan_digest(resumed)["digest"] == plan_digest(reference)["digest"]


# ---- SessionStore startup quarantine -------------------------------------


def test_session_store_quarantines_corrupt_journal(tmp_path):
    """A mid-file-corrupt session journal is quarantined at scan: the
    store boots, siblings rehydrate, the corrupt sid reports its stored
    E_CORRUPT on touch and shows up flagged in list()."""
    from open_simulator_tpu.replay import (
        ReplaySession,
        SessionSpec,
        SessionStore,
        synthetic_replay_cluster,
        synthetic_trace_dict,
    )

    td = synthetic_trace_dict(n_batches=2, batch_pods=2,
                              max_new_nodes=2)
    cluster = synthetic_replay_cluster(n_nodes=2, n_initial_pods=2)
    spec = SessionSpec(max_new_nodes=2, node_template=td["node_template"])
    sess = ReplaySession.create(cluster, spec, controllers=[],
                                root=str(tmp_path))
    sess.apply_events(td["events"][:1])
    sid_ok = sess.session_id

    corrupt_path, _ = _BUILDERS["session"](tmp_path)
    lines = open(corrupt_path, "rb").read().split(b"\n")
    lines[1] = lines[1][:-4] + b"XXXX"  # mid-file CRC break
    # drop the close record so the journal counts as an OPEN session
    with open(corrupt_path, "wb") as f:
        f.write(b"\n".join(lines[:-2]) + b"\n")

    store = SessionStore(root=str(tmp_path))
    found = store.scan()
    assert sid_ok in found and "sid0fuzz" not in found
    assert "sid0fuzz" in store.quarantined()

    with pytest.raises(JournalCorrupt) as ei:
        store.get("sid0fuzz")
    assert ei.value.code == "E_CORRUPT"
    from open_simulator_tpu.server.serving import STATUS_BY_CODE
    assert STATUS_BY_CODE["E_CORRUPT"] == 409

    rows = store.list()
    flagged = [r for r in rows if r.get("corrupt")]
    assert [r["session_id"] for r in flagged] == ["sid0fuzz"]
    assert flagged[0]["error"]["code"] == "E_CORRUPT"
    # the sibling is untouched by the quarantine
    ok = store.get(sid_ok)
    assert ok.session_id == sid_ok


# ---- ledger skipped_corrupt (satellite 3) --------------------------------


def test_ledger_counts_and_surfaces_skipped_corrupt(tmp_path, capsys,
                                                    monkeypatch):
    from open_simulator_tpu.telemetry import ledger as ledger_mod
    from open_simulator_tpu.telemetry.ledger import Ledger

    led = Ledger(str(tmp_path))
    for i in range(3):
        led.append({"run_id": f"r{i}", "surface": "sweep", "ts": i})
    lines = open(led.path, encoding="utf-8").read().splitlines()
    lines.insert(1, '{"torn half rec')      # undecodable
    lines.insert(3, '["not", "a", "dict"]')  # decodable, not a record
    lines.insert(4, '')                      # blank: ignored, not counted
    with open(led.path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")

    led2 = Ledger(str(tmp_path))
    recs = led2.records()
    assert [r["run_id"] for r in recs] == ["r0", "r1", "r2"]
    assert led2.skipped_corrupt == 2

    # the REST index carries the count
    from open_simulator_tpu.server import rest as rest_mod
    ledger_mod.configure(str(tmp_path))
    try:
        srv = rest_mod.SimulationServer()
        out = srv.runs_index({})
        assert out["skipped_corrupt"] == 2 and len(out["runs"]) == 3
    finally:
        ledger_mod.configure(None)

    # and the CLI warns on the runs surfaces
    from open_simulator_tpu.cli.main import main as cli_main
    try:
        rc = cli_main(["runs", "--ledger-dir", str(tmp_path), "list"])
    finally:
        ledger_mod.configure(None)
    assert rc == 0
    err = capsys.readouterr().err
    assert "skipped 2 corrupt ledger record(s)" in err


# ---- resolve_journal_path (the shared token resolution) ------------------


def test_resolve_journal_path_errors(tmp_path):
    with pytest.raises(lifecycle.ResumeError):
        journal_mod.resolve_journal_path(
            str(tmp_path / "absent"), "last", ".sweep.jsonl", "sweep")
    with pytest.raises(lifecycle.ResumeError):
        journal_mod.resolve_journal_path(
            str(tmp_path), "last", ".sweep.jsonl", "sweep")
    (tmp_path / "aaa111.sweep.jsonl").write_text("")
    (tmp_path / "aaa222.sweep.jsonl").write_text("")
    with pytest.raises(lifecycle.ResumeError) as ei:
        journal_mod.resolve_journal_path(
            str(tmp_path), "aaa", ".sweep.jsonl", "sweep")
    assert "ambiguous" in ei.value.message
    got = journal_mod.resolve_journal_path(
        str(tmp_path), "aaa1", ".sweep.jsonl", "sweep")
    assert got.endswith("aaa111.sweep.jsonl")


def test_empty_journal_is_not_torn(tmp_path):
    p = tmp_path / "empty.sweep.jsonl"
    p.write_text("")
    scan = read_journal(str(p), "sweep")
    assert scan.records == [] and not scan.torn_tail and not scan.legacy
