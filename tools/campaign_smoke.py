#!/usr/bin/env python
"""Campaign smoke: the fleet fault-isolation contract end to end.

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (`make campaign-smoke`, also a tools/smoke.sh stage):

1. A 3-cluster fixture fleet (one deliberately malformed) runs through
   `run_campaign`: the campaign must COMPLETE with exactly 1 quarantined
   cluster (E_SOURCE) and 2 completed ones whose audits pass.
2. Crash recovery: a child process re-runs the same fleet with
   checkpointing on and SIGKILLs ITSELF the moment the first cluster's
   journal line lands on disk (a real uncatchable kill between
   clusters). The parent resumes with `--resume last`; the resumed fleet
   report digest must be BIT-IDENTICAL to the uninterrupted run's, and
   the quarantined cluster must be reported exactly once (not re-run,
   not lost).
3. CLI surface: `simon-tpu campaign report last` renders the journal.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fleet(root: str) -> str:
    from open_simulator_tpu.campaign import write_synthetic_fleet

    fleet_dir = os.path.join(root, "fleet")
    write_synthetic_fleet(fleet_dir, n_clusters=3, nodes=4, pods=12,
                          malformed=1)
    return fleet_dir


def child_main() -> None:
    """Run the campaign but SIGKILL self after the first settled cluster
    hits the journal — invoked as a subprocess by stage 2."""
    from open_simulator_tpu.campaign import CampaignOptions, run_campaign
    from open_simulator_tpu.campaign import runner as campaign_runner

    real_append = campaign_runner.CampaignJournal._append

    def kamikaze(self, rec):
        real_append(self, rec)
        if rec.get("kind") in ("cluster", "quarantine"):
            os.kill(os.getpid(), signal.SIGKILL)

    campaign_runner.CampaignJournal._append = kamikaze
    run_campaign(CampaignOptions(fleet=os.environ["SMOKE_FLEET"]))
    raise SystemExit("unreachable: the kill must fire mid-campaign")


def main() -> int:
    from open_simulator_tpu.campaign import (
        CampaignOptions,
        run_campaign,
    )
    from open_simulator_tpu.resilience import lifecycle

    tmp = tempfile.mkdtemp(prefix="simon-campaign-smoke-")
    fleet_dir = _fleet(tmp)

    # ---- stage 1: fault isolation + audit ------------------------------
    report = run_campaign(CampaignOptions(fleet=fleet_dir,
                                          checkpoint=False))
    t = report["totals"]
    assert t["clusters"] == 3 and t["completed"] == 2, report["totals"]
    assert t["quarantined"] == 1, report["totals"]
    [quar] = report["quarantined"]
    assert quar["error"]["code"] == "E_SOURCE", quar
    assert all(r["audit_ok"] for r in report["clusters"]), report["clusters"]
    print(f"campaign-smoke stage 1 OK: 2 completed (audit pass), "
          f"1 quarantined [{quar['error']['code']}], "
          f"digest {report['digest']}")

    # ---- stage 2: SIGKILL after cluster 1, then resume -----------------
    ckpt = os.path.join(tmp, "ckpt")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SMOKE_FLEET": fleet_dir,
           lifecycle.CHECKPOINT_DIR_ENV: ckpt}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from tools.campaign_smoke import child_main; child_main()"
         % REPO],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, (
        f"child should die by SIGKILL, got rc={proc.returncode}\n"
        f"stdout: {proc.stdout}\nstderr: {proc.stderr}")
    [journal] = [n for n in os.listdir(ckpt)
                 if n.endswith(".campaign.jsonl")]
    with open(os.path.join(ckpt, journal), encoding="utf-8") as f:
        from open_simulator_tpu.resilience.journal import unframe_line
        kinds = [json.loads(unframe_line(ln))["kind"] for ln in f
                 if ln.strip()]
    assert kinds[0] == "header" and len(kinds) == 2 and "done" not in kinds, (
        f"expected a torn journal (header + 1 settled cluster), got {kinds}")

    os.environ[lifecycle.CHECKPOINT_DIR_ENV] = ckpt
    try:
        resumed = run_campaign(CampaignOptions(fleet=fleet_dir,
                                               resume="last"))
    finally:
        del os.environ[lifecycle.CHECKPOINT_DIR_ENV]
    assert resumed["resumed_clusters"] == 1, resumed["resumed_clusters"]
    assert resumed["digest"] == report["digest"], (
        f"resumed report digest {resumed['digest']} != uninterrupted "
        f"{report['digest']}")
    assert resumed["totals"] == report["totals"], (resumed["totals"],
                                                   report["totals"])
    assert len(resumed["quarantined"]) == 1, resumed["quarantined"]
    print(f"campaign-smoke stage 2 OK: SIGKILL after cluster 1, resume "
          f"replayed 1 settled cluster, digest bit-identical "
          f"({resumed['digest']}), quarantine reported once")

    # ---- stage 3: the report CLI over the finished journal -------------
    env2 = {**os.environ, "JAX_PLATFORMS": "cpu",
            lifecycle.CHECKPOINT_DIR_ENV: ckpt}
    out = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "campaign",
         "report", "last", "--json"],
        cwd=REPO, env=env2, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    cli_report = json.loads(out.stdout)
    assert cli_report["digest"] == report["digest"], cli_report["digest"]
    print("campaign-smoke stage 3 OK: campaign report CLI digest matches")
    print("campaign-smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
