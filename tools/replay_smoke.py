#!/usr/bin/env python
"""Replay smoke: the time-axis contract end to end.

A CPU rehearsal: it and every process it starts run with
JAX_PLATFORMS=cpu and never touch the chip; `chip_smoke.py` is the
chip path.

Stages (`make replay-smoke`, also a tools/smoke.sh stage):

1. A synthetic day-in-the-cluster (arrival waves, departures, one
   mid-trace ``kill_node``) runs with the autoscaler: the trajectory
   must CONVERGE (no pending pods at the end, every step's controller
   loop settled) with scale-ups recorded and the fault's evictions
   visible in its step row.
2. Crash recovery: a child process re-runs the same trajectory with
   checkpointing on and SIGKILLs ITSELF the moment step 3 lands in the
   journal (a real uncatchable kill between steps). The parent resumes
   with ``resume=last``; the resumed trajectory digest must be
   BIT-IDENTICAL to the uninterrupted run's.
3. Frontier CLI: ``simon-tpu replay --frontier`` over the same trace's
   workload must return a NON-TRIVIAL Pareto set (>= 2 points) as JSON.
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

KILL_AFTER_STEPS = 3


def _workload():
    from open_simulator_tpu.replay import (
        ReplayTrace,
        synthetic_replay_cluster,
        synthetic_trace_dict,
    )

    trace_dict = synthetic_trace_dict(n_batches=5, batch_pods=8,
                                      depart_every=2, max_new_nodes=6)
    return (synthetic_replay_cluster(n_nodes=3, n_initial_pods=3),
            ReplayTrace.from_dict(trace_dict), trace_dict)


def _controllers():
    from open_simulator_tpu.replay import AutoscalerPolicy

    return [AutoscalerPolicy(scale_step=2)]


def child_main() -> None:
    """Run the replay but SIGKILL self after step KILL_AFTER_STEPS hits
    the journal — invoked as a subprocess by stage 2."""
    from open_simulator_tpu.replay import ReplayOptions, run_replay
    from open_simulator_tpu.replay import engine as rep_engine

    real_append = rep_engine.ReplayJournal.append_step

    def kamikaze(self, row):
        real_append(self, row)
        if len(self.rows) >= KILL_AFTER_STEPS:
            os.kill(os.getpid(), signal.SIGKILL)

    rep_engine.ReplayJournal.append_step = kamikaze
    cluster, trace, _ = _workload()
    run_replay(cluster, trace, ReplayOptions(controllers=_controllers()))
    raise SystemExit("unreachable: the kill must fire mid-replay")


def main() -> int:
    from open_simulator_tpu.replay import ReplayOptions, run_replay
    from open_simulator_tpu.resilience import lifecycle

    tmp = tempfile.mkdtemp(prefix="simon-replay-smoke-")

    # ---- stage 1: chaos mid-trace + autoscaler convergence -------------
    cluster, trace, trace_dict = _workload()
    report = run_replay(cluster, trace, ReplayOptions(
        controllers=_controllers(), checkpoint=False))
    t = report["totals"]
    assert t["pending"] == 0, f"autoscaler did not converge: {t}"
    assert t["converged"], "a controller loop hit max iterations"
    assert t["scale_ups"] > 0, f"expected scale-ups, got {t}"
    kill_steps = [s for s in report["steps"]
                  if s["event"]["kind"] == "kill_node"]
    assert kill_steps and kill_steps[0]["evicted"], (
        "the mid-trace kill_node must evict the dead node's pods")
    print(f"replay-smoke stage 1 OK: {t['steps']} steps converged, "
          f"+{t['scale_ups']} scale-ups, kill_node evicted "
          f"{len(kill_steps[0]['evicted'])} pod(s), "
          f"digest {report['digest']}")

    # ---- stage 2: SIGKILL after step 3, then resume --------------------
    ckpt = os.path.join(tmp, "ckpt")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           lifecycle.CHECKPOINT_DIR_ENV: ckpt}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from tools.replay_smoke import child_main; child_main()" % REPO],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, (
        f"child should die by SIGKILL, got rc={proc.returncode}\n"
        f"stdout: {proc.stdout}\nstderr: {proc.stderr}")
    [journal] = [n for n in os.listdir(ckpt)
                 if n.endswith(".replay.jsonl")]
    with open(os.path.join(ckpt, journal), encoding="utf-8") as f:
        from open_simulator_tpu.resilience.journal import unframe_line
        kinds = [json.loads(unframe_line(ln))["kind"] for ln in f
                 if ln.strip()]
    assert kinds == ["header"] + ["step"] * KILL_AFTER_STEPS, (
        f"expected a torn journal, got {kinds}")

    os.environ[lifecycle.CHECKPOINT_DIR_ENV] = ckpt
    try:
        cluster, trace, _ = _workload()
        resumed = run_replay(cluster, trace, ReplayOptions(
            controllers=_controllers(), resume="last"))
    finally:
        del os.environ[lifecycle.CHECKPOINT_DIR_ENV]
    assert resumed["resumed_steps"] == KILL_AFTER_STEPS
    assert resumed["digest"] == report["digest"], (
        f"resumed digest {resumed['digest']} != uninterrupted "
        f"{report['digest']}")
    print(f"replay-smoke stage 2 OK: SIGKILL after step "
          f"{KILL_AFTER_STEPS}, resume replayed the settled prefix, "
          f"digest bit-identical ({resumed['digest']})")

    # ---- stage 3: the frontier CLI over the same workload --------------
    import yaml

    from open_simulator_tpu.replay import synthetic_frontier_specs

    trace_path = os.path.join(tmp, "trace.yaml")
    with open(trace_path, "w", encoding="utf-8") as f:
        yaml.safe_dump(trace_dict, f)
    specs_path = os.path.join(tmp, "specs.yaml")
    with open(specs_path, "w", encoding="utf-8") as f:
        yaml.safe_dump({"specs": synthetic_frontier_specs()}, f)
    cluster_dir = os.path.join(tmp, "cluster")
    os.makedirs(cluster_dir, exist_ok=True)
    cluster, _, _ = _workload()
    with open(os.path.join(cluster_dir, "nodes.yaml"), "w",
              encoding="utf-8") as f:
        yaml.safe_dump_all(
            [{"apiVersion": "v1", "kind": "Node", **n.raw}
             for n in cluster.nodes], f)
    out = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli", "replay",
         "--cluster-config", cluster_dir, "--trace", trace_path,
         "--frontier", specs_path, "--json"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.returncode, out.stdout[-2000:],
                                 out.stderr[-2000:])
    result = json.loads(out.stdout)
    assert len(result["pareto"]) >= 2, (
        f"expected a non-trivial Pareto set, got {result['pareto']}")
    assert result["n_mixes"] > len(result["pareto"])
    print(f"replay-smoke stage 3 OK: frontier CLI swept "
          f"{result['n_mixes']} mixes -> {len(result['pareto'])} "
          f"Pareto point(s)")

    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    print("replay-smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
