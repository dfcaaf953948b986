"""CLI: simon-tpu {apply, server, version, gen-doc}.

Command/flag parity with the reference's cobra tree (cmd/simon/simon.go:27-44,
cmd/apply/apply.go:27-36, cmd/server/server.go). LogLevel env knob kept.
"""

from __future__ import annotations

import argparse
import contextlib
import logging
import os
import sys
import time

from open_simulator_tpu import __version__
from open_simulator_tpu.errors import SimulationError


class _FaultAction(argparse.Action):
    """Append (kind, target) pairs to one shared `events` list, preserving
    command-line order across the three chaos flag types."""

    def __init__(self, option_strings, dest, fault_kind=None, **kw):
        self.fault_kind = fault_kind
        super().__init__(option_strings, dest, **kw)

    def __call__(self, parser, namespace, value, option_string=None):
        events = getattr(namespace, self.dest, None) or []
        events.append((self.fault_kind, value))
        setattr(namespace, self.dest, events)


_CACHE_HELP = ("persistent XLA compilation cache directory (default: "
               "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR, when "
               "set, wins): repeat runs skip cold compiles")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="simon-tpu",
        description="TPU-native Kubernetes cluster-capacity simulator",
    )
    sub = p.add_subparsers(dest="command")

    ap = sub.add_parser("apply", help="run a capacity-planning simulation")
    ap.add_argument("-f", "--simon-config", required=True, help="simon/v1alpha1 Config file")
    ap.add_argument(
        "--default-scheduler-config", default="",
        help="KubeSchedulerConfiguration file: Score plugin enable/disable/"
             "weights and NodeResourcesFit scoringStrategy are applied; "
             "Filter enable/disable is ignored with a warning",
    )
    ap.add_argument("--output-file", default="", help="redirect the report to a file")
    ap.add_argument("--use-greed", action="store_true", help="sort app pods by dominant share (big rocks first)")
    ap.add_argument("-i", "--interactive", action="store_true", help="interactive add-node prompt loop")
    ap.add_argument("--extended-resources", default="", help="comma list, e.g. gpu")
    ap.add_argument("--max-new-nodes", type=int, default=128, help="sweep upper bound for added nodes")
    ap.add_argument(
        "--sweep-mode", choices=("bisect", "exhaustive"), default="bisect",
        help="bisect (default): galloping bisection over the monotone "
             "node-count axis — ~log(max-new-nodes) fixed-width lane "
             "rounds reusing one compiled executable; exhaustive: one "
             "lane per candidate count (interactive mode always uses "
             "exhaustive)")
    ap.add_argument(
        "--compile-cache-dir", default="",
        help=_CACHE_HELP)
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON timeline of this run's "
                         "phases (open in chrome://tracing or Perfetto)")
    ap.add_argument("--ledger-dir", default="",
                    help="run-ledger directory: append one RunRecord for "
                         "this run (also honors SIMON_LEDGER_DIR); inspect "
                         "with `simon-tpu runs`")
    ap.add_argument("--resume", default="", metavar="SWEEP_ID",
                    help="resume a checkpointed capacity bisection after a "
                         "crash: sweep-id prefix (or 'last') of a journal "
                         "under <ledger>/checkpoints or SIMON_CHECKPOINT_DIR;"
                         " recorded rounds replay after the config "
                         "fingerprint is verified, and the final result is "
                         "identical to an uninterrupted run (bisect mode "
                         "only)")

    ex = sub.add_parser(
        "explain",
        help="per-pod scheduling explanation: why this node / why unschedulable",
        description="Run one simulation with per-op failure accounting and "
                    "top-k score recording on, then report per pod: the "
                    "chosen node with each score plugin's weighted "
                    "contribution at the top-k candidates, or the "
                    "per-filter-op node elimination counts ('0/N nodes "
                    "are available: ...') with the first failing op. The "
                    "numbers decode the engine's own fail_counts/score "
                    "tensors — nothing is recomputed on the host.")
    ex.add_argument("-f", "--simon-config", required=True,
                    help="simon/v1alpha1 Config file")
    ex.add_argument("--default-scheduler-config", default="",
                    help="KubeSchedulerConfiguration file (same semantics "
                         "as apply)")
    ex.add_argument("--pod", action="append", default=[], metavar="NS/NAME",
                    help="only explain this pod key (repeatable; default all)")
    ex.add_argument("--top-k", type=int, default=3,
                    help="candidate nodes to report per pod")
    ex.add_argument("--use-greed", action="store_true",
                    help="sort app pods by dominant share, like apply")
    ex.add_argument("--json", action="store_true",
                    help="emit the report as JSON")
    ex.add_argument("--output-file", default="")
    ex.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON timeline of this run's "
                         "phases (open in chrome://tracing or Perfetto)")

    sp = sub.add_parser("server", help="REST simulation server")
    sp.add_argument("--port", type=int, default=8899)
    sp.add_argument("--address", default="127.0.0.1")
    sp.add_argument(
        "--kubeconfig", default="",
        help="recorded cluster API dump (kubectl get ... -A -o json), "
             "replayed with the reference's live-snapshot semantics; an "
             "actual kubeconfig fails with the recording recipe (no live "
             "cluster access in this environment)")
    sp.add_argument("--master", default="", help="(unsupported here: no live cluster access)")
    sp.add_argument("--cluster-config", default="", help="cluster YAML dir serving as the live-cluster stand-in")
    sp.add_argument("--max-body-mib", type=int, default=8,
                    help="reject request bodies above this size with 413")
    sp.add_argument("--request-timeout", type=float, default=300.0,
                    help="per-request simulation deadline in seconds (504 past it)")
    sp.add_argument("--explain-topk", type=int, default=3,
                    help="candidate nodes recorded per pod during serving "
                         "simulations for GET /api/explain (0 disables)")
    sp.add_argument(
        "--compile-cache-dir", default="",
        help=_CACHE_HELP)
    sp.add_argument(
        "--ledger-dir", default="",
        help="run-ledger directory: every simulation this server runs "
             "appends one RunRecord, served back on GET /api/runs (also "
             "honors SIMON_LEDGER_DIR)")
    sp.add_argument(
        "--queue-depth", type=int, default=8,
        help="bounded admission-queue depth for POSTs: beyond it requests "
             "shed with 429 + a Retry-After computed from the queue's "
             "EWMA service time")
    sp.add_argument(
        "--drain-timeout", type=float, default=30.0,
        help="graceful-drain budget in seconds: on SIGTERM/SIGINT the "
             "server flips /readyz to 503, finishes in-flight work up to "
             "this long (then cancels it cooperatively), writes a final "
             "ledger record, and exits")
    sp.add_argument(
        "--max-sessions", type=int, default=8,
        help="resident digital-twin sessions held in device memory: past "
             "this the least-recently-touched session drops its device "
             "state (it stays open in its journal and rehydrates "
             "transparently on the next touch)")
    sp.add_argument(
        "--max-resident-mib", type=int, default=1024,
        help="byte budget (MiB) for device-resident snapshot arrays in "
             "the /api/simulate | /api/capacity serving cache: past it "
             "the least-recently-used snapshot drops its device arrays "
             "(the host copy stays — an evicted digest re-transfers "
             "transparently, never a 500); 0 disables the budget")
    sp.add_argument(
        "--workers", type=int, default=1,
        help="admission-queue worker threads: 1 (default) keeps the "
             "classic single-flight front end; more let coalesced "
             "serving batches and long singleton jobs (sweeps, "
             "campaigns) interleave so neither starves the other's "
             "deadlines — a crashed worker is replaced without losing "
             "queued jobs")
    sp.add_argument(
        "--fault-plan", default="", metavar="PLAN",
        help="deterministic device/storage fault injection (test rigs "
             "only): 'fn=<launch>,exc=<oom|device_lost|transfer|numeric|"
             "compile|enospc|eio>[,launch=<k>][,times=<n>]' rules joined "
             "by ';' — "
             "fail launch #k of that fn n times so every degradation "
             "rung and retry schedule is reproducibly testable (also "
             "honors SIMON_FAULT_PLAN; a malformed plan is a startup "
             "error here, not a per-request surprise)")
    sp.add_argument(
        "--blackbox-events", default="", metavar="N",
        help="black-box flight-recorder ring capacity (events): the "
             "bounded ring behind GET /api/trace/<id> and GET "
             "/api/events drops its OLDEST events past this (default "
             "4096; also honors SIMON_BLACKBOX_EVENTS; a malformed "
             "size is a startup error, not a lost incident)")

    tp = sub.add_parser(
        "top",
        help="live terminal view of a running server",
        description="Redraw-in-place operations view over GET "
                    "/debug/stats and GET /metrics: admission-queue "
                    "depth and wait, in-flight launches with trace ids, "
                    "device-memory owners with high-watermarks "
                    "(simon_devmem_bytes), resident snapshots/sessions, "
                    "per-launch latency percentiles "
                    "(simon_launch_seconds), and event-feed fan-out "
                    "state. No curses — plain ANSI clear-and-redraw, "
                    "safe over ssh; --once prints a single frame "
                    "(snapshot mode, scripts/smoke)")
    tp.add_argument("--server", default="http://127.0.0.1:8899",
                    help="base URL of the running simon-tpu server")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="seconds between redraws")
    tp.add_argument("--once", action="store_true",
                    help="print one frame and exit (no redraw loop)")

    ch = sub.add_parser(
        "chaos",
        help="fault-injection re-simulation: kill nodes/zones and report the disruption")
    ch.add_argument("--cluster-config", required=True, help="cluster YAML dir")
    # one shared ordered list: faults are cumulative, so
    # `--kill-zone z0 --drain-node n5` must run in command-line order
    ch.add_argument("--kill-node", action=_FaultAction, fault_kind="kill_node",
                    default=[], dest="events", metavar="NAME",
                    help="fail this node (repeatable; events run in "
                         "command-line order)")
    ch.add_argument("--kill-zone", action=_FaultAction, fault_kind="kill_zone",
                    dest="events", metavar="ZONE",
                    help="fail every node in this zone (repeatable)")
    ch.add_argument("--drain-node", action=_FaultAction, fault_kind="drain_node",
                    dest="events", metavar="NAME",
                    help="drain this node (repeatable)")
    ch.add_argument("--zone-key", default="topology.kubernetes.io/zone",
                    help="node label key that defines zones")
    ch.add_argument("--json", action="store_true", help="emit the report as JSON")
    ch.add_argument("--output-file", default="")
    ch.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON timeline of this run's "
                         "phases (open in chrome://tracing or Perfetto)")
    ch.add_argument("--ledger-dir", default="",
                    help="run-ledger directory: append one RunRecord for "
                         "this chaos run (also honors SIMON_LEDGER_DIR)")

    rn = sub.add_parser(
        "runs",
        help="inspect the persistent run ledger: list, show, diff",
        description="Flight-recorder surface over the run ledger "
                    "(--ledger-dir / SIMON_LEDGER_DIR): every simulation "
                    "appends one RunRecord (config fingerprint, per-phase "
                    "wall times, metric deltas, result digest). `list` "
                    "summarizes, `show` dumps one record, `diff` compares "
                    "two — phase-timing deltas with % change, result-"
                    "digest equality (nondeterminism flag), and config-"
                    "fingerprint drift explanation. Run ids resolve by "
                    "unique prefix, or use `last` / `prev`.")
    rn.add_argument("--ledger-dir", default="",
                    help="ledger directory (default: SIMON_LEDGER_DIR)")
    rn_sub = rn.add_subparsers(dest="runs_command")
    rn_ls = rn_sub.add_parser("list", help="summarize recorded runs")
    rn_ls.add_argument("--surface", default="",
                       help="only this surface (apply/chaos/sweep/"
                            "simulate/campaign/server:<route>)")
    rn_ls.add_argument("--campaign", default="", metavar="ID",
                       help="only records tagged with this campaign id "
                            "(prefix match) — the per-cluster RunRecords "
                            "a fleet campaign wrote")
    rn_ls.add_argument("-n", "--limit", type=int, default=0,
                       help="newest N records only")
    rn_ls.add_argument("--json", action="store_true",
                       help="emit summaries as JSON")
    rn_sh = rn_sub.add_parser("show", help="dump one full RunRecord")
    rn_sh.add_argument("run", metavar="RUN",
                       help="run id prefix, or last / prev")
    rn_df = rn_sub.add_parser(
        "diff", help="compare two runs: phases, digests, config drift")
    rn_df.add_argument("run_a", metavar="A",
                       help="run id prefix, or last / prev")
    rn_df.add_argument("run_b", metavar="B",
                       help="run id prefix, or last / prev")
    rn_df.add_argument("--json", action="store_true",
                       help="emit the structured diff as JSON")

    cp = sub.add_parser(
        "campaign",
        help="fault-isolated fleet campaigns over recorded cluster dumps",
        description="Stream a fleet (directory or manifest of recorded "
                    "API dumps) through the bucketed engine with "
                    "per-cluster fault isolation: a malformed dump, a "
                    "crashed encode, or an audit violation quarantines "
                    "THAT cluster with a structured record while the "
                    "campaign continues. One fsynced journal line per "
                    "settled cluster makes `run --resume <id|last>` "
                    "after a SIGKILL produce a fleet report digest "
                    "bit-identical to an uninterrupted run. "
                    "ARCHITECTURE.md §13.")
    cp_sub = cp.add_subparsers(dest="campaign_command")
    cp_run = cp_sub.add_parser(
        "run", help="run (or resume) a campaign over a fleet of dumps")
    cp_run.add_argument("--fleet", required=True, metavar="DIR|MANIFEST",
                        help="directory of recorded dumps (*.json/*.yaml, "
                             "subdirs = manifest dirs) or a manifest file "
                             "listing cluster paths")
    cp_run.add_argument("--apps", default="", metavar="DIR",
                        help="optional scenario apps (manifest dir) "
                             "deployed to EVERY cluster")
    cp_run.add_argument("--scenario", default="replay",
                        help="scenario-set name stamped on journal and "
                             "ledger records (default: replay)")
    cp_run.add_argument("--max-clusters", type=int, default=0,
                        help="only the first N clusters (0 = whole fleet)")
    cp_run.add_argument("--retries", type=int, default=2,
                        help="transient-failure retries per cluster "
                             "(full-jitter backoff)")
    cp_run.add_argument("--resume", default="", metavar="CAMPAIGN_ID",
                        help="resume a checkpointed campaign after a "
                             "crash: campaign-id prefix (or 'last'); "
                             "settled clusters replay from the journal "
                             "(quarantined ones are reported once, not "
                             "re-run) and the report digest matches an "
                             "uninterrupted run")
    cp_run.add_argument("--no-audit", action="store_true",
                        help="skip the per-cluster placement invariant "
                             "audit (campaign/audit.py) — not recommended")
    cp_run.add_argument("--ledger-dir", default="",
                        help="run-ledger directory: one RunRecord per "
                             "(cluster, scenario) + a campaign summary "
                             "(also honors SIMON_LEDGER_DIR); checkpoints "
                             "live in <ledger>/checkpoints")
    cp_run.add_argument("--compile-cache-dir", default="",
                        help=_CACHE_HELP)
    cp_run.add_argument("--json", action="store_true",
                        help="emit the fleet report as JSON")
    cp_run.add_argument("--output-file", default="")
    cp_rep = cp_sub.add_parser(
        "report", help="rebuild a fleet report from a campaign journal")
    cp_rep.add_argument("campaign", metavar="CAMPAIGN", nargs="?",
                        default="last",
                        help="campaign-id prefix or 'last' (default)")
    cp_rep.add_argument("--ledger-dir", default="",
                        help="ledger dir whose checkpoints/ holds the "
                             "journal (also honors SIMON_LEDGER_DIR / "
                             "SIMON_CHECKPOINT_DIR)")
    cp_rep.add_argument("--json", action="store_true",
                        help="emit the report as JSON")
    cp_rep.add_argument("--output-file", default="")
    cp_aud = cp_sub.add_parser(
        "audit",
        help="standalone placement invariant audit of one cluster")
    cp_aud.add_argument("cluster", metavar="DUMP|DIR",
                        help="recorded API dump file or manifest dir")
    cp_aud.add_argument("--json", action="store_true",
                        help="emit the audit report as JSON")
    cp_aud.add_argument("--output-file", default="")

    rp = sub.add_parser(
        "replay",
        help="time-stepped trace replay: arrivals, departures, chaos, "
             "autoscaler loops, cost frontiers",
        description="Execute a ReplayTrace (ordered timed events: pod-"
                    "batch arrivals, departures, node add/remove, the "
                    "chaos fault kinds) as a closed loop over the "
                    "bucketed scan — one encode for the whole "
                    "trajectory, pods pinned where they landed, pending "
                    "pods retried every step. --controller registers "
                    "autoscaler / descheduler loops that run between "
                    "events until convergence. With a checkpoint "
                    "directory (a ledger dir or SIMON_CHECKPOINT_DIR) "
                    "every settled step is journaled and --resume "
                    "continues a killed trajectory to a bit-identical "
                    "digest. --frontier switches to the cost-frontier "
                    "question: sweep heterogeneous node-spec mixes over "
                    "the trace's full workload and report the (cost, "
                    "utilization, disruption) Pareto set. "
                    "ARCHITECTURE.md section 14.")
    rp.add_argument("--cluster-config", required=True,
                    help="cluster YAML dir (the t=0 state)")
    rp.add_argument("--trace", required=True, metavar="FILE",
                    help="trace file (YAML or JSON): {events: [{t, kind, "
                         "...}], max_new_nodes, node_template, zone_key}")
    rp.add_argument("--controller", action="append", default=[],
                    metavar="NAME[:k=v,...]",
                    help="register a step controller, repeatable — "
                         "autoscaler[:scale_step=N,idle_steps=N,"
                         "up_cooldown=N,down_cooldown=N,max_nodes=N] or "
                         "descheduler[:period=N]")
    rp.add_argument("--frontier", default="", metavar="SPECS",
                    help="node-spec mix file ({specs: [{name, cost, "
                         "max_count, spec_yaml}], max_total}): report "
                         "the Pareto set over every mix instead of "
                         "replaying the timeline")
    rp.add_argument("--lane-width", type=int, default=8,
                    help="frontier mixes swept per device round")
    rp.add_argument("--max-mixes", type=int, default=2048,
                    help="frontier mix-grid guardrail")
    rp.add_argument("--resume", default="", metavar="REPLAY_ID",
                    help="resume a checkpointed replay after a crash: "
                         "replay-id prefix (or 'last'); settled steps "
                         "replay from the journal and the trajectory "
                         "digest is identical to an uninterrupted run")
    rp.add_argument("--no-fast-path", action="store_true",
                    help="disable the carry-threaded arrival fast path "
                         "(results are bit-identical either way — this "
                         "is a perf/debug switch)")
    rp.add_argument("--compile-cache-dir", default="",
                    help=_CACHE_HELP)
    rp.add_argument("--ledger-dir", default="",
                    help="run-ledger directory: one RunRecord per "
                         "executed step + a trajectory summary (also "
                         "honors SIMON_LEDGER_DIR); checkpoints live in "
                         "<ledger>/checkpoints")
    rp.add_argument("--json", action="store_true",
                    help="emit the report as JSON")
    rp.add_argument("--output-file", default="")
    rp.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON timeline of the "
                         "replay's phases")

    sn = sub.add_parser(
        "session",
        help="operate digital-twin sessions on a running server: create, "
             "feed events, interrogate, fork what-ifs, close",
        description="Client for the server's resident digital-twin "
                    "sessions (replay/session.py, ARCHITECTURE.md "
                    "section 15): a session is a journaled live "
                    "trajectory the server keeps between requests — "
                    "`create` encodes a cluster once and settles the "
                    "baseline, `events` appends timed events (one "
                    "fsynced journal line per settled step; a SIGKILL'd "
                    "server resumes every open session bit-identically "
                    "on restart), `status`/`list` interrogate between "
                    "events, `fork` runs what-if branches (chaos plans, "
                    "arrival bursts, controller variants) that are "
                    "quarantined with a structured record if they "
                    "raise, time out, or fail the placement audit — "
                    "the mainline is never disturbed — and `close` "
                    "retires the session. All subcommands talk HTTP to "
                    "--server.")
    sn.add_argument("--server", default="http://127.0.0.1:8899",
                    help="base URL of a running simon-tpu server")
    sn_sub = sn.add_subparsers(dest="session_command")
    sn_cr = sn_sub.add_parser(
        "create", help="create a session (settles the baseline step)")
    sn_cr.add_argument("--name", default="", help="human-readable label")
    sn_cr.add_argument("--cluster-yaml", default="", metavar="FILE",
                       help="multi-doc k8s YAML sent inline as the t=0 "
                            "cluster (default: the server's own "
                            "--cluster-config snapshot)")
    sn_cr.add_argument("--max-new-nodes", type=int, default=0,
                       help="template-cloned node slots the session may "
                            "scale into")
    sn_cr.add_argument("--node-template", default="", metavar="FILE",
                       help="Node spec YAML the new slots are cloned from")
    sn_cr.add_argument("--controller", action="append", default=[],
                       metavar="NAME[:k=v,...]",
                       help="register a step controller (repeatable), "
                            "same forms as simon-tpu replay")
    sn_ls = sn_sub.add_parser("list", help="list open sessions")
    sn_ls.add_argument("--json", action="store_true")
    sn_st = sn_sub.add_parser(
        "status", help="interrogate one session between events")
    sn_st.add_argument("session", metavar="SESSION_ID")
    sn_st.add_argument("--placements", action="store_true",
                       help="include the full node -> pod-keys map")
    sn_ev = sn_sub.add_parser(
        "events", help="append + settle timed events from a file")
    sn_ev.add_argument("session", metavar="SESSION_ID")
    sn_ev.add_argument("--events", required=True, metavar="FILE",
                       help="YAML/JSON file holding {events: [{t, kind, "
                            "...}]} (the ReplayTrace event vocabulary)")
    sn_fk = sn_sub.add_parser(
        "fork", help="run a what-if branch off the current step")
    sn_fk.add_argument("session", metavar="SESSION_ID")
    sn_fk.add_argument("--events", required=True, metavar="FILE",
                       help="YAML/JSON file holding the branch's "
                            "{events: [...]}")
    sn_fk.add_argument("--name", default="", help="fork label")
    sn_fk.add_argument("--deadline", type=float, default=0.0,
                       help="fork step budget in seconds (past it the "
                            "branch is quarantined E_DEADLINE)")
    sn_fk.add_argument("--controller", action="append", default=[],
                       metavar="NAME[:k=v,...]",
                       help="controller roster for the branch (default: "
                            "the mainline's, state carried over)")
    sn_cl = sn_sub.add_parser("close", help="close a session")
    sn_cl.add_argument("session", metavar="SESSION_ID")

    tr = sub.add_parser(
        "trace",
        help="follow one request through a running server: the black-box "
             "causal timeline for a trace id",
        description="Client for the server's black-box flight recorder "
                    "(telemetry/context.py, ARCHITECTURE.md section 20): "
                    "every HTTP request gets a trace id — client-supplied "
                    "via the X-Simon-Trace-Id header or minted by the "
                    "server and echoed back on the response — and every "
                    "queue transition, coalesced launch, fault-ladder "
                    "rung, journal append, and structured error it "
                    "causes is stamped with that id in a bounded "
                    "in-memory ring. `show` asks GET /api/trace/<id> for "
                    "the reconstructed causal timeline. The ring is "
                    "bounded: old traces age out.")
    tr.add_argument("--server", default="http://127.0.0.1:8899",
                    help="base URL of a running simon-tpu server")
    tr_sub = tr.add_subparsers(dest="trace_command")
    tr_sh = tr_sub.add_parser(
        "show", help="print the causal timeline for one trace id")
    tr_sh.add_argument("trace_id", metavar="TRACE_ID",
                       help="trace id (from the X-Simon-Trace-Id response "
                            "header, an access-log line, or a run "
                            "record's trace tag)")
    tr_sh.add_argument("--json", action="store_true",
                       help="emit the raw timeline JSON instead of the "
                            "rendered table")

    tn = sub.add_parser(
        "tune",
        help="scheduler-policy search on the lane axis: Pareto set over "
             "score-plugin weight vectors",
        description="Search the Score-plugin weight space (the "
                    "KubeSchedulerConfiguration v1beta2 weight table) "
                    "over ONE workload, executed as lanes of one AOT "
                    "executable: the traced-weights engine mode turns "
                    "the K weights into a traced [K] input, so W policy "
                    "variants batch as a [W, K] lane matrix with zero "
                    "recompiles across rounds. Each variant is scored "
                    "on (unplaced, cost, disruption) — all minimized, "
                    "disruption measured against the baseline vector's "
                    "placements — and the report is the Pareto set "
                    "under the frontier's dominance rule. "
                    "ARCHITECTURE.md §17.")
    tn.add_argument("--cluster-config", required=True,
                    help="cluster YAML dir (the workload's initial state)")
    tn.add_argument("--apps", default="", metavar="DIR",
                    help="optional workload apps (manifest dir) deployed "
                         "on top of the cluster's own pods")
    tn.add_argument("--mode", choices=("grid", "cem"), default="grid",
                    help="grid: coordinate grid around the baseline "
                         "(deterministic, exhaustive over its grid); "
                         "cem: cross-entropy-style mutation/selection "
                         "rounds (seeded, deterministic)")
    tn.add_argument("--variants", type=int, default=8,
                    help="policy lanes per device round (W)")
    tn.add_argument("--rounds", type=int, default=0,
                    help="cem generations (0 = 4); for grid, a cap on "
                         "the rounds (0 = the whole grid; a capped grid "
                         "reports grid_truncated)")
    tn.add_argument("--seed", type=int, default=0,
                    help="cem sampling seed")
    tn.add_argument("--grid-values", default="", metavar="V,V,...",
                    help="comma list of grid weight values "
                         "(default 0,0.5,1,2,4)")
    tn.add_argument("--elite-frac", type=float, default=0.25,
                    help="cem selection fraction")
    tn.add_argument("--sigma", type=float, default=0.75,
                    help="cem initial mutation scale")
    tn.add_argument("--max-weight", type=float, default=8.0,
                    help="weight-space clip ceiling")
    tn.add_argument("--scheduler-config", default="", metavar="FILE",
                    help="KubeSchedulerConfiguration file: its score "
                         "weights become the search center and the "
                         "disruption baseline; filter disables apply as "
                         "static engine gates")
    tn.add_argument("--json", action="store_true",
                    help="emit the full report (points, Pareto set) as "
                         "JSON")
    tn.add_argument("--output-file", default="")
    tn.add_argument("--ledger-dir", default="",
                    help="run-ledger directory: one RunRecord per tune "
                         "round + a summary event (also honors "
                         "SIMON_LEDGER_DIR)")
    tn.add_argument("--compile-cache-dir", default="",
                    help=_CACHE_HELP)
    tn.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON timeline of the "
                         "search's phases")

    mg = sub.add_parser("migrate", help="plan a defragmentation migration of placed pods")
    mg.add_argument("--cluster-config", required=True, help="cluster YAML dir (with placed pods)")
    mg.add_argument("--output-file", default="")

    lt = sub.add_parser(
        "lint",
        help="run graftlint: repo-specific static trace-safety, "
             "engine-contract, and runtime-discipline analysis "
             "(rules GL1-GL10)",
        description="graftlint: pure-AST static analysis of the scan "
                    "scheduler's cross-layer contracts — xs-leaf "
                    "wiring (GL1), partial-into-scan arity (GL2), dead "
                    "config flags (GL3), trace safety (GL4), compact-"
                    "carry dtype hygiene (GL5) — and the runtime "
                    "layer's disciplines: launch fault-domain wrapping "
                    "(GL6), lock ordering (GL7), error-boundary status "
                    "mapping (GL8), durable-write consolidation (GL9), "
                    "metric-name/doc sync (GL10). Exits 0 on a clean "
                    "tree, 1 on findings. Catalog: ARCHITECTURE.md §7.")
    lt.add_argument(
        "paths", nargs="*", metavar="PATH",
        help="files/dirs to lint, relative to the repo root "
             "(default: the product tree — open_simulator_tpu/, tools/, "
             "__graft_entry__.py)")
    lt.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text", help="finding output format")
    lt.add_argument("--select", default="",
                    help="comma list of rule codes to run (e.g. GL1,GL4); "
                         "default all")
    lt.add_argument("--changed", nargs="?", const="HEAD", default=None,
                    metavar="REF",
                    help="report only findings in files changed vs REF "
                         "(default HEAD) plus untracked files; the "
                         "analysis still resolves against the full tree "
                         "so interprocedural rules stay accurate. Falls "
                         "back to full-tree reporting when git is "
                         "unavailable; exits immediately when nothing "
                         "in scope changed")
    lt.add_argument("--jobs", type=int, default=0,
                    help="parse the lint set across N processes "
                         "(0/1 = serial)")
    lt.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    lt.add_argument("--output-file", default="")

    sub.add_parser("version", help="print version")

    gd = sub.add_parser("gen-doc", help="generate markdown docs for the CLI")
    gd.add_argument("--dir", default="docs/commandline")
    return p


@contextlib.contextmanager
def _trace_capture(path: str):
    """--trace-out: capture exactly this run's spans and write the
    Chrome-trace JSON on the way out (even when the run fails — a failed
    run's timeline is the one you want)."""
    if not path:
        yield
        return
    from open_simulator_tpu.telemetry.spans import RECORDER, export_chrome_trace

    RECORDER.clear()
    try:
        yield
    finally:
        export_chrome_trace(path)
        print(f"chrome trace written to {path}", file=sys.stderr)


def _init_logging() -> None:
    level = os.environ.get("LogLevel", "info").lower()
    logging.basicConfig(
        level={"debug": logging.DEBUG, "info": logging.INFO, "warn": logging.WARNING,
               "error": logging.ERROR}.get(level, logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )


def _runs_main(args) -> int:
    """simon-tpu runs {list, show, diff}: the flight-recorder CLI."""
    import json as _json

    from open_simulator_tpu.telemetry import ledger

    led = ledger.default_ledger()
    if led is None:
        print("error: no run ledger configured (pass --ledger-dir or set "
              "SIMON_LEDGER_DIR)", file=sys.stderr)
        return 1
    if not args.runs_command:
        print("error: pick a subcommand: runs {list, show, diff}",
              file=sys.stderr)
        return 2
    def _warn_corrupt() -> None:
        # every subcommand read the ledger through records(); a nonzero
        # skip count means the regression window silently shrank — say so
        if led.skipped_corrupt:
            print(f"warning: skipped {led.skipped_corrupt} corrupt ledger "
                  f"record(s) in {led.path}", file=sys.stderr)

    try:
        if args.runs_command == "list":
            recs = led.records(surface=args.surface or None,
                               limit=None if args.campaign
                               else (args.limit or None))
            _warn_corrupt()
            if args.campaign:
                recs = [r for r in recs
                        if str((r.get("tags") or {}).get("campaign", ""))
                        .startswith(args.campaign)]
                if args.limit:
                    recs = recs[-args.limit:]
            if args.json:
                print(_json.dumps([ledger.run_summary(r) for r in recs],
                                  indent=2))
            else:
                print(ledger.format_run_list(recs))
            return 0
        if args.runs_command == "show":
            rec = led.find(args.run)
            _warn_corrupt()
            print(_json.dumps(rec, indent=2, sort_keys=True))
            return 0
        # diff
        d = ledger.diff_records(led.find(args.run_a), led.find(args.run_b))
        _warn_corrupt()
        print(_json.dumps(d, indent=2) if args.json else ledger.format_diff(d))
        return 0
    except ledger.LedgerError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


def _emit(text: str, output_file: str) -> None:
    if output_file:
        with open(output_file, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    else:
        print(text)


def _campaign_main(args) -> int:
    """simon-tpu campaign {run, report, audit}: the fleet surface."""
    import json as _json

    from open_simulator_tpu.errors import SimulationError as _SimErr

    if not args.campaign_command:
        print("error: pick a subcommand: campaign {run, report, audit}",
              file=sys.stderr)
        return 2
    try:
        if args.campaign_command == "run":
            from open_simulator_tpu.campaign import (
                CampaignOptions,
                format_report,
                run_campaign,
            )

            report = run_campaign(CampaignOptions(
                fleet=args.fleet,
                apps_dir=args.apps,
                scenario=args.scenario,
                max_clusters=args.max_clusters,
                retries=args.retries,
                resume=args.resume,
                audit=not args.no_audit,
            ))
            _emit(_json.dumps(report, indent=2) if args.json
                  else format_report(report), args.output_file)
            # a poisoned cluster must not fail the fleet: exit 0 as long
            # as SOMETHING completed; 1 only when every cluster failed
            return 0 if report["totals"]["completed"] > 0 else 1
        if args.campaign_command == "report":
            from open_simulator_tpu.campaign import (
                format_report,
                report_from_journal,
                resolve_campaign,
            )

            journal = resolve_campaign(args.campaign)
            report = report_from_journal(journal)
            if journal.done is None:
                report["unfinished"] = True
            _emit(_json.dumps(report, indent=2) if args.json
                  else format_report(report)
                  + ("\n(journal has no done marker — the campaign is "
                     "unfinished; resume it with campaign run --resume "
                     f"{journal.campaign_id})" if journal.done is None
                     else ""), args.output_file)
            return 0
        # audit
        from open_simulator_tpu.campaign import format_audit, run_audit

        rep, info = run_audit(args.cluster)
        _emit(_json.dumps({**info, **rep.to_dict()}, indent=2)
              if args.json else format_audit(rep, name=info["cluster"]),
              args.output_file)
        return 0 if rep.ok else 1
    except (_SimErr, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


def _load_trace_file(path: str) -> dict:
    """Parse a trace/specs file (YAML or JSON — yaml is a superset).
    Malformed YAML is the user's input error: a structured E_SPEC (the
    `error:` exit path), never a parser traceback."""
    import yaml as _yaml

    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = _yaml.safe_load(f)
    except _yaml.YAMLError as e:
        raise SimulationError(
            f"{path} is not valid YAML/JSON: {e}",
            code="E_SPEC", ref="replay_trace", field="trace") from None
    if not isinstance(doc, dict):
        raise SimulationError(
            f"{path} must hold a mapping, got {type(doc).__name__}",
            code="E_SPEC", ref="replay_trace", field="trace")
    return doc


def _replay_main(args) -> int:
    """simon-tpu replay: trace replay or the cost-frontier question."""
    import json as _json

    from open_simulator_tpu.k8s.loader import load_resources_from_directory

    try:
        with _trace_capture(args.trace_out):
            from open_simulator_tpu.replay import (
                ReplayOptions,
                ReplayTrace,
                capacity_frontier,
                controller_from_arg,
                format_frontier,
                format_report,
                parse_specs,
                run_replay,
            )

            cluster = load_resources_from_directory(args.cluster_config)
            trace = ReplayTrace.from_dict(_load_trace_file(args.trace))
            trace.validate()
            if args.frontier:
                # the static mix question over the trace's FULL workload
                # (every arrival batch as an app): which node mixes sit
                # on the (cost, utilization, disruption) frontier?
                from open_simulator_tpu.replay.engine import arrival_apps

                spec_doc = _load_trace_file(args.frontier)
                result = capacity_frontier(
                    cluster, arrival_apps(trace),
                    parse_specs(spec_doc.get("specs")),
                    max_total=spec_doc.get("max_total"),
                    lane_width=args.lane_width, max_mixes=args.max_mixes)
                _emit(_json.dumps(result, indent=2) if args.json
                      else format_frontier(result), args.output_file)
                return 0
            controllers = [controller_from_arg(a) for a in args.controller]
            report = run_replay(cluster, trace, ReplayOptions(
                controllers=controllers, resume=args.resume,
                fast_path=not args.no_fast_path))
            _emit(_json.dumps(report, indent=2) if args.json
                  else format_report(report), args.output_file)
            return 0
    except (SimulationError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


def _tune_main(args) -> int:
    """simon-tpu tune: scheduler-policy search (tune/search.py). Every
    malformed knob or scheduler-config is a structured `error:` exit
    (the same E_SPEC/E_BAD_REQUEST taxonomy the REST surface maps to
    400), never a traceback."""
    import json as _json

    from open_simulator_tpu.k8s.loader import load_resources_from_directory

    body = {"mode": args.mode, "variants": args.variants,
            "rounds": args.rounds, "seed": args.seed,
            "elite_frac": args.elite_frac, "sigma": args.sigma,
            "max_weight": args.max_weight}
    if args.grid_values:
        body["grid_values"] = [v.strip()
                               for v in args.grid_values.split(",")
                               if v.strip()]
    try:
        if args.scheduler_config:
            with open(args.scheduler_config, "r", encoding="utf-8") as f:
                body["scheduler_config"] = f.read()
        with _trace_capture(args.trace_out):
            from open_simulator_tpu.tune import (
                TuneOptions,
                format_tune,
                tune_search,
            )

            opts = TuneOptions.from_body(body)
            cluster = load_resources_from_directory(args.cluster_config)
            apps = []
            if args.apps:
                from open_simulator_tpu.core import AppResource

                apps = [AppResource(
                    name="tune",
                    resources=load_resources_from_directory(args.apps))]
            report = tune_search(cluster, apps, opts)
        _emit(_json.dumps(report, indent=2) if args.json
              else format_tune(report), args.output_file)
        return 0
    except (SimulationError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


def _session_main(args) -> int:
    """simon-tpu session {create, list, status, events, fork, close}:
    the digital-twin client — thin HTTP over the server's /api/session
    surface (sessions are server-resident state; the CLI only asks)."""
    import json as _json
    import urllib.error
    import urllib.request

    base = args.server.rstrip("/")

    def call(method: str, path: str, payload=None):
        data = None if payload is None else _json.dumps(payload).encode()
        req = urllib.request.Request(
            base + path, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                return r.status, _json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return e.code, _json.loads(e.read())
            except _json.JSONDecodeError:
                return e.code, {"error": str(e)}

    if not args.session_command:
        print("error: pick a subcommand: session {create, list, status, "
              "events, fork, close}", file=sys.stderr)
        return 2
    try:
        if args.session_command == "create":
            body = {"name": args.name, "spec": {
                "max_new_nodes": args.max_new_nodes}}
            if args.node_template:
                with open(args.node_template, encoding="utf-8") as f:
                    body["spec"]["node_template"] = f.read()
            if args.cluster_yaml:
                with open(args.cluster_yaml, encoding="utf-8") as f:
                    body["cluster"] = {"yaml": f.read()}
            if args.controller:
                from open_simulator_tpu.replay import controller_from_arg

                body["controllers"] = [controller_from_arg(a).spec_dict()
                                       for a in args.controller]
            status, out = call("POST", "/api/session", body)
        elif args.session_command == "list":
            status, out = call("GET", "/api/session")
            if status == 200 and not args.json:
                rows = out.get("sessions") or []
                print(f"{len(rows)} open session(s) "
                      f"(max resident {out.get('max_resident')})")
                for s in rows:
                    print(f"  {s['session_id']}  steps={s['steps']:<4} "
                          f"placed={s['placed']:<5} pending={s['pending']:<4} "
                          f"{'resident' if s['resident'] else 'on-disk '} "
                          f"digest={s['digest']}  {s.get('name', '')}")
                return 0
        elif args.session_command == "status":
            q = "?placements=1" if args.placements else ""
            status, out = call("GET", f"/api/session/{args.session}{q}")
        elif args.session_command == "events":
            doc = _load_trace_file(args.events)
            status, out = call(
                "POST", f"/api/session/{args.session}/events",
                {"events": doc.get("events")})
        elif args.session_command == "fork":
            doc = _load_trace_file(args.events)
            body = {"events": doc.get("events")}
            if args.name:
                body["name"] = args.name
            if args.deadline > 0:
                body["deadline_s"] = args.deadline
            if args.controller:
                from open_simulator_tpu.replay import controller_from_arg

                body["controllers"] = [controller_from_arg(a).spec_dict()
                                       for a in args.controller]
            status, out = call(
                "POST", f"/api/session/{args.session}/fork", body)
        else:  # close
            status, out = call("DELETE", f"/api/session/{args.session}")
    except SimulationError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except (OSError, urllib.error.URLError) as e:
        print(f"error: cannot reach {base}: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0 if status < 400 else 1


def _trace_main(args) -> int:
    """simon-tpu trace show <id>: render a request's causal timeline."""
    import json as _json
    import urllib.error
    import urllib.request

    if not args.trace_command:
        print("error: pick a subcommand: trace {show}", file=sys.stderr)
        return 2
    base = args.server.rstrip("/")
    from urllib.parse import quote

    req = urllib.request.Request(
        base + "/api/trace/" + quote(args.trace_id, safe=""),
        method="GET")
    try:
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                status, out = r.status, _json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                status, out = e.code, _json.loads(e.read())
            except _json.JSONDecodeError:
                status, out = e.code, {"error": str(e)}
    except (OSError, urllib.error.URLError) as e:
        print(f"error: cannot reach {base}: {e}", file=sys.stderr)
        return 1
    if status >= 400 or args.json:
        print(_json.dumps(out, indent=2, sort_keys=True))
        return 0 if status < 400 else 1
    # rendered timeline: one line per black-box event, relative time
    summary = out.get("summary") or {}
    print(f"trace {out.get('trace_id')}  "
          f"status={summary.get('status')} "
          f"error={summary.get('error_code') or '-'} "
          f"queue_wait_ms={summary.get('queue_wait_ms')} "
          f"launches={summary.get('launches')} "
          f"attempts={summary.get('attempts')} "
          f"journal_appends={summary.get('journal_appends')}")
    rungs = summary.get("rungs") or []
    if rungs:
        print("  rungs: " + ", ".join(
            f"{r.get('fn')}:{r.get('rung')}[{r.get('code')}]"
            for r in rungs))
    for ev in out.get("events") or []:
        ev = dict(ev)
        kind = ev.pop("kind", "?")
        dt = ev.pop("dt_ms", 0.0)
        ev.pop("traces", None)
        detail = " ".join(f"{k}={v}" for k, v in ev.items())
        print(f"  {dt:>10.3f}ms  {kind:<10} {detail}")
    return 0


def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024.0
    return f"{n:.1f}GiB"


def _parse_buckets(metrics_text: str, name: str) -> dict:
    """{fn: sorted [(le_bound, cumulative_count), ...]} parsed from the
    Prometheus exposition — `top` computes launch percentiles
    client-side from the histogram buckets (the server only exports
    count/sum directly)."""
    import re as _re

    pat = _re.compile(r"^" + _re.escape(name)
                      + r"_bucket\{(.*)\}\s+([0-9.eE+-]+|inf)\s*$")
    out: dict = {}
    for ln in metrics_text.splitlines():
        m = pat.match(ln)
        if not m:
            continue
        labels = dict(_re.findall(r'([A-Za-z_][A-Za-z0-9_]*)="([^"]*)"',
                                  m.group(1)))
        le = labels.pop("le", None)
        if le is None:
            continue
        fn = labels.get("fn", "")
        bound = float("inf") if le in ("+Inf", "inf") else float(le)
        out.setdefault(fn, []).append((bound, float(m.group(2))))
    for fn in out:
        out[fn].sort()
    return out


def _bucket_quantile(buckets, q: float):
    """Linear-interpolated quantile from cumulative histogram buckets
    (the standard Prometheus histogram_quantile estimate). None when
    the histogram is empty."""
    if not buckets:
        return None
    total = buckets[-1][1]
    if total <= 0:
        return None
    target = q * total
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in buckets:
        if cum >= target:
            if bound == float("inf"):
                return prev_bound  # the conventional +Inf clamp
            width = bound - prev_bound
            inside = cum - prev_cum
            if inside <= 0:
                return bound
            return prev_bound + width * (target - prev_cum) / inside
        prev_bound, prev_cum = bound, cum
    return prev_bound


def _render_top_frame(base: str, stats: dict, metrics_text: str) -> str:
    """One `simon-tpu top` frame as a string (testable without a tty)."""
    lines = []
    lines.append(
        f"simon-tpu top — {base}   uptime {stats.get('uptime_s', '?')}s   "
        f"requests {stats.get('requests', '?')}  "
        f"simulations {stats.get('simulations', '?')}  "
        f"errors {stats.get('errors', '?')}  "
        f"rss {stats.get('max_rss_mib', '?')}MiB")
    queue = stats.get("queue") or {}
    lines.append("queue     " + (" ".join(
        f"{k}={v}" for k, v in sorted(queue.items())) or "-"))
    feed = stats.get("events_feed") or {}
    bb = stats.get("blackbox") or {}
    lines.append(
        f"feed      subscribers={feed.get('subscribers', 0)} "
        f"published={feed.get('published', 0)} "
        f"dropped={feed.get('dropped', 0)}   "
        f"blackbox {bb.get('events', 0)}/{bb.get('capacity', 0)} "
        f"(dropped={bb.get('dropped', 0)})")
    devmem = stats.get("devmem") or {}
    owners = devmem.get("owners") or {}
    peaks = devmem.get("peaks") or {}
    lines.append("")
    lines.append(f"{'devmem owner':<22}{'bytes':>12}{'peak':>12}")
    for owner in sorted(set(owners) | set(peaks)):
        lines.append(f"  {owner:<20}{_fmt_bytes(owners.get(owner, 0)):>12}"
                     f"{_fmt_bytes(peaks.get(owner, 0)):>12}")
    lines.append(f"  {'TOTAL':<20}{_fmt_bytes(devmem.get('total', 0)):>12}"
                 f"{_fmt_bytes(devmem.get('peak_total', 0)):>12}")
    resident = stats.get("resident_snapshots") or {}
    lines.append(
        f"resident  snapshots={resident.get('resident', 0)}"
        f"/{resident.get('entries', 0)} "
        f"bytes={_fmt_bytes(resident.get('resident_bytes', 0))} "
        f"budget={_fmt_bytes(resident.get('max_resident_bytes', 0))}")
    inflight = devmem.get("inflight") or []
    lines.append("")
    if inflight:
        lines.append("in-flight launches:")
        for row in inflight:
            lines.append(f"  {row.get('fn', '?'):<20} "
                         f"trace={row.get('trace') or '-':<18} "
                         f"age={row.get('age_ms', 0):.0f}ms")
    else:
        lines.append("in-flight launches: none")
    launches = stats.get("launches") or {}
    buckets = _parse_buckets(metrics_text, "simon_launch_seconds")
    lines.append("")
    lines.append(f"{'launch fn':<22}{'count':>8}{'mean':>10}"
                 f"{'p50':>10}{'p90':>10}{'p99':>10}")
    for fn in sorted(set(launches) | set(buckets)):
        row = launches.get(fn) or {}
        bk = buckets.get(fn) or []

        def pct(q):
            v = _bucket_quantile(bk, q)
            return f"{v * 1000.0:.1f}ms" if v is not None else "-"

        lines.append(f"  {fn:<20}{row.get('count', 0):>8}"
                     f"{row.get('mean_ms', 0):>8.1f}ms"
                     f"{pct(0.5):>10}{pct(0.9):>10}{pct(0.99):>10}")
    if not launches and not buckets:
        lines.append("  (no launches yet)")
    return "\n".join(lines)


def _top_main(args) -> int:
    """simon-tpu top: live redraw-in-place operations view (no curses —
    plain ANSI clear+home per frame, one plain frame with --once)."""
    import json as _json
    import time as _time
    import urllib.error
    import urllib.request

    base = args.server.rstrip("/")

    def fetch():
        with urllib.request.urlopen(
                urllib.request.Request(base + "/debug/stats", method="GET"),
                timeout=30) as r:
            stats = _json.loads(r.read())
        with urllib.request.urlopen(
                urllib.request.Request(base + "/metrics", method="GET"),
                timeout=30) as r:
            metrics_text = r.read().decode("utf-8", "replace")
        return stats, metrics_text

    try:
        while True:
            try:
                stats, metrics_text = fetch()
            except (OSError, urllib.error.URLError) as e:
                print(f"error: cannot reach {base}: {e}", file=sys.stderr)
                return 1
            frame = _render_top_frame(base, stats, metrics_text)
            if args.once:
                print(frame)
                return 0
            # ANSI clear + cursor home: redraw in place without curses
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            _time.sleep(max(0.2, float(args.interval)))
    except KeyboardInterrupt:
        return 0


# subcommands that compile and run the engine in this process
_ENGINE_COMMANDS = frozenset({"apply", "explain", "chaos", "migrate",
                              "server", "campaign", "replay", "tune"})


def main(argv=None) -> int:
    _init_logging()
    parser = build_parser()
    args = parser.parse_args(argv)

    if getattr(args, "ledger_dir", ""):
        # flight recorder: stdlib-only configuration, safe before jax loads
        from open_simulator_tpu.telemetry import ledger

        ledger.configure(args.ledger_dir)

    if args.command == "version":
        print(f"simon-tpu version {__version__}")
        return 0

    if args.command in _ENGINE_COMMANDS:
        # one persistent compile cache per process, placed before the
        # first compile (JAX_COMPILATION_CACHE_DIR wins over the flag)
        from open_simulator_tpu.engine.exec_cache import (
            enable_persistent_cache,
        )

        enable_persistent_cache(getattr(args, "compile_cache_dir", ""))

    if args.command == "runs":
        return _runs_main(args)

    if args.command == "campaign":
        return _campaign_main(args)

    if args.command == "replay":
        return _replay_main(args)

    if args.command == "session":
        return _session_main(args)

    if args.command == "trace":
        return _trace_main(args)

    if args.command == "tune":
        return _tune_main(args)

    if args.command == "lint":
        # analysis/ is pure-AST stdlib: linting never imports jax or the
        # code under analysis, so this path stays fast and side-effect-free
        from open_simulator_tpu.analysis import (
            RULE_CODES,
            LintError,
            assert_clean,
            format_json,
            format_rules,
            format_text,
        )
        from open_simulator_tpu.analysis.report import (
            changed_files,
            format_sarif,
        )

        if args.list_rules:
            print(format_rules())
            return 0
        codes = tuple(c.strip() for c in args.select.split(",") if c.strip())
        unknown = [c for c in codes if c not in RULE_CODES]
        if unknown:
            # an unchecked typo here would silently run ZERO rules and
            # report the tree clean — fail loudly instead
            print(f"error: unknown rule code(s): {', '.join(unknown)} "
                  f"(known: {', '.join(RULE_CODES)})", file=sys.stderr)
            return 2
        paths = args.paths or None
        report_paths = None
        if args.changed is not None and not args.paths:
            changed = changed_files(ref=args.changed)
            if changed is not None:
                if not changed:
                    # nothing in scope changed: a clean verdict, NOT a
                    # fall-through to the full default tree
                    print(format_text([]) if args.format == "text"
                          else (format_json([]) if args.format == "json"
                                else format_sarif([])))
                    return 0
                # analyze the FULL tree (interprocedural facts need it),
                # report only findings in the changed files
                report_paths = changed
        t0 = time.perf_counter()
        try:
            assert_clean(paths=paths, codes=codes or None, jobs=args.jobs,
                         report_paths=report_paths)
            findings = []
        except LintError as e:
            findings = e.findings
        except (OSError, SyntaxError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        wall = time.perf_counter() - t0
        from open_simulator_tpu.telemetry import ledger

        ledger.append_event("lint", tags={
            "findings": len(findings),
            "rules": ",".join(codes) if codes else "all",
            "scope": ("changed" if args.changed is not None and not args.paths
                      else ("paths" if args.paths else "full")),
            "files": (len(report_paths) if report_paths is not None
                      else (len(paths) if paths else None)),
        }, wall_s=wall)
        text = (format_json(findings) if args.format == "json"
                else format_sarif(findings) if args.format == "sarif"
                else format_text(findings))
        if args.output_file:
            with open(args.output_file, "w", encoding="utf-8") as f:
                f.write(text + "\n")
        else:
            print(text)
        return 1 if findings else 0

    if args.command == "apply":
        from open_simulator_tpu.apply.applier import Applier, ApplyOptions

        opts = ApplyOptions(
            config_path=args.simon_config,
            default_scheduler_config=args.default_scheduler_config,
            output_file=args.output_file,
            use_greed=args.use_greed,
            interactive=args.interactive,
            extended_resources=[s for s in args.extended_resources.split(",") if s],
            max_new_nodes=args.max_new_nodes,
            sweep_mode=args.sweep_mode,
            resume=args.resume,
        )
        try:
            with _trace_capture(args.trace_out):
                return Applier(opts).run()
        except Exception as e:  # surface config errors as exit-code-1 messages
            # (a SimulationError formats itself as "[CODE] ref.field: ...")
            print(f"error: {e}", file=sys.stderr)
            return 1

    if args.command == "explain":
        import json as _json

        from open_simulator_tpu.telemetry.explain import format_explain, run_explain

        try:
            with _trace_capture(args.trace_out):
                report = run_explain(
                    args.simon_config,
                    default_scheduler_config=args.default_scheduler_config,
                    top_k=args.top_k,
                    pods=args.pod or None,
                    use_greed=args.use_greed,
                )
        except Exception as e:  # config/admission errors -> exit-code-1 message
            print(f"error: {e}", file=sys.stderr)
            return 1
        text = (_json.dumps(report, indent=2) if args.json
                else format_explain(report))
        if args.output_file:
            with open(args.output_file, "w", encoding="utf-8") as f:
                f.write(text + "\n")
        else:
            print(text)
        return 0

    if args.command == "chaos":
        from open_simulator_tpu.k8s.loader import load_resources_from_directory
        from open_simulator_tpu.resilience.chaos import ChaosPlan, FaultEvent, run_chaos

        events = [FaultEvent(kind, target) for kind, target in args.events]
        plan = ChaosPlan(events=events, zone_key=args.zone_key)
        try:
            with _trace_capture(args.trace_out):
                cluster = load_resources_from_directory(args.cluster_config)
                report = run_chaos(cluster, plan)
        except (SimulationError, OSError) as e:
            # OSError: unreadable cluster dir or unwritable --trace-out —
            # a clean "error:" exit like apply/explain, not a traceback
            print(f"error: {e}", file=sys.stderr)
            return 1
        import json as _json

        text = (_json.dumps(report.to_dict(), indent=2) if args.json
                else report.format())
        if args.output_file:
            with open(args.output_file, "w", encoding="utf-8") as f:
                f.write(text + "\n")
        else:
            print(text)
        return 0

    if args.command == "migrate":
        from open_simulator_tpu.apply.migrate import plan_migration, report_migration
        from open_simulator_tpu.k8s.loader import load_resources_from_directory, make_valid_node

        cluster = load_resources_from_directory(args.cluster_config)
        if not cluster.nodes:
            print(f"error: no nodes in {args.cluster_config}", file=sys.stderr)
            return 1
        cluster.nodes = [make_valid_node(n) for n in cluster.nodes]
        plan = plan_migration(cluster)
        text = report_migration(plan)
        if args.output_file:
            with open(args.output_file, "w", encoding="utf-8") as f:
                f.write(text + "\n")
        else:
            print(text)
        return 0

    if args.command == "server":
        from open_simulator_tpu.server.rest import serve

        if args.fault_plan:
            # parse eagerly: a typo'd plan must be a startup error with
            # the structured E_SPEC, not a silently-ignored env string
            from open_simulator_tpu.resilience import faults

            try:
                faults.install_plan(args.fault_plan)
            except SimulationError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
        blackbox_events = None
        if args.blackbox_events:
            # same eager-validation contract as --fault-plan: a typo'd
            # ring size is a structured startup error, not a ring that
            # silently stayed at the default through an incident
            from open_simulator_tpu.telemetry import context

            try:
                blackbox_events = context.configure_ring(
                    args.blackbox_events)
            except SimulationError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
        return serve(
            address=args.address,
            port=args.port,
            cluster_config=args.cluster_config,
            kubeconfig=args.kubeconfig,
            max_body_bytes=args.max_body_mib * 1024 * 1024,
            request_timeout_s=args.request_timeout,
            explain_topk=args.explain_topk,
            compile_cache_dir=args.compile_cache_dir,
            ledger_dir=args.ledger_dir,
            queue_depth=args.queue_depth,
            drain_timeout_s=args.drain_timeout,
            max_sessions=args.max_sessions,
            max_resident_bytes=int(args.max_resident_mib) * 1024 * 1024,
            workers=args.workers,
            blackbox_events=blackbox_events,
        )

    if args.command == "top":
        return _top_main(args)

    if args.command == "gen-doc":
        from open_simulator_tpu.cli.gendoc import generate_docs

        generate_docs(build_parser(), args.dir)
        print(f"docs written to {args.dir}")
        return 0

    parser.print_help()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
