"""REST simulation server.

Endpoint parity with the reference (pkg/server/server.go:148-314):

  GET  /healthz           -> {"status": "healthy"} — LIVENESS: answers 200
                             for as long as the process runs, draining or
                             not (restart me only if this stops answering)
  GET  /readyz            -> READINESS: 200 {"ready": true} while the
                             server admits work; 503 {"ready": false}
                             once draining begins (take me out of the
                             load balancer, do not restart me)
  GET  /test              -> liveness echo
  GET  /metrics           -> Prometheus text exposition of the default
                             telemetry registry (request/scheduling/
                             admission/chaos series + on-demand jax
                             runtime gauges; telemetry/registry.py)
  GET  /api/explain       -> per-pod "why this node / why unschedulable"
                             decode of the LAST simulation this server
                             ran (?pod=ns/name repeatable, ?top_k=N);
                             404 E_NO_SIMULATION before the first one
  GET  /api/runs          -> run-ledger summaries (?surface=, ?limit=N);
                             empty list when no ledger is configured
                             (--ledger-dir / SIMON_LEDGER_DIR)
  GET  /api/runs/<id>     -> one full RunRecord (id prefix / last / prev);
                             404 E_NO_RUN when absent
  GET  /api/trace         -> Chrome-trace (Perfetto) JSON of the LAST
                             POST request's span tree — the server-side
                             mirror of the CLI --trace-out flag (the
                             window comes from the black-box ring's
                             newest request event, so concurrent
                             --workers N never clobber each other)
  GET  /api/trace/<id>    -> the causal timeline of ONE request by its
                             trace id (accepted inbound via the
                             X-Simon-Trace-Id header, or minted and
                             echoed back on every response): queue
                             admission + wait, coalesced siblings, the
                             launch, fault rungs walked with attempt
                             numbers, journal appends, evictions, and
                             the final status — reconstructed from the
                             always-on black-box event ring
                             (telemetry/context.py, ARCHITECTURE.md §20)
  GET  /debug/executables -> per-executable XLA cost profiles of the AOT
                             cache (flops / bytes accessed / peak-HBM
                             estimate per entry, harvested at compile
                             time)
  POST /api/deploy-apps   -> simulate deploying new apps (+ optional new nodes)
  POST /api/simulate      -> the inference-grade probe (server/serving.py,
                             ARCHITECTURE.md §16): one scheduling lane
                             against a RESIDENT snapshot. A full body
                             encodes once and returns "snapshot_digest";
                             {"base": "<digest>"} + optional {"delta":
                             {add_nodes, remove_nodes, remove_pods,
                              add_apps}} probes it with zero re-encode.
                             Concurrent mask-only probes of one snapshot
                             COALESCE into a single batched launch, each
                             caller getting its own lane back (digests
                             identical to singleton runs; a poisoned
                             lane fails alone)
  POST /api/capacity      -> "how many nodes of this spec must I add?" —
                             the capacity sweep as a service: monotone
                             bisection by default (sweep_mode
                             "exhaustive" opts out), reusing the AOT
                             executable cache across requests in the
                             same shape bucket. Accepts the same
                             "base"/"delta" resident-snapshot vocabulary
                             as /api/simulate; exhaustive-mode lanes
                             coalesce with sibling probes of the same
                             snapshot
  POST /api/scale-apps    -> simulate re-scaling existing workloads (their
                             current pods are removed first — the re-rollout
                             semantics of removePodsOfApp, server.go:404-444)
  POST /api/chaos         -> fault-injection re-simulation (resilience/chaos):
                             {"cluster": ..., "apps": [...], "plan":
                              {"events": [{"kind": "kill_node", "target": "n0"}],
                               "zone_key": "topology.kubernetes.io/zone"}}
  POST /api/campaign      -> fault-isolated fleet campaign over recorded
                             dumps on the server's filesystem
                             ({"fleet": "<dir|manifest>"} or
                              {"clusters": ["/a.json", ...]}, optional
                              "resume"/"max_clusters"/"scenario");
                             runs through the admission queue with
                             cancellation observed at cluster boundaries,
                             returns the fleet report (campaign/)
  POST /api/session       -> create a resident digital-twin session: a
                             journaled live trajectory events are fed
                             into as the day unfolds (replay/session.py)
  GET  /api/session       -> list open sessions (resident + on-disk)
  GET  /api/session/<id>  -> interrogate a session between events
                             (?placements=1 for the full node->pods map)
  POST /api/session/<id>/events
                          -> append + settle timed events; one fsynced
                             journal line per settled step — a SIGKILL'd
                             server restarts and resumes the session
                             bit-identically
  POST /api/session/<id>/fork
                          -> what-if branches (chaos plans, arrival
                             bursts, controller variants) off the
                             current step, zero new compiles; a fork
                             that raises / times out / fails the
                             placement audit is quarantined with a
                             structured record while the mainline and
                             sibling forks continue
  DELETE /api/session/<id> -> close (journal becomes prunable history)
  POST /api/replay        -> time-stepped trace replay (replay/):
                             {"trace": {"events": [...]}, "controllers":
                              [...], "resume"?, "frontier"?} — the
                             closed loop over the bucketed scan with
                             cancellation observed at STEP boundaries
                             (partial trajectories on deadline) and
                             journal resume; "frontier" switches to the
                             heterogeneous node-mix Pareto question
  POST /api/tune          -> scheduler-policy search (tune/search.py):
                             {"cluster"?, "apps"?, "mode": "grid"|"cem",
                              "variants", "rounds", "weights"?,
                              "scheduler_config"?} — W weight variants
                             run as lanes of ONE executable per round;
                             cancellation observed at ROUND boundaries
                             (partial points on deadline); the response
                             carries every point + the (unplaced, cost,
                             disruption) Pareto set

Survivable serving (resilience/lifecycle.py, ARCHITECTURE.md §11):

* **Admission queue.** POSTs enqueue into a bounded FIFO drained by ONE
  worker thread (the device runs one program at a time — single-flight
  is preserved by construction, not a TryLock). A full queue sheds with
  429 + a `Retry-After` header computed from the queue's EWMA service
  time; the instant busy-503 (E_BUSY) remains only while draining.
* **Deadlines + cooperative cancellation.** Every POST runs under a
  `CancelToken` armed from `--request-timeout` or the request's
  `deadline_s` field (the smaller wins). Past the deadline the handler
  replies 504 with an `E_DEADLINE` structured body — including partial
  results when the worker reaches a cancellation boundary (sweep round,
  chaos event) within the grace window — and the worker STOPS at its
  next boundary instead of orphaning the device. Jobs whose deadline
  lapsed while still queued are skipped, never executed.
* **Graceful drain.** SIGTERM/SIGINT flips `/readyz` to 503, stops
  admitting (new POSTs get 503 E_BUSY), finishes in-flight work up to
  `--drain-timeout` (then cancels it cooperatively), writes a final
  ledger record, and exits. `/healthz` stays 200 throughout — liveness
  and readiness are different questions.

Hardened paths (resilience layer): request bodies above `max_body_bytes`
are rejected 413 before being read; malformed specs surface as
structured error bodies ({"error", "code", "ref", "field", "hint",
"errors": [...]}) from the admission pass instead of 500 tracebacks.

Differences, by design of this environment: the reference watches a live
cluster through a kubeconfig; here the "live cluster" is a YAML snapshot
directory (--cluster-config) and/or an inline `cluster` field in the
request body.

Request bodies (JSON):
  deploy-apps: {"apps": [{"name": "a1", "yaml": "<multi-doc k8s yaml>"}],
                "new_nodes": [<Node object json>, ...] | {"spec_yaml": "...", "count": N}}
  scale-apps:  {"apps": [{"kind": "Deployment", "namespace": "shop",
                          "name": "web-frontend", "replicas": 10}]}
"""

from __future__ import annotations

import json
import logging
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

import yaml

from open_simulator_tpu import telemetry
from open_simulator_tpu.core import AppResource, SimulateResult, simulate
from open_simulator_tpu.errors import SimulationError
from open_simulator_tpu.resilience import lifecycle
from open_simulator_tpu.server import serving
from open_simulator_tpu.k8s.loader import (
    ClusterResources,
    demux_object,
    load_resources_from_directory,
    make_valid_node,
    new_fake_nodes,
    parse_yaml_documents,
)
from open_simulator_tpu.k8s.objects import LABEL_APP_NAME, Node


DEFAULT_MAX_BODY_BYTES = 8 * 1024 * 1024
DEFAULT_REQUEST_TIMEOUT_S = 300.0
DEFAULT_QUEUE_DEPTH = 8
DEFAULT_DRAIN_TIMEOUT_S = 30.0
DEFAULT_MAX_SESSIONS = 8
DEFAULT_WORKERS = 1
# after cancelling a timed-out job's token, how long the handler waits
# for the worker to reach a cancellation boundary and surface partial
# results before replying with a bare E_DEADLINE body
CANCEL_GRACE_S = 0.25

# access log (satellite of the telemetry PR): one debug line per request
# with method, path, status, duration — silent by default, switched on
# with LogLevel=debug like every other logger in the CLI
access_log = logging.getLogger("simon-tpu.http")

# request-metric path label vocabulary (unknown paths collapse to "other"
# so a scanner can't inflate the label cardinality)
_KNOWN_PATHS = frozenset({
    "/healthz", "/readyz", "/test", "/metrics", "/debug/stats",
    "/debug/profile", "/debug/executables",
    "/api/explain", "/api/deploy-apps", "/api/scale-apps", "/api/chaos",
    "/api/capacity", "/api/simulate", "/api/campaign", "/api/replay",
    "/api/runs", "/api/trace", "/api/session", "/api/tune",
    "/api/events",
})


def _http_metrics():
    """Get-or-create the request metric families (module import order must
    not matter, so handles are resolved at call time)."""
    return (
        telemetry.counter(
            "simon_http_requests_total",
            "REST requests served, by method/path/status",
            labelnames=("method", "path", "status")),
        telemetry.histogram(
            "simon_http_request_seconds",
            "REST request wall time (includes simulation time)",
            labelnames=("path",)),
        telemetry.gauge(
            "simon_http_in_flight", "REST requests currently being handled"),
    )


DEFAULT_EXPLAIN_TOPK = 3

# /api/capacity guardrail: padded new-node slots a single request may ask
# encode to materialize (the exhaustive mode also turns this into lanes)
MAX_CAPACITY_NEW_NODES = 4096

# route-table placeholder for the serving endpoints _do_post dispatches
# itself (never called; only marks the path as known, not a 404)
_SERVING_ROUTE = object()


class SimulationServer:
    def __init__(self, cluster_config: str = "", kubeconfig: str = "",
                 max_body_bytes: int = DEFAULT_MAX_BODY_BYTES,
                 request_timeout_s: float = DEFAULT_REQUEST_TIMEOUT_S,
                 explain_topk: int = DEFAULT_EXPLAIN_TOPK,
                 compile_cache_dir: str = "", ledger_dir: str = "",
                 queue_depth: int = DEFAULT_QUEUE_DEPTH,
                 drain_timeout_s: float = DEFAULT_DRAIN_TIMEOUT_S,
                 max_sessions: int = DEFAULT_MAX_SESSIONS,
                 max_resident_bytes: int = serving.DEFAULT_MAX_RESIDENT_BYTES,
                 workers: int = DEFAULT_WORKERS,
                 blackbox_events: Optional[int] = None):
        from open_simulator_tpu.telemetry import context

        # flight-recorder capacity (--blackbox-events / the environment);
        # eager-validated E_SPEC — a typo fails startup, not an incident
        context.configure_ring(blackbox_events)
        self.cluster_config = cluster_config
        # recorded API dump standing in for the reference's 10 live
        # informers (pkg/server/server.go:97-137; no cluster access here)
        self.kubeconfig = kubeconfig
        self.max_body_bytes = int(max_body_bytes)
        self.request_timeout_s = float(request_timeout_s)
        # candidates recorded per pod during serving simulations so
        # GET /api/explain can break scores down without re-running;
        # 0 disables the recording (and the explain candidate lists)
        self.explain_topk = max(0, int(explain_topk))
        self.drain_timeout_s = float(drain_timeout_s)
        # bounded admission queue drained by a small worker pool (1 by
        # default — the single-flight front end, resilience/lifecycle.py;
        # --workers N lets coalesced serving batches and long singleton
        # jobs interleave) — POSTs wait in line instead of bouncing off a
        # TryLock, full = 429 + Retry-After
        self._queue = lifecycle.AdmissionQueue(depth=queue_depth,
                                               workers=workers)
        # resident snapshot cache (server/serving.py, ARCHITECTURE.md
        # §16): encoded clusters keyed by workload digest, device arrays
        # held under an LRU + byte budget — the POST-once-probe-millions
        # fast path behind /api/simulate and /api/capacity
        self._snapshots = serving.ResidentSnapshotCache(
            max_bytes=max_resident_bytes)
        self._draining = threading.Event()
        self._stats = {"requests": 0, "simulations": 0, "errors": 0,
                       "last_elapsed_s": 0.0, "started_at": time.time()}
        self._profile_dir = ""
        self._profile_lock = threading.Lock()
        # full (untrimmed) result of the last simulation: the explain
        # endpoint decodes it without re-running anything
        self._last_result: Optional[SimulateResult] = None
        # NOTE: the old per-server `_trace_mark` (a single mutable slot
        # every POST overwrote) is retired — span-window markers now ride
        # the black-box "request" events, one per request, so concurrent
        # workers never clobber each other's GET /api/trace window
        if ledger_dir:
            telemetry.ledger.configure(ledger_dir)
        # digital-twin sessions (replay/session.py): resident journaled
        # trajectories bounded by an LRU residency cap. The store scans
        # the checkpoint dir NOW (after the ledger config resolves it) so
        # a restarted/SIGKILL'd server serves every open session again —
        # rehydration itself stays lazy, on first touch.
        from open_simulator_tpu.replay.session import SessionStore

        self._sessions = SessionStore(max_resident=max_sessions)
        self._sessions.scan()
        telemetry.install_runtime_gauges()
        # persistent XLA compilation cache: a restarted server skips
        # cold compiles for every shape bucket it has served before
        from open_simulator_tpu.engine.exec_cache import (
            enable_persistent_cache,
        )

        enable_persistent_cache(compile_cache_dir)

    # ---- lifecycle -----------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def begin_drain(self) -> Dict[str, Any]:
        """Graceful shutdown, phase one (idempotent): flip readiness
        (readyz -> 503), stop admitting work (new POSTs -> 503 E_BUSY),
        finish in-flight jobs up to ``drain_timeout_s`` — past it, cancel
        the running job's token so it stops at its next cooperative
        boundary — then write the final ledger record. The caller (the
        signal handler in ``serve``) shuts the HTTP listener down after
        this returns, so responses for finished work still go out."""
        t0 = time.monotonic()
        if self._draining.is_set():
            return {"draining": True, "already_draining": True}
        self._draining.set()
        self._queue.close()
        clean = self._queue.join(self.drain_timeout_s)
        if not clean:
            # past the budget: cancel the running job (stops at its next
            # cooperative boundary) AND everything still queued (skipped
            # by the worker, resolved with structured 504s) — no fresh
            # device work may start during shutdown
            self._queue.cancel_all("server draining")
            # one short follow-up wait: cooperative cancellation needs the
            # worker to reach its next round/event boundary
            clean = self._queue.join(max(1.0, 0.1 * self.drain_timeout_s))
        # flush the digital twins AFTER the queue is quiet: every settled
        # step is already fsynced in its session journal, so this only
        # records each open session's final status and drops device
        # state — a restarted server rehydrates every one of them
        session_info = self._sessions.drain()
        # release the resident snapshots (host + device): clients re-POST
        # after a restart (the digest is content-addressed, so the same
        # cluster lands on the same digest); gauges drain to 0
        resident = self._snapshots.stats()
        self._snapshots.drop_all()
        from open_simulator_tpu.telemetry import context, ledger, live

        # the black box auto-dumps on drain: the flight recorder's last
        # word lands in run history beside the drain record
        context.BLACKBOX.record("drain", clean=bool(clean))
        context.dump_to_ledger(None, "drain")
        # close every live event-feed stream AFTER the drain event above
        # (subscribers see it as their last event) and BEFORE the ledger
        # row below — the SSE handler threads unblock and return
        live.FEED.close_all()
        run_id = ledger.append_event(
            "server:drain",
            tags={"requests": self._stats["requests"],
                  "simulations": self._stats["simulations"],
                  "errors": self._stats["errors"],
                  "drained_clean": bool(clean),
                  "resident_snapshots": resident["entries"],
                  "resident_bytes": resident["resident_bytes"],
                  "blackbox_dropped": context.BLACKBOX.stats()["dropped"],
                  **session_info,
                  **self._queue.stats()},
            wall_s=time.monotonic() - t0)
        return {"draining": True, "drained_clean": bool(clean),
                "ledger_run_id": run_id, **session_info,
                "wall_s": round(time.monotonic() - t0, 3)}

    # ---- debug surface (the gin pprof analog, server.go:148-152) -------

    def debug_stats(self) -> Dict[str, Any]:
        import resource

        import jax

        from open_simulator_tpu.telemetry import context, live
        from open_simulator_tpu.telemetry.spans import RECORDER

        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            **self._stats,
            "uptime_s": round(time.time() - self._stats["started_at"], 1),
            "max_rss_mib": round(ru.ru_maxrss / 1024.0, 1),
            "cpu_user_s": round(ru.ru_utime, 2),
            "devices": [str(d) for d in jax.devices()],
            "profiling_to": self._profile_dir or None,
            "queue": self._queue.stats(),
            "resident_snapshots": self._snapshots.stats(),
            # observability self-accounting: span-recorder overflow (the
            # chrome-trace window silently lost its oldest records) and
            # the black-box ring's fill/drop state
            "spans_dropped": RECORDER.dropped,
            "blackbox": context.BLACKBOX.stats(),
            # live-ops surface (§21): who holds device bytes (owners +
            # watermarks + in-flight launches), the event feed's fan-out
            # state, and per-fn launch run-time summaries
            "devmem": live.DEVMEM.stats(),
            "events_feed": live.FEED.stats(),
            "launches": live.launch_stats(),
        }

    def toggle_profile(self, trace_dir: str = "") -> Dict[str, Any]:
        import jax

        # serialized: ThreadingHTTPServer handles GETs concurrently, and
        # the jax profiler is a process-wide singleton; state is committed
        # only after the profiler call succeeds so a failure cannot wedge
        # the toggle
        with self._profile_lock:
            if self._profile_dir:
                # clear state BEFORE stopping: if stop_trace raises (disk
                # full etc.) the toggle resets instead of wedging on the
                # stop branch forever
                out, self._profile_dir = self._profile_dir, ""
                jax.profiler.stop_trace()
                return {"profiling": "stopped", "trace_dir": out,
                        "view": "tensorboard --logdir <trace_dir> (profile plugin)"}
            target = trace_dir or tempfile.mkdtemp(prefix="simprof-")
            jax.profiler.start_trace(target)
            self._profile_dir = target
            return {"profiling": "started", "trace_dir": self._profile_dir}

    # ---- cluster snapshot ---------------------------------------------

    def base_cluster(self, inline: Optional[Dict[str, Any]] = None) -> ClusterResources:
        if inline and inline.get("yaml"):
            res = ClusterResources()
            for doc in parse_yaml_documents(inline["yaml"]):
                demux_object(doc, res)
            return res
        if self.kubeconfig:
            from open_simulator_tpu.k8s.cluster_source import resolve_cluster_source

            return resolve_cluster_source(self.kubeconfig).load()
        if self.cluster_config:
            return load_resources_from_directory(self.cluster_config)
        raise SimulationError(
            "no cluster snapshot: start with --cluster-config / --kubeconfig "
            "(a recorded API dump) or pass request.cluster.yaml",
            code="E_BAD_REQUEST", ref="request", field="cluster",
            hint="include {\"cluster\": {\"yaml\": \"<multi-doc k8s yaml>\"}}")

    # ---- handlers ------------------------------------------------------

    def deploy_apps(self, body: Dict[str, Any]) -> Dict[str, Any]:
        self._stats["requests"] += 1
        cluster = self.base_cluster(body.get("cluster"))
        cluster.nodes.extend(self._request_new_nodes(body.get("new_nodes")))
        apps = self._request_apps(body)
        result = self._simulate(cluster, apps)  # runs admission first
        self._stats["simulations"] += 1
        self._stats["last_elapsed_s"] = round(result.elapsed_s, 3)
        self._last_result = result
        return self._response(result, app_only=True)

    def _simulate(self, cluster: ClusterResources,
                  apps: List[AppResource]) -> SimulateResult:
        """All serving simulations record explain_topk candidates, so the
        explain endpoint has score breakdowns for the last result."""
        return simulate(cluster, apps,
                        config_overrides={"explain_topk": self.explain_topk})

    def campaign(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Fleet campaign as a service (POST /api/campaign).

        Body: {"fleet": "<dir|manifest on the server's fs>"} OR
              {"clusters": ["/abs/dump.json", ...]},
              optional "resume": "<campaign id|last>",
              "max_clusters": N, "scenario": "name", "retries": N,
              "audit": true, "deadline_s": 30.

        The request runs on the single-flight admission queue like every
        POST; the campaign observes the deadline/drain CancelToken at
        every CLUSTER boundary, so a 504 carries which clusters settled
        and the journal supports `resume` afterwards."""
        from open_simulator_tpu.campaign import (
            CampaignOptions,
            discover_fleet,
            entries_for_paths,
            run_campaign,
        )

        self._stats["requests"] += 1
        fleet = body.get("fleet") or ""
        clusters = body.get("clusters")
        if not fleet and not clusters:
            raise SimulationError(
                "a campaign needs a fleet: a directory/manifest path or "
                "an explicit cluster list",
                code="E_BAD_REQUEST", ref="request", field="fleet",
                hint='include {"fleet": "/dumps"} or '
                     '{"clusters": ["/a.json", ...]}')
        if clusters is not None and not isinstance(clusters, list):
            raise SimulationError(
                f"clusters must be a list of paths, got "
                f"{type(clusters).__name__}",
                code="E_BAD_REQUEST", ref="request", field="clusters")

        def req_int(field: str, default: int) -> int:
            # the campaign knobs get the same structured treatment as
            # deadline_s: a malformed value is the CLIENT's error (400
            # E_BAD_REQUEST with the field named), never a 500
            raw = body.get(field, default)
            try:
                return max(0, int(raw))
            except (TypeError, ValueError):
                raise SimulationError(
                    f"{field} must be a non-negative integer, got {raw!r}",
                    code="E_BAD_REQUEST", ref="request", field=field,
                    hint=f'e.g. {{"{field}": {default}}}') from None

        entries = (entries_for_paths(clusters) if clusters
                   else discover_fleet(fleet))
        report = run_campaign(CampaignOptions(
            fleet=fleet,
            scenario=str(body.get("scenario") or "replay"),
            max_clusters=req_int("max_clusters", 0),
            retries=req_int("retries", 2),
            resume=str(body.get("resume") or ""),
            audit=bool(body.get("audit", True)),
        ), entries=entries)
        self._stats["simulations"] += report["totals"]["completed"]
        return report

    def tune(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Scheduler-policy search as a service (POST /api/tune).

        Body: {"cluster": {"yaml": ...}?, "apps": [{"name", "yaml"}]?,
               "mode": "grid"|"cem", "variants": W, "rounds": R,
               "seed": N, "grid_values": [..]?, "elite_frac": f?,
               "sigma": f?, "max_weight": f?,
               "weights": {"w_spread": 0.0, ...}?,
               "scheduler_config": "<KubeSchedulerConfiguration yaml>"
                                   | {...}?,
               "deadline_s": 30?}

        Runs on the admission queue like every POST; the search observes
        the deadline/drain CancelToken at every ROUND boundary, so a 504
        carries {rounds_done, variants_done, pareto_so_far} partials.
        Every malformed knob — unknown weight field, negative weight,
        bogus grid value, malformed scheduler_config — is a structured
        400 (E_BAD_REQUEST / E_SPEC), never a 500 (the tune fuzz suite
        holds this). The response carries every evaluated point plus the
        (unplaced, cost, disruption) Pareto set; one executable serves
        all W x R variants (the traced-weights lane axis, §17)."""
        from open_simulator_tpu.tune import TuneOptions, tune_search

        self._stats["requests"] += 1
        opts = TuneOptions.from_body(body)
        cluster = self.base_cluster(body.get("cluster"))
        apps = self._request_apps(body)
        report = tune_search(cluster, apps, opts)
        self._stats["simulations"] += report["rounds_run"]
        return report

    def replay(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Trace replay as a service (POST /api/replay).

        Body: {"cluster": {...}?, "trace": {"events": [...],
               "max_new_nodes": N?, "node_template": "<Node yaml>"?,
               "zone_key": ...?},
               "controllers": [{"kind": "autoscaler", ...}]?,
               "frontier": {"specs": [...], "max_total": N?,
                            "lane_width": N?, "max_mixes": N?}?,
               "resume": "<replay id|last>"?, "deadline_s": 30?}

        Runs on the single-flight admission queue like every POST; the
        replay observes the deadline/drain CancelToken at every STEP
        boundary, so a 504 carries how many steps settled and the
        journal supports `resume` afterwards. Malformed traces
        (missing/bogus event fields, non-monotone timestamps) return
        structured 400s, never 500s. With "frontier", the request
        becomes the static mix question over the trace's full workload
        and returns the (cost, utilization, disruption) Pareto set."""
        from open_simulator_tpu.replay import (
            ReplayOptions,
            ReplayTrace,
            capacity_frontier,
            controller_from_dict,
            parse_specs,
            run_replay,
        )
        from open_simulator_tpu.replay.engine import arrival_apps

        self._stats["requests"] += 1
        cluster = self.base_cluster(body.get("cluster"))
        raw_trace = body.get("trace")
        if raw_trace is None:
            raise SimulationError(
                "replay needs a trace", code="E_BAD_REQUEST",
                ref="request", field="trace",
                hint='include {"trace": {"events": [{"t": 0, "kind": '
                     '"arrive", "app": {...}}]}}')
        trace = ReplayTrace.from_dict(raw_trace)
        trace.validate()
        frontier = body.get("frontier")
        if frontier is not None:
            if not isinstance(frontier, dict):
                raise SimulationError(
                    f"frontier must be an object, got "
                    f"{type(frontier).__name__}", code="E_BAD_REQUEST",
                    ref="request", field="frontier",
                    hint='{"frontier": {"specs": [...]}}')

            def fr_int(field: str, default: int) -> int:
                raw = frontier.get(field, default)
                try:
                    return max(1, int(raw))
                except (TypeError, ValueError):
                    raise SimulationError(
                        f"frontier.{field} must be an integer, got "
                        f"{raw!r}", code="E_BAD_REQUEST", ref="request",
                        field=f"frontier.{field}") from None

            raw_total = frontier.get("max_total")
            try:
                max_total = None if raw_total is None else int(raw_total)
            except (TypeError, ValueError):
                raise SimulationError(
                    f"frontier.max_total must be an integer, got "
                    f"{raw_total!r}", code="E_BAD_REQUEST", ref="request",
                    field="frontier.max_total") from None
            result = capacity_frontier(
                cluster, arrival_apps(trace),
                parse_specs(frontier.get("specs")),
                max_total=max_total,
                lane_width=fr_int("lane_width", 8),
                max_mixes=fr_int("max_mixes", 2048))
            self._stats["simulations"] += 1
            return result
        raw_ctrl = body.get("controllers") or []
        if not isinstance(raw_ctrl, list):
            raise SimulationError(
                f"controllers must be a list, got "
                f"{type(raw_ctrl).__name__}", code="E_BAD_REQUEST",
                ref="request", field="controllers",
                hint='[{"kind": "autoscaler", "scale_step": 2}]')
        controllers = [controller_from_dict(c) for c in raw_ctrl]
        report = run_replay(cluster, trace, ReplayOptions(
            controllers=controllers,
            resume=str(body.get("resume") or "")))
        self._stats["simulations"] += report["totals"]["steps"]
        return report

    # ---- digital-twin sessions (replay/session.py) ---------------------

    def session_create(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST /api/session: create a resident journaled trajectory.

        Body: {"cluster": {...}?, "name"?, "spec": {"max_new_nodes",
               "node_template", "zone_key", "config_overrides"}?,
               "controllers": [{"kind": "autoscaler", ...}]?}

        Encodes the cluster once, settles the baseline step (the
        cluster's own pods), journals it under the checkpoint dir — from
        here the session survives SIGKILL. Runs on the admission queue
        (the baseline settle is device work)."""
        from open_simulator_tpu.replay.session import SessionSpec

        self._stats["requests"] += 1
        cluster = self.base_cluster(body.get("cluster"))
        spec = SessionSpec.from_dict(body.get("spec"))
        raw_ctrl = body.get("controllers") or []
        if not isinstance(raw_ctrl, list):
            raise SimulationError(
                f"controllers must be a list, got "
                f"{type(raw_ctrl).__name__}", code="E_BAD_REQUEST",
                ref="request", field="controllers",
                hint='[{"kind": "autoscaler", "scale_step": 2}]')
        sess = self._sessions.create(cluster, spec=spec,
                                     controllers=raw_ctrl,
                                     name=str(body.get("name") or ""))
        self._stats["simulations"] += 1
        return {"created": True, **sess.status()}

    def session_events(self, sid: str, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST /api/session/<id>/events: append + settle timed events.

        Body: {"events": [{"t", "kind", ...}, ...]} — the ReplayTrace
        event vocabulary. Each event settles through the controller loop
        and lands as one fsynced journal line before the next begins;
        the deadline/drain CancelToken is observed BETWEEN steps, so a
        504 leaves every settled step journaled and the session
        resumable."""
        from open_simulator_tpu.replay.report import trim_row

        self._stats["requests"] += 1
        with self._sessions.hold(sid):
            sess = self._sessions.get(sid)
            rows = sess.apply_events(body.get("events"))
            self._stats["simulations"] += len(rows)
            return {"session_id": sess.session_id,
                    "steps": [trim_row(r) for r in rows],
                    "digest": sess.digest,
                    "status": sess.status()}

    def session_fork(self, sid: str, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST /api/session/<id>/fork: what-if branches off the current
        step. Body: one fork object ({"name"?, "events": [...],
        "controllers"?, "deadline_s"?, "audit"?}) or {"forks": [...]}
        for siblings. A poisoned fork returns a structured quarantine
        record; the mainline and its siblings are untouched."""
        self._stats["requests"] += 1
        raw_forks = body.get("forks")
        if raw_forks is not None and not isinstance(raw_forks, list):
            raise SimulationError(
                f"forks must be a list, got {type(raw_forks).__name__}",
                code="E_BAD_REQUEST", ref="request", field="forks",
                hint='{"forks": [{"events": [...]}, ...]}')
        with self._sessions.hold(sid):
            sess = self._sessions.get(sid)
            mainline = sess.digest
            if raw_forks is None:
                record = sess.fork(body)
                self._stats["simulations"] += record.get("steps", 0)
                return {"session_id": sess.session_id,
                        "mainline_digest": mainline, **record}
            records = [sess.fork(f) for f in raw_forks]
            self._stats["simulations"] += sum(
                r.get("steps", 0) for r in records)
            return {"session_id": sess.session_id,
                    "mainline_digest": mainline, "forks": records}

    def session_status(self, sid: str,
                       query: Dict[str, List[str]]) -> Dict[str, Any]:
        """GET /api/session/<id>: interrogate between events (host-side;
        answered from the last settled row — an evicted session costs no
        device work unless ?placements=1 asks for the full table)."""
        with self._sessions.hold(sid):
            sess = self._sessions.get(sid)
            out = sess.status()
            if (query.get("placements") or ["0"])[0] not in ("", "0",
                                                             "false"):
                out["placements"] = sess.placements()
            return out

    def session_list(self) -> Dict[str, Any]:
        """GET /api/session: every open session (resident or on-disk)."""
        return {"sessions": self._sessions.list(),
                "max_resident": self._sessions.max_resident}

    def session_close(self, sid: str) -> Dict[str, Any]:
        """DELETE /api/session/<id>: journal the close marker (the
        journal becomes prunable history) and release device state."""
        self._stats["requests"] += 1
        return self._sessions.close(sid)

    def chaos(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Fault-injection re-simulation (resilience/chaos.py)."""
        from open_simulator_tpu.resilience.chaos import ChaosPlan, run_chaos

        self._stats["requests"] += 1
        cluster = self.base_cluster(body.get("cluster"))
        apps = self._request_apps(body)
        plan = ChaosPlan.from_dict(body.get("plan") or {})
        report = run_chaos(cluster, plan, apps)
        self._stats["simulations"] += 1
        return report.to_dict()

    def scale_apps(self, body: Dict[str, Any]) -> Dict[str, Any]:
        self._stats["requests"] += 1
        cluster = self.base_cluster(body.get("cluster"))
        scaled: List[Dict[str, Any]] = body.get("apps") or []
        apps: List[AppResource] = []
        for entry in scaled:
            kind = entry.get("kind", "Deployment")
            ns = entry.get("namespace", "default")
            name = entry.get("name", "")
            replicas = entry.get("replicas")
            workload = self._pop_workload(cluster, kind, ns, name)
            if workload is None:
                raise SimulationError(
                    f"workload {kind} {ns}/{name} not found in cluster snapshot",
                    code="E_WORKLOAD_NOT_FOUND",
                    ref=f"{kind.lower()}/{ns}/{name}", field="apps[].name",
                    hint="scale targets must exist in the cluster snapshot")
            # remove pods owned by the workload (re-rollout), then re-add it
            # with the requested replica count as an app to schedule
            self._remove_owned_pods(cluster, workload, kind, ns, name)
            if replicas is not None:
                workload.replicas = int(replicas)
            app_res = ClusterResources()
            app_res.add(workload, kind)
            apps.append(AppResource(name=f"scale-{name}", resources=app_res))
        result = self._simulate(cluster, apps)
        self._stats["simulations"] += 1
        self._stats["last_elapsed_s"] = round(result.elapsed_s, 3)
        self._last_result = result
        return self._response(result, app_only=True)

    def explain(self, query: Dict[str, List[str]]) -> Dict[str, Any]:
        """Explain report over the last simulation (GET /api/explain)."""
        from open_simulator_tpu.telemetry.explain import explain_result

        result = self._last_result
        if result is None:
            raise SimulationError(
                "no simulation has run yet — nothing to explain",
                code="E_NO_SIMULATION", ref="server", field="",
                hint="POST /api/deploy-apps or /api/scale-apps first")
        raw_k = (query.get("top_k") or [""])[0]
        try:
            top_k = int(raw_k) if raw_k else None
        except ValueError:
            raise SimulationError(
                f"top_k must be an integer, got {raw_k!r}",
                code="E_BAD_REQUEST", ref="request", field="top_k",
                hint="GET /api/explain?top_k=3") from None
        pods = query.get("pod") or None
        return explain_result(result, top_k=top_k, pods=pods)

    def runs_index(self, query: Dict[str, List[str]]) -> Dict[str, Any]:
        """Run-ledger summaries (GET /api/runs?surface=&limit=N). An
        unconfigured ledger answers an empty list, not an error — the
        endpoint is how a scraper discovers whether history exists."""
        from open_simulator_tpu.telemetry import ledger

        led = ledger.default_ledger()
        if led is None:
            return {"ledger_dir": None, "runs": []}
        surface = (query.get("surface") or [None])[0]
        raw_limit = (query.get("limit") or [""])[0]
        try:
            limit = int(raw_limit) if raw_limit else None
        except ValueError:
            raise SimulationError(
                f"limit must be an integer, got {raw_limit!r}",
                code="E_BAD_REQUEST", ref="request", field="limit",
                hint="GET /api/runs?limit=20") from None
        runs = [ledger.run_summary(r)
                for r in led.records(surface=surface, limit=limit)]
        # corrupt lines the read skipped: operators watching this
        # endpoint see the ledger rotting instead of a shrinking history
        return {"ledger_dir": led.root, "runs": runs,
                "skipped_corrupt": led.skipped_corrupt}

    def run_record(self, run_id: str) -> Dict[str, Any]:
        """One full RunRecord (GET /api/runs/<id|last|prev>)."""
        from open_simulator_tpu.telemetry import ledger

        led = ledger.default_ledger()
        if led is None:
            raise SimulationError(
                "no run ledger configured", code="E_NO_RUN", ref="server",
                hint="start the server with --ledger-dir or set "
                     "SIMON_LEDGER_DIR")
        try:
            return led.find(run_id)
        except ledger.LedgerError as e:
            raise SimulationError(
                str(e), code="E_NO_RUN", ref=f"run/{run_id}",
                hint="list known runs with GET /api/runs") from None

    # ---- helpers -------------------------------------------------------

    def _request_apps(self, body: Dict[str, Any]) -> List[AppResource]:
        apps = []
        for a in body.get("apps") or []:
            res = ClusterResources()
            for doc in parse_yaml_documents(a.get("yaml", "")):
                demux_object(doc, res)
            apps.append(AppResource(name=a.get("name", "app"), resources=res))
        return apps

    def _request_new_nodes(self, spec) -> List[Node]:
        if not spec:
            return []
        if isinstance(spec, dict):
            template = Node.from_dict(yaml.safe_load(spec["spec_yaml"]))
            return new_fake_nodes(make_valid_node(template), int(spec.get("count", 1)))
        return [make_valid_node(Node.from_dict(d)) for d in spec]

    @staticmethod
    def _pop_workload(cluster: ClusterResources, kind: str, ns: str, name: str):
        attr = ClusterResources._FIELD_BY_KIND.get(kind)
        if attr is None:
            return None
        group = getattr(cluster, attr)
        for i, wl in enumerate(group):
            if wl.meta.namespace == ns and wl.meta.name == name:
                return group.pop(i)
        return None

    @staticmethod
    def _remove_owned_pods(cluster: ClusterResources, workload, kind: str, ns: str, name: str) -> None:
        """Reference walks actual ReplicaSet ownership for Deployments
        (removePodsOfApp, server.go:404-444): it lists the ReplicaSets
        controlled by the Deployment, then removes the pods controlled by
        those ReplicaSets — never by name prefix (Deployment ``web`` must
        not touch ``web-frontend``'s pods)."""
        wl_uid = getattr(workload.meta, "uid", "") if workload is not None else ""

        def controlled_by_workload(m) -> bool:
            if m.namespace != ns:
                return False
            if wl_uid and m.owner_uid:
                return m.owner_uid == wl_uid
            return m.owner_kind == kind and m.owner_name == name

        rs_names = set()
        rs_uids = set()
        if kind == "Deployment":
            for rs in cluster.replica_sets:
                if controlled_by_workload(rs.meta):
                    rs_names.add(rs.meta.name)
                    if rs.meta.uid:
                        rs_uids.add(rs.meta.uid)

        def owned(p) -> bool:
            if p.meta.namespace != ns:
                return False
            if controlled_by_workload(p.meta):
                return True
            # Deployment -> ReplicaSet -> Pod: only via an RS object that is
            # itself controlled by this Deployment (exact identity, no prefix)
            return p.meta.owner_kind == "ReplicaSet" and (
                p.meta.owner_name in rs_names or (p.meta.owner_uid and p.meta.owner_uid in rs_uids)
            )

        cluster.pods = [p for p in cluster.pods if not owned(p)]

    @staticmethod
    def _response(result: SimulateResult, app_only: bool) -> Dict[str, Any]:
        placements: Dict[str, List[str]] = {}
        for sp in result.scheduled_pods:
            if app_only and LABEL_APP_NAME not in sp.pod.meta.labels:
                continue
            placements.setdefault(sp.node_name, []).append(sp.pod.key)
        out = {
            "unscheduled_pods": [
                {"pod": up.pod.key, "reason": up.reason}
                for up in result.unscheduled_pods
                if not app_only or LABEL_APP_NAME in up.pod.meta.labels
            ],
            "placements": placements,
            "elapsed_s": round(result.elapsed_s, 3),
        }
        # claim -> PV choices (the PreBind volumeName writes); always
        # present so the response schema is stable
        out["volume_bindings"] = dict(result.volume_bindings)
        return out


def _make_handler(server: SimulationServer):
    req_total, req_seconds, in_flight = _http_metrics()

    class Handler(BaseHTTPRequestHandler):
        def log_request(self, code="-", size="-"):
            # replaced by the timed access line in _account (duration ms)
            pass

        def log_message(self, fmt, *args):
            # http.server internals (parse errors etc.) -> the access logger
            access_log.debug(fmt, *args)

        def _account(self, status: int) -> None:
            """Access log + request metrics, once per response."""
            from open_simulator_tpu.telemetry import context

            dur_s = time.perf_counter() - getattr(
                self, "_t0", time.perf_counter())
            path = self.path.split("?", 1)[0]
            if path.startswith("/api/runs/"):
                # per-run lookups collapse to one label (id cardinality)
                label = "/api/runs"
            elif path.startswith("/api/session/"):
                label = "/api/session"  # session-id cardinality collapses
            elif path.startswith("/api/trace/"):
                label = "/api/trace"  # trace-id cardinality collapses
            else:
                label = path if path in _KNOWN_PATHS else "other"
            method = self.command or "-"
            req_total.labels(method=method, path=label,
                             status=str(status)).inc()
            req_seconds.labels(path=label).observe(dur_s)
            trace = getattr(self, "_trace", None)
            context.BLACKBOX.record("response", trace=trace, status=status,
                                    method=method, path=label,
                                    dur_ms=round(dur_s * 1000.0, 3))
            access_log.debug("%s %s -> %d %.1fms trace=%s", method, path,
                             status, dur_s * 1000.0, trace or "-")

        def _send_raw(self, code: int, data: bytes, ctype: str,
                      headers: tuple = ()) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            trace = getattr(self, "_trace", None)
            if trace:
                # always echo the request's trace id: the client can GET
                # /api/trace/<id> (or `simon-tpu trace show <id>`) even
                # when it never supplied one
                self.send_header("X-Simon-Trace-Id", trace)
            for name, value in headers:
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(data)
            self._account(code)

        def _send(self, code: int, payload: Dict[str, Any],
                  headers: tuple = ()) -> None:
            if code >= 500 and payload.get("code"):
                # any structured 5xx auto-dumps the black box as a ledger
                # event: the flight recorder's narrative survives in run
                # history even if the ring later wraps
                from open_simulator_tpu.telemetry import context

                context.dump_to_ledger(getattr(self, "_trace", None),
                                       "http_5xx")
            self._send_raw(code, json.dumps(payload).encode(),
                           "application/json", headers=headers)

        def _stream_events(self):
            """GET /api/events: the live-ops stream (ARCHITECTURE.md
            §21) as server-sent events over the black-box feed — a
            bounded replay of the newest ring events (?replay=N,
            default 64), then with ?follow=1 live events as they
            record, until the client disconnects or drain closes every
            subscriber. ?queue=N bounds THIS subscriber's queue
            (clamped to [1, 8192]) — smaller means lossier under
            bursts, which the smoke uses to prove drops never stall. Runs on this connection's own handler thread
            (GETs never enter the admission queue) reading from ITS
            bounded subscription queue — a slow client only ever loses
            its own events, never anyone's requests."""
            from urllib.parse import parse_qs, urlparse

            from open_simulator_tpu.telemetry import context, live

            q = parse_qs(urlparse(self.path).query)
            follow = (q.get("follow") or ["0"])[0] \
                not in ("", "0", "false", "no")
            try:
                replay_n = int((q.get("replay") or ["64"])[0])
            except ValueError:
                replay_n = 64
            try:
                queue_n = int((q.get("queue")
                               or [str(live.DEFAULT_SUBSCRIBER_QUEUE)])[0])
            except ValueError:
                queue_n = live.DEFAULT_SUBSCRIBER_QUEUE
            queue_n = max(1, min(queue_n, 8192))

            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            trace = getattr(self, "_trace", None)
            if trace:
                self.send_header("X-Simon-Trace-Id", trace)
            # no Content-Length: the stream ends when the connection does
            self.send_header("Connection", "close")
            self.end_headers()

            def emit(ev):
                data = dict(ev)
                t = data.pop("t", None)
                if t is not None:
                    data["t_mono"] = round(float(t), 6)
                data["traces"] = list(data.get("traces") or ())
                body = json.dumps(data, default=str)
                self.wfile.write(
                    f"event: {data.get('kind', 'event')}\n"
                    f"data: {body}\n\n".encode())
                self.wfile.flush()

            sub = None
            try:
                for ev in context.BLACKBOX.tail(replay_n):
                    emit(ev)
                if follow:
                    sub = live.FEED.subscribe(maxsize=queue_n)
                    while not sub.closed.is_set():
                        ev = sub.get(timeout=0.5)
                        if ev is None:
                            if sub.closed.is_set():
                                break  # drain closed the feed
                            # idle: a comment line keeps proxies and the
                            # client's read loop alive without an event
                            self.wfile.write(b": keepalive\n\n")
                            self.wfile.flush()
                            continue
                        emit(ev)
                    # events queued before close still belong to this
                    # stream — flush them so the final `drain` record is
                    # the follower's last frame, not a casualty of the
                    # close racing the loop's own closed-check
                    while True:
                        ev = sub.get(timeout=0.05)
                        if ev is None:
                            break
                        emit(ev)
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass  # the client went away — the normal SSE ending
            finally:
                if sub is not None:
                    live.FEED.unsubscribe(sub)
                self._account(200)

        def do_GET(self):
            from open_simulator_tpu.telemetry import context

            self._t0 = time.perf_counter()
            self._trace = context.ensure_trace(
                self.headers.get(context.TRACE_HEADER))
            in_flight.inc()
            try:
                with context.trace_scope(self._trace):
                    self._do_get()
            finally:
                in_flight.dec()

        def _do_get(self):
            if self.path == "/healthz":
                # liveness: 200 while the process runs, even mid-drain —
                # an orchestrator must not SIGKILL a draining server
                # whose in-flight work is still finishing
                self._send(200, {"status": "healthy",
                                 "draining": server.draining})
            elif self.path == "/readyz":
                # readiness: flips to 503 the moment drain begins, BEFORE
                # healthz ever changes — take-out-of-rotation vs restart
                if server.draining:
                    self._send(503, {"ready": False, "draining": True})
                else:
                    self._send(200, {"ready": True})
            elif self.path == "/test":
                self._send(200, {"message": "simon-tpu server is running"})
            elif self.path == "/metrics":
                # Prometheus text exposition of the whole default registry
                # (jax runtime gauges sample inside the render)
                self._send_raw(200, telemetry.render_prometheus().encode(),
                               telemetry.PROMETHEUS_CONTENT_TYPE)
            elif self.path == "/api/explain" or self.path.startswith("/api/explain?"):
                from urllib.parse import parse_qs, urlparse

                q = parse_qs(urlparse(self.path).query)
                try:
                    self._send(200, server.explain(q))
                except SimulationError as e:
                    server._stats["errors"] += 1
                    self._send(_status_for(e), _err_payload(e))
                except Exception as e:  # noqa: BLE001
                    server._stats["errors"] += 1
                    err = _internal(e)
                    self._send(_status_for(err), _err_payload(err))
            elif self.path == "/api/runs" or self.path.startswith("/api/runs?") \
                    or self.path.startswith("/api/runs/"):
                from urllib.parse import parse_qs, unquote, urlparse

                parsed = urlparse(self.path)
                try:
                    if parsed.path.startswith("/api/runs/"):
                        run_id = unquote(parsed.path[len("/api/runs/"):])
                        self._send(200, server.run_record(run_id))
                    else:
                        self._send(200, server.runs_index(parse_qs(parsed.query)))
                except SimulationError as e:
                    server._stats["errors"] += 1
                    self._send(_status_for(e), _err_payload(e))
                except Exception as e:  # noqa: BLE001
                    server._stats["errors"] += 1
                    err = _internal(e)
                    self._send(_status_for(err), _err_payload(err))
            elif self.path == "/api/trace" or self.path.startswith("/api/trace?"):
                # Chrome-trace JSON of the last POST request's span tree —
                # the server-side mirror of --trace-out, without toggling
                # the process-wide jax profiler. The span-window mark rides
                # the black-box "request" event instead of a shared mutable
                # server attribute, so concurrent workers can't clobber
                # each other's window.
                from open_simulator_tpu.telemetry import context
                from open_simulator_tpu.telemetry.spans import RECORDER

                mark_ev = context.BLACKBOX.latest(kind="request",
                                                  with_field="span_mark",
                                                  server_id=id(server))
                if mark_ev is None:
                    # no POST yet: dumping the whole process history would
                    # masquerade as "the last request's timeline"
                    e = SimulationError(
                        "no request has run yet — nothing to trace",
                        code="E_NO_SIMULATION", ref="server",
                        hint="POST a simulation first, then GET /api/trace")
                    self._send(_status_for(e), _err_payload(e))
                else:
                    self._send_raw(
                        200,
                        json.dumps(RECORDER.chrome_trace(
                            since=tuple(mark_ev["span_mark"]))).encode(),
                        "application/json")
            elif self.path.startswith("/api/trace/"):
                # GET /api/trace/<trace_id>: causal timeline for one
                # request, reconstructed from the black-box flight
                # recorder (queue admission -> launch -> fault rungs ->
                # journal appends -> final status)
                from urllib.parse import unquote, urlparse

                from open_simulator_tpu.telemetry import context

                tid = unquote(
                    urlparse(self.path).path[len("/api/trace/"):]).strip("/")
                tl = context.timeline(tid)
                if tl is None:
                    e = SimulationError(
                        f"trace id {tid!r} not found in the flight recorder",
                        code="E_NO_TRACE", ref="server",
                        hint="the black box is a bounded ring — old traces "
                             "age out; re-run with X-Simon-Trace-Id set")
                    self._send(_status_for(e), _err_payload(e))
                else:
                    self._send(200, tl)
            elif self.path == "/api/session" \
                    or self.path.startswith("/api/session?") \
                    or self.path.startswith("/api/session/"):
                from urllib.parse import parse_qs, unquote, urlparse

                parsed = urlparse(self.path)
                try:
                    if parsed.path in ("/api/session", "/api/session/"):
                        self._send(200, server.session_list())
                    else:
                        sid = unquote(
                            parsed.path[len("/api/session/"):]).strip("/")
                        self._send(200, server.session_status(
                            sid, parse_qs(parsed.query)))
                except SimulationError as e:
                    server._stats["errors"] += 1
                    self._send(_status_for(e), _err_payload(e))
                except Exception as e:  # noqa: BLE001
                    server._stats["errors"] += 1
                    err = _internal(e)
                    self._send(_status_for(err), _err_payload(err))
            elif self.path == "/api/events" \
                    or self.path.startswith("/api/events?"):
                self._stream_events()
            elif self.path == "/debug/stats":
                # profiling surface, the gin pprof analog
                # (/root/reference/pkg/server/server.go:148-152): process +
                # request counters and device info instead of Go pprof
                try:
                    self._send(200, server.debug_stats())
                except Exception as e:  # noqa: BLE001
                    err = _internal(e)
                    self._send(_status_for(err), _err_payload(err))
            elif self.path == "/debug/executables":
                # per-executable XLA cost profiles harvested at compile
                # time: flops, bytes accessed, peak HBM, compile seconds
                from open_simulator_tpu.engine.exec_cache import EXEC_CACHE

                try:
                    self._send(200, {
                        "entries": EXEC_CACHE.debug_entries(),
                        "cost_by_fn": EXEC_CACHE.cost_snapshot(),
                    })
                except Exception as e:  # noqa: BLE001
                    err = _internal(e)
                    self._send(_status_for(err), _err_payload(err))
            elif self.path == "/debug/profile" or self.path.startswith("/debug/profile?"):
                # capture a jax profiler trace of the next simulation(s):
                # /debug/profile?dir=/tmp/simprof starts, a second call
                # stops and returns the trace directory (view in
                # TensorBoard's profile plugin)
                from urllib.parse import parse_qs, urlparse

                q = parse_qs(urlparse(self.path).query)
                try:
                    self._send(200, server.toggle_profile((q.get("dir") or [""])[0]))
                except Exception as e:  # noqa: BLE001
                    err = _internal(e)
                    self._send(_status_for(err), _err_payload(err))
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            from open_simulator_tpu.telemetry import context

            self._t0 = time.perf_counter()
            self._trace = context.ensure_trace(
                self.headers.get(context.TRACE_HEADER))
            in_flight.inc()
            try:
                with context.trace_scope(self._trace):
                    self._do_post()
            finally:
                in_flight.dec()

        def do_DELETE(self):
            from open_simulator_tpu.telemetry import context

            self._t0 = time.perf_counter()
            self._trace = context.ensure_trace(
                self.headers.get(context.TRACE_HEADER))
            in_flight.inc()
            try:
                with context.trace_scope(self._trace):
                    self._do_delete()
            finally:
                in_flight.dec()

        def _do_delete(self):
            # DELETE /api/session/<id>: host-side journal close — no
            # device work, so it runs on the handler thread (works even
            # while the worker settles another session's events)
            if not self.path.startswith("/api/session/"):
                self._send(404, {"error": "not found"})
                return
            from urllib.parse import unquote

            sid = unquote(self.path[len("/api/session/"):]).strip("/")
            try:
                self._send(200, server.session_close(sid))
            except SimulationError as e:
                server._stats["errors"] += 1
                self._send(_status_for(e), _err_payload(e))
            except Exception as e:  # noqa: BLE001
                server._stats["errors"] += 1
                err = _internal(e)
                self._send(_status_for(err), _err_payload(err))

        def _resolve_post(self):
            routes = {"/api/deploy-apps": server.deploy_apps,
                      "/api/scale-apps": server.scale_apps,
                      # serving routes are dispatched by _do_post itself
                      # (preparation runs on the handler thread); the
                      # truthy placeholder only marks the path as known
                      "/api/capacity": _SERVING_ROUTE,
                      "/api/simulate": _SERVING_ROUTE,
                      "/api/campaign": server.campaign,
                      "/api/replay": server.replay,
                      "/api/tune": server.tune,
                      "/api/chaos": server.chaos,
                      "/api/session": server.session_create}
            fn = routes.get(self.path)
            if fn is not None:
                return fn
            # session sub-resources carry the id in the path:
            # /api/session/<id>/{events,fork}
            if self.path.startswith("/api/session/"):
                parts = self.path[len("/api/session/"):].strip("/")
                sid, _, verb = parts.partition("/")
                if sid and verb == "events":
                    return lambda body: server.session_events(sid, body)
                if sid and verb == "fork":
                    return lambda body: server.session_fork(sid, body)
            return None

        def _do_post(self):
            handler_fn = self._resolve_post()
            if handler_fn is None:
                self._send(404, {"error": "not found"})
                return
            length = int(self.headers.get("Content-Length", 0))
            if length > server.max_body_bytes:
                # rejected BEFORE the body is read: an oversized payload
                # costs the server a header parse, nothing more
                server._stats["errors"] += 1
                err = SimulationError(
                    f"request body of {length} bytes exceeds the "
                    f"{server.max_body_bytes}-byte cap",
                    code="E_PAYLOAD_TOO_LARGE", ref="request",
                    field="Content-Length",
                    hint="split the request or raise --max-body-mib")
                self._send(_status_for(err), _err_payload(err))
                return
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                err = SimulationError(
                    f"bad json: {e}", code="E_BAD_REQUEST", ref="request",
                    hint="the body must be a JSON object")
                self._send(_status_for(err), _err_payload(err))
                return
            if not isinstance(body, dict):
                # valid JSON but not an object (42, [], "x"): every field
                # read below assumes a dict — reject structurally instead
                # of crashing the handler thread
                err = SimulationError(
                    f"request body must be a JSON object, got "
                    f"{type(body).__name__}",
                    code="E_BAD_REQUEST", ref="request",
                    hint='wrap the payload in an object: {"apps": [...]}')
                self._send(_status_for(err), _err_payload(err))
                return
            # (no draining pre-check here: begin_drain closes the queue,
            # so a draining server rejects at submit with the same 503
            # E_BUSY — one rejection path, not two copies)
            # per-request deadline: --request-timeout, tightened by the
            # client's own deadline_s (a client never widens the server's)
            deadline_s = server.request_timeout_s
            raw_deadline = body.get("deadline_s")
            if raw_deadline is not None:
                try:
                    client_deadline = float(raw_deadline)
                except (TypeError, ValueError):
                    err = SimulationError(
                        f"deadline_s must be a number, got {raw_deadline!r}",
                        code="E_BAD_REQUEST", ref="request",
                        field="deadline_s", hint='e.g. {"deadline_s": 30}')
                    self._send(_status_for(err), _err_payload(err))
                    return
                if client_deadline <= 0:
                    err = SimulationError(
                        f"deadline_s must be positive, got {client_deadline}",
                        code="E_BAD_REQUEST", ref="request",
                        field="deadline_s", hint='e.g. {"deadline_s": 30}')
                    self._send(_status_for(err), _err_payload(err))
                    return
                deadline_s = min(deadline_s, client_deadline)
            token = lifecycle.CancelToken(deadline_s)
            route = self.path
            if route in ("/api/simulate", "/api/capacity"):
                # the inference-grade serving path (server/serving.py):
                # resident snapshots, host-side deltas, coalesced lanes
                self._serving_post(route, body, token, deadline_s)
                return
            job = self._submit(self._work(route, token,
                                          lambda: handler_fn(body)),
                               token, route)
            if job is not None:
                self._await_job(job, token, deadline_s)

        def _work(self, route, token, thunk):
            """Wrap a handler thunk for the queue worker: cancel scope +
            ledger surface + the structured-error-to-status mapping."""

            def work():
                # span-window marker for GET /api/trace rides a black-box
                # "request" event: spans recorded from execution start
                # belong to this request, and concurrent workers each get
                # their own mark instead of clobbering a shared attribute
                from open_simulator_tpu.telemetry import context
                from open_simulator_tpu.telemetry.ledger import (
                    surface_override,
                )
                from open_simulator_tpu.telemetry.spans import RECORDER

                context.BLACKBOX.record("request", method="POST",
                                        path=route, server_id=id(server),
                                        span_mark=RECORDER.mark())
                try:
                    # the run the handler triggers records its ledger
                    # entry under this route's surface name; the cancel
                    # scope lets sweeps/chaos observe the deadline at
                    # their round/event boundaries
                    with lifecycle.cancel_scope(token), \
                            surface_override(f"server:{route}"):
                        return (200, thunk())
                except SimulationError as e:
                    # includes CancelledError: E_DEADLINE/E_CANCELLED map
                    # to 504 and carry partial results in the body
                    server._stats["errors"] += 1
                    return (_status_for(e), _err_payload(e))
                except ValueError as e:
                    server._stats["errors"] += 1
                    return (400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001 — 500 with message
                    server._stats["errors"] += 1
                    return (500, {"error": f"{type(e).__name__}: {e}"})

            return work

        def _serving_post(self, route, body, token, deadline_s):
            """POST /api/simulate | /api/capacity: preparation — body
            validation, delta resolution, host-side encode + cache
            admission — runs on the HANDLER thread, so malformed
            requests are structured 400s BEFORE anything is queued and
            the resident cache is never left half-touched. The prepared
            lanes then queue with a coalesce key: a worker popping one
            takes every queued sibling with the same key into ONE
            batched launch (serving.execute_group answers each member
            under its own token — fault isolation is per lane)."""
            from open_simulator_tpu.telemetry.spans import RECORDER

            if server.draining:
                # non-serving POSTs reject at queue submit; serving POSTs
                # must reject BEFORE preparation, which would otherwise
                # encode/admit into the just-dropped resident cache (and
                # answer 400 for digests the drain released)
                server._stats["errors"] += 1
                e = SimulationError(
                    "server is draining: not accepting new work",
                    code="E_BUSY", ref="server",
                    hint="retry against another replica, or after restart")
                self._send(_status_for(e), _err_payload(e))
                return
            try:
                if route == "/api/simulate":
                    prepared = serving.prepare_simulate(server, body)
                else:
                    prepared = serving.prepare_capacity(
                        server, body, MAX_CAPACITY_NEW_NODES)
            except SimulationError as e:
                server._stats["errors"] += 1
                self._send(_status_for(e), _err_payload(e))
                return
            except Exception as e:  # noqa: BLE001 — preparation bugs are
                # this request's 500; the queue and cache are untouched
                server._stats["errors"] += 1
                err = _internal(e)
                self._send(_status_for(err), _err_payload(err))
                return
            from open_simulator_tpu.telemetry import context

            context.BLACKBOX.record("request", method="POST", path=route,
                                    server_id=id(server),
                                    span_mark=RECORDER.mark())
            if callable(prepared):
                # bisect mode: a multi-round journaled sweep — a classic
                # singleton job with cancellation at round boundaries
                job = self._submit(self._work(route, token, prepared),
                                   token, route)
            else:
                job = self._submit(None, token, route,
                                   group_key=prepared.coalesce_key,
                                   group_fn=serving.execute_group,
                                   payload=prepared)
            if job is not None:
                self._await_job(job, token, deadline_s)

        def _submit(self, fn, token, route, **group_kw):
            """Queue a job, mapping admission rejections to structured
            responses. Returns None when the rejection was already sent."""
            try:
                return server._queue.submit(fn, token=token, label=route,
                                            **group_kw)
            except lifecycle.QueueClosedError as e:
                server._stats["errors"] += 1
                self._send(_status_for(e), _err_payload(e))
                return None
            except lifecycle.QueueFullError as e:
                # load shed: Retry-After from the queue's EWMA service
                # time x backlog, so clients pace themselves instead of
                # hammering a saturated server
                server._stats["errors"] += 1
                self._send(_status_for(e), _err_payload(e),
                           headers=(("Retry-After",
                                     str(int(e.retry_after_s))),))
                return None

        def _await_job(self, job, token, deadline_s):
            if not job.wait(deadline_s):
                # deadline passed (queued or executing): cancel
                # cooperatively, then give the worker one short grace
                # window to reach a boundary and hand back partials
                token.cancel(f"request deadline of {deadline_s:.1f}s "
                             "exceeded")
                job.wait(CANCEL_GRACE_S)
                job.abandon()
                resp = job.result if job.done.is_set() else None
                if resp is not None and resp[0] == 504:
                    # the worker's own CancelledError body (has partials)
                    self._send(*resp)
                    return
                server._stats["errors"] += 1
                err = lifecycle.CancelledError(
                    f"request exceeded the {deadline_s:.1f}s deadline",
                    code="E_DEADLINE", ref="request",
                    hint="shrink the request, raise --request-timeout / "
                         "deadline_s, or resume a checkpointed sweep; the "
                         "worker stops at its next round boundary")
                self._send(_status_for(err), _err_payload(err))
                return
            if job.error is not None:
                # work() catches Exception itself, so this is the escape
                # hatch for BaseException-grade failures — the queue
                # worker survived it; the client still gets an answer
                server._stats["errors"] += 1
                err = _internal(job.error)
                self._send(_status_for(err), _err_payload(err))
                return
            if job.result is None:
                # skipped before execution: the token was cancelled while
                # the job sat in the queue (deadline lapse, or a drain
                # past its budget) — the token knows which story to tell
                server._stats["errors"] += 1
                err = token.error("admission queue; the job was never "
                                  "started")
                self._send(_status_for(err), _err_payload(err))
                return
            self._send(*job.result)

    return Handler


# ONE code->status taxonomy for every route: the table lives in
# serving.py (the group executor needs it without importing the handler)
# — a second hand-maintained copy here had already drifted on E_AUDIT
_err_payload = serving.error_payload
_status_for = serving.status_for


def _internal(e: BaseException) -> SimulationError:
    """Wrap an unclassified handler exception so even server bugs answer
    through STATUS_BY_CODE (E_INTERNAL -> 500) with the structured error
    shape, instead of a hand-built {"error": ...} body (the PR-12 drift
    class, GL8)."""
    return SimulationError(
        f"{type(e).__name__}: {e}", code="E_INTERNAL", ref="server",
        hint="unexpected server-side failure; see the server log")


def serve(address: str = "127.0.0.1", port: int = 8899, cluster_config: str = "",
          kubeconfig: str = "",
          max_body_bytes: int = DEFAULT_MAX_BODY_BYTES,
          request_timeout_s: float = DEFAULT_REQUEST_TIMEOUT_S,
          explain_topk: int = DEFAULT_EXPLAIN_TOPK,
          compile_cache_dir: str = "", ledger_dir: str = "",
          queue_depth: int = DEFAULT_QUEUE_DEPTH,
          drain_timeout_s: float = DEFAULT_DRAIN_TIMEOUT_S,
          max_sessions: int = DEFAULT_MAX_SESSIONS,
          max_resident_bytes: int = serving.DEFAULT_MAX_RESIDENT_BYTES,
          workers: int = DEFAULT_WORKERS,
          blackbox_events: Optional[int] = None) -> int:
    if kubeconfig:
        # validate up front so a real kubeconfig fails fast with the
        # record-a-dump recipe instead of 500s per request
        from open_simulator_tpu.k8s.cluster_source import resolve_cluster_source

        resolve_cluster_source(kubeconfig).load()
    sim_server = SimulationServer(cluster_config=cluster_config, kubeconfig=kubeconfig,
                                  max_body_bytes=max_body_bytes,
                                  request_timeout_s=request_timeout_s,
                                  explain_topk=explain_topk,
                                  compile_cache_dir=compile_cache_dir,
                                  ledger_dir=ledger_dir,
                                  queue_depth=queue_depth,
                                  drain_timeout_s=drain_timeout_s,
                                  max_sessions=max_sessions,
                                  max_resident_bytes=max_resident_bytes,
                                  workers=workers,
                                  blackbox_events=blackbox_events)
    httpd = ThreadingHTTPServer((address, port), _make_handler(sim_server))

    def _drain_and_stop(signame: str) -> None:
        print(f"{signame}: draining (readyz -> 503, finishing in-flight "
              f"work, up to {drain_timeout_s:.0f}s)", flush=True)
        info = sim_server.begin_drain()
        print(f"drain finished (clean={info.get('drained_clean')}); "
              "shutting down", flush=True)
        # brief settle: handler threads waiting on just-finished jobs get
        # their response bytes out before the listener goes away
        time.sleep(0.2)
        httpd.shutdown()

    def _on_signal(signum, frame):
        if sim_server.draining:
            return  # second signal during drain: the drain keeps going
        import signal as _signal

        name = _signal.Signals(signum).name
        # drain off the signal frame: handlers must not block, and
        # httpd.shutdown() deadlocks if called from serve_forever's thread
        threading.Thread(target=_drain_and_stop, args=(name,),
                         daemon=True).start()

    try:
        import signal

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
    except ValueError:
        pass  # embedded serve() off the main thread: no signal hooks
    print(f"simon-tpu server listening on http://{address}:{port}")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        # signal hooks absent (non-main thread): legacy hard stop
        pass
    return 0
