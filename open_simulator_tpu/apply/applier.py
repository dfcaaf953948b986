"""The capacity planner ("simon apply").

Reference behavior (pkg/apply/apply.go:60-258): load Simon config, build
cluster + app list + newNode template, then loop { simulate; if
unscheduled pods remain, ask the user to add N nodes and re-simulate from
scratch }. Finally check occupancy thresholds and print reports.

TPU-first inversion: by default the add-node loop IS the batch axis — a
vmapped sweep over candidate counts answers "minimum nodes to add" in one
device program (parallel/sweep.py). Interactive mode is kept for parity
(--interactive), and even there each human guess is answered from the
already-computed sweep when possible.

Env knobs (reference: satisfyResourceSetting, apply.go:614-681):
  MaxCPU     max average cluster CPU occupancy %, default 100
  MaxMemory  max average cluster memory occupancy %, default 100
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from open_simulator_tpu.api.v1alpha1 import ConfigError, SimonConfig, load_config
from open_simulator_tpu.core import (
    AppResource,
    SimulateResult,
    build_pod_sequence,
    decode_result,
)
from open_simulator_tpu.encode.snapshot import EncodeOptions, encode_cluster
from open_simulator_tpu.engine.scheduler import make_config
from open_simulator_tpu.k8s.loader import (
    ClusterResources,
    load_resources_from_directory,
    make_valid_node,
)
from open_simulator_tpu.k8s.objects import Node
from open_simulator_tpu.parallel.sweep import (
    SweepThresholds,
    capacity_bisect,
    capacity_sweep,
)
from open_simulator_tpu.report.tables import full_report


@dataclass
class ApplyOptions:
    """CLI surface parity (cmd/apply/apply.go:27-36)."""

    config_path: str = ""
    default_scheduler_config: str = ""   # KubeSchedulerConfiguration file; Score
                                         # enable/disable/weights + pluginConfig
                                         # map onto EngineConfig (engine/sched_config.py)
    output_file: str = ""
    use_greed: bool = False
    interactive: bool = False
    extended_resources: List[str] = field(default_factory=list)
    max_new_nodes: int = 128             # sweep upper bound
    # "bisect" (default): galloping bisection over the monotone node-count
    # axis, ~log_W(max_new) W-lane rounds reusing one compiled executable.
    # "exhaustive": one lane per candidate count (what interactive mode
    # needs — it decodes arbitrary counts — and what fail_reasons=True
    # API callers keep).
    sweep_mode: str = "bisect"
    # resume a checkpointed bisection after a crash: sweep-id prefix (or
    # "last") of a journal under <ledger>/checkpoints or
    # SIMON_CHECKPOINT_DIR (resilience/lifecycle.py SweepJournal)
    resume: str = ""


class ApplyError(RuntimeError):
    pass


def _load_new_node_template(path: str) -> Optional[Node]:
    if not path:
        return None
    res = (
        load_resources_from_directory(path)
        if os.path.isdir(path)
        else _load_resources_file(path)
    )
    if not res.nodes:
        raise ApplyError(f"newNode path {path} contains no Node object")
    if len(res.nodes) > 1:
        raise ApplyError(f"newNode path {path}: only one node template is supported")
    return make_valid_node(res.nodes[0])


def _load_resources_file(path: str) -> ClusterResources:
    from open_simulator_tpu.k8s.loader import demux_object, parse_yaml_documents

    res = ClusterResources()
    with open(path, "r", encoding="utf-8") as f:
        for doc in parse_yaml_documents(f.read()):
            demux_object(doc, res)
    return res


def build_cluster_from_config(config: SimonConfig, base_dir: str) -> ClusterResources:
    """Cluster inputs for a Simon config (shared by the CLI applier and the
    golden regression tests so both exercise the same assembly path)."""
    cc = config.cluster
    if cc.kube_config:
        # live-cluster seam: kubeConfig points at a RECORDED API DUMP
        # (kubectl get ... -o json), replayed with the reference's
        # CreateClusterResourceFromClient snapshot semantics; an actual
        # kubeconfig fails with the record-a-dump recipe
        from open_simulator_tpu.k8s.cluster_source import (
            ClusterSourceError,
            resolve_cluster_source,
        )

        path = os.path.join(base_dir, cc.kube_config)
        try:
            cluster = resolve_cluster_source(path).load()
        except ClusterSourceError as e:
            raise ApplyError(str(e)) from e
    else:
        path = os.path.join(base_dir, cc.custom_config)
        cluster = load_resources_from_directory(path, strict=False)
    if not cluster.nodes:
        raise ApplyError(f"cluster source {path} contains no nodes")
    cluster.nodes = [make_valid_node(n) for n in cluster.nodes]
    return cluster


def build_apps_from_config(config: SimonConfig, base_dir: str) -> List[AppResource]:
    apps: List[AppResource] = []
    for entry in config.app_list:
        path = os.path.join(base_dir, entry.path)
        if entry.chart:
            from open_simulator_tpu.chart.renderer import process_chart
            from open_simulator_tpu.k8s.loader import demux_object

            res = ClusterResources()
            for doc in process_chart(path):
                demux_object(doc, res)
            apps.append(AppResource(name=entry.name, resources=res))
        else:
            apps.append(
                AppResource(name=entry.name, resources=load_resources_from_directory(path))
            )
    return apps


class Applier:
    def __init__(self, options: ApplyOptions):
        self.opts = options
        if not options.config_path:
            raise ApplyError("--simon-config is required")
        self.config: SimonConfig = load_config(options.config_path)
        self.base_dir = os.path.dirname(os.path.abspath(options.config_path))
        self.config.validate(self.base_dir)
        self._out = sys.stdout
        self._pdbs = []

    # ---- inputs --------------------------------------------------------

    def _build_cluster(self) -> ClusterResources:
        return build_cluster_from_config(self.config, self.base_dir)

    def _build_apps(self) -> List[AppResource]:
        return build_apps_from_config(self.config, self.base_dir)

    def _thresholds(self) -> SweepThresholds:
        def env_pct(name: str) -> float:
            v = os.environ.get(name, "")
            try:
                return float(v) if v else 100.0
            except ValueError:
                return 100.0

        return SweepThresholds(
            max_cpu_pct=env_pct("MaxCPU"),
            max_memory_pct=env_pct("MaxMemory"),
            max_vg_pct=env_pct("MaxVG"),
        )

    # ---- run -----------------------------------------------------------

    def run(self) -> int:
        from open_simulator_tpu.telemetry import ledger

        out_f = None
        if self.opts.output_file:
            out_f = open(self.opts.output_file, "w", encoding="utf-8")
            self._out = out_f
        try:
            # flight recorder: the whole apply run is ONE RunRecord
            # (surface "apply"); the sweep underneath is a nested capture
            # and therefore silent
            with ledger.run_capture("apply") as lcap:
                self._ledger_capture = lcap
                return self._run_inner()
        finally:
            if out_f:
                out_f.close()

    def _say(self, msg: str = "") -> None:
        print(msg, file=self._out)

    def _select_apps(self, apps: List[AppResource]) -> List[AppResource]:
        """Interactive app multi-select (reference: apply.go:172-194 survey
        MultiSelect): comma-separated indices, empty = all."""
        if not apps:
            return apps
        self._say("select apps to deploy (deployment order = config order):")
        for i, app in enumerate(apps):
            self._say(f"  [{i}] {app.name}")
        try:
            ans = input("indices (comma-separated, empty = all) > ").strip()
        except EOFError:
            return apps
        if not ans or ans.lower() == "all":
            return apps
        picked = []
        for tok in ans.split(","):
            tok = tok.strip()
            if tok.isdigit() and int(tok) < len(apps):
                picked.append(apps[int(tok)])
        return picked or apps

    def _run_inner(self) -> int:
        cluster = self._build_cluster()
        apps = self._build_apps()
        if self.opts.interactive:
            apps = self._select_apps(apps)
        template = _load_new_node_template(
            os.path.join(self.base_dir, self.config.new_node) if self.config.new_node else ""
        )

        self._pdbs = list(cluster.pdbs) + [p for a in apps for p in a.resources.pdbs]
        from open_simulator_tpu.core import with_volume_objects
        from open_simulator_tpu.telemetry.spans import span

        with span("expand"):
            pods = build_pod_sequence(cluster, apps, use_greed=self.opts.use_greed)
        max_new = self.opts.max_new_nodes if template is not None else 0
        snapshot = encode_cluster(
            cluster.nodes,
            pods,
            with_volume_objects(
                EncodeOptions(max_new_nodes=max_new, new_node_template=template),
                cluster, apps,
            ),
        )
        overrides = {}
        if self.opts.default_scheduler_config:
            from open_simulator_tpu.engine.sched_config import weight_overrides_from_file

            overrides = weight_overrides_from_file(self.opts.default_scheduler_config)
        self._preemption = not overrides.pop("_disable_preemption", False)
        cfg = make_config(snapshot, **overrides)
        lcap = getattr(self, "_ledger_capture", None)
        if lcap is not None:
            lcap.set_config(cfg, snapshot=snapshot)
            lcap.tag("sweep_mode",
                     "exhaustive" if self.opts.interactive
                     else self.opts.sweep_mode)
        thresholds = self._thresholds()

        if self.opts.resume and (self.opts.interactive
                                 or self.opts.sweep_mode != "bisect"):
            raise ApplyError(
                "--resume replays a checkpointed bisection; it requires "
                "--sweep-mode bisect and is incompatible with --interactive")
        if self.opts.interactive:
            # interactive decodes arbitrary user-chosen counts, so it needs
            # every lane — bisection only probes the bracket
            return self._run_interactive(snapshot, cfg, thresholds, max_new)

        if self.opts.sweep_mode == "bisect":
            # galloping bisection: feasibility is monotone in the count, so
            # ~log_W(max_new) W-lane rounds replace max_new+1 lanes and
            # every round reuses one compiled executable
            plan = capacity_bisect(snapshot, cfg, max_new, thresholds,
                                   resume=self.opts.resume or None)
            if plan.sweep_id:
                # name the journal in the report; after a crash the
                # journal file itself survives and `--resume last`
                # (or the id from a prior log) replays it
                self._say(
                    f"sweep checkpoint: {plan.sweep_id}"
                    + (f" (resumed {plan.resumed_rounds} round(s))"
                       if plan.resumed_rounds else
                       " (crash recovery: simon-tpu apply ... --resume "
                       f"{plan.sweep_id})"))
        else:
            # exhaustive: candidate counts 0..max_new, one lane each
            counts = list(range(max_new + 1))
            plan = capacity_sweep(snapshot, cfg, counts, thresholds)
        if plan.best_count is None:
            self._say(
                f"FAILED: apps do not fit even with {max_new} new node(s) "
                f"(raise --max-new-nodes or adjust the newNode spec)"
            )
            # both modes probe max_new, so the last (largest) lane is the
            # most-capacity view worth reporting
            worst = self._result_for(snapshot, plan, len(plan.counts) - 1, cfg)
            if lcap is not None:
                lcap.set_result(worst)
                lcap.tag("best_count", None)
            self._say(full_report(worst, self.opts.extended_resources))
            return 1

        best_idx = plan.counts.index(plan.best_count)
        result = self._result_for(snapshot, plan, best_idx, cfg)
        if lcap is not None:
            # the decoded best-lane result is the run's answer: its digest
            # is what two identical apply runs must reproduce bit-for-bit
            lcap.set_result(result)
            lcap.tag("best_count", plan.best_count)
        # the reasons/preemption re-run can tie-break differently from the
        # sweep lane (vmap vs single-lane reduction order); keep the summary
        # consistent with the per-pod report below by quoting the decoded
        # result's own count when they diverge
        sweep_sched = int(np.sum(plan.nodes_per_scenario[best_idx] >= 0))
        decoded_sched = len(result.scheduled_pods)
        if decoded_sched != sweep_sched:
            self._say(
                f"note: decoded report schedules {decoded_sched} pod(s) vs the "
                f"sweep lane's {sweep_sched} (the decode re-run applies "
                f"preemption and can resolve exact ties differently from the "
                f"batched sweep); the per-pod report below is authoritative"
            )
        if plan.best_count > 0:
            how = (f"bisected {max_new + 1} candidates in "
                   f"{len(plan.counts)} probes"
                   if self.opts.sweep_mode == "bisect"
                   else f"swept {len(plan.counts)} candidates in one batch")
            self._say(
                f"cluster requires {plan.best_count} new node(s) of the given spec "
                f"to satisfy all apps ({how})"
            )
        else:
            self._say("all apps fit on the existing cluster; no new nodes needed")
        self._say(
            f"occupancy at chosen size: cpu {plan.cpu_occupancy_pct[best_idx]:.1f}% "
            f"mem {plan.mem_occupancy_pct[best_idx]:.1f}% "
            f"(limits: cpu {thresholds.max_cpu_pct:.0f}% mem {thresholds.max_memory_pct:.0f}%)"
        )
        self._say()
        self._say(full_report(result, self.opts.extended_resources))
        return 0

    def _result_for(self, snapshot, plan, idx: int, cfg=None) -> SimulateResult:
        from open_simulator_tpu.parallel.sweep import active_masks_for_counts

        masks = active_masks_for_counts(snapshot, plan.counts)
        import numpy as np

        lane_has_unscheduled = bool(np.any(plan.nodes_per_scenario[idx] < 0))
        if (
            cfg is not None
            and lane_has_unscheduled
            and getattr(self, "_preemption", True)
            and len({p.priority for p in snapshot.pods}) > 1
        ):
            # The chosen lane's placements and reasons should reflect the
            # PostFilter pass. Note a multi-victim preemption can *shrink*
            # the scheduled count relative to the sweep lane (one preemptor
            # in, N victims out), so this decode — not the sweep's
            # best_count message — is the authoritative per-pod report.
            import time

            from open_simulator_tpu.engine import exec_cache
            from open_simulator_tpu.engine.preemption import run_with_preemption
            from open_simulator_tpu.engine.scheduler import schedule_pods

            arrs, n_pods = self._device_arrays_for(snapshot)
            lane_active = np.asarray(masks[idx])
            lane_active_pad = exec_cache.pad_vector(
                lane_active, arrs.alloc.shape[0], False)

            import jax as _jax

            from open_simulator_tpu.resilience import faults

            def schedule_fn(disabled, nominated):
                # block inside the fault domain: async-dispatch faults
                # must classify here, not at the preemption host reads
                return faults.run_launch(
                    "schedule_pods",
                    lambda: _jax.block_until_ready(
                        exec_cache.unpad_output(
                            schedule_pods(
                                arrs, lane_active_pad, cfg,
                                disabled=exec_cache.pad_vector(
                                    disabled, arrs.req.shape[0], False),
                                nominated=exec_cache.pad_vector(
                                    nominated, arrs.req.shape[0], -1)),
                            n_pods)))

            t0 = time.perf_counter()
            out, pre = run_with_preemption(
                snapshot, lane_active, schedule_fn, list(self._pdbs or [])
            )
            return decode_result(
                snapshot,
                np.asarray(out.node),
                np.asarray(out.fail_counts),
                lane_active,
                elapsed_s=time.perf_counter() - t0,
                gpu_pick=np.asarray(out.gpu_pick) if cfg.enable_gpu else None,
                preempted_by=pre.preempted_by,
                vol_pick=np.asarray(out.vol_pick) if cfg.enable_pv_match else None,
            )
        if lane_has_unscheduled and cfg is not None:
            # The sweep lanes run with fail_reasons off (EngineConfig); the
            # reported lane needs real per-op counts, so re-run just this
            # lane with the accounting on — and decode the re-run's own
            # assignments so node picks and fail rows come from one run
            # (vmap vs single-lane reduction order can break exact ties
            # differently).
            from open_simulator_tpu.engine import exec_cache
            from open_simulator_tpu.engine.scheduler import schedule_pods

            import jax as _jax

            from open_simulator_tpu.resilience import faults

            arrs, n_pods = self._device_arrays_for(snapshot)
            out = faults.run_launch(
                "schedule_pods",
                lambda: _jax.block_until_ready(
                    exec_cache.unpad_output(
                        schedule_pods(
                            arrs,
                            exec_cache.pad_vector(
                                np.asarray(masks[idx]), arrs.alloc.shape[0],
                                False),
                            cfg._replace(fail_reasons=True),
                        ),
                        n_pods)))
            return decode_result(
                snapshot,
                np.asarray(out.node),
                np.asarray(out.fail_counts),
                masks[idx],
                gpu_pick=np.asarray(out.gpu_pick) if cfg.enable_gpu else None,
                vol_pick=np.asarray(out.vol_pick) if cfg.enable_pv_match else None,
            )
        return decode_result(
            snapshot,
            plan.nodes_per_scenario[idx],
            plan.fail_counts[idx],
            masks[idx],
            gpu_pick=plan.gpu_pick[idx] if plan.gpu_pick is not None else None,
            vol_pick=plan.vol_pick[idx] if plan.vol_pick is not None else None,
        )

    def _device_arrays_for(self, snapshot):
        """One bucketed host->device upload per snapshot, reused across the
        interactive prompt loop's repeated lane decodes. Returns
        (padded device arrays, real pod count) — the same bucket the sweep
        lanes ran in, so a reasons-on re-run recompiles only for the
        fail_reasons flag, never for a shape."""
        if getattr(self, "_arrs_snapshot", None) is not snapshot:
            from open_simulator_tpu.engine import exec_cache

            arrs, _, n_pods = exec_cache.bucketed_device_arrays(snapshot.arrays)
            self._arrs_cache = (arrs, n_pods)
            self._arrs_snapshot = snapshot
        return self._arrs_cache

    def _run_interactive(self, snapshot, cfg, thresholds, max_new: int) -> int:
        """Parity mode: the reference's prompt loop (apply.go:202-258),
        answered from one precomputed sweep."""
        counts = list(range(max_new + 1))
        plan = capacity_sweep(snapshot, cfg, counts, thresholds)
        current = 0
        while True:
            idx = plan.counts.index(current)
            result = self._result_for(snapshot, plan, idx, cfg)
            n_failed = len(result.unscheduled_pods)
            if n_failed == 0:
                self._say(f"all pods scheduled with {current} new node(s)")
                self._say(full_report(result, self.opts.extended_resources))
                return 0
            self._say(f"{n_failed} pod(s) unschedulable with {current} new node(s)")
            try:
                ans = input("[a]dd N nodes / [r]easons / [q]uit > ").strip()
            except EOFError:
                return 1
            if ans.startswith("r"):
                for up in result.unscheduled_pods:
                    self._say(f"  {up.pod.key}: {up.reason}")
            elif ans.startswith("a"):
                try:
                    n = int(ans.split()[1]) if len(ans.split()) > 1 else 1
                except ValueError:
                    n = 1
                current = min(current + n, max_new)
            elif ans.startswith("q"):
                return 1
