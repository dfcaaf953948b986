"""simulate(): the one-call library API.

The analog of the reference's Simulate facade (pkg/simulator/core.go:75-131):
build the cluster, expand workloads, schedule everything, report. The
entire reference pipeline of fake clientset + informers + scheduler
goroutine + channel handshake collapses into: encode -> scan -> decode.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from open_simulator_tpu.encode.snapshot import ClusterSnapshot, EncodeOptions, encode_cluster
from open_simulator_tpu.engine import exec_cache
from open_simulator_tpu.engine.queue import sort_pods_greedy
from open_simulator_tpu.engine.scheduler import make_config, schedule_pods
from open_simulator_tpu.k8s.loader import ClusterResources, make_valid_node
from open_simulator_tpu.k8s.objects import ANNO_GPU_INDEX, Node, Pod
from open_simulator_tpu.models.expand import expand_app_resources, expand_cluster_pods


@dataclass
class AppResource:
    """One app to deploy, in order (reference: core.go:62-65)."""

    name: str
    resources: ClusterResources


@dataclass
class UnscheduledPod:
    pod: Pod
    reason: str


@dataclass
class ScheduledPod:
    pod: Pod
    node_name: str


@dataclass
class NodeStatus:
    node: Node
    pods: List[Pod] = field(default_factory=list)


@dataclass
class SimulateResult:
    """reference: core.go:20-44."""

    unscheduled_pods: List[UnscheduledPod]
    scheduled_pods: List[ScheduledPod]
    node_status: List[NodeStatus]
    elapsed_s: float = 0.0
    snapshot: Optional[ClusterSnapshot] = None
    # WaitForFirstConsumer claim -> PV name chosen at bind (the PreBind
    # PVC.spec.volumeName write the reference's binder would do)
    volume_bindings: Dict[str, str] = field(default_factory=dict)
    # pod key -> GPU device ids (with multiplicity) the engine allocated —
    # the integer truth behind the gpu-index annotation (decode-side view of
    # the Reserve allocation, open-gpu-share.go:147-188)
    gpu_assignments: Dict[str, List[int]] = field(default_factory=dict)
    # telemetry/explain decode surface: the raw per-pod per-op failure
    # counts behind the reason strings, the op vocabulary they index, and
    # (when the engine ran with explain_topk) the top-k candidate tensors
    # with their score-plugin row names
    fail_counts: Optional[np.ndarray] = field(default=None, repr=False)
    op_names: List[str] = field(default_factory=list)
    n_active_nodes: int = 0
    topk_node: Optional[np.ndarray] = field(default=None, repr=False)
    topk_score: Optional[np.ndarray] = field(default=None, repr=False)
    topk_parts: Optional[np.ndarray] = field(default=None, repr=False)
    score_part_names: List[str] = field(default_factory=list)
    # keys of pods deleted as preemption victims (structured marker —
    # explain must not infer this from the reason string's wording)
    preempted_pod_keys: List[str] = field(default_factory=list)
    # wave-scheduling decode (engine/waves.py): per-pod wave id in
    # sequence order and whether the pod was placed through a batched
    # wave or the fallback scan; None when the run had no wave plan
    # (waves off, preemption columns, or nothing provably independent)
    wave_id: Optional[np.ndarray] = field(default=None, repr=False)
    wave_batched: Optional[np.ndarray] = field(default=None, repr=False)

    def placements(self) -> Dict[str, str]:
        return {sp.pod.key: sp.node_name for sp in self.scheduled_pods}


def format_failure_reason(counts: np.ndarray, op_names: List[str], n_active: int) -> str:
    """Reproduce the scheduler's diagnostic line
    ('0/4 nodes are available: 3 Insufficient cpu, 1 node(s) had taint ...')."""
    parts = [
        f"{int(c)} {op_names[i]}"
        for i, c in enumerate(counts)
        if int(c) > 0 and i < len(op_names)
    ]
    return f"0/{n_active} nodes are available: " + ", ".join(parts) + "."


def decode_result(
    snapshot: ClusterSnapshot,
    node_assign: np.ndarray,
    fail_counts: np.ndarray,
    active: np.ndarray,
    elapsed_s: float = 0.0,
    gpu_pick: Optional[np.ndarray] = None,
    preempted_by: Optional[Dict[int, int]] = None,
    vol_pick: Optional[np.ndarray] = None,
    extra_op_names: Optional[List[str]] = None,
    topk_node: Optional[np.ndarray] = None,
    topk_score: Optional[np.ndarray] = None,
    topk_parts: Optional[np.ndarray] = None,
    score_part_names: Optional[List[str]] = None,
) -> SimulateResult:
    op_names = snapshot.op_names + list(extra_op_names or [])
    n_active = int(np.sum(active))
    scheduled: List[ScheduledPod] = []
    unscheduled: List[UnscheduledPod] = []
    pods_by_node: Dict[int, List[Pod]] = {}
    volume_bindings: Dict[str, str] = {}
    gpu_assignments: Dict[str, List[int]] = {}
    preempted_keys: List[str] = []
    forced = snapshot.arrays.forced_node
    for i, pod in enumerate(snapshot.pods):
        ni = int(node_assign[i])
        if ni >= 0:
            if vol_pick is not None and i < len(snapshot.wfc_claim_keys):
                # claim -> PV binding the engine's Reserve chose (PreBind
                # would write PVC.spec.volumeName)
                for j, claim_key in enumerate(snapshot.wfc_claim_keys[i]):
                    if j < vol_pick.shape[1] and int(vol_pick[i, j]) >= 0:
                        volume_bindings[claim_key] = (
                            snapshot.pv_names[int(vol_pick[i, j])])
            if gpu_pick is not None and pod.gpu_request()[0] > 0:
                devs_int: List[int] = []
                for d in np.nonzero(gpu_pick[i])[0]:
                    devs_int += [int(d)] * int(gpu_pick[i][d])
                if devs_int:
                    gpu_assignments[pod.key] = devs_int
                if bool(snapshot.arrays.gpu_has_forced[i]):
                    # user-pinned gpu-index is honored verbatim (the check
                    # is encode-time truth, NOT the annotation dict — decode
                    # itself writes that annotation, and repeated decodes of
                    # the same snapshot must not treat it as a pin)
                    pass
                else:
                    # gpu-index assignment annotation, as the reference's
                    # Reserve writes back (open-gpu-share.go:147-188);
                    # counts > 1 repeat the device id ("0-0-1"), matching
                    # the two-pointer's candDevIdList order
                    if devs_int:
                        pod.meta.annotations[ANNO_GPU_INDEX] = "-".join(
                            str(d) for d in devs_int)
            scheduled.append(ScheduledPod(pod=pod, node_name=snapshot.node_names[ni]))
            pods_by_node.setdefault(ni, []).append(pod)
        else:
            if ni == -3 and preempted_by and i in preempted_by:
                # victim of DefaultPreemption: deleted to admit the preemptor
                pre = snapshot.pods[preempted_by[i]]
                reason = f'preempted to admit higher-priority pod "{pre.key}"'
                preempted_keys.append(pod.key)
            elif i in snapshot.pre_reasons:
                # unschedulable before any node was considered (PreFilter
                # UnschedulableAndUnresolvable — missing / Lost / unbound
                # immediate PVCs, volume_binding.go PreFilter)
                reason = snapshot.pre_reasons[i]
            elif int(forced[i]) == -2:  # nodeName pointed at a node that doesn't exist
                reason = f'node "{pod.node_name}" not found'
            else:
                reason = format_failure_reason(fail_counts[i], op_names, n_active)
            unscheduled.append(UnscheduledPod(pod=pod, reason=reason))
    node_status = [
        NodeStatus(node=snapshot.nodes[ni], pods=pods_by_node.get(ni, []))
        for ni in range(snapshot.n_nodes)
        if active[ni]
    ]
    return SimulateResult(
        unscheduled_pods=unscheduled,
        scheduled_pods=scheduled,
        node_status=node_status,
        elapsed_s=elapsed_s,
        snapshot=snapshot,
        volume_bindings=volume_bindings,
        gpu_assignments=gpu_assignments,
        fail_counts=np.asarray(fail_counts),
        op_names=list(op_names),
        n_active_nodes=n_active,
        topk_node=topk_node,
        topk_score=topk_score,
        topk_parts=topk_parts,
        score_part_names=list(score_part_names or []),
        preempted_pod_keys=preempted_keys,
    )


def _resolve_priorities(pods: List[Pod], cluster: ClusterResources, apps: List[AppResource]) -> None:
    """Stamp pod.priority from PriorityClass objects (name -> value, plus a
    globalDefault class), mirroring the admission defaulting the reference
    gets for free from its typed fixtures."""
    classes: Dict[str, int] = {}
    default = 0
    for src in [cluster] + [a.resources for a in apps]:
        for pc in src.priority_classes:
            classes[pc.meta.name] = pc.value
            if pc.global_default:
                default = pc.value
    for p in pods:
        if p.priority:
            continue
        if p.priority_class_name:
            p.priority = classes.get(p.priority_class_name, default)
        else:
            p.priority = default


def with_volume_objects(
    encode_options: Optional[EncodeOptions],
    cluster: ClusterResources,
    apps: List[AppResource],
) -> EncodeOptions:
    """Fill EncodeOptions with the PVC/PV/StorageClass objects from the
    cluster and every app (the reference creates app SCs in the fake
    clientset per app, simulator.go:244-258) so the VolumeBinding /
    VolumeZone ops see the full volume world. Caller-supplied objects on
    the options are kept and extended, not replaced."""
    import dataclasses

    opts = encode_options or EncodeOptions()
    srcs = [cluster] + [a.resources for a in apps]
    return dataclasses.replace(
        opts,
        pvcs=list(opts.pvcs) + [p for s in srcs for p in s.pvcs],
        pvs=list(opts.pvs) + [p for s in srcs for p in s.pvs],
        storage_classes=(list(opts.storage_classes)
                         + [p for s in srcs for p in s.storage_classes]),
        csi_nodes=(list(opts.csi_nodes)
                   + [c for s in srcs for c in getattr(s, "csi_nodes", [])]),
    )


def _priority_sort(pods: List[Pod]) -> List[Pod]:
    """PrioritySort queue plugin (vendored queuesort/priority_sort.go):
    higher priority pops first; stable keeps submission order among equals."""
    return sorted(pods, key=lambda p: -p.priority)


def build_pod_sequence(
    cluster: ClusterResources,
    apps: List[AppResource],
    use_greed: bool = False,
) -> List[Pod]:
    """Cluster pods first (placed + pending), then each app in config order
    (reference: core.go:93-131); each scheduling batch is priority-ordered
    like the activeQ. --use-greed additionally sorts each app's pods by
    descending dominant share (the reference parses but never wires this
    flag; here it works)."""
    nodes = cluster.nodes
    pods = expand_cluster_pods(cluster)
    totals: Dict[str, int] = {}
    for n in nodes:
        for r, v in n.allocatable.items():
            totals[r] = totals.get(r, 0) + v
    all_batches = [pods]
    for app in apps:
        app_pods = expand_app_resources(app.resources, nodes, app.name)
        if use_greed:
            app_pods = sort_pods_greedy(app_pods, totals)
        all_batches.append(app_pods)
    out: List[Pod] = []
    for batch in all_batches:
        _resolve_priorities(batch, cluster, apps)
        out.extend(_priority_sort(batch))
    return out


def simulate(
    cluster: ClusterResources,
    apps: List[AppResource],
    use_greed: bool = False,
    encode_options: Optional[EncodeOptions] = None,
    config_overrides: Optional[Dict] = None,
    preemption: bool = True,
    validate: bool = True,
) -> SimulateResult:
    """Run one full simulation on the default device (TPU when present).

    preemption=True enables the DefaultPreemption PostFilter pass (a no-op
    unless some pod carries a nonzero priority, so the default costs nothing
    on priority-free clusters — the reference's own fixtures are such).

    validate=True runs the resilience admission pass first, so malformed
    specs raise a structured SimulationError taxonomy (code + object ref +
    hint) instead of a traceback from deep inside encode."""
    from open_simulator_tpu import telemetry
    from open_simulator_tpu.telemetry import ledger
    from open_simulator_tpu.telemetry.spans import span

    t0 = time.perf_counter()
    config_overrides = dict(config_overrides or {})
    preemption = preemption and not config_overrides.pop("_disable_preemption", False)
    # flight recorder: one RunRecord per simulate() call when a ledger is
    # configured (no-op otherwise; entry points name the surface via
    # ledger.surface_override)
    with ledger.run_capture("simulate") as lcap, span("simulate"):
        nodes = [make_valid_node(n) for n in cluster.nodes]
        cluster = _with_nodes(cluster, nodes)
        if validate:
            from open_simulator_tpu.resilience.admission import admit

            with span("admit"):
                admit(cluster, apps)
        with span("expand"):
            pods = build_pod_sequence(cluster, apps, use_greed=use_greed)
        encode_options = with_volume_objects(encode_options, cluster, apps)
        snapshot = encode_cluster(nodes, pods, encode_options)
        cfg = make_config(snapshot, **config_overrides)
        with span("transfer"):
            # bucketed padding: snapshots in the same shape bucket present
            # ONE shape to XLA, so consecutive simulate() calls on slightly
            # different clusters reuse the compiled scan (exec_cache.py)
            arrs, _, n_pods = exec_cache.bucketed_device_arrays(snapshot.arrays)
        # wave plan: provably carry-independent pod runs execute batched
        # (engine/waves.py); None leaves the compiled scan untouched
        from open_simulator_tpu.engine.waves import waves_for

        wave_plan = waves_for(snapshot.arrays, cfg,
                              n_pods_total=int(arrs.req.shape[0]))
        lcap.set_config(cfg, snapshot=snapshot, arrs=arrs)
        active_np = np.asarray(snapshot.arrays.active)
        preempted_by: Optional[Dict[int, int]] = None
        # schedule_phase counts compile-miss vs cache-hit off the jit-cache
        # delta and stamps a nested "compile" span on a miss
        import jax as _jax

        from open_simulator_tpu.resilience import faults

        def _wave_scan(launch_with_plan):
            """The shared waves -> scan rung (faults.run_wave_launch),
            mutating the enclosing wave_plan so later preemption passes
            and the wave decode below see the degraded mode."""
            nonlocal wave_plan
            out, wave_plan = faults.run_wave_launch(
                "schedule_pods", launch_with_plan, wave_plan)
            return out

        with telemetry.schedule_phase(schedule_pods):
            if preemption:
                from open_simulator_tpu.engine.preemption import run_with_preemption

                pdbs = list(cluster.pdbs) + [p for a in apps for p in a.resources.pdbs]

                def schedule_fn(disabled, nominated):
                    # victim/nomination columns are built against the real
                    # pod axis; pad to the bucket, slice the outputs back.
                    # Waves only on the column-free first pass: passing the
                    # (ignored) plan alongside preemption columns would key
                    # a second executable for the identical program.
                    # Each pass is one device launch in the fault domain;
                    # the wave-eligible first pass carries the scan rung.
                    # block_until_ready keeps async-dispatch faults
                    # INSIDE the wrapper (they would otherwise surface
                    # at run_with_preemption's host reads, unclassified).
                    def launch(wp):
                        return _jax.block_until_ready(
                            exec_cache.unpad_output(
                                schedule_pods(
                                    arrs, arrs.active, cfg,
                                    disabled=exec_cache.pad_vector(
                                        disabled, arrs.req.shape[0], False),
                                    nominated=exec_cache.pad_vector(
                                        nominated, arrs.req.shape[0], -1),
                                    waves=(wp if disabled is None
                                           and nominated is None else None)),
                                n_pods))

                    if disabled is None and nominated is None:
                        return _wave_scan(launch)
                    return faults.run_launch("schedule_pods",
                                             lambda: launch(None))

                out, pre = run_with_preemption(snapshot, active_np, schedule_fn, pdbs)
                preempted_by = pre.preempted_by
                node_assign = np.asarray(out.node)
                fail_counts = np.asarray(out.fail_counts)
            else:
                def scan(wp):
                    # hosting inside the launch: device faults surface at
                    # the blocking np.asarray, and the fault domain must
                    # see them to classify
                    o = exec_cache.unpad_output(
                        schedule_pods(arrs, arrs.active, cfg, waves=wp),
                        n_pods)
                    return o, np.asarray(o.node), np.asarray(o.fail_counts)

                out, node_assign, fail_counts = _wave_scan(scan)
        gpu_pick = np.asarray(out.gpu_pick) if cfg.enable_gpu else None
        elapsed = time.perf_counter() - t0
        with span("decode"):
            result = decode_result(
                snapshot, node_assign, fail_counts, active_np, elapsed, gpu_pick,
                preempted_by=preempted_by,
                vol_pick=np.asarray(out.vol_pick) if cfg.enable_pv_match else None,
                extra_op_names=list(cfg.extension_op_names),
                **explain_decode_kwargs(cfg, out),
            )
            if wave_plan is not None and not preempted_by:
                # per-pod wave decode for the explain surface (preempted
                # reruns fall back to the scan, so no plan applies there)
                wid, wbat = wave_plan.pod_waves()
                result.wave_id = wid[:n_pods]
                result.wave_batched = wbat[:n_pods]
        lcap.set_result(result)
    _record_simulation(telemetry, result)
    return result


def explain_decode_kwargs(cfg, out) -> Dict:
    """The explain-surface decode kwargs (top-k tensors + part names),
    shared by simulate() and Simulator._run; {} when explain_topk is off."""
    if not cfg.explain_topk:
        return {}
    from open_simulator_tpu.engine.scheduler import score_part_names

    return dict(
        topk_node=np.asarray(out.topk_node),
        topk_score=np.asarray(out.topk_score),
        topk_parts=np.asarray(out.topk_parts),
        score_part_names=list(score_part_names(cfg)),
    )


def _record_simulation(telemetry, result: SimulateResult) -> None:
    """Post-decode counters: one simulate() call's scheduling outcomes."""
    telemetry.counter(
        "simon_simulations_total", "completed simulate() calls").inc()
    telemetry.counter(
        "simon_pods_scheduled_total",
        "pods placed across all simulations").inc(len(result.scheduled_pods))
    telemetry.counter(
        "simon_pods_unscheduled_total",
        "pods left unschedulable across all simulations").inc(
        len(result.unscheduled_pods))


def _with_nodes(cluster: ClusterResources, nodes: List[Node]) -> ClusterResources:
    import copy

    out = copy.copy(cluster)
    out.nodes = nodes
    return out
