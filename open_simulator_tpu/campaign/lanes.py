"""Fleet lanes (ARCHITECTURE.md §17): same-bucket campaign clusters
execute as lanes of ONE device launch.

The §13 bucket map has always been a *witness* — a 100-cluster fleet in
three shape buckets compiles three executables — but the runner still
paid one device dispatch per cluster through the serial `_run_one`
boundary. The traced-weights refactor generalized `schedule_pods` to a
vmapped per-lane form whose EVERY input can lane-vary
(`exec_cache.run_fleet_batched`), so clusters that share a bucket (the
full `_shape_sig`, not just the [N, P] bucket: vocab widths included)
and an `EngineConfig` now pack as lanes of one launch.

Equivalence contract (tier-1 `test_tune.py::TestFleetLanes`): each
lane's decoded row is **identical to the serial boundary's** — the vmap
adds no cross-lane ops, `cluster_row`/`quarantine_row` are the shared
row constructors, and the report digest of a fleet-lane campaign equals
the `fleet_lanes=False` serial run bit for bit.

Quarantine semantics are unchanged and PER LANE:

* a cluster whose host-side load/admit/encode fails, whose pods carry
  mixed priorities (preemption is an iterative host fixed-point — not a
  lane), or whose config registers extension ops, falls back to the
  serial `_run_one` boundary (full retry/quarantine machinery);
* a lane whose decode or placement audit fails is quarantined alone —
  its siblings in the same launch settle normally;
* a launch that fails with a DETERMINISTIC device fault
  (resilience/faults.py classification) walks the batch-split rung:
  the chunk halves and re-launches, isolating the poison down to one
  cluster's own serial verdict while siblings stay batched — per-lane
  rows are chunking-invariant, so the report digest is unchanged;
* a launch that fails any other way (transient retries already spent
  inside the launch's fault domain, or an unclassified lane-path bug)
  re-runs its members through the serial boundary, whose
  classifier-gated retry/quarantine machinery owns the verdict.

Cancellation (REST deadline, drain) is observed BETWEEN launches with
the campaign's own partial-result shape, so a 504 mid-fleet still names
the settled clusters and the journal resumes past them.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from open_simulator_tpu.errors import SimulationError
from open_simulator_tpu.resilience import lifecycle

_log = logging.getLogger(__name__)


def _fleet_metrics():
    from open_simulator_tpu import telemetry

    return telemetry.counter(
        "simon_campaign_fleet_launches_total",
        "campaign dispatch boundaries by kind (serial counts one per "
        "cluster boundary incl. its internal retries; batched one per "
        "lane chunk)",
        labelnames=("kind",))  # batched | serial


@dataclass
class _Prepared:
    """One lane candidate: the host-side pipeline of `simulate()` up to
    (and including) the device transfer, run inside the per-cluster
    fault boundary."""

    entry: Any
    snapshot: Any
    cfg: Any                 # the engine config simulate() would run
    fp_cfg: Any              # the fingerprint config _run_one records
    arrs: Any                # bucket-padded HOST arrays (stack_fleet_arrays
    #                          stacks on host; the one device transfer is
    #                          the stacked batch in run_fleet_batched)
    n_pods: int
    active: np.ndarray       # UNPADDED activation (decode reads this)
    lane_ok: bool            # provably equivalent to the serial path?
    why_serial: str = ""


def _prepare(entry, apps, opts) -> _Prepared:
    """Mirror `core.simulate()`'s host pipeline exactly (validate=True,
    use_greed=False — the campaign's fixed calling convention) so a lane
    run answers the same question `_run_one` would."""
    from open_simulator_tpu.campaign.runner import load_and_admit
    from open_simulator_tpu.core import (
        _with_nodes,
        build_pod_sequence,
        with_volume_objects,
    )
    from open_simulator_tpu.encode.snapshot import encode_cluster
    from open_simulator_tpu.engine import exec_cache
    from open_simulator_tpu.engine.scheduler import make_config
    from open_simulator_tpu.k8s.loader import make_valid_node
    from open_simulator_tpu.resilience.admission import admit

    cluster = load_and_admit(entry)
    nodes = [make_valid_node(n) for n in cluster.nodes]
    cluster = _with_nodes(cluster, nodes)
    admit(cluster, apps)
    pods = build_pod_sequence(cluster, apps)
    snapshot = encode_cluster(nodes, pods,
                              with_volume_objects(None, cluster, apps))
    overrides = dict(opts.config_overrides)
    overrides.pop("_disable_preemption", None)
    cfg = make_config(snapshot, **overrides)
    fp_cfg = make_config(snapshot, **{
        k: v for k, v in opts.config_overrides.items()
        if not k.startswith("_")})
    # pad on host, transfer NOTHING here: the lane path's only device
    # hop is the stacked fleet batch (a per-cluster transfer would be
    # pulled straight back for stacking — a wasted device round trip)
    n_nodes = snapshot.arrays.alloc.shape[0]
    n_pods = snapshot.arrays.req.shape[0]
    arrs = exec_cache.pad_snapshot_arrays(
        snapshot.arrays, *exec_cache.bucket_shape(n_nodes, n_pods))

    lane_ok, why = True, ""
    if len({p.priority for p in snapshot.pods}) > 1:
        # preemption is a host-side fixed-point per cluster — a lane
        # cannot iterate it; the serial boundary runs it unchanged
        lane_ok, why = False, "mixed pod priorities (preemption)"
    elif cfg.extensions:
        lane_ok, why = False, "extension ops registered"
    return _Prepared(entry=entry, snapshot=snapshot, cfg=cfg,
                     fp_cfg=fp_cfg, arrs=arrs, n_pods=n_pods,
                     active=np.asarray(snapshot.arrays.active),
                     lane_ok=lane_ok, why_serial=why)


def _decode_lane(prep: _Prepared, out, lane: int, n_lanes: int,
                 opts, campaign_id: str
                 ) -> Tuple[Dict[str, Any], Dict[str, str]]:
    """One lane's outputs -> the SAME report row + fingerprint the
    serial boundary produces (shared `cluster_row`; raises AuditError /
    SimulationError into the caller's per-lane quarantine boundary)."""
    from open_simulator_tpu.campaign.audit import AuditError, audit_result
    from open_simulator_tpu.campaign.runner import cluster_row
    from open_simulator_tpu.core import decode_result
    from open_simulator_tpu.telemetry import ledger

    cfg, snapshot, n_pods = prep.cfg, prep.snapshot, prep.n_pods
    t0 = time.perf_counter()
    with ledger.run_capture(
            "campaign",
            tags={"campaign": campaign_id, "cluster": prep.entry.name,
                  "scenario": opts.scenario, "fleet_lanes": n_lanes}) as cap:
        node_assign = np.asarray(out.node)[lane, :n_pods]
        fail_counts = np.asarray(out.fail_counts)[lane, :n_pods]
        kw: Dict[str, Any] = {}
        if cfg.explain_topk:
            from open_simulator_tpu.engine.scheduler import score_part_names

            kw = dict(
                topk_node=np.asarray(out.topk_node)[lane, :n_pods],
                topk_score=np.asarray(out.topk_score)[lane, :n_pods],
                topk_parts=np.asarray(out.topk_parts)[lane, :n_pods],
                score_part_names=list(score_part_names(cfg)))
        result = decode_result(
            snapshot, node_assign, fail_counts, prep.active,
            elapsed_s=time.perf_counter() - t0,
            gpu_pick=(np.asarray(out.gpu_pick)[lane, :n_pods]
                      if cfg.enable_gpu else None),
            vol_pick=(np.asarray(out.vol_pick)[lane, :n_pods]
                      if cfg.enable_pv_match else None),
            extra_op_names=list(cfg.extension_op_names),
            **kw)
        if cap.recording:
            cap.set_config(cfg, snapshot=snapshot)
            cap.set_result(result)
    audit = audit_result(result)
    if opts.audit and not audit.ok:
        raise AuditError(audit, ref=f"cluster/{prep.entry.name}")
    row = cluster_row(prep.entry, result, audit)
    fingerprint = {"source": prep.entry.digest,
                   "engine": ledger.engine_config_hash(prep.fp_cfg)}
    return row, fingerprint


def _settle_serial(entry, apps, opts, campaign_id: str,
                   settle: Callable, partial: Callable) -> int:
    """The unchanged serial boundary for one cluster (full
    retry/quarantine machinery); returns the launches it cost (1)."""
    from open_simulator_tpu.campaign import runner

    lifecycle.check_current("campaign cluster boundary", partial=partial)
    kind, row, fingerprint = runner._run_one(entry, apps, opts,
                                             campaign_id)
    settle(entry, kind, row, fingerprint)
    _fleet_metrics().labels(kind="serial").inc()
    return 1


def _run_chunk(chunk: List[_Prepared], apps, opts, campaign_id: str,
               settle: Callable, partial: Callable,
               width: int = 0) -> int:
    """Execute up to lane_width prepared clusters as ONE launch; per-lane
    quarantine; whole-launch failure falls back to the serial boundary.
    Returns the device launches dispatched. A short chunk pads to
    `width` by repeating its last lane (never decoded): the lane count
    is part of the AOT cache key, so a 2-cluster remainder launched
    unpadded would compile a second executable per bucket — the tune
    search pads its short rounds the same way."""
    from open_simulator_tpu.campaign import runner
    from open_simulator_tpu.engine import exec_cache
    from open_simulator_tpu.telemetry.spans import span

    cfg = chunk[0].cfg
    n_pad = max(0, max(width, len(chunk)) - len(chunk))
    lifecycle.check_current("campaign fleet-lane boundary",
                            partial=partial)
    try:
        with span("fleet.launch", lanes=len(chunk)):
            arrs_batch = exec_cache.stack_fleet_arrays(
                [p.arrs for p in chunk]
                + [chunk[-1].arrs] * n_pad)
            out = exec_cache.run_fleet_batched(
                arrs_batch, arrs_batch.active, cfg)
            # sync every field decode will read to host HERE, inside the
            # whole-launch boundary: a transient device error on these
            # reads must take the serial fallback (with its retry
            # machinery), not quarantine a lane — and one copy per array
            # beats one per lane
            sync = {"node": np.asarray(out.node),
                    "fail_counts": np.asarray(out.fail_counts)}
            if cfg.explain_topk:
                sync.update(topk_node=np.asarray(out.topk_node),
                            topk_score=np.asarray(out.topk_score),
                            topk_parts=np.asarray(out.topk_parts))
            if cfg.enable_gpu:
                sync["gpu_pick"] = np.asarray(out.gpu_pick)
            if cfg.enable_pv_match:
                sync["vol_pick"] = np.asarray(out.vol_pick)
            # E_NUMERIC sentinel scan over the launch's float state: a
            # NaN escaping a fused score must raise here (and walk the
            # batch-split ladder down to the poisoned cluster's own
            # quarantine), not settle into report rows undetected
            from open_simulator_tpu.resilience import faults as _faults

            _faults.check_finite(
                "fleet_schedule",
                headroom=np.asarray(out.state.headroom),
                **({"topk_score": sync["topk_score"]}
                   if cfg.explain_topk else {}))
            out = out._replace(**sync)
    except lifecycle.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — classified below; the serial
        # boundary stays the last line of defense either way
        from open_simulator_tpu.resilience import faults

        if (isinstance(e, faults.DeviceFault) and not e.transient
                and len(chunk) > 1):
            # batch-split rung: a deterministic device fault (a NaN in
            # one lane, an OOM the exec-cache rung couldn't absorb)
            # halves the chunk and re-launches each side — per-lane rows
            # are chunking-invariant, so the report digest is identical;
            # a single poisoned cluster degrades all the way down to its
            # own verdict while siblings stay batched
            faults.record_rung("fleet_schedule", "batch_split", e.code)
            half = len(chunk) // 2
            return (_run_chunk(chunk[:half], apps, opts, campaign_id,
                               settle, partial, width=len(chunk[:half]))
                    + _run_chunk(chunk[half:], apps, opts, campaign_id,
                                 settle, partial,
                                 width=len(chunk[half:])))
        if (isinstance(e, faults.DeviceFault) and not e.transient
                and e.code == faults.E_NUMERIC):
            # the ladder bottom for a NaN: the serial boundary runs the
            # same data through a scan with NO finite sentinel, so a
            # fallback would settle NaN-derived placements as a
            # completed row — the one outcome the sentinel exists to
            # prevent. The launch verdict IS the verdict: quarantine
            # the cluster with the structured E_NUMERIC.
            prep = chunk[0]
            runner._campaign_metrics()[0].labels(
                outcome="quarantined").inc()
            _log.warning(
                "campaign %s: cluster %s quarantined [E_NUMERIC] by the "
                "fleet-lane sentinel: %s", campaign_id, prep.entry.name, e)
            settle(prep.entry, "quarantine",
                   runner.quarantine_row(prep.entry, e.to_dict(),
                                         attempts=1), {})
            return 1
        # transient retries already spent inside the launch's fault
        # domain (or an unclassified lane-path bug): the serial boundary
        # re-runs every member with its own retry/quarantine machinery,
        # so no cluster's verdict depends on the batched path working —
        # and because the classifier gates the serial retries too, a
        # deterministic fault quarantines on attempt 1 there instead of
        # being retried like a transient
        faults.record_rung(
            "fleet_schedule", "serial",
            e.code if isinstance(e, faults.DeviceFault) else "")
        _log.warning(
            "fleet-lane launch of %d cluster(s) failed (%s: %s); "
            "falling back to the serial boundary",
            len(chunk), type(e).__name__, e)
        return sum(_settle_serial(p.entry, apps, opts, campaign_id,
                                  settle, partial) for p in chunk)
    _fleet_metrics().labels(kind="batched").inc()
    clusters_total = runner._campaign_metrics()[0]
    for i, prep in enumerate(chunk):
        try:
            row, fingerprint = _decode_lane(prep, out, i, len(chunk),
                                            opts, campaign_id)
            clusters_total.labels(outcome="completed").inc()
            settle(prep.entry, "cluster", row, fingerprint)
            continue
        except lifecycle.CancelledError:
            raise
        except SimulationError as e:
            err = e.to_dict()
        except Exception as e:  # noqa: BLE001 — per-lane last line of
            # defense, mirroring _run_one's
            err = {"code": "E_INTERNAL",
                   "ref": f"cluster/{prep.entry.name}", "field": "",
                   "hint": "file the dump as a repro",
                   "message": f"{type(e).__name__}: {e}"}
        clusters_total.labels(outcome="quarantined").inc()
        _log.warning("campaign %s: cluster %s quarantined [%s] in a "
                     "fleet lane: %s", campaign_id, prep.entry.name,
                     err.get("code"), err.get("message"))
        settle(prep.entry, "quarantine",
               runner.quarantine_row(prep.entry, err, attempts=1), {})
    return 1


def run_fleet(entries, apps, opts, campaign_id: str,
              settle: Callable, partial: Callable) -> int:
    """Drive the pending fleet: group shape+config-identical clusters,
    launch groups as lanes, serial-boundary everything else. Returns the
    total device launches dispatched (the `report["launches"]` witness:
    same-bucket fleets finish in fewer launches than clusters)."""
    launches = 0
    width = max(1, int(opts.lane_width))
    # A full group launches the moment it reaches lane_width (the chunk
    # membership is identical to batching after a whole-fleet prepass —
    # same-signature clusters chunk in arrival order either way), so
    # peak residency is bounded by lane_width PREPARED clusters per
    # distinct signature, not by the fleet size: a 100-cluster fleet
    # must not hold 100 host snapshots + device arrays at once.
    groups: Dict[Tuple, List[_Prepared]] = {}
    for entry in entries:
        lifecycle.check_current("campaign cluster boundary",
                                partial=partial)
        if width == 1:
            # a lone lane gains nothing over the serial boundary — and
            # preparing first would run the host pipeline twice
            launches += _settle_serial(entry, apps, opts, campaign_id,
                                       settle, partial)
            continue
        try:
            prep = _prepare(entry, apps, opts)
        except lifecycle.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — the serial boundary owns the
            # retry/quarantine verdict; re-running the host pipeline for
            # a failing cluster is cheap next to mis-shaping its record
            launches += _settle_serial(entry, apps, opts, campaign_id,
                                       settle, partial)
            continue
        if not prep.lane_ok:
            _log.debug("campaign %s: cluster %s takes the serial "
                       "boundary (%s)", campaign_id, entry.name,
                       prep.why_serial)
            launches += _settle_serial(entry, apps, opts, campaign_id,
                                       settle, partial)
            continue
        from open_simulator_tpu.engine.exec_cache import _shape_sig

        key = (prep.cfg, _shape_sig(prep.arrs))
        bucket = groups.setdefault(key, [])
        bucket.append(prep)
        if len(bucket) >= width:
            groups[key] = []
            launches += _run_chunk(bucket, apps, opts, campaign_id,
                                   settle, partial, width=width)

    # remainders, in first-seen signature order (dict insertion order)
    for group in groups.values():
        if not group:
            continue
        if len(group) == 1:
            # a lone lane gains nothing over the serial boundary —
            # and the serial path keeps its retry machinery
            launches += _settle_serial(group[0].entry, apps, opts,
                                       campaign_id, settle, partial)
        else:
            launches += _run_chunk(group, apps, opts, campaign_id,
                                   settle, partial, width=width)
    return launches
