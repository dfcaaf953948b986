"""The batched capacity sweep.

"How many nodes of spec X must I add so the app list schedules fully?"
— the reference answers by interactive bisection, one full sequential
re-simulation per guess (apply.go:202-258). Here every candidate count is
one lane of a vmapped batch: encode once with the node axis padded to
N_real + max_new, give each lane its own active-node mask, and run the
scan for all lanes simultaneously. The answer is an argmin over lanes
that satisfy (all pods scheduled) AND (occupancy thresholds).

Thresholds mirror the reference's satisfyResourceSetting
(apply.go:614-681): cluster-average CPU/memory occupancy percentages
must stay under MaxCPU/MaxMemory.
"""

from __future__ import annotations

import functools
import logging
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from open_simulator_tpu.encode.snapshot import ClusterSnapshot
from open_simulator_tpu.engine.exec_cache import (
    bucketed_device_arrays,
    refuse_node_axis,
    run_batched_cached,
    run_mesh_cached,
)
from open_simulator_tpu.engine.scheduler import (
    EngineConfig,
    ScheduleOutput,
    device_arrays,
)

_log = logging.getLogger(__name__)


def _with_run_record(fn):
    """Flight-recorder wiring for both sweep modes: a library-level call
    (or POST /api/capacity, which names the surface via
    ledger.surface_override) writes one "sweep" RunRecord with the config
    fingerprint and the plan digest; under an already-active capture (the
    applier's) this is a silent no-op — one record per run.

    Disabled path contract (tested by test_waves.py): when no ledger is
    configured (SIMON_LEDGER_DIR unset, no --ledger-dir), the wrapper
    costs exactly `run_capture`'s enabled-check — one dict lookup plus an
    env read — and NO fingerprint or digest hashing happens: the
    `cap.recording` guard below keeps `set_config`/`set_plan` (which
    hash the whole snapshot and every lane's assignments) off the
    disabled and nested paths entirely."""

    @functools.wraps(fn)
    def wrapper(snapshot, cfg, *args, **kwargs):
        from open_simulator_tpu.telemetry import ledger

        with ledger.run_capture("sweep") as cap:
            plan = fn(snapshot, cfg, *args, **kwargs)
            if cap.recording:
                cap.set_config(cfg, snapshot=snapshot)
                cap.set_plan(plan)
                if getattr(plan, "checkpointing_disabled", False):
                    # the storage degradation rung rides the RunRecord:
                    # the ledger shows WHICH runs lost crash-safety
                    cap.tag("checkpointing_disabled", True)
            return plan

    return wrapper


class SweepThresholds(NamedTuple):
    max_cpu_pct: float = 100.0
    max_memory_pct: float = 100.0
    max_vg_pct: float = 100.0  # open-local VG occupancy (MaxVG env, apply.go:614-681)


@dataclass
class CapacityPlan:
    """The sweep verdict."""

    counts: List[int]                  # candidate new-node counts, as swept
    all_scheduled: List[bool]          # per candidate
    cpu_occupancy_pct: List[float]
    mem_occupancy_pct: List[float]
    satisfied: List[bool]
    best_count: Optional[int]          # min satisfying count, None if none
    nodes_per_scenario: np.ndarray = field(repr=False, default=None)  # [S, P]
    fail_counts: np.ndarray = field(repr=False, default=None)         # [S, P, OPS]
    gpu_pick: Optional[np.ndarray] = field(repr=False, default=None)  # [S, P, G]
    vol_pick: Optional[np.ndarray] = field(repr=False, default=None)  # [S, P, Lw]
    # lane index -> error string for trials that failed even after the
    # per-trial fallback; failed lanes report all_scheduled=False,
    # satisfied=False, occupancy 0 (resilience: one bad trial no longer
    # kills the sweep)
    trial_errors: Dict[int, str] = field(default_factory=dict)
    # checkpoint-journal id when the sweep ran with round checkpointing
    # (resilience/lifecycle.py SweepJournal): `apply --resume <sweep_id>`
    # or POST /api/capacity {"resume": <sweep_id>} replays from it
    sweep_id: Optional[str] = None
    # rounds replayed from a checkpoint instead of executed (0 on a
    # fresh run) — the resume witness for tests and responses
    resumed_rounds: int = 0
    # True when a storage fault disabled the sweep journal mid-run (the
    # checkpointing_disabled degradation rung, ARCH §19): the plan is
    # complete and correct, but the run cannot be resumed past the last
    # durable round — surfaced on the final report/ledger, not just a
    # log line
    checkpointing_disabled: bool = False


def make_mesh(
    n_scenario: Optional[int] = None,
    n_node: int = 1,
    require_all: bool = False,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ("scenario", "node") mesh over the available devices, all
    of them on the scenario axis by default. The "node" axis stays 1:
    a larger one is refused (`exec_cache.refuse_node_axis`, ROADMAP B3).
    Unused trailing devices are dropped unless require_all — multi-host
    callers must not silently exclude a host's devices (a host with no
    addressable shard hangs instead of erroring)."""
    refuse_node_axis(n_node)
    devs = np.array(jax.devices() if devices is None else list(devices))
    if n_scenario is None:
        n_scenario = len(devs) // n_node
    used = n_scenario * n_node
    if used > len(devs):
        raise ValueError(f"mesh {n_scenario}x{n_node} needs {used} devices, have {len(devs)}")
    if require_all and used != len(devs):
        raise ValueError(
            f"mesh {n_scenario}x{n_node} uses {used} of {len(devs)} devices; "
            f"pick a node axis that divides the device count"
        )
    return Mesh(devs[:used].reshape(n_scenario, n_node), axis_names=("scenario", "node"))


def batched_schedule(
    arrs,
    active_batch: jnp.ndarray,  # [S, N]
    cfg: EngineConfig,
    mesh: Optional[Mesh] = None,
    carry: Optional[object] = None,
    waves=None,
    weights=None,
    retries: int = 2,
    backoff_s: float = 0.05,
) -> ScheduleOutput:
    """vmap the scan over scenario lanes; shard lanes over the mesh.

    The snapshot arrays are broadcast (replicated) across the scenario
    axis; only the active mask differs per lane. With a mesh, GSPMD
    shards the lane axis; without, the single-device vmap runs through
    the AOT executable cache (engine/exec_cache.py), so every call with
    the same bucketed shapes + cfg reuses one compiled executable —
    building a fresh `jax.jit(jax.vmap(lambda ...))` wrapper per call
    (the old shape of this function) defeats jax's function-identity
    cache and recompiled the whole sweep every time.

    `carry` is an optional DONATED state batch (a previous round's
    `out.state`, dead after this call) whose buffers back this run's
    carry. Both paths support it: under a mesh the donated batch is
    sharded like the lane axis and reset in place shard-for-shard (the
    §9 x*0 contract, unchanged).

    `waves` is an optional static engine.waves.WavePlan for THIS arrs +
    cfg (lane activation does not enter the plan — footprints are
    computed activation-agnostic, so one plan serves every lane). Both
    paths carry the plan in the executable-cache key.

    `weights` is the per-lane [S, K] traced score-weight matrix under
    ``cfg.traced_weights`` (the tune subsystem's policy-variant lanes),
    sharded along the scenario axis under a mesh. A traced cfg with no
    explicit weights runs every lane at the config's own vector —
    digest-identical to constant mode — so the capacity sweeps accept
    traced configs unchanged.
    """
    if mesh is None or mesh.empty:
        return run_batched_cached(arrs, active_batch, cfg, carry=carry,
                                  waves=waves, weights=weights,
                                  retries=retries, backoff_s=backoff_s)
    # the mesh-sharded launch boundary of the device fault domain, now
    # through the AOT executable cache (engine/exec_cache.py): the SAME
    # module-level lane-fn the single-device path compiles, AOT-lowered
    # with in/out shardings and cached under the key + mesh axis split —
    # same-bucket mesh launches are zero recompiles, and a deterministic
    # E_DEVICE_LOST still classifies here for the single-device rung in
    # _execute_sweep (a lost chip takes the whole mesh with it)
    return run_mesh_cached(arrs, active_batch, cfg, mesh, carry=carry,
                           waves=waves, weights=weights,
                           retries=retries, backoff_s=backoff_s)


def active_masks_for_counts(snapshot: ClusterSnapshot, counts: Sequence[int]) -> np.ndarray:
    """[S, N] lane masks: all real nodes + the first c padded new-node slots."""
    n = snapshot.n_nodes
    n_real = snapshot.n_real_nodes
    max_new = n - n_real
    masks = np.zeros((len(counts), n), dtype=bool)
    for si, c in enumerate(counts):
        if c > max_new:
            raise ValueError(f"count {c} exceeds padded new-node slots ({max_new})")
        masks[si, :n_real] = True
        masks[si, n_real : n_real + c] = True
    return masks


def _padded_lane_masks(masks: np.ndarray, n_nodes_padded: int) -> np.ndarray:
    """Widen [S, N] lane masks to the bucketed node axis (pads are never
    active in any lane)."""
    s, n = masks.shape
    if n == n_nodes_padded:
        return masks
    out = np.zeros((s, n_nodes_padded), dtype=bool)
    out[:, :n] = masks
    return out


class _LaneStats(NamedTuple):
    all_scheduled: bool
    cpu_pct: float
    mem_pct: float
    satisfied: bool


def _lane_stats(alloc, cpu_i, mem_i, vg_cap, has_storage, lane_active,
                nodes_row, headroom_row, vg_row, error,
                thresholds: SweepThresholds) -> _LaneStats:
    """Verdict for one lane from its hosted outputs — shared by the
    exhaustive sweep and the bisection so both apply one definition of
    "satisfied" (all pods scheduled AND occupancy under thresholds)."""
    ok = error is None and bool(np.all(nodes_row >= 0))
    used = alloc - headroom_row                         # [N, R]

    def occupancy(ri) -> float:
        tot = float(np.sum(alloc[lane_active, ri]))
        u = float(np.sum(used[lane_active, ri]))
        return 100.0 * u / tot if tot else 0.0

    def vg_occupancy() -> float:
        """MaxVG is enforced per volume group: the WORST VG's occupancy
        across active nodes (the reference parses MaxVG but never checks
        it, apply.go:614-681 — per-VG is the meaningful strictness)."""
        cap = vg_cap[lane_active]                       # [n, V]
        u = vg_row[lane_active]
        with np.errstate(invalid="ignore", divide="ignore"):
            pct = np.where(cap > 0, 100.0 * u / np.where(cap > 0, cap, 1.0), 0.0)
        return float(pct.max()) if pct.size else 0.0

    c_pct = occupancy(cpu_i)
    m_pct = occupancy(mem_i)
    v_pct = vg_occupancy() if has_storage else 0.0
    sat = (
        ok
        and c_pct <= thresholds.max_cpu_pct
        and m_pct <= thresholds.max_memory_pct
        and v_pct <= thresholds.max_vg_pct
    )
    return _LaneStats(ok, c_pct, m_pct, sat)


@_with_run_record
def capacity_sweep(
    snapshot: ClusterSnapshot,
    cfg: EngineConfig,
    counts: Sequence[int],
    thresholds: SweepThresholds = SweepThresholds(),
    mesh: Optional[Mesh] = None,
    fail_reasons: bool = False,
    retries: int = 2,
    backoff_s: float = 0.05,
    isolate_trials: bool = True,
) -> CapacityPlan:
    """Run the full sweep and pick the smallest satisfying node count.

    Per-op failure-reason accounting costs ~45% of scan throughput
    (EngineConfig.fail_reasons), so the what-if lanes run without it by
    default and CapacityPlan.fail_counts is zeros; callers that report
    reasons re-run just their decoded lane with reasons on (the applier
    does). Pass fail_reasons=True to keep the accounting in every lane.

    Device execution is retried with exponential backoff (`retries`,
    `backoff_s`) — the knobs are threaded to the launch-layer fault
    domain (resilience/faults.py), which retries only
    transient-classified failures; if the batched run still fails and
    `isolate_trials`, each lane re-runs alone so one failing trial
    cannot kill the sweep — failed lanes land in
    CapacityPlan.trial_errors instead.

    When feasibility alone is the question, `capacity_bisect` answers
    with ~log_W(max_new) W-lane rounds instead of one lane per count."""
    from open_simulator_tpu.resilience import lifecycle
    from open_simulator_tpu.telemetry.spans import span

    # deadline observed before the batch launches: the exhaustive sweep
    # is one device program, so its only cooperative boundary is here
    lifecycle.check_current("exhaustive sweep start")
    with span("sweep.upload"):
        arrs, _, n_pods = bucketed_device_arrays(snapshot.arrays)
    masks = _padded_lane_masks(
        active_masks_for_counts(snapshot, counts), arrs.alloc.shape[0])
    sweep_cfg = cfg if fail_reasons else cfg._replace(fail_reasons=False)
    from open_simulator_tpu.engine.waves import waves_for

    wave_plan = waves_for(snapshot.arrays, sweep_cfg,
                          n_pods_total=int(arrs.req.shape[0]))
    with span("sweep", lanes=len(counts)):
        nodes, fail, headroom, vg_used_arr, gpu, vol, trial_errors, _ = (
            _execute_sweep(arrs, masks, sweep_cfg, mesh, fail_reasons,
                           retries, backoff_s, isolate_trials, n_pods=n_pods,
                           waves=wave_plan))
    all_scheduled, cpu_occ, mem_occ, satisfied = [], [], [], []
    with span("sweep.lane_stats", lanes=len(counts)):
        alloc = np.asarray(arrs.alloc)             # [N, R]
        cpu_i = snapshot.resources.index("cpu")
        mem_i = snapshot.resources.index("memory")
        vg_cap = np.asarray(arrs.vg_cap)           # [N, V]
        has_storage = bool(np.any(vg_cap > 0))
        for si in range(len(counts)):
            st = _lane_stats(
                alloc, cpu_i, mem_i, vg_cap, has_storage, masks[si], nodes[si],
                headroom[si], vg_used_arr[si], trial_errors.get(si), thresholds)
            all_scheduled.append(st.all_scheduled)
            cpu_occ.append(st.cpu_pct)
            mem_occ.append(st.mem_pct)
            satisfied.append(st.satisfied)

    best = None
    for si in sorted(range(len(counts)), key=lambda i: counts[i]):
        if satisfied[si]:
            best = counts[si]
            break
    return CapacityPlan(
        counts=list(counts),
        all_scheduled=all_scheduled,
        cpu_occupancy_pct=cpu_occ,
        mem_occupancy_pct=mem_occ,
        satisfied=satisfied,
        best_count=best,
        nodes_per_scenario=nodes,
        fail_counts=fail,
        gpu_pick=gpu if cfg.enable_gpu else None,
        vol_pick=vol if cfg.enable_pv_match else None,
        trial_errors=trial_errors,
    )


def _probe_ladder(max_new: int, lanes: int) -> List[int]:
    """First bisection round: a geometric ladder with both endpoints —
    0 (is the cluster already enough?) and max_new (is it impossible?) —
    downsampled evenly to the lane budget."""
    ladder = sorted({0, max_new} | {
        min(1 << i, max_new) for i in range(max(max_new, 1).bit_length())})
    if len(ladder) > lanes:
        idx = np.round(np.linspace(0, len(ladder) - 1, lanes)).astype(int)
        ladder = sorted({ladder[i] for i in idx})
    return ladder


def _journal_lane_payload(rec: dict, cfg: EngineConfig) -> Dict[str, Any]:
    """One lane's checkpoint record: everything the final plan (and its
    digest) needs, JSON-exact — ints stay ints, floats round-trip via
    repr, the gpu/vol picks are stored only when their op is compiled in
    (disabled picks never reach the plan)."""
    st = rec["stats"]
    return {
        "nodes": np.asarray(rec["nodes"]).tolist(),
        "gpu": np.asarray(rec["gpu"]).tolist() if cfg.enable_gpu else None,
        "vol": np.asarray(rec["vol"]).tolist() if cfg.enable_pv_match else None,
        "error": rec["error"],
        "stats": [bool(st.all_scheduled), float(st.cpu_pct),
                  float(st.mem_pct), bool(st.satisfied)],
    }


def _seed_from_journal(journal) -> Dict[int, dict]:
    """Rebuild the bisection's `records` dict from a checkpoint journal,
    with the exact dtypes the live path hosts (int32 picks), so a
    resumed plan's digest is bit-identical to an uninterrupted run's."""
    out: Dict[int, dict] = {}
    for c, p in journal.recorded_lanes().items():
        s = p["stats"]
        out[c] = dict(
            nodes=np.asarray(p["nodes"], dtype=np.int32),
            gpu=(np.asarray(p["gpu"], dtype=np.int32)
                 if p.get("gpu") is not None else None),
            vol=(np.asarray(p["vol"], dtype=np.int32)
                 if p.get("vol") is not None else None),
            error=p.get("error"),
            stats=_LaneStats(bool(s[0]), float(s[1]), float(s[2]),
                             bool(s[3])),
        )
    return out


SWEEP_CHECKPOINT_ENV = "SIMON_SWEEP_CHECKPOINT"


@_with_run_record
def capacity_bisect(
    snapshot: ClusterSnapshot,
    cfg: EngineConfig,
    max_new: int,
    thresholds: SweepThresholds = SweepThresholds(),
    mesh: Optional[Mesh] = None,
    lanes: int = 8,
    retries: int = 2,
    backoff_s: float = 0.05,
    isolate_trials: bool = True,
    resume: Optional[str] = None,
    checkpoint: Optional[bool] = None,
) -> CapacityPlan:
    """Minimum satisfying node count by batched galloping bisection.

    Feasibility is monotone in the node count (more nodes never
    unschedule a pod, and occupancy only falls), so instead of one lane
    per candidate (S = max_new + 1 device lanes) each round runs `lanes`
    probes covering the current bracket and shrinks it ~(lanes+1)x:
    round one is a geometric ladder bracketing the answer (endpoints 0
    and max_new always probed, so "fits already" and "impossible" are
    one-round answers), later rounds spread evenly inside the bracket.
    The `[lanes, N]` mask shape is FIXED across rounds, so every round
    after the first reuses the round-one compiled executable (the AOT
    cache), and each round donates the previous round's carry buffers
    back to the device.

    Returns a CapacityPlan over the PROBED counts only (sorted);
    `best_count` equals the exhaustive sweep's on monotone clusters.
    Probes run with fail_reasons off always — callers that want per-op
    reasons in every lane need `capacity_sweep(fail_reasons=True)`.
    Retry/isolation semantics per round match the exhaustive sweep
    (`trial_errors` keys index the sorted probed counts).

    **Checkpoint/resume** (resilience/lifecycle.py): when a checkpoint
    directory is configured (SIMON_CHECKPOINT_DIR, or <ledger>/checkpoints
    when the ledger is on; `checkpoint=False` opts out, `=True` requires
    it), every completed round appends one journal line. ``resume`` names
    a prior journal (sweep-id prefix or "last"): after verifying the
    config fingerprint + sweep parameters match, recorded rounds are
    replayed instead of executed and the bisection continues from the
    first unprobed round — the final plan digest equals an uninterrupted
    run's. **Deadlines**: an armed ``lifecycle`` cancel scope is observed
    at every round boundary; cancellation raises ``CancelledError``
    carrying the probed counts and best-so-far as partial results."""
    from open_simulator_tpu.resilience import lifecycle
    from open_simulator_tpu.telemetry import ledger
    from open_simulator_tpu.telemetry.spans import span

    if max_new < 0:
        raise ValueError(f"max_new must be >= 0, got {max_new}")
    with span("sweep.upload"):
        arrs, _, n_pods = bucketed_device_arrays(snapshot.arrays)
    n_pad = arrs.alloc.shape[0]
    alloc = np.asarray(arrs.alloc)
    cpu_i = snapshot.resources.index("cpu")
    mem_i = snapshot.resources.index("memory")
    vg_cap = np.asarray(arrs.vg_cap)
    has_storage = bool(np.any(vg_cap > 0))
    sweep_cfg = cfg._replace(fail_reasons=False)
    lanes = max(1, min(lanes, max_new + 1))
    from open_simulator_tpu.engine.waves import waves_for

    wave_plan = waves_for(snapshot.arrays, sweep_cfg,
                          n_pods_total=int(arrs.req.shape[0]))

    # ---- checkpoint journal (create fresh, or load + verify on resume);
    # the fingerprint hashes every snapshot content field, so it is only
    # computed on the journaled paths — never on a plain bisect call
    root = lifecycle.checkpoint_dir()
    journal = None
    records: Dict[int, dict] = {}      # count -> hosted lane outputs
    resumed_rounds = 0
    if resume:
        fp = ledger.config_fingerprint(cfg, snapshot=snapshot, arrs=arrs)
        journal = lifecycle.SweepJournal.load(root or "", resume)
        journal.verify(fp, max_new, lanes, tuple(thresholds))
        records = _seed_from_journal(journal)
        resumed_rounds = len(journal.rounds)
        _log.info("resumed sweep %s: %d recorded round(s), %d count(s) "
                  "replayed", journal.sweep_id, resumed_rounds, len(records))
    elif checkpoint or (checkpoint is None and root
                        and os.environ.get(SWEEP_CHECKPOINT_ENV, "1") != "0"):
        if not root:
            raise ValueError(
                "checkpoint=True needs a checkpoint directory: set "
                "SIMON_CHECKPOINT_DIR or configure a ledger dir")
        fp = ledger.config_fingerprint(cfg, snapshot=snapshot, arrs=arrs)
        try:
            journal = lifecycle.SweepJournal.create(
                root, fp, max_new, lanes, tuple(thresholds))
        except OSError as e:
            # readonly/full checkpoint dir: the sweep must still run —
            # degrade to no-checkpoint with one warning (the same
            # contract the run ledger follows on an unwritable dir)
            _log.warning(
                "checkpoint dir %s is unwritable (%s); sweep "
                "checkpointing disabled for this run", root, e)
            journal = None

    def _partial() -> Dict[str, Any]:
        sat = sorted(c for c, r in records.items() if r["stats"].satisfied)
        return {"probed_counts": sorted(records),
                "best_count_so_far": sat[0] if sat else None,
                "sweep_id": journal.sweep_id if journal else None}

    carry_holder = {"carry": None}     # donated across rounds (both paths)

    def probe(counts_round: List[int]) -> None:
        # counts already replayed from a checkpoint are never re-executed;
        # a fully-recorded round (resume) costs nothing
        new = [c for c in counts_round if c not in records]
        if not new:
            return
        # the deadline/cancel boundary: a 504'd or draining request stops
        # HERE, before the next device launch, instead of orphaning the
        # worker for the rest of the bisection
        lifecycle.check_current("sweep round boundary", partial=_partial)
        # fixed [lanes, N] mask shape: pad the round by repeating the
        # last probe so every round reuses one compiled executable
        cs = list(new) + [new[-1]] * (lanes - len(new))
        masks = _padded_lane_masks(
            active_masks_for_counts(snapshot, cs), n_pad)
        with span("sweep", lanes=lanes, mode="bisect"):
            nodes, _, headroom, vg_used, gpu, vol, errs, state = _execute_sweep(
                arrs, masks, sweep_cfg, mesh, False, retries, backoff_s,
                isolate_trials, n_pods=n_pods,
                carry=carry_holder["carry"],
                return_state=True, waves=wave_plan)
        carry_holder["carry"] = state
        fresh: Dict[int, dict] = {}
        with span("sweep.lane_stats", lanes=len(new)):
            for i, c in enumerate(cs):
                if c in records:
                    continue
                stats = _lane_stats(alloc, cpu_i, mem_i, vg_cap, has_storage,
                                    masks[i], nodes[i], headroom[i], vg_used[i],
                                    errs.get(i), thresholds)
                records[c] = fresh[c] = dict(
                    nodes=nodes[i], gpu=gpu[i], vol=vol[i],
                    error=errs.get(i), stats=stats)
        if journal is not None and fresh:
            # appended only when the round's outputs are fully hosted: a
            # crash mid-round resumes from the previous complete round
            journal.append_round(sorted(fresh), {
                c: _journal_lane_payload(rec, cfg)
                for c, rec in fresh.items()})

    probe(_probe_ladder(max_new, lanes))

    def bracket():
        sat = sorted(c for c, r in records.items() if r["stats"].satisfied)
        hi = sat[0] if sat else None
        lo = max((c for c in records
                  if (hi is None or c < hi) and not records[c]["stats"].satisfied),
                 default=-1)
        return lo, hi

    lo, hi = bracket()
    while hi is not None and hi - lo > 1:
        cands = sorted(set(
            int(c) for c in np.round(np.linspace(lo + 1, hi - 1, lanes))
        ) - set(records))
        if not cands:
            break  # every interior count probed; hi is the minimum
        probe(cands)
        lo, hi = bracket()

    probed = sorted(records)
    stats = [records[c]["stats"] for c in probed]
    plan = CapacityPlan(
        counts=probed,
        all_scheduled=[s.all_scheduled for s in stats],
        cpu_occupancy_pct=[s.cpu_pct for s in stats],
        mem_occupancy_pct=[s.mem_pct for s in stats],
        satisfied=[s.satisfied for s in stats],
        best_count=hi,
        nodes_per_scenario=np.stack([records[c]["nodes"] for c in probed]),
        fail_counts=np.zeros((len(probed), n_pods, cfg.n_ops), dtype=np.int32),
        gpu_pick=(np.stack([records[c]["gpu"] for c in probed])
                  if cfg.enable_gpu else None),
        vol_pick=(np.stack([records[c]["vol"] for c in probed])
                  if cfg.enable_pv_match else None),
        trial_errors={i: records[c]["error"] for i, c in enumerate(probed)
                      if records[c]["error"]},
        sweep_id=journal.sweep_id if journal is not None else None,
        resumed_rounds=resumed_rounds,
    )
    if journal is not None and journal.done is None:
        journal.finish(plan.best_count, ledger.plan_digest(plan)["digest"])
    # surface the storage degradation rung on the verdict itself: a plan
    # from a run whose journal died mid-sweep is correct but unresumable
    plan.checkpointing_disabled = bool(journal is not None
                                       and journal.broken)
    return plan


def _record_lane_error(trial_errors: Dict[int, str], si: int, msg: str) -> None:
    """Accumulate (never overwrite) per-lane diagnostics — a lane whose
    gpu AND vol pick widths both drifted must report both."""
    trial_errors[si] = f"{trial_errors[si]}; {msg}" if si in trial_errors else msg


def _execute_sweep(arrs, masks, sweep_cfg, mesh, fail_reasons,
                   retries, backoff_s, isolate_trials, n_pods=None,
                   carry=None, return_state=False, waves=None):
    """Run the batched sweep with retry; fall back to isolated per-lane
    runs when the batch keeps failing. Returns host numpy
    (nodes, fail, headroom, vg_used, gpu_pick, vol_pick, trial_errors,
    state); pod-axis outputs are sliced to `n_pods` (the bucketing pad
    rows carry no information). `state` is the device-side output carry
    when `return_state` (for donation into the next round; None on the
    isolated-fallback path), else None. A passed `carry` is donated to
    the FIRST batched attempt only — retries re-run from fresh buffers
    because the donated ones are already dead. Failed lanes hold neutral
    values (all -1 nodes, pristine headroom)."""
    from open_simulator_tpu.resilience import faults

    if mesh is not None:
        # before the fault ladder, which would turn the refusal into
        # isolated lanes that each fail the same way
        refuse_node_axis(int(dict(mesh.shape).get("node", 1)))
    from open_simulator_tpu.resilience.retry import run_with_retries
    from open_simulator_tpu.telemetry import registry as _telemetry
    from open_simulator_tpu.telemetry.spans import span

    if n_pods is None:
        n_pods = arrs.req.shape[0]
    trials_total = _telemetry.counter(
        "simon_sweep_trials_total", "capacity-sweep lane outcomes",
        labelnames=("outcome",))

    def host(out):
        # the device->host copy of the lane outputs and the finite scan,
        # on both the batched and the isolated-lane path
        with span("sweep.fetch", lanes=int(out.node.shape[0])):
            # lane count from the OUTPUT, not the closure's masks — the
            # isolated fallback hosts single-lane outputs and must not
            # allocate a full-batch-shaped zeros block per lane
            fail = (np.asarray(out.fail_counts)[:, :n_pods] if fail_reasons
                    else np.zeros((out.node.shape[0], n_pods, sweep_cfg.n_ops),
                                  dtype=np.int32))
            headroom = np.asarray(out.state.headroom)
            vg_used = np.asarray(out.state.vg_used)
            # the E_NUMERIC sentinel scan: a NaN escaping a fused score
            # into the carry must fail the lane loudly, not flow into
            # occupancy verdicts (on the batched path the isolation
            # fallback then narrows it to the offending lane)
            faults.check_finite("batched_schedule", headroom=headroom,
                                vg_used=vg_used)
            return (np.asarray(out.node)[:, :n_pods], fail,
                    headroom, vg_used,
                    np.asarray(out.gpu_pick)[:, :n_pods],
                    np.asarray(out.vol_pick)[:, :n_pods])

    carry_once = {"carry": carry}

    def _batched():
        # carry only on the first attempt (donated buffers are dead after
        # it), and only as an explicit kwarg when present — the
        # fault-injection tests monkeypatch batched_schedule with the
        # carry-less signature. The caller's retry knobs are threaded to
        # the LAUNCH layer (faults.run_launch owns transient retries;
        # an escalated DeviceFault is final — see faults.is_transient).
        kw = {"retries": retries, "backoff_s": backoff_s}
        c = carry_once.pop("carry", None)
        if c is not None:
            kw["carry"] = c
        if waves is not None:
            kw["waves"] = waves
        return batched_schedule(arrs, jnp.asarray(masks), sweep_cfg,
                                mesh=mesh, **kw)

    def _run_batch(batched_fn):
        out = run_with_retries(batched_fn, retries=retries,
                               backoff_s=backoff_s)
        hosted = host(out)
        trials_total.labels(outcome="ok").inc(masks.shape[0])
        return hosted + ({}, out.state if return_state else None)

    try:
        try:
            return _run_batch(_batched)
        except faults.DeviceFault as f:
            # mesh -> single-device rung: a lost chip takes the whole
            # GSPMD mesh down, but the AOT single-device path answers the
            # same question (digest-identical — the multichip gate's own
            # contract); everything else falls through to lane isolation
            if (mesh is not None and not mesh.empty and not f.transient
                    and f.code == faults.E_DEVICE_LOST):
                faults.record_rung("mesh_schedule", "single_device", f.code)
                return _run_batch(lambda: batched_schedule(
                    arrs, jnp.asarray(masks), sweep_cfg, mesh=None,
                    retries=retries, backoff_s=backoff_s,
                    **({"waves": waves} if waves is not None else {})))
            raise
    except Exception as e:
        if not isolate_trials:
            raise
        faults.record_rung(
            "batched_schedule", "lane_isolate",
            e.code if isinstance(e, faults.DeviceFault) else "")

    s = masks.shape[0]
    alloc = np.asarray(arrs.alloc)
    nodes = np.full((s, n_pods), -1, dtype=np.int32)
    fail = np.zeros((s, n_pods, sweep_cfg.n_ops), dtype=np.int32)
    headroom = np.broadcast_to(alloc, (s,) + alloc.shape).copy()
    vg_used = np.zeros((s,) + np.asarray(arrs.vg_cap).shape, dtype=np.float32)
    # pick widths mirror the engine's output contract: width 0 when the
    # gate compiles the op out (so a width drift below is genuine, not
    # the old always-mismatching gate-off case that silently kept zeros)
    g_w = arrs.gpu_slot.shape[1] if sweep_cfg.enable_gpu else 0
    v_w = arrs.wfc_ccid.shape[1] if sweep_cfg.enable_pv_match else 0
    gpu = np.zeros((s, n_pods, g_w), dtype=np.int32)
    vol = np.full((s, n_pods, v_w), -1, dtype=np.int32)
    trial_errors = {}
    for si in range(s):
        try:
            out_i = run_with_retries(
                lambda: batched_schedule(arrs, jnp.asarray(masks[si:si + 1]),
                                         sweep_cfg, mesh=None,
                                         retries=retries,
                                         backoff_s=backoff_s,
                                         **({"waves": waves}
                                            if waves is not None else {})),
                retries=retries, backoff_s=backoff_s)
            nodes_i, fail_i, hr_i, vg_i, gpu_i, vol_i = host(out_i)
            trials_total.labels(outcome="ok").inc()
            nodes[si], fail[si], headroom[si], vg_used[si] = (
                nodes_i[0], fail_i[0], hr_i[0], vg_i[0])
            # A width drift between the isolated lane's outputs and the
            # batch layout means the pick columns cannot be trusted —
            # surface the lane instead of silently reporting zero picks
            # (the placements themselves are still the lane's own).
            if gpu_i[0].shape == gpu[si].shape:
                gpu[si] = gpu_i[0]
            else:
                _log.warning(
                    "sweep lane %d: isolated gpu_pick shape %s != batch "
                    "shape %s; recording the lane as failed instead of "
                    "dropping its GPU picks", si, gpu_i[0].shape, gpu[si].shape)
                _record_lane_error(
                    trial_errors, si,
                    f"isolated gpu_pick shape {gpu_i[0].shape} != "
                    f"batch shape {gpu[si].shape}")
            if vol_i[0].shape == vol[si].shape:
                vol[si] = vol_i[0]
            else:
                _log.warning(
                    "sweep lane %d: isolated vol_pick shape %s != batch "
                    "shape %s; recording the lane as failed instead of "
                    "dropping its volume picks", si, vol_i[0].shape,
                    vol[si].shape)
                _record_lane_error(
                    trial_errors, si,
                    f"isolated vol_pick shape {vol_i[0].shape} != "
                    f"batch shape {vol[si].shape}")
        except Exception as e:  # noqa: BLE001 — isolate, record, continue
            trials_total.labels(outcome="failed").inc()
            trial_errors[si] = f"{type(e).__name__}: {e}"
    if len(trial_errors) == s:
        # every lane failed — this is a systemic failure (dead device,
        # engine bug), not a flaky trial; surface it instead of returning
        # an all-failed plan with no diagnostics. (Keyed access would
        # KeyError if lane numbering ever changed — take any error.)
        raise RuntimeError(
            f"all {s} sweep trials failed; "
            f"first: {next(iter(trial_errors.values()))}")
    return nodes, fail, headroom, vg_used, gpu, vol, trial_errors, None
