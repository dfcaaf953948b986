"""The time-stepped replay engine (ARCHITECTURE.md section 14).

Executes a ``ReplayTrace`` as a closed loop over the bucketed scan:

* **One encode for the whole trajectory.** The pod universe (cluster
  pods + every arrival batch, in event order) and the node universe
  (cluster nodes + ``max_new_nodes`` deterministic template clones) are
  encoded ONCE and padded to their shape bucket. Every step then mutates
  only the forced-bind column and the active-node mask — the same two
  levers the chaos re-scans pull — so every full step after the first
  reuses one compiled executable (zero recompiles per step).

* **Step semantics.** A step's outcome is DEFINED as: scan the full
  universe with departed/not-yet-arrived pods as bind-nothing sentinels
  (``forced_node = -4``, the bucketing-pad treatment), placed live pods
  pinned to their nodes (bound pods never move), and pending live pods
  free (the activeQ retries them every step). Everything below is an
  optimization that must be bit-identical to that definition.

* **Carry fast path.** When an arrival lands on a trajectory with no
  pending pods, the new batch is scheduled ALONE: ``slice_pods`` cuts
  the batch out of the encoded universe, the slice is padded to its pod
  bucket, and the previous step's output carry is threaded in through
  ``schedule_pods``' donated-state contract (the split-scan property:
  scan(prefix) then scan(batch, state=carry) == scan(prefix+batch)).
  Same-bucket arrival batches share one executable; the donated carry
  buffers never double-buffer in HBM. The fast path is skipped whenever
  its exactness preconditions fail (pending pods would deserve a retry,
  a nonzero tie-break seed keys jitter off the global pod index,
  extension ops may read anything).

* **Controllers** (replay/controllers.py) run after each event until
  convergence; their scale actions flip the active mask, and a
  descheduler defrag re-places every movable pod under the bin-packing
  profile (``apply/migrate.py`` generalized into a periodic loop).

* **Journal + resume** (the section-11 pattern): one fsynced JSON line
  per SETTLED step; ``resume`` verifies the fingerprint (engine hash +
  bucket + workload digest + trace digest + controller roster) and
  replays recorded steps, so an interrupted-and-resumed trajectory's
  result digest is BIT-IDENTICAL to an uninterrupted run — the report
  is always built from journal-schema JSON-native rows.

* **Ledger**: each executed step appends one "replay" RunRecord (tagged
  replay id / step / event kind) so trajectories are diffable with
  ``simon-tpu runs diff``; a final summary event records the trajectory
  digest. **Cancellation** (REST deadline / drain) is observed at every
  step boundary with partial-trajectory results.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import time
import uuid
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from open_simulator_tpu.errors import SimulationError
from open_simulator_tpu.replay.trace import (
    BASELINE_KIND,
    CHAOS_KINDS,
    ReplayTrace,
    TraceEvent,
    clone_template_nodes,
    parse_node_template,
)
from open_simulator_tpu.replay.controllers import controllers_digest
from open_simulator_tpu.resilience import faults, lifecycle
from open_simulator_tpu.resilience import journal as journal_mod

_log = logging.getLogger(__name__)

REPLAY_JOURNAL_SUFFIX = ".replay.jsonl"
# the bind-nothing sentinel (engine/exec_cache.py pads with the same):
# departed and not-yet-arrived pods take zero scan work and zero carry
SENTINEL = -4
# score profile of the descheduler's defrag pass — the shared
# bin-packing overrides (ONE definition, engine/sched_config.py, also
# used by the migration planner) as an EngineConfig replace: one extra
# executable, compiled once, reused by every defrag step
from open_simulator_tpu.engine.sched_config import MOST_ALLOCATED_OVERRIDES

DEFRAG_OVERRIDES = dict(MOST_ALLOCATED_OVERRIDES)


@dataclass
class ReplayOptions:
    """One replay's knobs (CLI flags / REST body fields map 1:1)."""

    controllers: List[Any] = dc_field(default_factory=list)
    resume: str = ""                   # replay-id prefix or "last"
    checkpoint: Optional[bool] = None  # None = auto (on when a dir exists)
    config_overrides: Dict[str, Any] = dc_field(default_factory=dict)
    # carry-threaded arrival steps (bit-identical; a perf/debug switch)
    fast_path: bool = True
    max_control_iters: int = 8
    validate: bool = True


def rows_digest(rows: List[Dict[str, Any]]) -> str:
    """The trajectory digest: a hash over the journal-schema rows (always
    JSON-native, so live and resumed runs digest identical bytes)."""
    return hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()[:16]


def row_digest(row: Dict[str, Any]) -> str:
    return rows_digest([row])


# ---- journal -------------------------------------------------------------


class ReplayJournal(journal_mod.DurableJournal):
    """Append-only per-replay step log, section-11 SweepJournal-shaped:

      {"kind": "header", "replay_id", "ts", "fingerprint", "n_events",
       "controllers": [spec...], "surface"}
      {"kind": "step", "row": {...}}
      {"kind": "done", "digest", "steps"}

    A row is appended only when the step SETTLED (event applied,
    controllers converged, outputs hosted) and fsynced — a SIGKILL
    resumes from the last settled step. Records ride the shared
    CRC-framed ``DurableJournal`` format (ARCH §19): torn final line →
    resume from the prefix; mid-file corruption → ``E_CORRUPT``;
    unwritable dir → the shared checkpointing_disabled rung.
    """

    KIND = "replay"

    def __init__(self, path: str, header: Dict[str, Any],
                 rows: Optional[List[Dict[str, Any]]] = None,
                 done: Optional[Dict[str, Any]] = None):
        super().__init__(path, header)
        self.rows = rows or []
        self.done = done

    @property
    def replay_id(self) -> str:
        return self.header["replay_id"]

    @classmethod
    def create(cls, root: str, fingerprint: Dict[str, Any], n_events: int,
               controller_specs: List[Dict[str, Any]],
               surface: str = "replay") -> "ReplayJournal":
        os.makedirs(root, exist_ok=True)
        # bounded-disk tax on every new replay: completed journals past
        # the shared keep cap go, resumable (unfinished) ones stay
        lifecycle.prune_journals(root, REPLAY_JOURNAL_SUFFIX)
        replay_id = uuid.uuid4().hex[:12]
        header = {"kind": "header", "replay_id": replay_id,
                  "ts": round(time.time(), 6), "fingerprint": fingerprint,
                  "n_events": int(n_events),
                  "controllers": controller_specs, "surface": surface}
        journal = cls(os.path.join(root, replay_id + REPLAY_JOURNAL_SUFFIX),
                      header)
        journal._append(header)
        return journal

    @classmethod
    def load(cls, root: str, token: str) -> "ReplayJournal":
        path = journal_mod.resolve_journal_path(
            root, token, REPLAY_JOURNAL_SUFFIX, "replay")
        scan = journal_mod.read_journal(path, cls.KIND)
        header, rows, done = None, [], None
        for rec in scan.records:
            kind = rec.get("kind")
            if kind == "header":
                header = rec
            elif kind == "step":
                rows.append(rec["row"])
            elif kind == "done":
                done = rec
        if header is None:
            raise lifecycle.ResumeError(
                f"checkpoint {os.path.basename(path)} has no header line",
                ref="resume")
        journal = cls(path, header, rows, done)
        journal._adopt_scan(scan)
        return journal

    def verify(self, fingerprint: Dict[str, Any]) -> None:
        """Resume contract: the rebuilt trajectory must ask the engine
        the SAME questions the checkpointed one asked — engine config,
        shape bucket, encoded workload, trace content, and the
        controller roster all hash into the fingerprint."""
        want = self.header.get("fingerprint") or {}
        if want != fingerprint:
            drift = sorted(k for k in set(want) | set(fingerprint)
                           if want.get(k) != fingerprint.get(k))
            raise lifecycle.ResumeError(
                f"replay fingerprint drifted since the checkpoint "
                f"(changed: {drift}): recorded steps answer a different "
                f"question", ref=f"replay/{self.replay_id}",
                field="fingerprint",
                hint="re-run without --resume, or restore the original "
                     "cluster/trace/controllers")

    def append_step(self, row: Dict[str, Any]) -> None:
        rec = {"kind": "step", "row": row}
        self._append(rec)
        self.rows.append(row)

    def finish(self, digest: str, steps: int) -> None:
        rec = {"kind": "done", "digest": digest, "steps": int(steps)}
        self._append(rec)
        self.done = rec


def resolve_replay(token: str) -> ReplayJournal:
    """Load a replay journal by id prefix / ``last``."""
    return ReplayJournal.load(lifecycle.checkpoint_dir() or "", token)


# ---- trajectory state ----------------------------------------------------


def arrival_apps(trace: ReplayTrace) -> List[Any]:
    """Parse every arrival event's manifest into AppResources (event
    order), behind the structured taxonomy — shared by the replay
    program build and the frontier's workload-union question."""
    import yaml as _yaml

    from open_simulator_tpu.core import AppResource
    from open_simulator_tpu.k8s.loader import (
        ClusterResources,
        demux_object,
        parse_yaml_documents,
    )

    apps: List[AppResource] = []
    for ev in trace.arrivals():
        res_obj = ClusterResources()
        try:
            for doc in parse_yaml_documents(ev.app["yaml"]):
                demux_object(doc, res_obj)
        except _yaml.YAMLError as e:
            raise SimulationError(
                f"arrival app {ev.app.get('name')!r} has invalid YAML: "
                f"{e}", code="E_SPEC", ref="replay_trace",
                field="events[].app.yaml") from None
        apps.append(AppResource(name=ev.app["name"], resources=res_obj))
    return apps


class _Program:
    """The encoded-once universe a trajectory executes against."""

    def __init__(self, cluster, trace: ReplayTrace, opts: ReplayOptions):
        import jax
        import jax.numpy as jnp

        from open_simulator_tpu.core import (
            _priority_sort,
            _resolve_priorities,
            _with_nodes,
            with_volume_objects,
        )
        from open_simulator_tpu.encode.snapshot import encode_cluster
        from open_simulator_tpu.engine import exec_cache
        from open_simulator_tpu.engine.scheduler import make_config
        from open_simulator_tpu.k8s.loader import make_valid_node
        from open_simulator_tpu.models.expand import (
            expand_app_resources,
            expand_cluster_pods,
        )

        # allow_empty: a session program starts from a bare baseline
        # trajectory; the non-session surfaces (CLI/REST/run_replay's
        # callers) reject empty traces before ever building a program
        trace.validate(allow_empty=True)
        nodes = [make_valid_node(n) for n in cluster.nodes]
        if not nodes:
            raise SimulationError(
                "cannot replay against a cluster with zero nodes",
                code="E_SPEC", ref="cluster", field="nodes")
        cluster = _with_nodes(cluster, nodes)
        self.trace = trace
        apps = arrival_apps(trace)
        self.apps = apps
        if opts.validate:
            from open_simulator_tpu.resilience.admission import admit

            admit(cluster, apps)

        # node universe: cluster nodes + deterministic template clones
        self.n_cluster_nodes = len(nodes)
        self.n_slots = int(trace.max_new_nodes)
        all_nodes = list(nodes)
        if self.n_slots > 0:
            template = parse_node_template(trace.node_template)
            all_nodes += clone_template_nodes(template, self.n_slots)

        # pod universe: cluster batch, then each arrival batch in event
        # order (each batch priority-sorted like an activeQ batch)
        batch0 = expand_cluster_pods(cluster)
        _resolve_priorities(batch0, cluster, apps)
        universe = list(_priority_sort(batch0))
        self.batch_ranges: Dict[str, Tuple[int, int]] = {}
        for app in apps:
            batch = expand_app_resources(app.resources, nodes, app.name)
            _resolve_priorities(batch, cluster, apps)
            batch = _priority_sort(batch)
            self.batch_ranges[app.name] = (len(universe),
                                           len(universe) + len(batch))
            universe.extend(batch)
        self.n_cluster_pods = len(batch0)
        self.pods = universe
        self.key_to_idx: Dict[str, int] = {}
        for i, p in enumerate(universe):
            self.key_to_idx.setdefault(p.key, i)

        opts_enc = with_volume_objects(None, cluster, apps)
        self.snapshot = encode_cluster(all_nodes, universe, opts_enc)
        # forced_prefix off: the step loop rewrites the forced column, so
        # a prefix hoist keyed to the ORIGINAL column would fold stale
        # binds (same reason chaos pins it to 0); fail_reasons off: steps
        # only need assignments (the sweep-lane precedent) — and it keeps
        # every step on one lean executable
        self.cfg = make_config(
            self.snapshot, **dict(opts.config_overrides))._replace(
            forced_prefix=0, fail_reasons=False)
        self.cfg_defrag = self.cfg._replace(**DEFRAG_OVERRIDES)

        self.N = self.snapshot.n_nodes
        self.P = self.snapshot.n_pods
        nb, pb = exec_cache.bucket_shape(self.N, self.P)
        self.N_pad, self.P_pad = int(nb), int(pb)
        self.host_master = exec_cache.pad_snapshot_arrays(
            self.snapshot.arrays, self.N_pad, self.P_pad)
        self.dev_master = jax.tree_util.tree_map(jnp.asarray,
                                                 self.host_master)
        self.alloc = np.asarray(self.host_master.alloc)  # [N_pad, R]
        res = self.snapshot.resources
        self.cpu_i = res.index("cpu")
        self.mem_i = res.index("memory")
        self.node_names = list(self.snapshot.node_names)
        self.node_labels = [n.meta.labels for n in self.snapshot.nodes]
        from open_simulator_tpu.apply.migrate import is_movable

        self.movable = np.fromiter((is_movable(p) for p in universe),
                                   dtype=bool, count=self.P)
        self.is_ds = np.fromiter(
            (p.meta.owner_kind == "DaemonSet" for p in universe),
            dtype=bool, count=self.P)
        self.base_forced = np.array(
            np.asarray(self.snapshot.arrays.forced_node), dtype=np.int32,
            copy=True)
        # pinned-consumption hoist (scheduler.apply_forced_mask): every
        # full step folds ALL pinned pods into the init carry so evicted
        # pods earlier in pod order see true headroom — exact only when
        # no pod that could ever be pinned carries an order-dependent
        # gpu/storage/WFC/shared-volume contribution (the make_config
        # prefix gate, applied over the whole universe)
        a = self.snapshot.arrays
        self.hoist_forced = not (
            bool(self.cfg.extensions)
            or (self.cfg.enable_gpu
                and bool(np.any(np.asarray(a.gpu_cnt) > 0)))
            or (self.cfg.enable_storage
                and bool(np.any(np.asarray(a.lvm_req) > 0)
                         or np.any(np.asarray(a.sdev_req) > 0)))
            or bool(np.any(np.asarray(a.wfc_valid)))
            or (bool(np.any(np.asarray(a.svol_id) >= 0))
                and bool(np.any(np.asarray(a.vol_limit_cap) < 1e9))))

    def fingerprint(self, controllers) -> Dict[str, Any]:
        from open_simulator_tpu.telemetry import ledger

        return {
            "engine": ledger.engine_config_hash(self.cfg),
            "bucket": [self.N_pad, self.P_pad],
            "workload": ledger.workload_digest(self.snapshot.arrays),
            "trace": self.trace.digest(),
            "controllers": controllers_digest(controllers),
        }

    def presence_after(self, events: List[TraceEvent]) -> np.ndarray:
        """Pure host reconstruction of the present mask after a replayed
        event prefix (resume restores bound/active from the journal row;
        presence is a function of the event list alone)."""
        present = np.zeros(self.P, dtype=bool)
        present[: self.n_cluster_pods] = True
        for ev in events:
            if ev.kind == "arrive":
                start, stop = self.batch_ranges[ev.app["name"]]
                present[start:stop] = True
            elif ev.kind == "depart":
                for i in self._depart_indices(ev):
                    present[i] = False
        return present

    def _depart_indices(self, ev: TraceEvent) -> List[int]:
        if ev.app_name:
            start, stop = self.batch_ranges[ev.app_name]
            return list(range(start, stop))
        out = []
        for key in ev.pods:
            idx = self.key_to_idx.get(key)
            if idx is None:
                raise SimulationError(
                    f"depart event references unknown pod {key!r}",
                    code="E_SPEC", ref="replay_trace", field="events[].pods",
                    hint="pod keys are ns/name of cluster or arrival pods")
            out.append(idx)
        return out


class _World:
    """Mutable host trajectory state + the device scan plumbing."""

    def __init__(self, prog: _Program):
        self.prog = prog
        self.present = np.zeros(prog.P, dtype=bool)
        self.present[: prog.n_cluster_pods] = True
        # bound: >=0 node, -1 pending (retries every step), -2 lost
        # (pinned node died — DaemonSets), never SENTINEL for live pods
        self.bound = prog.base_forced[: prog.P].copy()
        self.active = np.zeros(prog.N, dtype=bool)
        self.active[: prog.n_cluster_nodes] = np.asarray(
            prog.snapshot.arrays.active)[: prog.n_cluster_nodes]
        self.carry = None          # device SimState, donated forward

    # -- masks -----------------------------------------------------------

    def _forced_pad(self, forced: np.ndarray):
        out = np.full(self.prog.P_pad, SENTINEL, dtype=np.int32)
        out[: self.prog.P] = forced
        return out

    def _active_pad(self) -> np.ndarray:
        out = np.zeros(self.prog.N_pad, dtype=bool)
        out[: self.prog.N] = self.active
        return out

    def step_forced(self) -> np.ndarray:
        return np.where(self.present, self.bound,
                        np.int32(SENTINEL)).astype(np.int32)

    # -- device scans ------------------------------------------------------

    def full_scan(self, cfg=None, forced: Optional[np.ndarray] = None):
        """The defining semantics: scan the whole (padded) universe with
        the step's forced column. Same shapes every step -> one compiled
        executable for the whole trajectory. Runs inside the device
        fault domain (fn="replay_step"): transients retry, classified
        faults surface structured."""
        import jax.numpy as jnp

        from open_simulator_tpu.engine.scheduler import schedule_pods

        prog = self.prog
        arrs = dataclasses.replace(
            prog.dev_master,
            forced_node=jnp.asarray(self._forced_pad(
                self.step_forced() if forced is None else forced)))

        def fire():
            out = schedule_pods(arrs, jnp.asarray(self._active_pad()),
                                cfg or prog.cfg,
                                hoist_forced=prog.hoist_forced)
            return out.state, np.asarray(out.node)[: prog.P]

        self.carry, assign = faults.run_launch("replay_step", fire)
        return assign

    def slice_scan(self, start: int, stop: int):
        """The carry fast path: schedule ONLY pods [start:stop) against
        the donated previous carry — exact by the split-scan property
        (tests/test_checkpoint.py), padded to the slice's pod bucket so
        same-bucket arrival batches reuse one executable."""
        import jax
        import jax.numpy as jnp

        from open_simulator_tpu.engine import exec_cache
        from open_simulator_tpu.engine.scheduler import (
            schedule_pods,
            slice_pods,
        )

        prog = self.prog
        sl = slice_pods(prog.host_master, start, stop)
        _, pb = exec_cache.bucket_shape(prog.N_pad, stop - start)
        sl = exec_cache.pad_snapshot_arrays(sl, prog.N_pad, int(pb))
        # NO transient retries here (retries=0): the previous carry is
        # DONATED to the first attempt, so a re-run cannot be proven
        # exact — any fault, transient or not, falls back to the
        # defining full scan in settle_step (which needs no carry)
        carry = self.carry
        self.carry = None  # donated below: dead either way

        def fire():
            out = schedule_pods(
                jax.tree_util.tree_map(jnp.asarray, sl),
                jnp.asarray(self._active_pad()), prog.cfg,
                state=carry, state_is_fresh=False)
            return out.state, np.asarray(out.node)[: stop - start]

        self.carry, assign = faults.run_launch("replay_step", fire,
                                               retries=0)
        return assign

    def update_bound(self, assign: np.ndarray,
                     lo: int = 0, hi: Optional[int] = None) -> None:
        """Fold scan outputs back into the host binding table: placed
        pods pin, failed placements go pending (-1) unless the pod
        carries a sticky sentinel — -2 (pinned node died: DaemonSets
        never retry) or -4 (encode-time pre-reason, e.g. an unbindable
        immediate PVC: the scan must never be asked to place it)."""
        hi = self.prog.P if hi is None else hi
        seg = slice(lo, hi)
        a = assign.astype(np.int32)
        cur = self.bound[seg]
        sticky = (cur == -2) | (cur == SENTINEL)
        self.bound[seg] = np.where(
            self.present[seg],
            np.where(a >= 0, a, np.where(sticky, cur, np.int32(-1))),
            cur)

    # -- derived stats -----------------------------------------------------

    def pods_per_node(self) -> np.ndarray:
        placed = self.present & (self.bound >= 0)
        return np.bincount(self.bound[placed],
                           minlength=self.prog.N)[: self.prog.N]

    def counts(self) -> Tuple[int, int, int]:
        """(placed, pending, lost) among live pods. Lost covers both
        dead-pinned-node pods (-2) and encode-time pre-reason sentinels
        (-4) — neither ever retries."""
        live = self.present
        placed = int(np.sum(live & (self.bound >= 0)))
        lost = int(np.sum(live & ((self.bound == -2)
                                  | (self.bound == SENTINEL))))
        pending = int(np.sum(live)) - placed - lost
        return placed, pending, lost

    def occupancy(self) -> Tuple[float, float]:
        if self.carry is None:
            return 0.0, 0.0
        headroom = np.asarray(self.carry.headroom)  # [N_pad, R]
        used = self.prog.alloc - headroom
        act = self._active_pad()

        def pct(ri: int) -> float:
            tot = float(np.sum(self.prog.alloc[act, ri]))
            return 100.0 * float(np.sum(used[act, ri])) / tot if tot else 0.0

        return pct(self.prog.cpu_i), pct(self.prog.mem_i)


# ---- event application ---------------------------------------------------


def _apply_event(world: _World, ev: TraceEvent) -> Dict[str, Any]:
    """Mutate the world for one event; returns JSON-native event detail
    for the step row (evicted pod keys, nodes touched)."""
    prog = world.prog
    detail: Dict[str, Any] = {"evicted": [], "nodes": []}
    if ev.kind == BASELINE_KIND:
        return detail
    if ev.kind == "arrive":
        start, stop = prog.batch_ranges[ev.app["name"]]
        world.present[start:stop] = True
        return detail
    if ev.kind == "depart":
        for i in prog._depart_indices(ev):
            world.present[i] = False
        return detail
    if ev.kind == "node_add":
        slots = range(prog.n_cluster_nodes, prog.N)
        free = [i for i in slots if not world.active[i]]
        take = free[: ev.count]
        for i in take:
            world.active[i] = True
        detail["nodes"] = [int(i) for i in take]
        return detail

    # node_remove + the ChaosPlan kinds: nodes fail, their pods unbind
    # (DaemonSet pods die with the node — the chaos.py semantics)
    if ev.kind in CHAOS_KINDS:
        from open_simulator_tpu.resilience.chaos import (
            FaultEvent,
            _resolve_event,
        )

        failed = _resolve_event(
            FaultEvent(kind=ev.kind, target=ev.target),
            prog.trace.zone_key, prog.node_names, prog.node_labels,
            world.active)
    else:  # node_remove
        if ev.target not in prog.node_names:
            raise SimulationError(
                f"node {ev.target!r} not found in cluster", code="E_SPEC",
                ref=f"node/{ev.target}", field="events[].target",
                hint="node_remove targets a cluster node or an added "
                     "template slot by name")
        idx = prog.node_names.index(ev.target)
        failed = [idx] if world.active[idx] else []
    failed_mask = np.zeros(prog.N, dtype=bool)
    failed_mask[failed] = True
    world.active &= ~failed_mask
    on_dead = (world.present & (world.bound >= 0)
               & failed_mask[np.maximum(world.bound, 0)])
    detail["evicted"] = sorted(prog.pods[i].key
                               for i in np.nonzero(on_dead)[0])
    detail["nodes"] = [int(i) for i in failed]
    world.bound = np.where(
        on_dead, np.where(prog.is_ds, np.int32(-2), np.int32(-1)),
        world.bound)
    return detail


# ---- controller loop -----------------------------------------------------


def _make_view(world: _World, step: int, t: float, kind: str):
    from open_simulator_tpu.replay.controllers import StepView

    placed, pending, lost = world.counts()
    return StepView(step=step, t=float(t), event_kind=kind, pending=pending,
                    lost=lost, placed=placed, active=world.active.copy(),
                    pods_per_node=world.pods_per_node(),
                    n_cluster_nodes=world.prog.n_cluster_nodes,
                    n_slots=world.prog.n_slots)


def _run_defrag(world: _World) -> List[List[int]]:
    """Unpin every movable placed pod and re-place the world under the
    bin-packing profile; returns [pod_idx, from, to] moves."""
    prog = world.prog
    unpin = world.present & (world.bound >= 0) & prog.movable
    if not np.any(unpin):
        return []
    before = world.bound.copy()
    forced = np.where(world.present,
                      np.where(unpin, np.int32(-1), world.bound),
                      np.int32(SENTINEL)).astype(np.int32)
    assign = world.full_scan(cfg=prog.cfg_defrag, forced=forced)
    world.update_bound(assign)
    moved = np.nonzero(unpin & (world.bound != before))[0]
    return [[int(i), int(before[i]), int(world.bound[i])] for i in moved]


def _controller_loop(world: _World, controllers, step: int, t: float,
                     kind: str, max_iters: int
                     ) -> Tuple[List[Dict[str, Any]], int, bool]:
    """Run controllers to convergence; returns (actions, iters,
    converged). Every mutating action is followed by the re-simulation
    that makes its effect observable to the next iteration."""
    actions: List[Dict[str, Any]] = []
    iters = 0
    while iters < max_iters:
        view = _make_view(world, step, t, kind)
        proposed = [(c, a) for c in controllers for a in c.actions(view)]
        if not proposed:
            break
        iters += 1
        rescan = False
        for ctrl, act in proposed:
            rec: Dict[str, Any] = {"controller": ctrl.name,
                                   "kind": act["kind"], "iter": iters}
            if act["kind"] == "scale_up":
                for i in act["nodes"]:
                    world.active[i] = True
                rec["nodes"] = [int(i) for i in act["nodes"]]
                rescan = True  # pending pods may now place
            elif act["kind"] == "scale_down":
                # the policy only ever proposes EMPTY owned slots with no
                # pending pods, so deactivation changes no placement and
                # the carry stays exact — no rescan needed
                for i in act["nodes"]:
                    world.active[i] = False
                rec["nodes"] = [int(i) for i in act["nodes"]]
            elif act["kind"] == "defrag":
                moves = _run_defrag(world)
                rec["moves"] = moves
                rec["n_moves"] = len(moves)
            else:  # pragma: no cover — controller contract violation
                raise SimulationError(
                    f"controller {ctrl.name} proposed unknown action "
                    f"{act['kind']!r}", code="E_INTERNAL",
                    ref="replay_controllers")
            actions.append(rec)
        if rescan:
            world.update_bound(world.full_scan())
    converged = iters < max_iters
    final_view = _make_view(world, step, t, kind)
    for c in controllers:
        c.observe(final_view)
    return actions, iters, converged


# ---- one settled step ----------------------------------------------------


def settle_step(prog: "_Program", world: "_World", controllers, ev: TraceEvent,
                step: int, *, fast_path: bool = True,
                max_control_iters: int = 8) -> Dict[str, Any]:
    """Apply ONE event to the trajectory and settle it: event mutation,
    the defining scan (or the carry fast path when its exactness
    preconditions hold), then the controller loop to convergence.
    Returns the JSON-native journal-schema row. Shared verbatim by
    ``run_replay`` (the closed-trace loop) and ``replay/session.py``
    (resident digital-twin sessions) so both surfaces settle steps with
    bit-identical semantics."""
    steps_total, events_total, actions_total = _metrics()
    had_pending = bool(np.any(world.present & (world.bound == -1)))
    detail = _apply_event(world, ev)
    events_total.labels(kind=ev.kind).inc()
    if ev.kind == "arrive":
        start, stop = prog.batch_ranges[ev.app["name"]]
    else:
        start = stop = 0
    fast_ok = (
        fast_path and ev.kind == "arrive"
        and world.carry is not None and not had_pending
        and stop > start and prog.cfg.tie_break_seed == 0
        and not prog.cfg.extensions)
    if fast_ok:
        try:
            world.update_bound(world.slice_scan(start, stop),
                               lo=start, hi=stop)
            steps_total.labels(path="slice").inc()
        except faults.DeviceFault as f:
            # fast-path -> full-scan rung: the defining semantics IS the
            # full re-scan (the fast path is only ever an optimization
            # proven bit-identical to it), so a device fault on the
            # donated-carry slice launch degrades to the full scan from
            # fresh state — the settled row, journal line and trajectory
            # digest are identical to a healthy step
            faults.record_rung("replay_step", "full_scan", f.code)
            world.carry = None
            world.update_bound(world.full_scan())
            steps_total.labels(path="full").inc()
    elif ev.kind == "arrive" and stop == start:
        steps_total.labels(path="noop").inc()  # empty batch
    else:
        world.update_bound(world.full_scan())
        steps_total.labels(path="full").inc()
    actions, iters, converged = _controller_loop(
        world, controllers, step, ev.t, ev.kind, max_control_iters)
    for a in actions:
        actions_total.labels(controller=a["controller"],
                             action=a["kind"]).inc()
    placed, pending, lost = world.counts()
    cpu_pct, mem_pct = world.occupancy()
    return {
        "step": step,
        "t": float(ev.t),
        "event": ({"kind": BASELINE_KIND, "t": float(ev.t)}
                  if ev.kind == BASELINE_KIND else ev.row_dict()),
        "placed": placed, "pending": pending, "lost": lost,
        "active_nodes": int(np.sum(world.active)),
        "evicted": detail["evicted"],
        "event_nodes": detail["nodes"],
        "actions": actions,
        "iters": int(iters),
        "converged": bool(converged),
        "cpu_pct": round(float(cpu_pct), 3),
        "mem_pct": round(float(mem_pct), 3),
        "assign": [int(b) for b in world.bound],
        "active": [int(a) for a in world.active],
        "controllers": {c.name: c.state_dict() for c in controllers},
    }


# ---- the replay ----------------------------------------------------------


def _metrics():
    from open_simulator_tpu import telemetry

    return (
        telemetry.counter("simon_replay_steps_total",
                          "replay steps executed, by path",
                          labelnames=("path",)),
        telemetry.counter("simon_replay_events_total",
                          "trace events applied, by kind",
                          labelnames=("kind",)),
        telemetry.counter("simon_replay_controller_actions_total",
                          "controller actions applied during replays",
                          labelnames=("controller", "action")),
    )


def run_replay(cluster, trace: ReplayTrace,
               options: Optional[ReplayOptions] = None) -> Dict[str, Any]:
    """Execute (or resume) one trace replay; returns the report dict.

    Deterministic end to end: same cluster + trace + controllers ->
    bit-identical journal rows and trajectory digest, interrupted or
    not. See the module docstring for the step semantics."""
    from open_simulator_tpu.replay.report import build_report
    from open_simulator_tpu.telemetry import ledger
    from open_simulator_tpu.telemetry.spans import span

    opts = options or ReplayOptions()
    controllers = list(opts.controllers)
    names = [c.name for c in controllers]
    if len(set(names)) != len(names):
        raise SimulationError(
            f"controller names must be unique, got {names}", code="E_SPEC",
            ref="replay_controllers", field="controllers",
            hint="register each controller kind at most once")
    t0 = time.perf_counter()
    prog = _Program(cluster, trace, opts)
    world = _World(prog)

    fingerprint = prog.fingerprint(controllers)
    root = lifecycle.checkpoint_dir()
    journal: Optional[ReplayJournal] = None
    rows: List[Dict[str, Any]] = []
    resumed_steps = 0
    if opts.resume:
        journal = ReplayJournal.load(root or "", opts.resume)
        journal.verify(fingerprint)
        rows = list(journal.rows)
        resumed_steps = len(rows)
        if rows:
            last = rows[-1]
            world.bound = np.array(last["assign"], dtype=np.int32)
            world.active = np.array(last["active"], dtype=bool)
            world.present = prog.presence_after(
                trace.events[: resumed_steps - 1])
            for c in controllers:
                c.load_state((last.get("controllers") or {}).get(c.name, {}))
        _log.info("resumed replay %s: %d settled step(s) replayed",
                  journal.replay_id, resumed_steps)
    elif opts.checkpoint or (opts.checkpoint is None and root):
        if not root:
            raise ValueError(
                "checkpoint=True needs a checkpoint directory: set "
                "SIMON_CHECKPOINT_DIR or configure a ledger dir")
        try:
            journal = ReplayJournal.create(
                root, fingerprint, len(trace.events),
                [c.spec_dict() for c in controllers])
        except OSError as e:
            _log.warning("checkpoint dir %s is unwritable (%s); replay "
                         "checkpointing disabled for this run", root, e)
            journal = None
    replay_id = (journal.replay_id if journal is not None
                 else uuid.uuid4().hex[:12])

    # step 0 is the synthetic baseline (the cluster's own pods), then one
    # step per trace event; a resumed run skips the settled prefix
    baseline = TraceEvent(
        t=trace.events[0].t if trace.events else 0.0, kind=BASELINE_KIND)
    schedule = [baseline] + list(trace.events)

    def _partial() -> Dict[str, Any]:
        placed, pending, lost = world.counts()
        return {"replay_id": replay_id, "steps_completed": len(rows),
                "total_steps": len(schedule), "placed": placed,
                "pending": pending, "lost": lost}

    for step in range(resumed_steps, len(schedule)):
        ev = schedule[step]
        # the deadline/drain boundary: a cancelled request stops HERE,
        # between steps, with the journal intact (resume picks it up) and
        # the settled prefix as partial results
        lifecycle.check_current("replay step boundary", partial=_partial)
        with ledger.run_capture(
                "replay", tags={"replay": replay_id, "step": step,
                                "t": float(ev.t), "event": ev.kind}) as cap:
            with span("replay.step", step=step, event=ev.kind):
                row = settle_step(prog, world, controllers, ev, step,
                                  fast_path=opts.fast_path,
                                  max_control_iters=opts.max_control_iters)
            if cap.recording:
                cap.set_config(prog.cfg, snapshot=prog.snapshot)
                cap.set_result_info(row["placed"],
                                    row["pending"] + row["lost"],
                                    row_digest(row))
        rows.append(row)
        if journal is not None:
            journal.append_step(row)

    digest = rows_digest(rows)
    report = build_report(replay_id, rows, trace,
                          wall_s=time.perf_counter() - t0,
                          resumed_steps=resumed_steps)
    assert report["digest"] == digest
    if journal is not None and journal.done is None:
        journal.finish(digest, len(rows))
    # storage degradation rung on the report (outside the digested core,
    # like wall_s): complete and correct, but unresumable past the last
    # durable step
    if journal is not None and journal.broken:
        report["checkpointing_disabled"] = True
    # one trajectory-summary line beside the per-step records: how the
    # day went, surviving process exit (diffable across engine versions)
    tags = {"replay": replay_id, "steps": len(rows),
            "events": len(trace.events), "digest": digest,
            "placed": report["totals"]["placed"],
            "pending": report["totals"]["pending"],
            "lost": report["totals"]["lost"],
            "resumed_steps": resumed_steps}
    if report.get("checkpointing_disabled"):
        tags["checkpointing_disabled"] = True
    ledger.append_event("replay", tags=tags, wall_s=report["wall_s"])
    return report


def report_from_journal(journal: ReplayJournal) -> Dict[str, Any]:
    """Rebuild a replay report from its journal rows (crash inspection —
    works on unfinished journals too)."""
    from open_simulator_tpu.replay.report import build_report

    return build_report(journal.replay_id, list(journal.rows), None)
