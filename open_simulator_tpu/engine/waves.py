"""Wave scheduling: host-side conflict analysis for the scan engine.

The scan scheduler (engine/scheduler.py) is a faithful serialization of
the vendored scheduleOne loop: one `lax.scan` step per pod, every pod's
filter+score waiting on the previous pod's carry update — even when the
two pods *cannot possibly interact*. This module partitions the pod
sequence into **carry-independent waves**: contiguous runs of pods where
no earlier pod in the run can change a later pod's feasible set, score
ranking, or recorded diagnostics. Each wave then executes as ONE batched
filter+score over `[wave, N]` with a vectorized carry merge
(`scheduler._wave_merge` segment-sums the wave's claims into the state
once), instead of `wave` sequential scan steps.

**Exactness contract.** Results are bit-identical to scan order: a pod is
admitted to a wave only when the analysis PROVES independence from every
earlier pod in the same wave, so "evaluate the whole wave against the
wave-start state" is observationally equal to scan order. Pods the
analysis cannot prove independent fall back to in-wave scan order (SCAN
segments). The proof obligations, per ordered pair (A before B in a
wave), are writes(A) ∩ reads(B) = ∅ over every carry channel the scan
step touches:

* **per-node channels** (headroom/fit + the resource scores, host ports,
  GPU share, open-local storage, volume-limit counts, shared-volume
  presence): A's bind writes only at A's bound node; B reads them across
  B's *feasible-superset footprint* — the statically-known node set
  `class_affinity ∧ class_taint ∧ ¬unschedulable` for B's compat class
  (`active` is deliberately ignored: the plan must hold for every sweep
  lane's activation). Conflict iff the footprints can overlap. A forced
  pod's footprint is exactly its pinned node; with per-op failure
  accounting ON, every pod additionally *reads* its whole class
  footprint (the fail_counts row observes every carry-dependent op
  there), which is the same set — so the test is uniform.
* **selector-group channels** (`group_count`/`dom_count`, read by
  required pod-affinity, forward anti-affinity, topology spread, and the
  preference score): these reads are global (domain minima, column
  totals), so B reading group g conflicts with ANY earlier A matching g,
  regardless of geometry.
* **anti-affinity term channels** (`term_block`): A's bind paints its
  own terms across the bound node's whole topology domain, so B hitting
  term t conflicts with any earlier A owning t.
* **preferred-term channels** (`pref_paint`): same shape — B hitting
  preferred term t2 conflicts with any earlier A owning t2.
* **the PV channel** (`pv_taken`): WaitForFirstConsumer matching is a
  global claim ledger; at most one WFC pod per wave, ordered first.

Float exactness of the batched merge rides the same invariant the
forced-prefix hoist documents (scheduler.apply_forced_prefix): carry
counts are 0/1 increments and resource requests are integer-valued in
their encoded units, so scatter-add order is immaterial bit-for-bit.

**What waves cannot batch**: two generic schedulable pods whose
footprints overlap ALWAYS conflict — the resource scores read headroom
at every feasible node, which is the genuine kube semantics (the real
scheduler is sequential for the same reason). Waves win where real
clusters actually decouple: interleaved already-bound pods (cluster-dump
replay), multi-tenant node pools with per-pool selectors, and the
bucketing pad's sentinel tail. Everything else stays on the scan path,
unchanged.

**Footprint narrowing.** A GRID segment's pods each score over their
own footprint, never over the whole node axis (WavePlan.narrow;
scheduler._narrowed_step). The planner records per GRID segment a
static width K and the segment's scheduled compat classes: K is the
largest of those classes' footprints plus one, rounded up to
NARROW_STEP. The segment stays on the full node axis where K exceeds a
NARROW_MAX_SHARE-th of the nodes, where two of those classes'
footprints overlap, where the classes' K-slot tables would hold more
slots than one dense wave's [width, N] rows, or where the config is not
narrowing-exact (`narrowing_exact`). The step then sees, per pod, the K
slots of its class's row: the footprint's node ids in ascending order,
then pad slots that are never active. The argument:

* feasible ⊆ footprint: a feasible node passes every filter the config
  runs, and the footprint (`footprints`) is the conjunction of the
  unschedulable, node-affinity and taint filters the config runs, each
  all-true where its gate is off (a KubeSchedulerConfiguration may
  disable any of the three). So the mask over the K slots holds
  exactly the feasible set.
* each row keeps at least one pad slot, as the full row keeps the
  ≥ N/2 nodes outside the footprint: a masked reduction sees the same
  feasible values plus its fill on both paths.
* ascending ids: "min index among maxima" over the slots is the min
  node id among maxima; the pick maps back through the row, so the
  wave merge works in node ids as before.
* headroom: the segment carries its classes' rows of it beside the
  state. A bind subtracts its request at the one row slot its node has
  (the segment's scheduled classes have pairwise disjoint footprints; a
  bound pod's node outside them has none, and no narrowed pod reads
  it), as the merge subtracts it in the state: integer-valued requests,
  so both give the same floats.
* memory: the tables and the headroom rows hold S·K slots a lane for
  the segment's S classes, which the planner keeps within one dense
  wave's [width, N] rows, which the dense step computes every wave.

The node-axis reductions and per-node reads of `_step`, and why each is
exact over the slots or turns narrowing off:

* the normalizer mins (NodeAffinity, TaintToleration: masked with fill
  0; InterPodAffinity, PodTopologySpread, Simon, GPU share: fill 3.4e38)
  reduce the feasible values plus the fill. Min is exact and order-free
  but for the sign of a zero, which no normalizer reads: each divisor is
  guarded by `> 0`, and a zero enters sums that start from +0.
* selectHost's max and min-index: over the masked score, above.
* `feasible_n` sums the mask (exact), but only under `fail_reasons`;
  `fail_counts` counts every active node: gated (`fail_reasons`).
* `explain_topk` ranks non-feasible filler nodes; `tie_break_seed` draws
  its jitter per node position: gated.
* the group-count path (`needs_group_count`, which covers the batched
  `gcr_seg` read): domain sums over every node, pod-affinity totals,
  hard spread's min over eligible (not feasible) nodes, the
  inter-pod preference matmuls: gated.
* the dom_count spread path reads per-domain tables (not narrowed; the
  hoisted per-class domain rows are taken for the segment's classes)
  and each node's domain id (narrowed): exact.
* per-node carries the narrowed step does not gather (GPU share,
  open-local storage, volume-limit and shared-volume counts) and
  per-node tables indexed by a volume class (`class_vol_*`,
  `pv_node_ok`): gated (`enable_gpu`, `enable_storage`,
  `enable_vol_static`, `enable_pv_match`, `enable_vol_limits`);
  extension ops may read anything: gated.
* nominated nodes and preemption victims never reach a wave plan
  (`schedule_pods` drops it), and the bind half of a wave pod's step is
  discarded (the merge binds), so their node-id reads never run narrowed.
* `n_nodes` sizes the all-true rows, the min-index fill (reached only
  when no slot is feasible, and then the pod stays unbound) and the
  gated rows above.

Everything in this module is host-side numpy — pure, static, and tested
on hand-built conflict graphs (tests/test_waves.py), following the
graftlint resolver discipline. Nothing here runs inside jit scope.
"""

from __future__ import annotations

import logging
import threading
import weakref
from collections import OrderedDict
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

_log = logging.getLogger(__name__)

# segment kinds (WavePlan.segments[i][2])
SCAN = 0      # sequential lax.scan over the slice (the fallback path)
BATCH = 1     # one wave: vmapped filter+score + one carry merge
FORCED = 2    # forced/sentinel run: constant outputs + one carry merge
GRID = 3      # uniform-width wave run: lax.scan over [width]-batched steps
SENTINEL = 4  # pure bind-nothing run: constant outputs, no merge at all

KIND_NAMES = {SCAN: "scan", BATCH: "batch", FORCED: "forced",
              GRID: "grid", SENTINEL: "sentinel"}

# planner thresholds: a batched segment must amortize its merge (~1-2
# scan steps of work) and the per-segment trace/compile cost
MIN_FORCED = 4      # min width for a FORCED merge segment
MIN_SENTINEL = 2    # min width for a SENTINEL constant segment
MIN_BATCH = 8       # min width for a standalone BATCH segment
GRID_MIN_RUN = 4    # min consecutive equal-width waves to fuse into a GRID
GRID_MIN_WIDTH = 2
MAX_SEGMENTS = 24   # compile-time guard: each segment traces its own body
# Analysis-cost guard: footprint overlaps are precomputed as a dense
# [C, C] product over the node axis. C (distinct compat classes) is
# small on real clusters, but a pathological dump with per-pod distinct
# affinity/tolerations makes C ~ P and the product O(C^2 * N) — past
# this cap the planner returns all-SCAN instead of stalling the host.
MAX_CLASSES = 512
# Footprint narrowing (see the module docstring): a GRID segment's node
# axis narrows to its pods' footprints, bucketed to the TPU's 128-lane
# width, where that leaves at most half of the node axis. On v5e, a
# 64-lane pools launch narrowed to 35% of its nodes took 16% less time
# than the dense one, and narrowed to 52% of them 4% more (PERF.md).
NARROW_STEP = 128
NARROW_MAX_SHARE = 2  # narrow only where K * NARROW_MAX_SHARE <= N


class WavePlan(NamedTuple):
    """Static, hashable execution plan for one encoded pod sequence.

    ``segments`` are ``(lo, hi, kind, width)`` covering ``[start,
    n_pods)`` in order (``width`` is the wave width for GRID segments, 0
    otherwise). ``start`` is the forced-bind prefix the engine hoists
    before the plan applies (nonzero only under failure accounting /
    explain, where the hoist's zero-diagnostics convention must be
    preserved). The plan joins the AOT executable-cache key, so two runs
    in the same shape bucket with different plans compile separately and
    same-plan reruns stay zero-recompile.

    ``narrow`` holds ``(K, classes)`` for each segment: K > 0 runs that
    GRID segment's steps over each pod's footprint padded to K slots,
    with per-class tables for ``classes`` alone (the segment's scheduled
    compat classes, ascending); ``(0, ())`` runs a segment, and every
    non-GRID one, over the full node axis."""

    segments: Tuple[Tuple[int, int, int, int], ...]
    start: int
    n_pods: int
    narrow: Tuple[Tuple[int, Tuple[int, ...]], ...]

    @property
    def n_waves(self) -> int:
        """Batched placement units (GRID segments count their waves)."""
        n = 0
        for lo, hi, kind, w in self.segments:
            if kind == GRID:
                n += (hi - lo) // w
            elif kind != SCAN:
                n += 1
        return n

    @property
    def max_wave_width(self) -> int:
        out = 0
        for lo, hi, kind, w in self.segments:
            if kind == GRID:
                out = max(out, w)
            elif kind != SCAN:
                out = max(out, hi - lo)
        return out

    @property
    def batched_pods(self) -> int:
        return sum(hi - lo for lo, hi, kind, _ in self.segments
                   if kind != SCAN)

    @property
    def wave_fraction(self) -> float:
        """Fraction of the pod axis placed through batched waves (the
        rest rides the fallback scan; the hoisted prefix counts as
        batched — it is one merged wave by construction)."""
        if not self.n_pods:
            return 0.0
        return (self.batched_pods + self.start) / float(self.n_pods)

    @property
    def narrowed_fraction(self) -> float:
        """Fraction of the pod axis placed through footprint-narrowed
        GRID segments (a part of ``wave_fraction``)."""
        if not self.n_pods:
            return 0.0
        return sum(hi - lo for (lo, hi, _, _), (k, _) in
                   zip(self.segments, self.narrow) if k) / float(self.n_pods)

    def stats(self) -> Dict[str, float]:
        return {"n_waves": self.n_waves,
                "max_wave_width": self.max_wave_width,
                "wave_fraction": round(self.wave_fraction, 4),
                "narrowed_fraction": round(self.narrowed_fraction, 4),
                "n_segments": len(self.segments)}

    def pod_waves(self) -> "tuple[np.ndarray, np.ndarray]":
        """(wave_id [n_pods] i32, batched [n_pods] bool) — the explain
        surface's per-pod decode. Wave ids number every placement unit
        in sequence order (scan segments: one id per pod — each pod is
        its own degenerate wave); ``batched`` marks pods placed through
        a batched wave rather than the fallback scan."""
        wave_id = np.zeros(self.n_pods, dtype=np.int32)
        batched = np.zeros(self.n_pods, dtype=bool)
        wid = 0
        if self.start:
            wave_id[: self.start] = wid
            batched[: self.start] = True
            wid += 1
        for lo, hi, kind, w in self.segments:
            if kind == SCAN:
                for i in range(lo, hi):
                    wave_id[i] = wid
                    wid += 1
            elif kind == GRID:
                for j, i in enumerate(range(lo, hi)):
                    wave_id[i] = wid + (j // w)
                batched[lo:hi] = True
                wid += (hi - lo) // w
            else:
                wave_id[lo:hi] = wid
                batched[lo:hi] = True
                wid += 1
        return wave_id, batched


def _slot_union(out: np.ndarray, idx: np.ndarray, valid: np.ndarray) -> None:
    """OR one-hot columns of ``idx`` (masked by ``valid``) into the
    [P, W] bool matrix ``out`` — slot arrays to dense read/write sets."""
    if idx.size == 0 or out.shape[1] == 0:
        return
    p_idx = np.arange(out.shape[0])
    for k in range(idx.shape[1]):
        m = valid[:, k] & (idx[:, k] >= 0) & (idx[:, k] < out.shape[1])
        out[p_idx[m], idx[m, k]] = True


class _PodModel(NamedTuple):
    """Per-pod read/write sets, host numpy."""

    forced: np.ndarray        # [P] i32
    cid: np.ndarray           # [P] i32
    fp: np.ndarray            # [C, N] class feasible-superset footprints
    ov: np.ndarray            # [C, C] footprint-overlap
    read_groups: np.ndarray   # [P, S]
    write_groups: np.ndarray  # [P, S]
    read_terms: np.ndarray    # [P, T]
    write_terms: np.ndarray   # [P, T]
    read_prefs: np.ndarray    # [P, T2]
    write_prefs: np.ndarray   # [P, T2]
    gpu: np.ndarray           # [P] wants GPU share
    heavy: np.ndarray         # [P] storage / WFC / shared-volume pods
    wfc: np.ndarray           # [P] reads+writes the global pv channel
    reads_all: bool           # failure accounting / explain: every pod
    #                           observes its class footprint


def footprints(arrs, cfg):
    """[C, N] bool: each compat class's feasible-superset footprint,
    `class_affinity ∧ class_taint ∧ ¬unschedulable` over the filters the
    config runs (numpy or jax): a row whose gate is off admits every
    node, as the step's filter does."""
    fp = arrs.class_affinity | (not cfg.enable_class_aff)
    if cfg.enable_class_taint:
        fp = fp & arrs.class_taint
    if cfg.enable_unsched:
        fp = fp & ~arrs.unschedulable[None, :]
    return fp


def _pod_model(arrs, cfg) -> _PodModel:
    a = lambda name: np.asarray(getattr(arrs, name))  # noqa: E731
    forced = a("forced_node").astype(np.int64)
    cid = a("class_id").astype(np.int64)
    fp = np.asarray(footprints(arrs, cfg))
    ovf = fp.astype(np.float32)
    ov = (ovf @ ovf.T) > 0

    p_n = forced.shape[0]
    match = a("match_groups")
    own = a("own_terms")
    hitp = a("hit_pref")
    read_groups = np.zeros_like(match)
    if cfg.enable_pod_affinity:
        _slot_union(read_groups, a("aff_group"), a("aff_valid"))
    if cfg.enable_anti_affinity:
        _slot_union(read_groups, a("anti_group"), a("anti_valid"))
    if cfg.enable_spread:
        _slot_union(read_groups, a("spread_group"), a("spread_valid"))
    # traced weights keep every enabled score row live (a lane's variant
    # may weight preferences even when the config's constant is 0), so
    # the plan must treat the preference channel as read/written
    pref_live = bool(cfg.enable_pref
                     and (cfg.w_interpod or cfg.traced_weights))
    pv = a("pref_valid") & (a("pref_weight") != 0)
    if pref_live:
        _slot_union(read_groups, a("pref_group"), pv)
    write_prefs = np.zeros_like(hitp)
    if pref_live:
        _slot_union(write_prefs, a("pref_tid"), pv)

    gpu = (a("gpu_cnt") > 0) if cfg.enable_gpu else np.zeros(p_n, bool)
    storage = np.zeros(p_n, bool)
    if cfg.enable_storage:
        storage = (np.any(a("lvm_req") > 0, axis=1)
                   | np.any(a("sdev_req") > 0, axis=1))
    wfc = (np.any(a("wfc_valid"), axis=1) if cfg.enable_pv_match
           else np.zeros(p_n, bool))
    svol = np.zeros(p_n, bool)
    if cfg.enable_vol_limits:
        svol = np.any(a("svol_id") >= 0, axis=1)

    return _PodModel(
        forced=forced.astype(np.int32), cid=cid.astype(np.int32),
        fp=fp, ov=ov,
        read_groups=read_groups,
        write_groups=(match if cfg.needs_group_count or cfg.enable_spread
                      else np.zeros_like(match)),
        read_terms=(a("hit_terms") if cfg.enable_anti_affinity
                    else np.zeros_like(own)),
        write_terms=own if cfg.enable_anti_affinity else np.zeros_like(own),
        read_prefs=hitp if pref_live else np.zeros_like(hitp),
        write_prefs=write_prefs,
        gpu=gpu, heavy=storage | wfc | svol, wfc=wfc,
        reads_all=bool(cfg.fail_reasons or cfg.explain_topk),
    )


def compute_wave_plan(arrs, cfg, n_pods_total: Optional[int] = None,
                      max_segments: int = MAX_SEGMENTS) -> WavePlan:
    """Partition the pod sequence into carry-independent waves.

    ``arrs`` is the (unpadded) host SnapshotArrays; ``n_pods_total`` is
    the bucketed pod-axis length — the pad tail [P, total) is a known
    sentinel run (bind-nothing pods whose outputs are sliced off) and
    becomes one constant SENTINEL segment. Pure host analysis; returns a
    plan even when degenerate (all SCAN) — `waves_for` maps those to
    None so the engine keeps its exact pre-wave executable."""
    p_real = int(np.asarray(arrs.forced_node).shape[0])
    total = int(n_pods_total) if n_pods_total else p_real
    if np.asarray(arrs.class_affinity).shape[0] > MAX_CLASSES:
        _log.info("wave planning skipped: %d compat classes exceeds the "
                  "analysis cap (%d)",
                  np.asarray(arrs.class_affinity).shape[0], MAX_CLASSES)
        segments = ((0, total, SCAN, 0),) if total else ()
        return WavePlan(segments=segments, start=0, n_pods=total,
                        narrow=((0, ()),) * len(segments))
    m = _pod_model(arrs, cfg)
    merge_ok = not (cfg.fail_reasons or cfg.explain_topk)
    # under failure accounting / explain the leading forced prefix must
    # keep the hoist's zero-diagnostics convention — hoist it and plan
    # the suffix; without accounting the greedy below subsumes the hoist
    start = 0 if merge_ok else min(int(cfg.forced_prefix), p_real)

    waves = []  # (lo, hi)
    info = []   # per wave: dict(forced_only, sentinel_only, heavy, gpu)
    w_lo = start
    w_classes: set = set()
    w_nodes: set = set()
    w_groups = np.zeros(m.write_groups.shape[1], bool)
    w_terms = np.zeros(m.write_terms.shape[1], bool)
    w_prefs = np.zeros(m.write_prefs.shape[1], bool)
    w_pv = False
    w_info = {"forced_only": True, "sentinel_only": True,
              "heavy": False, "gpu": False}

    def close(i: int) -> None:
        nonlocal w_lo, w_pv, w_info
        if i > w_lo:
            waves.append((w_lo, i))
            info.append(w_info)
        w_lo = i
        w_classes.clear()
        w_nodes.clear()
        w_groups[:] = False
        w_terms[:] = False
        w_prefs[:] = False
        w_pv = False
        w_info = {"forced_only": True, "sentinel_only": True,
                  "heavy": False, "gpu": False}

    for i in range(start, p_real):
        f = int(m.forced[i])
        sched = f == -1
        sentinel = f <= -2
        ci = int(m.cid[i])
        # ---- reads of pod i vs. the wave's accumulated writes ----------
        conflict = False
        reads_fp = sched or m.reads_all
        reads_node = f if (f >= 0 and (m.gpu[i] or m.heavy[i])
                           and not reads_fp) else -1
        if reads_fp:
            if any(m.ov[ci, c] for c in w_classes):
                conflict = True
            elif w_nodes and m.fp[ci, list(w_nodes)].any():
                conflict = True
        elif reads_node >= 0:
            if reads_node in w_nodes or any(
                    m.fp[c, reads_node] for c in w_classes):
                conflict = True
        if not conflict:
            conflict = (
                bool(np.any(m.read_groups[i] & w_groups))
                or bool(np.any(m.read_terms[i] & w_terms))
                or bool(np.any(m.read_prefs[i] & w_prefs))
                or (bool(m.wfc[i]) and w_pv))
        if conflict:
            close(i)
        # ---- writes of pod i -------------------------------------------
        if sched:
            w_classes.add(ci)
        elif f >= 0:
            w_nodes.add(f)
        if not sentinel:
            w_groups |= m.write_groups[i]
            w_terms |= m.write_terms[i]
            w_prefs |= m.write_prefs[i]
            w_pv = w_pv or bool(m.wfc[i])
            w_info["sentinel_only"] = False
            if sched:
                w_info["forced_only"] = False
            w_info["heavy"] = w_info["heavy"] or bool(m.heavy[i])
            w_info["gpu"] = w_info["gpu"] or bool(m.gpu[i])
    close(p_real)

    segments = _classify(waves, info, merge_ok)
    if total > p_real:
        # bucketing pad tail: bind-nothing sentinels whose outputs are
        # sliced off by unpad_output — constants regardless of accounting
        segments.append((p_real, total, SENTINEL, 0))
    segments = _coalesce(segments, max_segments)
    return WavePlan(segments=tuple(segments), start=start, n_pods=total,
                    narrow=_narrowing(m, segments, cfg))


def _classify(waves, info, merge_ok):
    """Wave list -> segment list: fuse uniform-width runs into GRIDs,
    classify the rest, demote narrow waves to SCAN."""
    segments = []
    n = len(waves)
    i = 0
    while i < n:
        lo, hi = waves[i]
        w = hi - lo
        # GRID: >= GRID_MIN_RUN consecutive waves of identical width,
        # none carrying storage/WFC/shared-volume pods (their bind picks
        # are not merge-representable). Only widths that could grid are
        # run-scanned — width-1 degenerate sequences must stay O(n).
        j = i
        if w >= GRID_MIN_WIDTH:
            while (j < n and waves[j][1] - waves[j][0] == w
                   and not info[j]["heavy"]
                   and waves[j][0] == (waves[i][0] + (j - i) * w)):
                j += 1
        if w >= GRID_MIN_WIDTH and (j - i) >= GRID_MIN_RUN:
            segments.append((lo, waves[j - 1][1], GRID, w))
            i = j
            continue
        if (info[i]["sentinel_only"] and merge_ok and w >= MIN_SENTINEL):
            segments.append((lo, hi, SENTINEL, 0))
        elif (info[i]["forced_only"] and merge_ok and w >= MIN_FORCED
              and not info[i]["heavy"] and not info[i]["gpu"]):
            segments.append((lo, hi, FORCED, 0))
        elif w >= MIN_BATCH and not info[i]["heavy"]:
            segments.append((lo, hi, BATCH, 0))
        else:
            segments.append((lo, hi, SCAN, 0))
        i += 1
    return segments


def narrowing_exact(cfg) -> bool:
    """Whether every node-axis reduction the config's step performs runs
    over the pod's feasible set, so a step over the pod's footprint
    alone is exact (module docstring, "Footprint narrowing")."""
    return not (cfg.fail_reasons or cfg.explain_topk or cfg.tie_break_seed
                or cfg.extensions or cfg.needs_group_count or cfg.enable_gpu
                or cfg.enable_storage or cfg.enable_vol_static
                or cfg.enable_pv_match or cfg.enable_vol_limits)


def _narrowing(m: _PodModel, segments, cfg):
    """Per segment, ``(K, classes)`` of a GRID segment that narrows: its
    scheduled pods' compat classes, ascending, and K their largest
    footprint plus one pad slot, rounded up to NARROW_STEP. ``(0, ())``
    where K would exceed a NARROW_MAX_SHARE-th of the node axis, where
    two of those classes have overlapping footprints (the segment's
    binds update a node's headroom in one class row only), where the
    classes' tables would hold more slots than one dense wave's
    [width, N] rows, where the config is not narrowing-exact, and for
    every other kind. Forced and sentinel pods are left out: their
    outputs are predetermined, whatever node axis their step sees."""
    n_nodes = m.fp.shape[1]
    dense = (0, ())
    if not narrowing_exact(cfg):
        return (dense,) * len(segments)
    fp_size = m.fp.sum(axis=1)
    out = []
    for lo, hi, kind, w in segments:
        cls = np.unique(m.cid[lo:hi][m.forced[lo:hi] == -1])
        if kind != GRID or not cls.size:
            out.append(dense)
            continue
        k = -(-(int(fp_size[cls].max()) + 1) // NARROW_STEP) * NARROW_STEP
        shared = m.ov[np.ix_(cls, cls)] & ~np.eye(cls.size, dtype=bool)
        if (k * NARROW_MAX_SHARE > n_nodes or shared.any()
                or cls.size * k > w * n_nodes):
            out.append(dense)
        else:
            out.append((k, tuple(int(c) for c in cls)))
    return tuple(out)


def _coalesce(segments, max_segments):
    """Merge adjacent SCANs; past the segment budget, demote the
    narrowest batched segments back to SCAN (compile-time guard)."""

    def merge_scans(segs):
        out = []
        for s in segs:
            if out and out[-1][2] == SCAN and s[2] == SCAN \
                    and out[-1][1] == s[0]:
                out[-1] = (out[-1][0], s[1], SCAN, 0)
            else:
                out.append(list(s) if isinstance(s, tuple) else s)
                out[-1] = tuple(out[-1])
        return [tuple(s) for s in out]

    segs = merge_scans(segments)
    while sum(1 for s in segs if s[2] != SCAN) and len(segs) > max_segments:
        batched = [s for s in segs if s[2] != SCAN]
        victim = min(batched, key=lambda s: s[1] - s[0])
        segs = [((s[0], s[1], SCAN, 0) if s == victim else s) for s in segs]
        segs = merge_scans(segs)
        if all(s[2] == SCAN for s in segs):
            break
    return segs


# ---- plan cache ----------------------------------------------------------
# Keyed on (workload digest + plan-input digest, EngineConfig hash,
# padded pod count). The ledger's workload digest (ARCHITECTURE §10)
# hashes only a cheap discriminative core (alloc/req/forced/active/...),
# which is NOT sufficient here: the analysis also reads node
# schedulability, compat-class masks, and every selector/term/port
# array, and a stale plan is a CORRECTNESS bug (it would batch pods the
# new workload couples). _plan_inputs_digest therefore hashes the
# content of every array _pod_model consumes. Host-side LRU, same
# discipline as the exec cache.

# every SnapshotArrays field whose CONTENT the conflict analysis reads
# (beyond ledger._WORKLOAD_CONTENT_FIELDS, which covers alloc, req,
# forced_node, active, class_id, gpu_cnt, spread_valid)
_PLAN_INPUT_FIELDS = (
    "unschedulable", "class_affinity", "class_taint",
    "match_groups", "own_terms", "hit_terms", "hit_pref",
    "aff_group", "aff_valid", "anti_group", "anti_valid",
    "spread_group", "pref_group", "pref_valid", "pref_weight", "pref_tid",
    "lvm_req", "sdev_req", "wfc_valid", "svol_id",
)


def _plan_inputs_digest(arrs) -> str:
    import hashlib

    h = hashlib.sha256()
    for name in _PLAN_INPUT_FIELDS:
        x = np.ascontiguousarray(np.asarray(getattr(arrs, name)))
        h.update(name.encode())
        h.update(x.tobytes())
    return h.hexdigest()[:16]


# Per-object digest memo: entry points pass the same SnapshotArrays
# object repeatedly (resident server snapshots, every bisect round, the
# bench warm loop), and hashing tens of MB per call would make cache
# HITS as expensive as misses. Keyed by id() with a weakref finalizer
# so a recycled id can never serve a dead object's digests.
_digest_memo: Dict[int, Tuple[str, str]] = {}


def _arrs_digests(arrs) -> Tuple[str, str]:
    from open_simulator_tpu.telemetry.ledger import workload_digest

    key = id(arrs)
    hit = _digest_memo.get(key)
    if hit is not None:
        return hit
    val = (workload_digest(arrs), _plan_inputs_digest(arrs))
    try:
        weakref.finalize(arrs, _digest_memo.pop, key, None)
        _digest_memo[key] = val
    except TypeError:  # non-weakref-able container: recompute next time
        pass
    return val


_PLAN_CACHE: "OrderedDict[Tuple, Optional[WavePlan]]" = OrderedDict()
_PLAN_CACHE_SIZE = 32
_plan_lock = threading.Lock()


def waves_for(arrs, cfg, n_pods_total: Optional[int] = None
              ) -> Optional[WavePlan]:
    """The product entry point: plan for (host snapshot arrays, config),
    or None when the analysis found nothing to batch (the engine then
    keeps its exact pre-wave executable and cache key). Plans are cached
    by workload digest."""
    if cfg.extensions:
        return None  # extension ops may read/write any carry channel
    from open_simulator_tpu.telemetry.ledger import engine_config_hash

    key = _arrs_digests(arrs) + (
        engine_config_hash(cfg), int(n_pods_total or 0))
    with _plan_lock:
        if key in _PLAN_CACHE:
            _PLAN_CACHE.move_to_end(key)
            return _PLAN_CACHE[key]
    from open_simulator_tpu.telemetry.spans import span

    with span("wave_plan"):
        plan = compute_wave_plan(arrs, cfg, n_pods_total=n_pods_total)
    # Degenerate plans map to None so the engine keeps its pre-wave
    # executable — and, critically, its SHARED one: a wave plan is a
    # static jit argument keyed per workload, so "nothing batched but
    # the bucketing pad tail" (or only the prefix the hoist already
    # covers) must NOT trade the §9 same-bucket executable reuse for a
    # few sentinel steps. Only plans batching REAL pods survive.
    p_real = int(np.asarray(arrs.req).shape[0])
    if not any(s[2] != SCAN and s[0] < p_real for s in plan.segments):
        plan = None
    with _plan_lock:
        _PLAN_CACHE[key] = plan
        _PLAN_CACHE.move_to_end(key)
        while len(_PLAN_CACHE) > _PLAN_CACHE_SIZE:
            _PLAN_CACHE.popitem(last=False)
    if plan is not None:
        _log.debug("wave plan: %s", plan.stats())
    return plan
