"""Compile-once, run-many: shape bucketing + AOT executable cache.

Every new snapshot shape used to trigger a full XLA recompile of the
scheduling scan — a re-simulated cluster that grew by one node, the
applier's reasons-on re-run, and every fresh `jax.jit(jax.vmap(...))`
wrapper in the sweep paid compile time again. This module amortizes all
of that:

* **Bucketing** (`bucket_dim`, `pad_snapshot_arrays`): the node and pod
  axes of `SnapshotArrays` are padded up to bucket boundaries — next
  power of two with a linear tail, like serving-stack batch bucketing —
  so every snapshot inside a bucket presents ONE shape to XLA. Padded
  nodes are inactive (never feasible, never scored into a normalizer any
  differently than existing inactive nodes) and padded pods are
  bind-nothing sentinels (`forced_node == -4`, zero requests), so
  results are bit-identical to the unpadded run; callers slice the
  pod-axis outputs back with `unpad_output`.

* **AOT executable cache** (`run_batched_cached`): the batched sweep
  executable — `jax.jit(...).lower(...).compile()` — is cached in a
  bounded LRU keyed on `(fn, cfg, array shapes, lane count, devices)`.
  The sweep previously rebuilt a fresh `jax.jit(jax.vmap(lambda ...))`
  wrapper per call, which defeats jax's own function-identity cache;
  here round two of a bisection (and every later capacity question in
  the same bucket) reuses round one's executable.

* **Donated carries**: the cached executable takes the scan carry batch
  as an argument and donates it (`donate_argnums`), resetting it to the
  pristine init state on device. Back-to-back sweep rounds hand the
  previous round's output state in, so the `[S, N, R]` headroom (and
  the rest of the carry roster) stops double-buffering in HBM.
  Contract: a donated state is DEAD after the call — host anything you
  need from it first (see ARCHITECTURE.md section 9).

* **Persistent compilation cache** (`enable_persistent_cache`): every
  entry point places jax's on-disk cache once at start —
  `JAX_COMPILATION_CACHE_DIR` if set, else `--compile-cache-dir`, else
  `<checkout>/.jax_cache` — so restarts skip cold compiles.

Telemetry extends the PR 3 jit-cache series instead of inventing names:
hits/misses/evictions land in `simon_compile_cache_total{fn, event}` and
compile wall time is a "compile" span (-> `simon_phase_seconds`).

Trace-safety: all cache bookkeeping here is host-side (dict ops, string
keys, counters) and runs strictly OUTSIDE jit scope; the traced bodies
stay pure jnp (the pattern pinned by
tests/fixtures/lint/gl4_execcache_ok.py).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from open_simulator_tpu.encode.snapshot import (
    NODE_AXIS_FIRST,
    NODE_AXIS_SECOND,
    POD_AXIS_FIRST,
    SnapshotArrays,
)

_log = logging.getLogger(__name__)


# ---- bucketing policy ---------------------------------------------------

@dataclass(frozen=True)
class BucketPolicy:
    """Round an axis length up to its bucket boundary.

    Power-of-two steps up to `linear_from`, then multiples of
    `linear_step` (the serving-stack batch-bucketing shape ladder:
    geometric where relative padding waste is bounded, linear where a
    doubling would waste half the axis). The defaults keep the tracked
    north-star shape (5120 nodes x 51200 pods) exactly on a boundary, so
    the benchmark series stays comparable.
    """

    enabled: bool = True
    node_linear_from: int = 1024
    node_linear_step: int = 1024
    pod_linear_from: int = 2048
    pod_linear_step: int = 2048


def _default_policy() -> BucketPolicy:
    # SIMON_BUCKETING=0 opts the whole process out (debug escape hatch)
    return BucketPolicy(enabled=os.environ.get("SIMON_BUCKETING", "1") != "0")


DEFAULT_POLICY = _default_policy()


def bucket_dim(n: int, linear_from: int, linear_step: int) -> int:
    """Smallest bucket boundary >= n (n <= 0 passes through untouched)."""
    if n <= 0:
        return n
    if n <= linear_from:
        p = 1
        while p < n:
            p *= 2
        return p
    return -(-n // linear_step) * linear_step


def bucket_shape(n_nodes: int, n_pods: int,
                 policy: Optional[BucketPolicy] = None) -> Tuple[int, int]:
    p = policy or DEFAULT_POLICY
    if not p.enabled:
        return n_nodes, n_pods
    return (bucket_dim(n_nodes, p.node_linear_from, p.node_linear_step),
            bucket_dim(n_pods, p.pod_linear_from, p.pod_linear_step))


# ---- SnapshotArrays padding --------------------------------------------

# Non-default pad values. Everything else pads with 0/False, which is the
# "does not exist" encoding already used for inactive nodes and invalid
# term slots: forced_node -4 is the engine's bind-nothing sentinel (the
# pre-reason path), the slot arrays use -1 as their empty marker, and a
# padded node is marked unschedulable for defense in depth (its active
# mask is already False, which alone keeps it infeasible and scored like
# any other inactive node).
_PAD_VALUES: Dict[str, Any] = {
    "forced_node": -4,
    "match_gid": -1,
    "own_tid": -1,
    "hit_tid": -1,
    "svol_id": -1,
    "unschedulable": True,
}


def pad_snapshot_arrays(arrs: SnapshotArrays, n_nodes_to: int,
                        n_pods_to: int) -> SnapshotArrays:
    """Pad the node and pod axes up to the given sizes (host numpy).

    Padded nodes are inactive (`active` False) and padded pods are
    bind-nothing sentinels, so the scan's placements, failure counts for
    real pods, and carry trajectory are bit-identical to the unpadded
    run — the padding only changes the static shapes XLA compiles for.
    """
    n = arrs.alloc.shape[0]
    p = arrs.req.shape[0]
    dn = n_nodes_to - n
    dp = n_pods_to - p
    if dn < 0 or dp < 0:
        raise ValueError(
            f"bucket ({n_nodes_to}, {n_pods_to}) smaller than snapshot "
            f"({n}, {p})")
    if dn == 0 and dp == 0:
        return arrs

    def pad(name: str, x):
        x = np.asarray(x)
        if name in NODE_AXIS_FIRST:
            axis, grow = 0, dn
        elif name in NODE_AXIS_SECOND:
            axis, grow = 1, dn
        elif name in POD_AXIS_FIRST:
            axis, grow = 0, dp
        else:
            return x
        if grow == 0:
            return x
        fill = _PAD_VALUES.get(name, False if x.dtype == np.bool_ else 0)
        shape = list(x.shape)
        shape[axis] = grow
        block = np.full(shape, fill, dtype=x.dtype)
        return np.concatenate([x, block], axis=axis)

    out = {f.name: pad(f.name, getattr(arrs, f.name))
           for f in dataclasses.fields(arrs)}
    return type(arrs)(**out)


def bucketed_device_arrays(arrs: SnapshotArrays,
                           policy: Optional[BucketPolicy] = None):
    """Pad to the bucket and transfer to the default device in one hop.
    Returns (device_arrays, n_nodes_orig, n_pods_orig) — the originals
    are what `unpad_output` and host-side decode need back."""
    import jax
    import jax.numpy as jnp

    n, p = arrs.alloc.shape[0], arrs.req.shape[0]
    nb, pb = bucket_shape(n, p, policy)
    padded = pad_snapshot_arrays(arrs, nb, pb)
    return jax.tree_util.tree_map(jnp.asarray, padded), n, p


def pad_vector(vec, n_to: int, fill):
    """Widen a host [K] vector to a padded axis length (None passes
    through) — the preemption victim/nomination columns and chaos active
    masks are built against the real axis and padded at the call site."""
    if vec is None:
        return None
    vec = np.asarray(vec)
    if vec.shape[0] >= n_to:
        return vec
    out = np.full((n_to,), fill, dtype=vec.dtype)
    out[: vec.shape[0]] = vec
    return out


def unpad_output(out, n_pods: int):
    """Slice the pod-axis outputs of a ScheduleOutput back to the real pod
    count (the state keeps its padded node axis; host consumers read it
    through active masks)."""
    if out.node.shape[0] == n_pods:
        return out
    return out._replace(
        node=out.node[:n_pods],
        fail_counts=out.fail_counts[:n_pods],
        feasible=out.feasible[:n_pods],
        gpu_pick=out.gpu_pick[:n_pods],
        vol_pick=out.vol_pick[:n_pods],
        topk_node=out.topk_node[:n_pods],
        topk_score=out.topk_score[:n_pods],
        topk_parts=out.topk_parts[:n_pods],
    )


# ---- AOT executable cache ----------------------------------------------

# the XLA cost fields harvested per executable (ISSUE 18): flops and
# bytes accessed from compiled.cost_analysis(), the peak-HBM estimate
# assembled from memory_analysis() sizes (arguments + outputs + temp
# scratch, minus donated aliasing)
_COST_FIELDS = ("flops", "bytes_accessed", "peak_hbm_bytes")


def harvest_cost(compiled) -> Dict[str, Any]:
    """Read the per-executable XLA cost profile, defensively.

    `cost_analysis()` returns a dict on current jax, a one-element list
    on older versions, and raises/returns None on backends that do not
    implement it (CPU included on some versions); `memory_analysis()`
    mirrors that. Harvest failures yield an empty profile — cost
    accounting must never fail a compile."""
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend-optional API
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        if ca.get("flops") is not None:
            out["flops"] = float(ca["flops"])
        ba = ca.get("bytes accessed", ca.get("bytes_accessed"))
        if ba is not None:
            out["bytes_accessed"] = float(ba)
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — backend-optional API
        ma = None
    if ma is not None:
        sizes: Dict[str, float] = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)):
                sizes[attr] = float(v)
        if sizes:
            out["memory"] = sizes
            # live-at-once estimate: arguments + outputs + scratch, with
            # donated buffers (aliased into outputs) counted once
            out["peak_hbm_bytes"] = max(0.0, (
                sizes.get("argument_size_in_bytes", 0.0)
                + sizes.get("output_size_in_bytes", 0.0)
                + sizes.get("temp_size_in_bytes", 0.0)
                - sizes.get("alias_size_in_bytes", 0.0)))
    return out


def _carry_nbytes(carry) -> int:
    """Summed device bytes of a carry batch's leaves — what the devmem
    ledger accounts for a donated carry while a launch owns it."""
    import jax

    return sum(int(getattr(leaf, "nbytes", 0) or 0)
               for leaf in jax.tree_util.tree_leaves(carry))


def _key_digest(key: Tuple) -> str:
    """Stable short digest of a cache key — the devmem ledger's and
    /debug/executables' holder identity for an executable."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:12]


def _shape_sig(arrs) -> Tuple:
    out = []
    for f in dataclasses.fields(arrs):
        x = getattr(arrs, f.name)
        out.append((f.name, tuple(x.shape), str(x.dtype)))
    return tuple(out)


class ExecutableCache:
    """Bounded LRU of AOT-compiled executables.

    Keys are host tuples (fn name, EngineConfig, shape signatures, device
    ids); values are `jax.stages.Compiled` objects. Thread-safe: the REST
    server can answer capacity questions concurrently with a chaos run.
    Hits/misses/evictions extend the PR 3 `simon_compile_cache_total`
    series; compile wall time is recorded as a "compile" span.
    """

    def __init__(self, capacity: int = 8):
        self.capacity = max(1, int(capacity))
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        # parallel store: cached values must stay directly callable, so
        # the harvested cost profile lives beside the executable, keyed
        # and evicted identically
        self._costs: Dict[Tuple, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        self._hooks_installed = False

    def _count(self, fn_name: str, event: str) -> None:
        from open_simulator_tpu.telemetry import counter
        from open_simulator_tpu.telemetry.runtime import COMPILE_CACHE_TOTAL

        counter(
            COMPILE_CACHE_TOTAL,
            "jit compilation-cache outcomes per schedule phase",
            labelnames=("fn", "event"),
        ).labels(fn=fn_name, event=event).inc()

    def get_or_compile(self, key: Tuple, fn_name: str,
                       build: Callable[[], Any]):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self._count(fn_name, "hit")
                return hit
        # compile OUTSIDE the lock: a cold north-star compile takes
        # minutes and must not block a concurrent cache hit
        self._count(fn_name, "miss")
        from open_simulator_tpu.resilience import faults
        from open_simulator_tpu.telemetry.spans import span

        # the compile boundary of the device fault domain: an injected
        # (or real) compilation failure surfaces here — classified by
        # the caller's launch wrapper, never retried (E_COMPILE is
        # deterministic)
        faults.maybe_inject("compile")
        t0 = time.perf_counter()
        with span("compile", fn=fn_name):
            compiled = build()
        compile_s = time.perf_counter() - t0
        _log.debug("compiled %s in %.3fs (cache size %d)", fn_name,
                   compile_s, len(self._entries) + 1)
        # harvest the XLA cost profile at compile time — one host-side
        # read per compile, amortized over every cached launch
        cost = harvest_cost(compiled)
        cost["fn"] = fn_name
        cost["compile_s"] = round(compile_s, 6)
        self._install_hooks()
        from open_simulator_tpu.telemetry.context import BLACKBOX

        BLACKBOX.record("compile", fn=fn_name,
                        compile_ms=round(compile_s * 1000.0, 3),
                        flops=cost.get("flops"),
                        peak_hbm_bytes=cost.get("peak_hbm_bytes"))
        from open_simulator_tpu.telemetry import live

        evicted: List[Tuple] = []
        with self._lock:
            self._entries[key] = compiled
            self._entries.move_to_end(key)
            self._costs[key] = cost
            while len(self._entries) > self.capacity:
                k, _ = self._entries.popitem(last=False)
                self._costs.pop(k, None)
                self._count(fn_name, "eviction")
                evicted.append(k)
        # devmem ledger: an AOT executable holds its generated code on
        # device — registered by cache-key digest, released on eviction
        code_bytes = int((cost.get("memory") or {})
                         .get("generated_code_size_in_bytes") or 0)
        live.DEVMEM.register(live.OWNER_EXECUTABLES,
                             _key_digest(key), code_bytes)
        for k in evicted:
            live.DEVMEM.release(live.OWNER_EXECUTABLES, _key_digest(k))
        return compiled

    def _install_hooks(self) -> None:
        """Register the simon_exec_cost_* callback gauges + the ledger
        cost provider, once, lazily (at the first compile — a process
        that never compiles never touches the registry)."""
        if self._hooks_installed:
            return
        self._hooks_installed = True
        from open_simulator_tpu.telemetry import gauge, ledger

        def sample(field):
            def cb():
                return {(fn,): v[field]
                        for fn, v in self.cost_snapshot().items()
                        if isinstance(v.get(field), (int, float))}
            return cb

        gauge("simon_exec_cost_flops",
              "XLA cost_analysis flops of the newest cached executable "
              "per launch fn", labelnames=("fn",)).set_callback(
                  sample("flops"))
        gauge("simon_exec_cost_bytes_accessed",
              "XLA cost_analysis bytes accessed of the newest cached "
              "executable per launch fn", labelnames=("fn",)).set_callback(
                  sample("bytes_accessed"))
        gauge("simon_exec_cost_peak_hbm_bytes",
              "estimated live-at-once device bytes (args + outputs + "
              "temp - aliased) of the newest cached executable per "
              "launch fn", labelnames=("fn",)).set_callback(
                  sample("peak_hbm_bytes"))
        ledger.set_cost_provider(self.cost_snapshot)

        # the devmem ledger's in-flight estimator: a launch of fn is
        # assumed to touch its newest executable's peak-HBM estimate
        # (registered as a hook — telemetry must not import the engine)
        from open_simulator_tpu.telemetry import live

        def estimate(fn: str):
            v = (self.cost_snapshot().get(fn) or {}).get("peak_hbm_bytes")
            return float(v) if isinstance(v, (int, float)) else None

        live.set_inflight_estimator(estimate)

    def cost_snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Per-fn cost summary ({fn: {flops, bytes_accessed,
        peak_hbm_bytes, compile_s, entries}}; the newest entry's profile
        wins when a fn holds several shapes). Feeds the gauges, the
        ledger cost section, and bench JSON lines."""
        with self._lock:
            costs = [dict(c) for c in self._costs.values()]
        out: Dict[str, Dict[str, Any]] = {}
        for cost in costs:  # insertion-ordered: newest last
            fn = cost.pop("fn", "?")
            cost.pop("memory", None)
            agg = out.setdefault(fn, {"entries": 0})
            entries = agg["entries"] + 1
            agg.update(cost)
            agg["entries"] = entries
        return out

    def debug_entries(self) -> List[Dict[str, Any]]:
        """One row per cached executable (GET /debug/executables): the
        launch fn, a stable digest of the cache key, and the full
        harvested cost profile."""
        with self._lock:
            items = [(k, dict(self._costs.get(k, {})))
                     for k in self._entries.keys()]
        rows = []
        for key, cost in items:
            fn = cost.pop("fn", key[0] if key else "?")
            rows.append({
                "fn": fn,
                "key": _key_digest(key),
                "cost": cost,
            })
        return rows

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._costs.clear()
        from open_simulator_tpu.telemetry import live

        live.DEVMEM.release_owner(live.OWNER_EXECUTABLES)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


EXEC_CACHE = ExecutableCache(
    capacity=int(os.environ.get("SIMON_EXEC_CACHE_SIZE", "8")))


def _fresh_lane_state(prev, arrs):
    """Reset a (donated) carry to the pristine init values on device.

    Reading every leaf (`x * 0` / `x & False`) keeps the donated buffers
    live inputs so XLA aliases them into the output state instead of
    allocating a second copy; the values are exactly `init_state`'s
    (zeros everywhere, headroom = alloc)."""
    import jax
    import jax.numpy as jnp

    def z(x):
        return x & False if jnp.issubdtype(x.dtype, jnp.bool_) else x * 0

    zeroed = jax.tree_util.tree_map(z, prev)
    return zeroed._replace(
        headroom=zeroed.headroom + jnp.asarray(arrs.alloc, jnp.float32))


def _zeros_carry_batch(arrs, cfg, lanes: int):
    import jax
    import jax.numpy as jnp

    from open_simulator_tpu.engine.scheduler import init_state

    proto = init_state(arrs, cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((lanes,) + x.shape, x.dtype), proto)


@functools.lru_cache(maxsize=32)
def batched_lane_fn(cfg, waves, with_weights: bool):
    """The batched scan body as a MODULE-LEVEL function of its static
    configuration — (cfg, waves, weights-mode) — instead of a per-call
    closure. One Python callable per static config means jax's own
    function-identity cache can also see reuse, and (more importantly)
    the mesh path below traces EXACTLY the program the single-device AOT
    path traces: lanes vmapped over (mask_row, carry_row[, w_row]), the
    donated carry reset in place per the §9 x*0 contract. cfg is a
    hashable EngineConfig NamedTuple and waves a hashable WavePlan (both
    already serve as executable-cache key components)."""
    import jax

    from open_simulator_tpu.engine.scheduler import schedule_pods

    if with_weights:
        def fnw(a, m, c, w):
            def lane(mask_row, carry_row, w_row):
                return schedule_pods(a, mask_row, cfg,
                                     state=_fresh_lane_state(carry_row, a),
                                     state_is_fresh=True, waves=waves,
                                     weights=w_row)

            return jax.vmap(lane)(m, c, w)

        return fnw

    def fn(a, m, c):
        def lane(mask_row, carry_row):
            return schedule_pods(a, mask_row, cfg,
                                 state=_fresh_lane_state(carry_row, a),
                                 state_is_fresh=True, waves=waves)

        return jax.vmap(lane)(m, c)

    return fn


def _check_lane_weights(cfg, weights, lanes: int):
    """Shared [S, K] validation for the single-device and mesh paths:
    a traced cfg with no explicit weights runs every lane at the
    config's own vector (digest-identical to constant mode); passing
    weights with ``traced_weights`` off is an error."""
    import jax.numpy as jnp

    from open_simulator_tpu.engine.scheduler import WEIGHT_FIELDS, weight_vector

    if cfg.traced_weights and weights is None:
        weights = np.tile(weight_vector(cfg), (lanes, 1))
    if weights is None:
        return None
    if not cfg.traced_weights:
        raise ValueError(
            "per-lane weights need cfg.traced_weights (the constant "
            "engine bakes its weights into the executable)")
    weights = jnp.asarray(weights, jnp.float32)
    if weights.shape != (lanes, len(WEIGHT_FIELDS)):
        raise ValueError(
            f"weights must be [{lanes}, {len(WEIGHT_FIELDS)}] "
            f"(lanes x WEIGHT_FIELDS), got {tuple(weights.shape)}")
    return weights


def _home_device(arrs):
    """The one device a single-device batch runs on: where the snapshot
    arrays already live (a caller may commit them anywhere, e.g. to the
    CPU for a reference run beside the chip), else JAX's default."""
    import jax

    x = arrs.alloc
    if isinstance(x, jax.Array) and len(x.devices()) == 1:
        return next(iter(x.devices()))
    return jax.devices()[0]


def run_batched_cached(arrs, masks, cfg, carry=None,
                       fn_name: str = "batched_schedule", waves=None,
                       weights=None, retries: int = 2,
                       backoff_s: float = 0.05):
    """Run the vmapped scan over scenario lanes through the AOT cache.

    `masks` is the [S, N] per-lane active matrix. `carry` is an optional
    donated state batch (a previous round's `out.state`); its buffers are
    reset to the init values on device and reused for this round's carry
    — after the call the passed-in state is DEAD. With carry=None a fresh
    zeros batch is allocated (and still donated, so the executable is the
    same either way). `waves` is an optional static WavePlan
    (engine/waves.py): it joins the cache key — wave count/width are part
    of the compiled program — so same-plan reruns stay zero-recompile
    and a plan change never aliases a stale executable.

    `weights` is the per-lane [S, K] traced score-weight matrix
    (scheduler.WEIGHT_FIELDS order) under ``cfg.traced_weights`` — the
    tune subsystem's lane axis: W policy variants share THIS one
    executable. Omitted under a traced config, every lane runs the
    config's own ``weight_vector`` (so the capacity sweeps work
    unchanged under a traced config, digest-identical to constant mode);
    passing weights with ``traced_weights`` off is an error."""
    import jax
    import jax.numpy as jnp

    dev = _home_device(arrs)
    arrs = jax.device_put(arrs, dev)
    masks = jax.device_put(jnp.asarray(masks), dev)
    lanes = int(masks.shape[0])
    weights = _check_lane_weights(cfg, weights, lanes)
    if weights is not None:
        weights = jax.device_put(weights, dev)
    if carry is None:
        carry = _zeros_carry_batch(arrs, cfg, lanes)
    carry = jax.device_put(carry, dev)
    key = (fn_name, cfg, _shape_sig(arrs), (lanes,) + tuple(masks.shape[1:]),
           str(masks.dtype), waves,
           None if weights is None else tuple(weights.shape), str(dev))
    fn = batched_lane_fn(cfg, waves, weights is not None)

    def build():
        if weights is None:
            return jax.jit(fn, donate_argnums=(2,)).lower(
                arrs, masks, carry).compile()
        return jax.jit(fn, donate_argnums=(2,)).lower(
            arrs, masks, carry, weights).compile()

    from open_simulator_tpu.resilience import faults

    # The fault domain around the launch. The donated carry backs the
    # FIRST attempt only: a launch that executed-and-failed consumed its
    # buffers, so every re-attempt (transient retry or ladder rung) runs
    # from a fresh zeros batch — value-identical, because the executable
    # resets the carry to the init state on device either way.
    holder = {"carry": carry}

    def fire():
        compiled = EXEC_CACHE.get_or_compile(key, fn_name, build)
        c = holder.pop("carry", None)
        if c is None:
            c = jax.device_put(_zeros_carry_batch(arrs, cfg, lanes), dev)
        out = (compiled(arrs, masks, c) if weights is None
               else compiled(arrs, masks, c, weights))
        # block INSIDE the fault domain: dispatch is async, so a real
        # device fault otherwise surfaces at the caller's first host
        # read — outside this wrapper, unclassified. Every caller hosts
        # immediately after, so the sync costs no pipelining.
        return jax.block_until_ready(out)

    # OOM rung: run_cached_launch evicts every cached executable (their
    # buffers and scratch are what crowd the device) and re-compiles +
    # re-launches once from fresh buffers — bit-identical outputs, later
    from open_simulator_tpu.telemetry import live

    carry_key = f"{fn_name}:{id(holder):x}"
    live.DEVMEM.register(live.OWNER_CARRIES, carry_key, _carry_nbytes(carry))
    try:
        return faults.run_cached_launch(fn_name, fire,
                                        evict=EXEC_CACHE.clear,
                                        retries=retries, backoff_s=backoff_s)
    finally:
        live.DEVMEM.release(live.OWNER_CARRIES, carry_key)


def refuse_node_axis(n_node: int) -> None:
    """Meshes split lanes over "scenario" only. A "node" axis above 1 is
    refused: splitting the node-major snapshot fields over it placed
    ~51,150 of 51,200 pods per lane differently from one chip on a 2x2
    v5e mesh (chip_smoke.py --mesh, PR 21) while XLA:CPU matched. With
    the one-hot broadcasts turned into domain-id gathers the same split
    matched chip 0 in a diagnostic run, but the product path has not
    been run on four chips since — ROADMAP B3."""
    if n_node > 1:
        raise ValueError(
            f"a 'node' mesh axis of {n_node} is refused until the "
            "node-split program is verified on TPU against one chip "
            "(ROADMAP B3); use n_node=1 and split lanes over 'scenario'")


def mesh_shardings(arrs, carry, mesh):
    """((arrs, masks, carry, weights) in-shardings, out-shardings) of the
    mesh executable: lanes split over "scenario", the snapshot arrays
    replicated on every chip (`refuse_node_axis` says why there is no
    node split)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from open_simulator_tpu.engine.scheduler import ScheduleOutput

    refuse_node_axis(int(dict(mesh.shape).get("node", 1)))
    replicated = NamedSharding(mesh, P())
    lane_sh = NamedSharding(mesh, P("scenario"))
    rows_sh = NamedSharding(mesh, P("scenario", None))
    carry_sh = jax.tree_util.tree_map(lambda _: lane_sh, carry)
    # every output follows the lane axis, the state included — matching
    # the donated carry's in_sharding so donation aliases shard-for-shard
    out_sh = ScheduleOutput(
        node=lane_sh, fail_counts=lane_sh, feasible=lane_sh,
        gpu_pick=lane_sh, vol_pick=lane_sh, topk_node=lane_sh,
        topk_score=lane_sh, topk_parts=lane_sh, state=carry_sh)
    return ((jax.tree_util.tree_map(lambda _: replicated, arrs), rows_sh,
             carry_sh, rows_sh), out_sh)


def run_mesh_cached(arrs, masks, cfg, mesh, carry=None,
                    fn_name: str = "mesh_schedule", waves=None,
                    weights=None, retries: int = 2,
                    backoff_s: float = 0.05):
    """`run_batched_cached` under a GSPMD mesh: the SAME module-level
    lane-fn, AOT-compiled with in/out shardings — scenario lanes split
    across the "scenario" mesh axis, the snapshot replicated
    (`mesh_shardings`) — and cached under the single-device key EXTENDED by
    the mesh axis split. Same-bucket mesh launches are zero recompiles
    (`simon_compile_cache_total{fn=mesh_schedule}`), and because the
    traced program is identical to the single-device path's, outputs are
    digest-identical (the PR-7 multichip contract, now on the cached
    executable).

    Carry donation holds under the mesh: the donated state batch is
    sharded like the lane axis (every leaf `P("scenario", ...)`), its
    in_sharding equals the output state's out_sharding, so XLA aliases
    the buffers shard-for-shard and resets them in place per the §9 x*0
    contract — after the call the passed-in state is DEAD. `weights` is
    the [S, K] traced lane matrix, sharded along the scenario axis like
    the masks. Inputs are placed with `jax.device_put` against the
    declared shardings up front (a no-op for already-placed donated
    state / pre-sharded arrays), so callers may hand host arrays
    straight in."""
    import jax
    import jax.numpy as jnp

    masks = jnp.asarray(masks)
    lanes = int(masks.shape[0])
    weights = _check_lane_weights(cfg, weights, lanes)
    if carry is None:
        carry = _zeros_carry_batch(arrs, cfg, lanes)
    # the single-device key + the mesh axis split and the mesh's own
    # device set (a different split of the same chips is a different
    # partitioned program; jax.devices() alone cannot see that)
    axis_split = tuple((str(name), int(size))
                       for name, size in mesh.shape.items())
    key = (fn_name, cfg, _shape_sig(arrs), (lanes,) + tuple(masks.shape[1:]),
           str(masks.dtype), waves,
           None if weights is None else tuple(weights.shape),
           axis_split, tuple(str(d) for d in mesh.devices.flat))
    fn = batched_lane_fn(cfg, waves, weights is not None)

    (arrs_sh, mask_sh, carry_sh, w_sh), out_sh = mesh_shardings(
        arrs, carry, mesh)
    # place every input against its declared sharding BEFORE lowering —
    # a no-op for data already resident there (the donated state from
    # the previous round), a copy for host arrays and arrays placed
    # elsewhere; pjit rejects committed args whose sharding disagrees
    # with in_shardings, so placement cannot be deferred to launch time
    arrs = jax.device_put(arrs, arrs_sh)
    masks = jax.device_put(masks, mask_sh)
    carry = jax.device_put(carry, carry_sh)
    if weights is not None:
        weights = jax.device_put(weights, w_sh)
    def build():
        if weights is None:
            return jax.jit(
                fn, donate_argnums=(2,),
                in_shardings=(arrs_sh, mask_sh, carry_sh),
                out_shardings=out_sh,
            ).lower(arrs, masks, carry).compile()
        return jax.jit(
            fn, donate_argnums=(2,),
            in_shardings=(arrs_sh, mask_sh, carry_sh, w_sh),
            out_shardings=out_sh,
        ).lower(arrs, masks, carry, weights).compile()

    from open_simulator_tpu.resilience import faults

    # donated carry backs the FIRST attempt only; re-attempts (transient
    # retry, cache_drop rung) run from a fresh sharded zeros batch —
    # value-identical, the executable resets the carry either way
    holder = {"carry": carry}

    def fire():
        compiled = EXEC_CACHE.get_or_compile(key, fn_name, build)
        c = holder.pop("carry", None)
        if c is None:
            # a re-attempt (the donated batch died with the failed
            # launch): fresh sharded zeros, value-identical
            c = jax.device_put(_zeros_carry_batch(arrs, cfg, lanes),
                               carry_sh)
        if weights is None:
            out = compiled(arrs, masks, c)
        else:
            out = compiled(arrs, masks, c, weights)
        # block INSIDE the fault domain (async dispatch would surface a
        # real device fault at the caller's host read, unclassified)
        return jax.block_until_ready(out)

    # OOM rung: cache_drop evicts every cached executable — the mesh
    # executables with everything else — recompiles, and re-launches once
    # from a fresh sharded carry; bit-identical outputs, later. Anything
    # non-OOM re-raises for the caller's mesh -> single_device ladder.
    from open_simulator_tpu.telemetry import live

    carry_key = f"{fn_name}:{id(holder):x}"
    live.DEVMEM.register(live.OWNER_CARRIES, carry_key, _carry_nbytes(carry))
    try:
        return faults.run_cached_launch(fn_name, fire,
                                        evict=EXEC_CACHE.clear,
                                        retries=retries, backoff_s=backoff_s)
    finally:
        live.DEVMEM.release(live.OWNER_CARRIES, carry_key)


def stack_fleet_arrays(arrs_list):
    """Stack same-shape SnapshotArrays along a NEW leading lane axis —
    the fleet-lane batch (campaign/lanes.py). Every field must already
    agree in shape (same node/pod bucket AND the same vocab widths);
    callers group by the full `_shape_sig` before stacking."""
    first = arrs_list[0]
    sig = _shape_sig(first)
    for a in arrs_list[1:]:
        if _shape_sig(a) != sig:
            raise ValueError(
                "fleet lanes need shape-identical snapshots; group by "
                "the full shape signature before stacking")
    out = {}
    for f in dataclasses.fields(first):
        out[f.name] = np.stack(
            [np.asarray(getattr(a, f.name)) for a in arrs_list])
    return type(first)(**out)


def run_fleet_batched(arrs_batch, masks, cfg,
                      fn_name: str = "fleet_schedule"):
    """Run schedule_pods vmapped over PER-LANE SnapshotArrays: same-bucket
    fleet clusters (the §13 bucket-map witness) execute as lanes of ONE
    launch instead of one dispatch each. Where the scenario sweep
    lane-varies only the active mask, here the WHOLE snapshot batch is
    the vmapped input — `arrs_batch` is a SnapshotArrays whose every
    field carries a leading lane axis (stack_fleet_arrays), `masks` is
    the per-lane [S, N] active matrix. Each lane's outputs are
    bit-identical to running that cluster alone (the vmap adds no
    cross-lane ops; asserted in test_tune.py). Cached like every other
    executable; the key is the batch's own shape signature + cfg."""
    import jax
    import jax.numpy as jnp

    from open_simulator_tpu.engine.scheduler import init_state, schedule_pods

    arrs_batch = jax.tree_util.tree_map(jnp.asarray, arrs_batch)
    masks = jnp.asarray(masks)
    lanes = int(masks.shape[0])
    proto = init_state(
        jax.tree_util.tree_map(lambda x: x[0], arrs_batch), cfg)
    carry = jax.tree_util.tree_map(
        lambda x: jnp.zeros((lanes,) + x.shape, x.dtype), proto)
    key = (fn_name, cfg, _shape_sig(arrs_batch),
           (lanes,) + tuple(masks.shape[1:]), str(masks.dtype),
           tuple(str(d) for d in jax.devices()))

    def build():
        def fn(ab, m, c):
            def lane(a_row, mask_row, carry_row):
                return schedule_pods(
                    a_row, mask_row, cfg,
                    state=_fresh_lane_state(carry_row, a_row),
                    state_is_fresh=True)

            return jax.vmap(lane)(ab, m, c)

        return jax.jit(fn, donate_argnums=(2,)).lower(
            arrs_batch, masks, carry).compile()

    from open_simulator_tpu.resilience import faults

    # first attempt donates the carry built above; re-attempts rebuild
    # (an executed-but-failed launch consumed the donated buffers)
    holder = {"carry": carry}

    def fire():
        compiled = EXEC_CACHE.get_or_compile(key, fn_name, build)
        c = holder.pop("carry", None)
        if c is None:
            c = jax.tree_util.tree_map(
                lambda x: jnp.zeros((lanes,) + x.shape, x.dtype), proto)
        # block inside the fault domain (async dispatch would surface a
        # real fault at the caller's host read, unclassified)
        return jax.block_until_ready(compiled(arrs_batch, masks, c))

    from open_simulator_tpu.telemetry import live

    carry_key = f"{fn_name}:{id(holder):x}"
    live.DEVMEM.register(live.OWNER_CARRIES, carry_key, _carry_nbytes(carry))
    try:
        return faults.run_launch(fn_name, fire)
    finally:
        live.DEVMEM.release(live.OWNER_CARRIES, carry_key)


# ---- persistent compilation cache --------------------------------------

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the cache's home when neither the environment nor a flag names one: a
# fixed path inside the checkout (never a temp name, pid or time — a
# directory that moves is a cache that never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_persistent_dir: Optional[str] = None


def enable_persistent_cache(path: str = "") -> str:
    """Place jax's on-disk compilation cache; every entry point (CLI
    ``main``, the server, ``bench.py``, ``chip_smoke.py``) calls this
    once, before its first compile. ``JAX_COMPILATION_CACHE_DIR``, when
    set, IS the cache and no flag overrides it; otherwise ``path`` (the
    ``--compile-cache-dir`` flag), else ``DEFAULT_CACHE_DIR``. Returns
    the directory in effect. Idempotent."""
    global _persistent_dir
    path = os.environ.get(CACHE_ENV) or path or DEFAULT_CACHE_DIR
    if _persistent_dir == path:
        return path
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # the scan compiles this repo cares about are small on tier-1 shapes;
    # cache everything rather than only minute-long compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        # jax initializes its on-disk cache AT MOST ONCE, on the first
        # compile — and imports (chex) compile tiny helpers before any
        # caller can reach this function, freezing "no cache dir" forever.
        # Reset so the next compile re-initializes against the dir above.
        from jax._src import compilation_cache as _jax_cc

        _jax_cc.reset_cache()
    except Exception:  # noqa: BLE001 — private API drift: cache best-effort
        _log.warning("could not reset jax's compilation-cache state; the "
                     "persistent cache may stay cold this process")
    _persistent_dir = path
    _log.info("persistent compilation cache at %s", path)
    return path
