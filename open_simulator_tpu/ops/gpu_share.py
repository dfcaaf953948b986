"""Open-Gpu-Share as tensor ops.

Re-expresses the reference's GPU-share plugin + cache
(plugin/open-gpu-share.go, pkg/type/open-gpu-share/cache/gpunodeinfo.go)
on a dense per-device memory array:

  carry gpu_used [N, G]   memory used per device slot
  node  gpu_cap  [N]      per-device memory capacity (uniform per node)
        gpu_slot [N, G]   1.0 for real device slots

Allocation parity with AllocateGpuId (gpunodeinfo.go:232-290):

  * single-GPU (cnt == 1): tightest fit — the feasible device with the
    least idle memory, first (lowest id) wins ties;
  * multi-GPU: the two-pointer greedy packs requested GPUs onto devices in
    ascending id order, and a single physical device takes as many of the
    requested GPUs as its idle memory holds (floor(idle/mem) "slots") —
    so an assignment is a per-device COUNT, e.g. "0-0-1";
  * a pre-pinned gpu-index annotation is honored verbatim (found=true
    without capacity checks, gpunodeinfo.go:247-253).

Filter parity (open-gpu-share.go:51-81): no-GPU pods pass; otherwise the
node's TOTAL GPU capacity must cover the pod's per-GPU memory (the
reference compares against GetGpuMemoryFromPodAnnotation, NOT mem*cnt)
and AllocateGpuId must succeed (pinned pods auto-pass that second check).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from open_simulator_tpu.ops.exact import div, mul

_BIG = jnp.float32(3.4e38)


def _slots_per_device(
    gpu_used: jnp.ndarray, gpu_cap, gpu_slot: jnp.ndarray, mem_p: jnp.ndarray
) -> jnp.ndarray:
    """floor(idle/mem) per device — how many of the pod's requested GPUs a
    single physical device can hold (the two-pointer inner loop)."""
    free = gpu_cap - gpu_used
    mem_safe = jnp.where(mem_p > 0, mem_p, 1.0)
    slots = jnp.floor(div(jnp.clip(free, 0.0), mem_safe))
    return jnp.where(gpu_slot > 0, slots, 0.0)


def gpu_fit(
    gpu_used: jnp.ndarray,  # [N, G]
    gpu_cap: jnp.ndarray,   # [N]
    gpu_slot: jnp.ndarray,  # [N, G]
    mem_p: jnp.ndarray,     # scalar: per-device memory request
    cnt_p: jnp.ndarray,     # scalar: device count request
    has_forced_p: jnp.ndarray = False,  # scalar bool: pre-pinned gpu-index
) -> jnp.ndarray:
    """[N] bool: GPU-share Filter. The capacity precheck mirrors the
    reference exactly: node TOTAL GPU memory >= the pod's per-GPU memory
    (open-gpu-share.go:64-67 compares GetTotalGpuMemory against
    GetGpuMemoryFromPodAnnotation — NOT mem*count), then the two-pointer
    allocation must succeed: sum_d floor(idle_d/mem) >= cnt (for cnt == 1
    this reduces to "some device has idle >= mem"). Pods without a GPU
    request pass everywhere; pinned (gpu-index) pods skip the
    allocation-feasibility check like AllocateGpuId's early return
    (gpunodeinfo.go:247-253), so for them only the capacity precheck and
    device presence apply. A pin to a device id the node does not have is
    accepted here exactly like the reference accepts it: its cache drops
    the unknown id with a warning (gpunodeinfo.go:129-134, "failed to find
    the GPU ID"), so the pod holds no memory there — our debit lands on a
    gpu_slot=0 column, which _slots_per_device ignores, giving identical
    downstream placements."""
    has_dev = jnp.sum(gpu_slot, axis=1) > 0
    total_cap = gpu_cap * jnp.sum(gpu_slot, axis=1)
    cap_ok = total_cap >= mem_p
    slots = _slots_per_device(gpu_used, gpu_cap[:, None], gpu_slot, mem_p)  # [N, G]
    alloc_ok = jnp.sum(slots, axis=1) >= cnt_p
    ok = cap_ok & has_dev & (alloc_ok | jnp.asarray(has_forced_p, dtype=bool))
    return jnp.where(cnt_p > 0, ok, True)


def gpu_share_score(
    gpu_used: jnp.ndarray,
    gpu_cap: jnp.ndarray,
    gpu_slot: jnp.ndarray,
    mem_p: jnp.ndarray,
    cnt_p: jnp.ndarray,
    feasible: jnp.ndarray,
) -> jnp.ndarray:
    """Score mirrors the plugin's max-share formula on the GPU dimension
    (open-gpu-share.go:85-110): prefer nodes where the request consumes a
    larger share of remaining GPU memory (defragmentation bias)."""
    raw = gpu_share_raw(gpu_used, gpu_cap, gpu_slot, mem_p, cnt_p)
    lo = jnp.min(jnp.where(feasible, raw, _BIG))
    hi = jnp.max(jnp.where(feasible, raw, -_BIG))
    rng = hi - lo
    out = jnp.where(rng > 0, (raw - lo) * 100.0 / jnp.where(rng > 0, rng, 1.0), 0.0)
    return jnp.where(cnt_p > 0, jnp.where(feasible, out, 0.0), 0.0)


def gpu_share_raw(
    gpu_used: jnp.ndarray,
    gpu_cap: jnp.ndarray,
    gpu_slot: jnp.ndarray,
    mem_p: jnp.ndarray,
    cnt_p: jnp.ndarray,
) -> jnp.ndarray:
    """Pre-normalize raw of gpu_share_score (the engine folds the min/max
    into its single stacked per-step reduction)."""
    free_total = jnp.sum(jnp.where(gpu_slot > 0, gpu_cap[:, None] - gpu_used, 0.0), axis=1)
    want = mem_p * cnt_p
    avail = free_total - want
    share = jnp.where(avail > 0, div(want, jnp.where(avail > 0, avail, 1.0)),
                      jnp.where(want > 0, 1.0, 0.0))
    return mul(jnp.clip(share, 0.0, 1.0), 100.0)


def gpu_pick_devices(
    gpu_used_n: jnp.ndarray,  # [G] used on the chosen node
    gpu_cap_n: jnp.ndarray,   # scalar per-device capacity
    gpu_slot_n: jnp.ndarray,  # [G]
    mem_p: jnp.ndarray,
    cnt_p: jnp.ndarray,
    forced_counts: jnp.ndarray,  # [G] i32 pre-pinned multiplicities (gpu-index)
    has_forced: jnp.ndarray,     # scalar bool
) -> jnp.ndarray:
    """[G] int32: how many of the pod's requested GPUs each device receives
    (device d's memory debit is count*mem). Exact AllocateGpuId parity:
    tightest fit for cnt == 1, ascending-id two-pointer greedy with
    per-device multiplicity for cnt > 1, pinned gpu-index verbatim."""
    g = gpu_used_n.shape[0]
    free = gpu_cap_n - gpu_used_n
    feasible = (gpu_slot_n > 0) & (free >= mem_p)

    # multi-GPU: ascending two-pointer; device d takes
    # min(floor(idle_d/mem), cnt - slots already taken by devices < d)
    slots = _slots_per_device(gpu_used_n, gpu_cap_n, gpu_slot_n, mem_p)  # [G]
    before = jnp.cumsum(slots) - slots
    take = jnp.clip(cnt_p - before, 0.0, slots)
    complete = jnp.sum(slots) >= cnt_p                # two-pointer found?
    multi = jnp.where(complete, take, 0.0)

    # single GPU: tightest fit; argmin keeps the first (lowest id) on ties
    # like the reference's strict < update
    key = jnp.where(feasible, free, _BIG)
    sel = jnp.argmin(key)
    single = jax.nn.one_hot(sel, g, dtype=jnp.float32) * jnp.any(feasible)

    pick = jnp.where(cnt_p == 1, single, multi)
    pick = jnp.where(has_forced, forced_counts.astype(jnp.float32), pick)
    return (pick * (cnt_p > 0)).astype(jnp.int32)
