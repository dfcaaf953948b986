"""Topology-domain primitives.

A topology key partitions nodes into domains (hostname -> every node its
own domain; zone/region -> few domains). Counting "pods matching selector
s within node n's domain" is the core aggregation behind InterPodAffinity
and PodTopologySpread. For non-hostname keys this is a pair of small
matmuls against the precomputed one-hot domain matrix ``O [N, D]``:

    per_domain = O^T @ v        # [D]
    per_node   = O @ per_domain # [N]  (broadcast domain total back to nodes)

For hostname (key id 0) the domain count is the vector itself. Both sides
are computed and selected with `jnp.where` — branchless, fusible, and
trace-once under jit (no data-dependent control flow).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from open_simulator_tpu.ops.exact import log_table, mm


def _onehot_for_key(topo_onehot: jnp.ndarray, key_id) -> jnp.ndarray:
    """Gather the [N, D] one-hot matrix for a (traced) key id >= 1."""
    k1 = jnp.maximum(key_id - 1, 0)
    return topo_onehot[k1]  # dynamic gather along K1


def domain_count(count_vec: jnp.ndarray, key_id, topo_onehot: jnp.ndarray) -> jnp.ndarray:
    """[N] -> [N]: for each node, the sum of count_vec over its topology domain."""
    oh = _onehot_for_key(topo_onehot, key_id)
    per_node = mm(oh, mm(oh.T, count_vec))
    return jnp.where(key_id == 0, count_vec, per_node)


def domain_min(count_vec: jnp.ndarray, key_id, topo_onehot: jnp.ndarray, eligible: jnp.ndarray):
    """Global min of per-domain totals over domains containing >=1 eligible node.

    Returns (min_value, any_eligible_domain). Matches the PodTopologySpread
    `minMatchNum` semantics (vendored podtopologyspread/filtering.go).
    """
    big = jnp.float32(3.4e38)
    oh = _onehot_for_key(topo_onehot, key_id)
    elig_f = eligible.astype(count_vec.dtype)
    per_domain = mm(oh.T, count_vec)                  # [D]
    domain_has = mm(oh.T, elig_f) > 0                 # [D]
    min_other = jnp.min(jnp.where(domain_has, per_domain, big))
    # hostname: every node is a domain; min over eligible nodes directly
    min_host = jnp.min(jnp.where(eligible, count_vec, big))
    any_elig = jnp.any(eligible)
    min_val = jnp.where(key_id == 0, min_host, min_other)
    return jnp.where(any_elig, min_val, jnp.float32(0.0)), any_elig


class ActiveHoist(NamedTuple):
    """Scan-loop-invariant domain statistics, computed once per (arrs,
    active) pair before the pod scan instead of per step. `active` never
    changes inside a scan, so everything derived from it — domain
    membership of active nodes, per-class eligibility — is hoisted here
    (the analog of the reference computing its node snapshot once per
    scheduling cycle, vendored generic_scheduler.go:85)."""

    dom_counts: jnp.ndarray   # [K] f32: #domains holding an active node, per key
    log_dom: jnp.ndarray      # [K] f32: log(dom_counts + 2) — the spread
                              # topologyNormalizingWeight, hoisted
    elig_host: jnp.ndarray    # [C, N] bool: active & class-affinity (hostname elig)
    domain_has: jnp.ndarray   # [C, K1, D] bool: domain holds an eligible node
    any_elig: jnp.ndarray     # [C, K] bool: any eligible node exists under key
    dom_idx: jnp.ndarray      # [K1, N] i32: the node's domain per key, D if
                              # it lacks the key — a one-hot broadcast
                              # `O @ v` is the exact gather `v[dom_idx]`


def hoist_active_stats(
    topo_onehot: jnp.ndarray,   # [K1, N, D]
    has_key: jnp.ndarray,       # [K, N]
    class_affinity: jnp.ndarray,  # [C, N] bool
    active: jnp.ndarray,        # [N] bool
) -> ActiveHoist:
    f32 = jnp.float32
    act = active.astype(f32)
    k1 = topo_onehot.shape[0]
    # domains-with-an-active-member per key (hostname = active node count)
    dom_counts = [jnp.sum(act)]
    for k in range(k1):
        present = jnp.any((topo_onehot[k] * act[:, None]) > 0, axis=0)   # [D]
        dom_counts.append(jnp.sum(present.astype(f32)))
    # per-class spread eligibility: active & class node-affinity & has-key
    elig_ck = class_affinity[:, None, :] & active[None, None, :] & (has_key[None, :, :] > 0)  # [C, K, N]
    domain_has = jnp.stack([
        mm(elig_ck[:, k + 1, :].astype(f32), topo_onehot[k]) > 0 for k in range(k1)
    ], axis=1) if k1 else jnp.zeros((class_affinity.shape[0], 0, 0), bool)   # [C, K1, D]
    stacked = jnp.stack(dom_counts)
    return ActiveHoist(
        dom_counts=stacked,
        # counts are exact integers <= N, so log(count + 2) is a lookup
        log_dom=log_table(active.shape[0] + 3)[
            stacked.astype(jnp.int32) + 2],
        elig_host=elig_ck[:, 0, :],
        domain_has=domain_has,
        any_elig=jnp.any(elig_ck, axis=2),
        dom_idx=domain_index(topo_onehot),
    )


def domain_index(topo_onehot: jnp.ndarray) -> jnp.ndarray:
    """[K1, N] i32 domain id of every node under every non-hostname key;
    D (one past the last domain) where the node lacks the key."""
    d = topo_onehot.shape[2]
    ids = jnp.max(jnp.where(topo_onehot > 0, jnp.arange(d, dtype=jnp.int32),
                            -1), axis=2)
    return jnp.where(ids < 0, d, ids)


# v5e, per 1,024 f32: one domain's compare+select ~1.3 ns; gather + relayout 3 HBM passes, 5+ ns each
SELECT_MAX_DOMAINS = 16


def broadcast_domains(per_domain: jnp.ndarray, dom_idx: jnp.ndarray) -> jnp.ndarray:
    """[D, ...] per-domain values -> [N, ...] per node by domain id, 0
    where the node lacks the key: `O @ per_domain` without a matmul, exact
    on every backend. Few domains (zones) select by id, so XLA fuses the
    broadcast into the op that reads it; many (racks) take one gather."""
    d = per_domain.shape[0]
    if d > SELECT_MAX_DOMAINS:
        return jnp.take(per_domain, dom_idx, axis=0, mode="fill", fill_value=0)
    idx = dom_idx.reshape(dom_idx.shape + (1,) * (per_domain.ndim - 1))
    out = jnp.zeros(dom_idx.shape + per_domain.shape[1:], per_domain.dtype)
    for k in range(d):
        out = jnp.where(idx == k, per_domain[k], out)
    return out


def domain_min_hoisted(
    count_vec: jnp.ndarray, key_id, class_id, topo_onehot: jnp.ndarray, h: ActiveHoist
) -> jnp.ndarray:
    """domain_min with the eligibility side precomputed (ActiveHoist): the
    in-loop work is one [D, N] mat-vec + a masked min, instead of an extra
    eligibility mat-vec per constraint per step."""
    big = jnp.float32(3.4e38)
    oh = _onehot_for_key(topo_onehot, key_id)
    per_domain = mm(oh.T, count_vec)                  # [D]
    dhas = h.domain_has[class_id, jnp.maximum(key_id - 1, 0)]
    min_other = jnp.min(jnp.where(dhas, per_domain, big))
    min_host = jnp.min(jnp.where(h.elig_host[class_id], count_vec, big))
    min_val = jnp.where(key_id == 0, min_host, min_other)
    return jnp.where(h.any_elig[class_id, key_id], min_val, jnp.float32(0.0))


def same_domain(node_id, key_id, topo_onehot: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
    """[N] float mask: nodes sharing node_id's domain under key_id
    (used to paint anti-affinity term blocks across a domain on bind)."""
    oh = _onehot_for_key(topo_onehot, key_id)
    dom_row = oh[node_id]                             # [D]
    same = mm(oh, dom_row)                            # [N]
    host = jnp.zeros((n_nodes,), dtype=topo_onehot.dtype).at[node_id].set(1.0)
    return jnp.where(key_id == 0, host, same)
