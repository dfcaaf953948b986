"""Filter ops: each returns an [N] boolean feasibility mask for one pod.

Per-step inputs are the scan carry (dynamic occupancy state) plus the
current pod's rows gathered from the snapshot arrays. All control flow is
branchless; padded term slots are neutralized with their `valid` flags.
"""

from __future__ import annotations

import jax.numpy as jnp

from open_simulator_tpu.ops.domains import domain_count, domain_min
from open_simulator_tpu.ops.exact import mm


def fit_per_resource(headroom: jnp.ndarray, req_p: jnp.ndarray) -> jnp.ndarray:
    """NodeResourcesFit (vendored noderesources/fit.go:221-283 fitsRequest):
    [N, R] bool — per-resource feasibility, so reasons can say which
    resource was insufficient. Zero-allocatable resources fail only if
    requested (matches k8s: a node that doesn't expose a resource cannot
    host a pod requesting it). The engine carries headroom = alloc - used,
    so the vendored `used + req <= alloc` is one compare against the carry
    (bit-equivalent: encoded requests are integer-valued below 2^24)."""
    return req_p[None, :] <= headroom


def ports_free(ports_used: jnp.ndarray, pod_ports: jnp.ndarray) -> jnp.ndarray:
    """NodePorts: no requested (hostPort, protocol) already taken on the node."""
    conflict = jnp.any(ports_used & pod_ports[None, :], axis=1)
    return ~conflict


def pod_affinity_ok(
    group_count: jnp.ndarray,   # [N, S] carry
    topo_onehot: jnp.ndarray,   # [K1, N, D]
    has_key: jnp.ndarray,       # [K, N]
    aff_group: jnp.ndarray,     # [A]
    aff_key: jnp.ndarray,       # [A]
    aff_valid: jnp.ndarray,     # [A]
    aff_self: jnp.ndarray,      # [A]
) -> jnp.ndarray:
    """InterPodAffinity required terms (vendored interpodaffinity/filtering.go
    satisfyPodAffinity): every term needs a matching pod in the node's
    domain; if no pod matches anywhere and the incoming pod matches its own
    selector, the term passes on nodes that have the topology key
    (first-pod bootstrap, filtering.go:214-260)."""
    n = group_count.shape[0]
    ok = jnp.ones((n,), dtype=bool)
    for a in range(aff_group.shape[0]):  # A is tiny and static -> unrolled
        vec = group_count[:, aff_group[a]].astype(jnp.float32)
        dc = domain_count(vec, aff_key[a], topo_onehot)
        node_has = has_key[aff_key[a]] > 0
        total = jnp.sum(vec)
        term_ok = node_has & ((dc > 0) | ((total == 0) & aff_self[a]))
        ok &= jnp.where(aff_valid[a], term_ok, True)
    return ok


def pod_anti_affinity_ok(
    group_count: jnp.ndarray,
    topo_onehot: jnp.ndarray,
    has_key: jnp.ndarray,
    anti_group: jnp.ndarray,    # [B]
    anti_key: jnp.ndarray,      # [B]
    anti_valid: jnp.ndarray,    # [B]
    blocked: jnp.ndarray,       # [N] reverse-direction verdict (see below)
) -> jnp.ndarray:
    """InterPodAffinity required anti-affinity, both directions
    (filtering.go satisfyPodAntiAffinity + satisfyExistingPodsAntiAffinity):
      forward: no existing pod matching the incoming pod's term in the domain;
      reverse: `blocked` — nodes where an existing pod's own anti-affinity
      term covers this pod, read off the term-paint carry by the engine
      (dense matvec or per-hit-term column gathers; identical verdicts)."""
    n = group_count.shape[0]
    ok = jnp.ones((n,), dtype=bool)
    for b in range(anti_group.shape[0]):
        vec = group_count[:, anti_group[b]].astype(jnp.float32)
        dc = domain_count(vec, anti_key[b], topo_onehot)
        term_ok = dc == 0
        ok &= jnp.where(anti_valid[b], term_ok, True)
    return ok & ~blocked


def anti_blocked_dense(term_block: jnp.ndarray, hit_terms_p: jnp.ndarray) -> jnp.ndarray:
    """Reverse anti-affinity verdict, dense form: sum the paint over every
    term whose selector matches this pod (sum of nonnegative counts > 0
    cannot false-positive in bf16)."""
    return mm(term_block, hit_terms_p.astype(term_block.dtype)) > 0


# NOTE: the standalone topology_spread_ok op was removed in round 4: the
# scan engine inlines the DoNotSchedule filter against the dom_count carry
# (engine/scheduler._step), and the inline path is oracle-tested end to end
# in tests/test_engine_spread_oracle.py.
