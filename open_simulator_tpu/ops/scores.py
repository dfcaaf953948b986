"""Score ops: each returns an [N] float32 vector, higher = better.

Weights and normalization mirror the v1beta2 default Score plugin set plus
the appended Simon plugin (reference: default_plugins.go:30-100,
pkg/simulator/utils.go:332-343, plugin/simon.go:45-101). All scores are
produced on the 0..100 scale of the scheduler framework before weighting.
"""

from __future__ import annotations

import jax.numpy as jnp

from open_simulator_tpu.ops.domains import domain_count
from open_simulator_tpu.ops.exact import div, mul

MAX_SCORE = jnp.float32(100.0)
_EPS = jnp.float32(1e-9)


def minmax_normalize(raw: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """Framework NormalizeScore (min-max to 0..100) over feasible nodes
    (plugin/simon.go:76-101, interpodaffinity NormalizeScore)."""
    big = jnp.float32(3.4e38)
    lo = jnp.min(jnp.where(feasible, raw, big))
    hi = jnp.max(jnp.where(feasible, raw, -big))
    rng = hi - lo
    out = jnp.where(rng > 0, (raw - lo) * MAX_SCORE / jnp.where(rng > 0, rng, 1.0), 0.0)
    return jnp.where(feasible, out, 0.0)


def max_normalize(raw: jnp.ndarray, feasible: jnp.ndarray, reverse: bool = False) -> jnp.ndarray:
    """helper.DefaultNormalizeScore: scale by max; reverse flips so that
    smaller raw = higher score (used by TaintToleration)."""
    hi = jnp.max(jnp.where(feasible, raw, 0.0))
    scaled = jnp.where(hi > 0, raw * MAX_SCORE / jnp.where(hi > 0, hi, 1.0), 0.0)
    out = MAX_SCORE - scaled if reverse else scaled
    return jnp.where(feasible, out, 0.0)


def least_allocated_score(
    used: jnp.ndarray, alloc: jnp.ndarray, req_p: jnp.ndarray, cpu_mem_idx
) -> jnp.ndarray:
    """NodeResourcesFit default LeastAllocated strategy over cpu+memory
    (vendored noderesources/least_allocated.go): mean of free fractions x100."""
    total = jnp.float32(0.0)
    for r in cpu_mem_idx:
        cap = alloc[:, r]
        free = cap - used[:, r] - req_p[r]
        frac = jnp.where(cap > 0, jnp.clip(free, 0.0) / jnp.where(cap > 0, cap, 1.0), 0.0)
        total = total + frac
    return total * MAX_SCORE / len(cpu_mem_idx)


def most_allocated_score(
    used: jnp.ndarray, alloc: jnp.ndarray, req_p: jnp.ndarray, cpu_mem_idx
) -> jnp.ndarray:
    """NodeResourcesMostAllocated strategy (vendored
    noderesources/most_allocated.go): mean of post-bind utilization
    fractions x100 — the bin-packing preference used for defragmentation."""
    total = jnp.float32(0.0)
    for r in cpu_mem_idx:
        cap = alloc[:, r]
        want = used[:, r] + req_p[r]
        frac = jnp.where(cap > 0, jnp.clip(want / jnp.where(cap > 0, cap, 1.0), 0.0, 1.0), 0.0)
        total = total + frac
    return total * MAX_SCORE / len(cpu_mem_idx)


def balanced_allocation_score(
    used: jnp.ndarray, alloc: jnp.ndarray, req_p: jnp.ndarray, cpu_mem_idx
) -> jnp.ndarray:
    """NodeResourcesBalancedAllocation (balanced_allocation.go): score =
    (1 - std(requested fractions)) x 100 over cpu+memory."""
    fracs = []
    for r in cpu_mem_idx:
        cap = alloc[:, r]
        want = used[:, r] + req_p[r]
        fracs.append(jnp.where(cap > 0, want / jnp.where(cap > 0, cap, 1.0), 0.0))
    stacked = jnp.stack(fracs)                      # [2, N]
    mean = jnp.mean(stacked, axis=0)
    var = jnp.mean((stacked - mean[None, :]) ** 2, axis=0)
    std = jnp.sqrt(var)
    return (1.0 - std) * MAX_SCORE


def resource_scores_fused(
    headroom: jnp.ndarray,    # [N, R] = alloc - used (the engine carry)
    inv_alloc: jnp.ndarray,   # [N, R] = 1/alloc where alloc > 0 else 0
    req_p: jnp.ndarray,       # [R]
    cpu_mem_idx,
    w_balanced,
    w_least,
    w_most,
    always_on: bool = False,
) -> jnp.ndarray:
    """Balanced + Least(+Most)Allocated in one pass over shared FREE
    fractions h = (headroom - req) * inv_alloc — the scan engine's
    hot-path form of the three functions above. The per-step divides
    become multiplies by the loop-invariant inv_alloc; the 2-point std
    collapses to |a-b|/2 and is invariant under a -> 1-a, so balanced
    reads |h_cpu - h_mem| directly (algebraically identical; float
    rounding differs at the ulp level, which only reorders ties that were
    already rounding-level). LeastAllocated's max(free, 0)*inv is
    bit-identical to the used-form. Pathological nodes (allocatable <= 0):
    h is 0 there, which Least/Balanced read as 0% free (score 0 — matches
    the reference), and Most would read as 100% used (full score); the
    (inv_alloc > 0) mask keeps Most at 0 like mostRequestedScore's
    capacity==0 early-out (most_allocated.go:49-51).

    ``always_on`` is the traced-weights mode (EngineConfig.traced_weights):
    the weights are traced f32 scalars — never branched on — and every
    term is computed unconditionally. A zero traced weight contributes an
    exact ``+0.0`` (the terms are finite and nonnegative), so the traced
    path at the constant path's weight values is bit-identical to it."""
    ci, mi = cpu_mem_idx
    h_c = mul(headroom[:, ci] - req_p[ci], inv_alloc[:, ci])
    h_m = mul(headroom[:, mi] - req_p[mi], inv_alloc[:, mi])
    out = jnp.zeros(headroom.shape[:1], dtype=jnp.float32)
    if always_on or w_balanced:
        out = out + mul(w_balanced, mul(1.0 - jnp.abs(h_c - h_m) * 0.5,
                                        MAX_SCORE))
    if always_on or w_least:
        out = out + mul(w_least, mul(
            jnp.maximum(h_c, 0.0) + jnp.maximum(h_m, 0.0), MAX_SCORE / 2.0))
    if always_on or w_most:
        # mostRequestedScore returns 0 when capacity == 0
        # (most_allocated.go:49-51): h is 0 there (inv_alloc == 0), which
        # would read as "fully used" = full score — mask those resources out
        out = out + mul(w_most, mul(
            jnp.clip(1.0 - h_c, 0.0, 1.0) * (inv_alloc[:, ci] > 0)
            + jnp.clip(1.0 - h_m, 0.0, 1.0) * (inv_alloc[:, mi] > 0),
            MAX_SCORE / 2.0))
    return out


def simon_max_share_raw(alloc: jnp.ndarray, req_p: jnp.ndarray) -> jnp.ndarray:
    """Simon plugin raw Score (plugin/simon.go:45-68): bin-packing
    preference. raw = max over resources of share(req_r, alloc_r - req_r),
    where share(a, t) = a/t, with 0/0 = 0 and a/0 = 1 (pkg/algo/greed.go
    Share). Note the reference reads *static* node allocatable (the fake
    apiserver never decrements it), so this score is deliberately
    usage-independent."""
    avail = alloc - req_p[None, :]
    requested = jnp.broadcast_to(req_p[None, :], alloc.shape)
    share = jnp.where(
        avail != 0,
        div(requested, jnp.where(avail != 0, avail, 1.0)),
        jnp.where(requested != 0, 1.0, 0.0),
    )
    share = jnp.where(requested > 0, jnp.clip(share, 0.0, 1.0), 0.0)
    return mul(jnp.max(share, axis=1), MAX_SCORE)


def simon_max_share_score(alloc: jnp.ndarray, req_p: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """simon_max_share_raw + the plugin's min-max NormalizeScore."""
    return minmax_normalize(simon_max_share_raw(alloc, req_p), feasible)


# ---- "from-reduced" normalizers ---------------------------------------
# The scan engine reduces every normalizer's min/max per step (one min
# over each masked row); these helpers apply the normalize formulas given
# the already-reduced lo/hi scalars. Two deliberate hot-path transforms vs the
# standalone functions (both argmax-preserving):
#   * wide divide -> scalar reciprocal + wide multiply (x*100/rng and
#     x*(100/rng) differ at the ulp level; equal raws still map to equal
#     scores, so exact ties are preserved);
#   * no feasibility masking — infeasible nodes get whatever the formula
#     yields (finite), and selectHost masks them to -inf before the argmax,
#     so their score values are never observable.


def minmax_apply(raw: jnp.ndarray, lo, hi) -> jnp.ndarray:
    rng = hi - lo
    inv = jnp.where(rng > 0, div(MAX_SCORE, jnp.where(rng > 0, rng, 1.0)), 0.0)
    return mul(raw - lo, inv)


def max_apply(raw: jnp.ndarray, hi, reverse: bool = False) -> jnp.ndarray:
    inv = jnp.where(hi > 0, div(MAX_SCORE, jnp.where(hi > 0, hi, 1.0)), 0.0)
    return MAX_SCORE - mul(raw, inv) if reverse else mul(raw, inv)


def spread_apply(raw: jnp.ndarray, s_min, s_max, node_ok: jnp.ndarray,
                 any_soft: jnp.ndarray) -> jnp.ndarray:
    """score = 100*(max+min-raw)/max when max>0 else 100, but as one wide
    FMA: base + (c1 - raw)*inv with scalar (base, c1, inv); nodes missing a
    constraint key score 0 (the only wide select kept), and any_soft folds
    into the scalars."""
    pos = s_max > 0
    soft = any_soft.astype(jnp.float32)
    inv = jnp.where(pos, div(100.0, jnp.maximum(s_max, 1e-9)), 0.0) * soft
    base = jnp.where(pos, 0.0, 100.0) * soft
    c1 = s_max + s_min
    return jnp.where(node_ok, base + mul(c1 - raw, inv), 0.0)


def node_affinity_score(class_na_row: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """NodeAffinity score: preferred-term weight sum, max-normalized
    (vendored nodeaffinity plugin + DefaultNormalizeScore)."""
    return max_normalize(class_na_row, feasible)


def taint_toleration_score(class_tt_row: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """TaintToleration score: fewer intolerable PreferNoSchedule taints is
    better (vendored tainttoleration.go CountIntolerableTaintsPreferNoSchedule
    + reversed DefaultNormalizeScore)."""
    return max_normalize(class_tt_row, feasible, reverse=True)


def interpod_preference_score(
    group_count: jnp.ndarray,
    topo_onehot: jnp.ndarray,
    has_key: jnp.ndarray,
    pref_group: jnp.ndarray,   # [Ap]
    pref_key: jnp.ndarray,     # [Ap]
    pref_weight: jnp.ndarray,  # [Ap] (negative = anti)
    pref_valid: jnp.ndarray,   # [Ap]
    feasible: jnp.ndarray,
    extra_raw: jnp.ndarray = None,
) -> jnp.ndarray:
    """InterPodAffinity score, both directions (vendored
    interpodaffinity/scoring.go): incoming pod's preferred terms sum
    weight x (#matching pods in the node's domain); `extra_raw` carries the
    existing-pods direction (their weighted preferred-term domain paint
    matched against this pod). Min-max normalized over the sum."""
    raw = interpod_preference_raw(
        group_count, topo_onehot, has_key, pref_group, pref_key, pref_weight,
        pref_valid, extra_raw)
    return minmax_normalize(raw, feasible)


def interpod_preference_raw(
    group_count: jnp.ndarray,
    topo_onehot: jnp.ndarray,
    has_key: jnp.ndarray,
    pref_group: jnp.ndarray,
    pref_key: jnp.ndarray,
    pref_weight: jnp.ndarray,
    pref_valid: jnp.ndarray,
    extra_raw: jnp.ndarray = None,
) -> jnp.ndarray:
    """Pass 1 of interpod_preference_score (pre-normalize raw sums)."""
    n = group_count.shape[0]
    raw = jnp.zeros((n,), dtype=jnp.float32) if extra_raw is None else extra_raw
    for a in range(pref_group.shape[0]):
        vec = group_count[:, pref_group[a]].astype(jnp.float32)
        dc = domain_count(vec, pref_key[a], topo_onehot)
        contrib = pref_weight[a] * dc * (has_key[pref_key[a]] > 0)
        raw = raw + jnp.where(pref_valid[a], contrib, 0.0)
    return raw


# NOTE: the standalone topology_spread_score / spread_normalize ops were
# removed with the fused kernel: the scan engine inlines spread pass 1
# (sharing per-constraint domain counts with the DoNotSchedule filter via
# the dom_count carry) and applies pass 2 via spread_apply below. The
# inline path is oracle-tested at the engine level in
# tests/test_engine_spread_oracle.py.
