"""Differential tests: tensor ops vs straightforward numpy oracles.

The reference has no per-op tests (its one integration test covers the
vendored scheduler); SURVEY.md section 4 calls for adding these in the
rebuild — random instances, independently recomputed expectations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.ops import filters, scores
from open_simulator_tpu.ops.domains import (
    SELECT_MAX_DOMAINS,
    broadcast_domains,
    domain_count,
    domain_index,
    domain_min,
    same_domain,
)


def random_topology(rng, n, d):
    """one-hot [1, N, D] + per-node domain ids (some nodes lack the key)."""
    ids = rng.randint(-1, d, size=n)
    onehot = np.zeros((1, n, d), dtype=np.float32)
    for i, v in enumerate(ids):
        if v >= 0:
            onehot[0, i, v] = 1.0
    return onehot, ids


@pytest.mark.parametrize("seed", range(5))
def test_domain_count_oracle(seed):
    rng = np.random.RandomState(seed)
    n, d = 17, 5
    onehot, ids = random_topology(rng, n, d)
    counts = rng.randint(0, 7, size=n).astype(np.float32)

    # hostname key (id 0): identity
    np.testing.assert_allclose(
        np.asarray(domain_count(jnp.asarray(counts), 0, jnp.asarray(onehot))), counts
    )
    # zone-like key (id 1)
    got = np.asarray(domain_count(jnp.asarray(counts), 1, jnp.asarray(onehot)))
    want = np.zeros(n, dtype=np.float32)
    for i in range(n):
        if ids[i] >= 0:
            want[i] = sum(counts[j] for j in range(n) if ids[j] == ids[i])
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("d", [6, SELECT_MAX_DOMAINS, SELECT_MAX_DOMAINS + 1, 40])
@pytest.mark.parametrize("seed", range(4))
def test_domain_index_gather_equals_onehot_broadcast(seed, d):
    """The scan's one-hot broadcasts select by domain id (few domains) or
    gather by it (many): bit for bit the `O @ v` they replaced (0 where a
    node lacks the key), for vector and matrix per-domain values."""
    rng = np.random.RandomState(seed)
    n, q = 23, 3
    onehot, ids = random_topology(rng, n, d)
    onehot[0, :2], ids[:2] = 0.0, -1              # nodes without the key
    idx = np.asarray(domain_index(jnp.asarray(onehot)))[0]
    np.testing.assert_array_equal(idx, np.where(ids < 0, d, ids))
    per_dom = (rng.standard_normal((d, q)) * 400).astype(np.float32)
    for v in (per_dom, per_dom[:, 0]):
        got = np.asarray(broadcast_domains(jnp.asarray(v), jnp.asarray(idx)))
        assert got.dtype == np.float32 and got.shape == (n,) + v.shape[1:]
        np.testing.assert_array_equal(got, onehot[0].astype(np.float64) @ v)
        jaxpr = str(jax.make_jaxpr(broadcast_domains)(v, idx))
        assert ("gather" in jaxpr) == (d > SELECT_MAX_DOMAINS), jaxpr


@pytest.mark.parametrize("seed", range(5))
def test_domain_min_oracle(seed):
    rng = np.random.RandomState(seed)
    n, d = 13, 4
    onehot, ids = random_topology(rng, n, d)
    counts = rng.randint(0, 9, size=n).astype(np.float32)
    eligible = rng.rand(n) > 0.3

    got, _ = domain_min(jnp.asarray(counts), 1, jnp.asarray(onehot), jnp.asarray(eligible))
    elig_domains = {ids[i] for i in range(n) if eligible[i] and ids[i] >= 0}
    if elig_domains:
        want = min(sum(counts[j] for j in range(n) if ids[j] == dom) for dom in elig_domains)
    else:
        # nodes without the key can still be eligible -> min over eligible... the
        # op returns 0.0 only when NO node is eligible at all
        want = float(np.asarray(got)) if eligible.any() else 0.0
    if elig_domains:
        assert float(got) == want
    # hostname variant
    got_h, _ = domain_min(jnp.asarray(counts), 0, jnp.asarray(onehot), jnp.asarray(eligible))
    if eligible.any():
        assert float(got_h) == counts[eligible].min()


def test_same_domain_oracle():
    rng = np.random.RandomState(0)
    n, d = 11, 3
    onehot, ids = random_topology(rng, n, d)
    node = 4
    got = np.asarray(same_domain(node, 1, jnp.asarray(onehot), n))
    want = np.array([1.0 if ids[i] == ids[node] and ids[i] >= 0 else 0.0 for i in range(n)],
                    dtype=np.float32)
    if ids[node] < 0:
        want = np.zeros(n, dtype=np.float32)
    np.testing.assert_allclose(got, want)
    got_h = np.asarray(same_domain(node, 0, jnp.asarray(onehot), n))
    assert got_h[node] == 1.0 and got_h.sum() == 1.0


@pytest.mark.parametrize("seed", range(3))
def test_fit_oracle(seed):
    """The headroom-form fit must equal the vendored `used + req <= alloc`
    (fit.go fitsRequest). Integer-valued quantities (the encoder's units)
    keep both forms bit-exact; used may exceed alloc (forced overcommit)."""
    rng = np.random.RandomState(seed)
    n, r = 9, 4
    alloc = rng.randint(0, 100, size=(n, r)).astype(np.float32)
    used = rng.randint(0, 120, size=(n, r)).astype(np.float32)
    req = rng.randint(0, 30, size=r).astype(np.float32)
    got = np.asarray(filters.fit_per_resource(jnp.asarray(alloc - used), jnp.asarray(req)))
    want = used + req[None, :] <= alloc
    np.testing.assert_array_equal(got, want)


def test_least_allocated_oracle():
    alloc = np.array([[4000, 8192], [2000, 4096]], dtype=np.float32)
    used = np.array([[1000, 2048], [0, 0]], dtype=np.float32)
    req = np.array([500, 1024], dtype=np.float32)
    got = np.asarray(scores.least_allocated_score(
        jnp.asarray(used), jnp.asarray(alloc), jnp.asarray(req), (0, 1)))
    # node0: cpu free (4000-1500)/4000=0.625, mem (8192-3072)/8192=0.625 -> 62.5
    # node1: cpu 0.75, mem 0.75 -> 75
    np.testing.assert_allclose(got, [62.5, 75.0], rtol=1e-5)


def test_balanced_allocation_oracle():
    alloc = np.array([[4000, 8192]], dtype=np.float32)
    used = np.array([[0, 0]], dtype=np.float32)
    req = np.array([2000, 2048], dtype=np.float32)
    got = float(np.asarray(scores.balanced_allocation_score(
        jnp.asarray(used), jnp.asarray(alloc), jnp.asarray(req), (0, 1)))[0])
    fr = np.array([2000 / 4000, 2048 / 8192])
    want = (1 - fr.std()) * 100
    assert abs(got - want) < 1e-3


def test_simon_max_share_oracle():
    # share(req, alloc-req) per resource, max, min-max normalized over feasible
    alloc = np.array([[4000, 8192, 0, 110], [8000, 8192, 0, 110]], dtype=np.float32)
    req = np.array([2000, 2048, 0, 1], dtype=np.float32)
    feas = np.array([True, True])
    got = np.asarray(scores.simon_max_share_score(jnp.asarray(alloc), jnp.asarray(req), jnp.asarray(feas)))

    def raw(alloc_row):
        shares = []
        for a, r in zip(alloc_row, req):
            t = a - r
            shares.append((1.0 if r else 0.0) if t == 0 else min(max(r / t, 0), 1) if t > 0 else 1.0)
        return max(shares) * 100

    raws = np.array([raw(alloc[0]), raw(alloc[1])])
    lo, hi = raws.min(), raws.max()
    want = (raws - lo) * 100 / (hi - lo)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_minmax_and_max_normalize_edges():
    feas = jnp.asarray([True, True, False])
    raw = jnp.asarray([5.0, 5.0, 99.0])
    out = np.asarray(scores.minmax_normalize(raw, feas))
    np.testing.assert_allclose(out, [0.0, 0.0, 0.0])  # zero range -> 0, infeasible -> 0
    out2 = np.asarray(scores.max_normalize(jnp.asarray([0.0, 0.0, 0.0]), feas, reverse=True))
    np.testing.assert_allclose(out2[:2], [100.0, 100.0])  # no taints anywhere -> all max


# (The standalone topology_spread_score op and its oracles moved: the scan
# engine inlines spread pass 1; the live inline path is oracle-tested end to
# end in tests/test_engine_spread_oracle.py.)


@pytest.mark.parametrize("seed", range(4))
def test_hoist_active_stats_oracle(seed):
    """ActiveHoist vs a direct numpy recount: domains-with-an-active-member
    per key, per-class eligibility, and the hoisted log weights."""
    from open_simulator_tpu.ops.domains import hoist_active_stats

    rng = np.random.RandomState(seed)
    n, d, c = 13, 4, 3
    onehot, ids = random_topology(rng, n, d)
    has_key = np.ones((2, n), dtype=np.float32)
    has_key[1] = (ids >= 0).astype(np.float32)
    class_aff = rng.rand(c, n) > 0.3
    active = rng.rand(n) > 0.25

    h = hoist_active_stats(
        jnp.asarray(onehot), jnp.asarray(has_key), jnp.asarray(class_aff),
        jnp.asarray(active))

    want_dom = [float(active.sum()),
                float(len({ids[i] for i in range(n) if active[i] and ids[i] >= 0}))]
    np.testing.assert_allclose(np.asarray(h.dom_counts), want_dom)
    np.testing.assert_allclose(np.asarray(h.log_dom), np.log(np.array(want_dom) + 2.0))

    elig = class_aff & active[None, :] & (has_key[None, 1] > 0)  # key 1
    for ci in range(c):
        want_has = np.zeros(d, dtype=bool)
        for i in range(n):
            if elig[ci, i] and ids[i] >= 0:
                want_has[ids[i]] = True
        np.testing.assert_array_equal(np.asarray(h.domain_has)[ci, 0], want_has)
        # hostname eligibility ignores has_key (every node is its own domain)
        np.testing.assert_array_equal(
            np.asarray(h.elig_host)[ci], class_aff[ci] & active)
    np.testing.assert_array_equal(
        np.asarray(h.any_elig)[:, 0], (class_aff & active[None, :]).any(axis=1))
    np.testing.assert_array_equal(np.asarray(h.any_elig)[:, 1], elig.any(axis=1))


@pytest.mark.parametrize("seed", range(4))
def test_domain_min_hoisted_oracle(seed):
    """domain_min_hoisted vs a recount of the vendored minMatchNum: min of
    per-domain totals over domains holding an eligible node."""
    from open_simulator_tpu.ops.domains import domain_min_hoisted, hoist_active_stats

    rng = np.random.RandomState(seed + 100)
    n, d = 11, 3
    onehot, ids = random_topology(rng, n, d)
    has_key = np.ones((2, n), dtype=np.float32)
    class_aff = (rng.rand(1, n) > 0.3)
    active = rng.rand(n) > 0.2
    counts = rng.randint(0, 6, size=n).astype(np.float32)

    h = hoist_active_stats(
        jnp.asarray(onehot), jnp.asarray(has_key), jnp.asarray(class_aff),
        jnp.asarray(active))
    got = float(domain_min_hoisted(
        jnp.asarray(counts), 1, 0, jnp.asarray(onehot), h))

    elig = class_aff[0] & active
    elig_domains = {ids[i] for i in range(n) if elig[i] and ids[i] >= 0}
    if elig.any():
        if elig_domains:
            want = min(
                sum(counts[j] for j in range(n) if ids[j] == dom)
                for dom in elig_domains
            )
            assert got == want
    else:
        assert got == 0.0
    # hostname: min over eligible nodes' own counts
    got_h = float(domain_min_hoisted(jnp.asarray(counts), 0, 0, jnp.asarray(onehot), h))
    if elig.any():
        assert got_h == counts[elig].min()
    else:
        assert got_h == 0.0


@pytest.mark.parametrize("seed", range(4))
def test_resource_scores_fused_matches_component_ops(seed):
    """The scan engine's fused Balanced+Least+Most must match the three
    component score ops (which are themselves oracle-tested above)."""
    rng = np.random.RandomState(seed)
    n, r = 12, 4
    alloc = rng.randint(1, 100, size=(n, r)).astype(np.float32)
    alloc[0, 0] = 0.0  # cap<=0: headroom-form convention checked separately
    used = (alloc * rng.rand(n, r)).astype(np.float32)
    req = rng.randint(0, 30, size=r).astype(np.float32)
    inv = np.where(alloc > 0, 1.0 / np.where(alloc > 0, alloc, 1.0), 0.0)
    for wb, wl, wm in [(1.0, 1.0, 0.0), (1.0, 0.0, 2.0), (0.5, 1.5, 1.0)]:
        got = np.asarray(scores.resource_scores_fused(
            jnp.asarray(alloc - used), jnp.asarray(inv),
            jnp.asarray(req), (0, 1), wb, wl, wm))
        want = (
            wb * np.asarray(scores.balanced_allocation_score(
                jnp.asarray(used), jnp.asarray(alloc), jnp.asarray(req), (0, 1)))
            + wl * np.asarray(scores.least_allocated_score(
                jnp.asarray(used), jnp.asarray(alloc), jnp.asarray(req), (0, 1)))
            + wm * np.asarray(scores.most_allocated_score(
                jnp.asarray(used), jnp.asarray(alloc), jnp.asarray(req), (0, 1)))
        )
        # row 0 has a zero-capacity cpu: Least (h=0 -> 0 free) and Most
        # (masked to 0 by inv_alloc > 0, like mostRequestedScore's
        # capacity==0 early-out) agree with the component ops; only
        # Balanced diverges there (component reads 0% utilized, headroom
        # form 0% free) — compare healthy rows to the oracle and row 0 to
        # the headroom-form expectation
        np.testing.assert_allclose(got[1:], want[1:], rtol=1e-4, atol=1e-3)
        h_m0 = (alloc[0, 1] - used[0, 1] - req[1]) * inv[0, 1]
        want0 = (
            wb * (1.0 - abs(0.0 - h_m0) * 0.5) * 100.0
            + wl * (max(h_m0, 0.0) * 50.0)
            + wm * ((0.0 + min(max(1.0 - h_m0, 0.0), 1.0)) * 50.0)
        )
        np.testing.assert_allclose(got[0], want0, rtol=1e-4, atol=1e-3)
