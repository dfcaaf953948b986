"""The plain reference agrees with capacity_sweep on the benchmark's
configurations at a toy size, and catches a wrong placement."""

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import kube
from benchmark.tests.helpers import all_pods, toy_sweep

MAX_NEW = 8


def cluster(dicts):
    nd, pd, td = dicts
    return kube.Cluster(list(nd) + kube.template_copies(td, MAX_NEW), pd), len(nd)


@pytest.mark.parametrize("config,seed", [("pools5k", 3), ("pools5k", 2 ** 31 + 7),
                                         ("spread5k", 5), ("spread5k", 2 ** 31 + 9)])
def test_reference_agrees_with_the_sweep(config, seed):
    dicts, plan = toy_sweep(config, seed, MAX_NEW)
    c, n_real = cluster(dicts)
    for k in (0, MAX_NEW // 2, MAX_NEW):
        row = np.asarray(plan.nodes_per_scenario[k])
        got = check.check_lane(c, n_real, plan.counts[k], row, all_pods(row))
        assert got["bad_picks"] == 0 and got["missed_pods"] == 0, got
        assert got["score_gap"] < 1e-3, got
    limit = check.decision_errors(c, n_real, MAX_NEW, plan, 100.0, bisect=False)
    assert limit == 0


def test_a_wrong_placement_is_caught():
    dicts, plan = toy_sweep("pools5k", 3, MAX_NEW)
    c, n_real = cluster(dicts)
    row = np.asarray(plan.nodes_per_scenario[0]).copy()
    i = 300
    # a node of another pool: its nodeSelector fails there
    other = next(j for j in range(n_real)
                 if c.node_labels[j]["pool"] != c.pods[i].node_selector["pool"])
    bad = row.copy()
    bad[i] = other
    got = check.check_lane(c, n_real, 0, bad, [i])
    assert got["bad_picks"] == 1
    # a feasible node, but the reference's worst one
    st = kube.State(c)
    st.advance(row, i)
    active = check.lane_active(c, n_real, 0)
    ok, score = kube.evaluate(c, st, i, active)
    worst = int(np.argmin(np.where(ok, score, np.inf)))
    bad = row.copy()
    bad[i] = worst
    got = check.check_lane(c, n_real, 0, bad, [i])
    assert got["bad_picks"] == 0 and got["score_gap"] > 1.0
    # a pod left unscheduled that fits
    bad = row.copy()
    bad[i] = -1
    assert check.check_lane(c, n_real, 0, bad, [i])["missed_pods"] == 1


def test_a_wrong_verdict_is_caught():
    dicts, plan = toy_sweep("spread5k", 5, MAX_NEW)
    c, n_real = cluster(dicts)
    assert check.decision_errors(c, n_real, MAX_NEW, plan, 100.0, bisect=False) == 0
    plan.satisfied[3] = not plan.satisfied[3]
    assert check.decision_errors(c, n_real, MAX_NEW, plan, 100.0, bisect=False) >= 1
