"""The comparison that decides ``correct``.

After the window has closed, a sample drawn from the seed of what the
window produced is compared with the plain reference
(``benchmark/reference/kube.py``), which reads the generator's objects
and nothing the program made:

- ``bad_picks``: sampled pods the program put on a node where the
  reference's filters fail (or on a node outside the lane), and pods
  with ``spec.nodeName`` bound elsewhere. Limit 0.
- ``missed_pods``: pods the program left unscheduled although the
  reference finds a feasible node. Every unscheduled pod of a compared
  lane is checked, up to the sample size. Limit 0.
- ``score_gap``: the widest gap between the reference's best total
  score and its score at the program's pick (0 to 700 points). Its
  limit is per cell, set from the readings in PERF.md.
- ``decision_errors``: lanes of every compared answer whose satisfied
  flag differs from the reference's (all pods placed on the lane's
  nodes, CPU and memory occupancy under the limits), a best count that
  is not the reference's smallest satisfying count, for a bisection an
  answer whose count below was not probed unsatisfied, and lanes that
  failed. Limit 0.

With ``control=True`` each compared pod is also judged as the reference
computed in bfloat16 would pick it (PERF.md, the control); the readings
come back under ``control_*``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Sequence

import numpy as np

from benchmark.reference import kube

HERE = os.path.dirname(os.path.abspath(__file__))
EXACT = ("bad_picks", "missed_pods", "decision_errors")


def limits_for(cell: str) -> Dict[str, float]:
    """Every compared number's limit: 0 for the exact ones, the cell's
    file (benchmark/limits/<cell>.json) for the rest."""
    out = {k: 0.0 for k in EXACT}
    path = os.path.join(HERE, "limits", f"{cell}.json")
    with open(path) as f:
        out.update({k: float(v) for k, v in json.load(f)["limits"].items()})
    return out


def verdict(got: Dict[str, float], limits: Dict[str, float], prefix: str = ""):
    """(compared, correct): each compared number beside its limit, and
    whether every one is within it. With prefix="control_" the control's
    readings are judged by the same limits; the control has no lane
    verdicts of its own, so it takes the program's decision_errors."""
    compared = {k: {"value": got.get(prefix + k, got[k]), "limit": lim}
                for k, lim in limits.items()}
    return compared, all(v["value"] <= v["limit"] for v in compared.values())


def lane_active(c: kube.Cluster, n_real: int, count: int) -> np.ndarray:
    act = np.zeros(c.n, bool)
    act[:n_real] = True
    act[n_real:n_real + count] = True
    return act


def sample_pods(rng: np.random.Generator, row: np.ndarray, k: int) -> np.ndarray:
    """k pods drawn from the seed, every unscheduled pod (up to k more),
    and the first and last pod."""
    p = row.shape[0]
    drawn = rng.choice(p, size=min(k, p), replace=False)
    unsched = np.nonzero(row < 0)[0]
    if unsched.size > k:
        unsched = rng.choice(unsched, size=k, replace=False)
    return np.unique(np.concatenate([drawn, unsched, [0, p - 1]]))


def check_lane(c: kube.Cluster, n_real: int, count: int, row: np.ndarray,
               sample: Sequence[int], control: bool = False) -> Dict[str, float]:
    """Teacher-forced comparison of one lane's placements at the sampled
    pods."""
    active = lane_active(c, n_real, count)
    row = np.asarray(row, np.int64)
    out = {"bad_picks": 0, "missed_pods": 0, "score_gap": 0.0, "pods": 0}
    if control:
        out.update(control_bad_picks=0, control_missed_pods=0, control_score_gap=0.0)
    # a placement outside the lane's nodes is wrong wherever it is
    out["bad_picks"] += int(np.sum((row >= c.n) | ((row >= 0) & ~active[np.clip(row, 0, c.n - 1)])))
    st = kube.State(c)
    st_lo = kube.State(c, kube.LOW) if control else None
    for i in sample:
        st.advance(row, i)
        pick = int(row[i])
        forced = c.pods[i].node_name
        out["pods"] += 1
        if forced:
            out["bad_picks"] += int(pick != c.node_index.get(forced, -2))
            continue
        ok, score = kube.evaluate(c, st, i, active)
        if pick < 0:
            out["missed_pods"] += int(ok.any())
        elif pick < c.n and active[pick]:
            if not ok[pick]:
                out["bad_picks"] += 1
            else:
                out["score_gap"] = max(out["score_gap"], float(score.max() - score[pick]))
        if control:
            st_lo.advance(row, i)
            ok_lo, score_lo = kube.evaluate(c, st_lo, i, active)
            if not ok_lo.any():
                out["control_missed_pods"] += int(ok.any())
            else:
                lo_pick = int(np.argmax(np.where(ok_lo, score_lo.astype(np.float64), -np.inf)))
                if not ok[lo_pick]:
                    out["control_bad_picks"] += 1
                else:
                    out["control_score_gap"] = max(out["control_score_gap"],
                                                   float(score.max() - score[lo_pick]))
    return out


def decode(c: kube.Cluster, n_real: int, count: int, dtype=kube.LOW) -> np.ndarray:
    """One lane's placements by the reference itself in `dtype`, every pod
    in order on its own earlier picks: the reference put in the
    program's place (the control, whole, at a test's size)."""
    active = lane_active(c, n_real, count)
    row = np.full(len(c.pods), -1, np.int64)
    st = kube.State(c, dtype)
    for i, p in enumerate(c.pods):
        st.advance(row, i)
        if p.node_name:
            row[i] = c.node_index.get(p.node_name, -1)
            continue
        ok, score = kube.evaluate(c, st, i, active)
        if ok.any():
            row[i] = int(np.argmax(score))
    return row


def decision_errors(c: kube.Cluster, n_real: int, max_new: int, plan,
                    limit: float, bisect: bool) -> int:
    """Lanes whose verdict differs from the reference's, plus a wrong
    best count."""
    cpu_req = c.req[:, 0].astype(np.float64)
    mem_req = c.req[:, 1].astype(np.float64)
    errs = len(plan.trial_errors)
    sat = {}
    for k, count in enumerate(plan.counts):
        row = np.asarray(plan.nodes_per_scenario[k], np.int64)
        act = lane_active(c, n_real, count)
        placed = row >= 0
        on = np.where(placed, np.clip(row, 0, c.n - 1), 0)
        all_ok = bool(placed.all()) and bool(np.all(act[on[placed]]))
        used_cpu = cpu_req[placed].sum()
        used_mem = mem_req[placed].sum()
        occ_cpu = 100.0 * used_cpu / c.alloc[act, 0].sum()
        occ_mem = 100.0 * used_mem / c.alloc[act, 1].sum()
        sat[count] = all_ok and occ_cpu <= limit and occ_mem <= 100.0
        errs += int(sat[count] != bool(plan.satisfied[k]))
    best = min((n for n, s in sat.items() if s), default=None)
    errs += int(best != plan.best_count)
    if bisect:
        if best is None:
            errs += int(sat.get(max_new, True))
        elif best > 0:
            errs += int(sat.get(best - 1, True))
    return errs


def run(dicts, max_new: int, kind: str, results: List[Dict], seed: int,
        spec: Dict, control: bool = False, log=None) -> Dict[str, float]:
    """Compare the window's answers with the reference; returns the
    compared numbers (and the control's readings with control=True)."""
    t0 = time.perf_counter()
    nodes, pods, template = dicts
    n_real = len(nodes)
    c = kube.Cluster(list(nodes) + kube.template_copies(template, max_new), pods)
    rng = np.random.default_rng([seed, 0x5EED])
    bisect = kind == "bisect"
    totals: Dict[str, float] = {"bad_picks": 0, "missed_pods": 0, "score_gap": 0.0,
                                "decision_errors": 0, "pods": 0, "lanes": 0}
    for r in results:
        totals["decision_errors"] += decision_errors(c, n_real, max_new, r["plan"],
                                                     r["limit"], bisect)
    picked = results[-1:] if not bisect else [
        results[k] for k in sorted(rng.choice(len(results),
                                              size=min(spec["questions"], len(results)),
                                              replace=False))]
    for r in picked:
        plan = r["plan"]
        counts = list(plan.counts)
        lanes = []
        for near in (plan.best_count, None if plan.best_count is None else plan.best_count - 1):
            if near in counts:
                lanes.append(counts.index(near))
        rest = [k for k in range(len(counts)) if k not in lanes]
        lanes += [int(k) for k in rng.choice(rest, size=min(spec["lanes"], len(rest)),
                                             replace=False)]
        for k in lanes:
            row = np.asarray(plan.nodes_per_scenario[k])
            got = check_lane(c, n_real, counts[k], row,
                             sample_pods(rng, row, spec["pods"]), control)
            totals["lanes"] += 1
            for key, v in got.items():
                if key.endswith("score_gap"):
                    totals[key] = max(totals.get(key, 0.0), v)
                else:
                    totals[key] = totals.get(key, 0) + v
    totals["reference_s"] = time.perf_counter() - t0
    if log:
        log(f"reference: {totals['lanes']} lanes, {totals['pods']} pods in "
            f"{totals['reference_s']:.2f} s")
    return totals
