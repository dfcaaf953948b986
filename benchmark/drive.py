"""The general traffic generator: reads a traffic mix's data file
(`benchmark/traffic/<mix>.json`) and drives the product entry point it
names, back to back, for the window.

Kinds:

- ``sweep``: ``parallel/sweep.capacity_sweep`` over counts 0..lanes-1,
  with the CPU-occupancy limit that puts the answer at
  ``int(answer * max_new) + 1`` (chip_smoke.py's ``_cpu_threshold``).
- ``bisect``: ``parallel/sweep.capacity_bisect(lanes=...)``, questions
  from ``questions``: each limit is the occupancy at ``answer - offset``
  new nodes, so the answer is that count; every answer of the mix comes
  once per cycle, no question repeats an earlier one, and every seed
  asks the same work in its own order.

Every call is timed on the host clock, with the program's own ``sweep``
spans that fell inside it, so the metric readers can split the call into
device program and driver work.
"""

from __future__ import annotations

import time
from typing import Dict, Iterator, List, Tuple

import numpy as np

from benchmark.reference.kube import quantity


def occupancy_limit(node_dicts, template, pod_dicts, n_new: float) -> float:
    """The cluster CPU occupancy (percent) with every pod placed and
    `n_new` template nodes added: a limit at n_new = a - 0.5 makes a the
    smallest count that satisfies it."""
    alloc = sum(quantity(d["status"]["allocatable"]["cpu"], milli=True)
                for d in node_dicts)
    t_alloc = quantity(template["status"]["allocatable"]["cpu"], milli=True)
    req = 0
    for d in pod_dicts:
        for c in d["spec"].get("containers") or []:
            r = (c.get("resources") or {}).get("requests") or {}
            req += quantity(r.get("cpu", 0), milli=True)
    return 100.0 * req / (alloc + n_new * t_alloc)


def cycle(groups: List[List[int]]) -> List[int]:
    """The group at each place of a cycle of questions: the members of
    each group spread evenly over it, the same places for every seed."""
    places = sorted(((k + 0.5) / len(g), gi) for gi, g in enumerate(groups)
                    for k in range(len(g)))
    return [gi for _, gi in places]


def questions(groups: List[List[int]], seed: int) -> Iterator[Tuple[int, float]]:
    """Endless distinct questions (answer, offset): every answer once per
    cycle, each group's answers at the group's places in an order drawn
    from the seed, so any run of questions holds the same mix of groups
    on every seed; each question has the next offset of a golden-ratio
    sequence in (0.05, 0.95), which no two questions share."""
    rng = np.random.default_rng(seed)
    places = cycle(groups)
    j = 0
    while True:
        order = [iter(rng.permutation(g)) for g in groups]
        for gi in places:
            j += 1
            yield int(next(order[gi])), 0.05 + 0.9 * ((j * 0.6180339887498949) % 1.0)


class Traffic:
    """One cell's traffic over one encoded snapshot."""

    def __init__(self, mix: Dict, snap, cfg, dicts, max_new: int, seed: int):
        from open_simulator_tpu.parallel.sweep import SweepThresholds

        self.mix, self.snap, self.cfg, self.dicts = mix, snap, cfg, dicts
        self.max_new = max_new
        self.kind = mix["kind"]
        self.lanes = int(mix["lanes"])
        nodes, pods, template = dicts
        # a question (answer a, offset o): the limit at a - o new nodes,
        # which every count from a on satisfies and none below
        self._limit = lambda q: SweepThresholds(
            max_cpu_pct=occupancy_limit(nodes, template, pods, q[0] - q[1]))
        if self.kind == "sweep":
            self.answer = (int(mix["answer"] * max_new) + 1, 0.5)
            self.counts = list(range(self.lanes))
        elif self.kind == "bisect":
            self.queue = questions(mix["answers"], seed)
            self.warm_answer = (int(mix["warm_answer"]), 0.5)
        else:
            raise ValueError(f"unknown traffic kind {self.kind!r}")

    def _call(self, answer):
        from open_simulator_tpu.parallel.sweep import capacity_bisect, capacity_sweep

        th = self._limit(answer)
        if self.kind == "sweep":
            plan = capacity_sweep(self.snap, self.cfg, self.counts, th)
        else:
            plan = capacity_bisect(self.snap, self.cfg, self.max_new, th,
                                   lanes=self.lanes)
        return plan, th

    def warm(self) -> None:
        """The window's shapes, once: compiles or loads every executable."""
        self._call(self.answer if self.kind == "sweep" else self.warm_answer)

    def window(self, seconds: float, annotate=None) -> Dict:
        """Calls back to back until `seconds` have passed; the call in
        flight then finishes and counts."""
        from open_simulator_tpu.telemetry.spans import RECORDER

        calls, results = [], []
        t_start = time.perf_counter()
        while True:
            if self.kind == "sweep":
                answer = self.answer
            else:
                answer = next(self.queue)
            mark = RECORDER.mark()
            t0 = time.perf_counter()
            if annotate is not None:
                with annotate(f"bench.{self.kind}"):
                    plan, th = self._call(answer)
            else:
                plan, th = self._call(answer)
            t1 = time.perf_counter()
            spans = [(r.t0 + mark[0], r.dur) for r in RECORDER.records_since(mark)
                     if r.name == "sweep"]
            calls.append({"t0": t0, "t1": t1, "spans": spans})
            results.append({"plan": plan, "limit": th.max_cpu_pct, "answer": answer[0]})
            if t1 - t_start >= seconds:
                break
        t_end = calls[-1]["t1"]
        if self.kind == "sweep":
            done = sum(len(r["plan"].counts) for r in results)
        else:
            done = len(results)
        return {"t_start": t_start, "t_end": t_end, "calls": calls,
                "results": results, "done": done}
