"""The control (PERF.md): the plain reference computed in bfloat16, put
in the program's place, must come out not correct through the harness's
own decision, where the program's placements come out correct."""

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import kube
from benchmark.tests.helpers import run_cell


def bfloat16_reference(traffic, results):
    """Every lane of the window's answers placed by the reference itself
    in bfloat16, each pod on its own earlier picks."""
    nd, pd, td = traffic.dicts
    c = kube.Cluster(list(nd) + kube.template_copies(td, traffic.max_new), pd)
    rows = {}
    for r in results:
        plan = r["plan"]
        plan.nodes_per_scenario = np.stack([
            rows[n] if n in rows else rows.setdefault(n, check.decode(c, len(nd), n))
            for n in plan.counts])


@pytest.mark.parametrize("cell", ["pools5k.sweep64", "spread5k.sweep64", "pools5k.bisect8"])
def test_bfloat16_control_is_not_correct(capsys, monkeypatch, cell):
    assert run_cell(capsys, monkeypatch, cell)["correct"] is True
    out = run_cell(capsys, monkeypatch, cell, bfloat16_reference)
    assert out["correct"] is False, out["check"]
