"""The reduction from a trace to busy and idle time, per-launch device
time and labelled gaps, and the span subtraction behind driver_host_ms."""

import os

import pytest

from benchmark import readers
from benchmark.trace import MARK, Trace, covered, gaps, union

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")


def test_union_covered_gaps():
    u = union([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0), (4.0, 4.5)])
    assert u == [(0.0, 2.0), (3.0, 4.5)]
    assert covered(u, 1.0, 3.5) == pytest.approx(1.5)
    assert gaps(u, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.5, 5.0)]


def hand_made():
    # two launches of "main" (the second with an idle hole inside), one
    # small helper launch, nested and overlapping op events
    ops = [("fusion.1", 1.0, 1.4), ("fusion.2", 1.2, 1.5), ("copy", 1.5, 1.6),
           ("fusion.1", 3.0, 3.3), ("fusion.1", 3.5, 3.9), ("tiny", 5.0, 5.05)]
    mods = [("main", 1.0, 1.6), ("main", 3.0, 3.9), ("helper", 5.0, 5.05)]
    host = {MARK: [(0.5, 0.5)], "bench.sweep": [(0.9, 2.0), (2.5, 5.1)]}
    return Trace({"/device:TPU:0": {"ops": ops, "modules": mods}}, host)


def test_busy_idle_and_launches():
    tr = hand_made()
    assert tr.window() == (0.9, 5.1)
    assert tr.busy_s() == pytest.approx(0.6 + 0.3 + 0.4 + 0.05)
    launches = tr.launches()
    assert [round(b, 9) for *_, b in launches] == [0.6, 0.7]
    ctx = {"kind": "sweep", "trace": tr, "window_s": 4.2}
    assert readers.device_ms_per_launch(ctx, "sweep") == pytest.approx(650.0)
    assert readers.device_idle_pct(ctx, "sweep") == pytest.approx(100 * (1 - 1.35 / 4.2))
    assert readers.device_ms_per_launch(ctx, "bisect") is None
    top = tr.top_ops(2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(1.1)


def test_idle_gaps_are_labelled_by_the_innermost_span():
    tr = hand_made()
    labels = [("call", 0.9, 2.0), ("call", 2.5, 5.1), ("program", 2.9, 4.0)]
    got = [(n, round(s, 9)) for n, s in tr.idle_gaps(labels)]
    assert got == [("between calls", 1.4), ("call", 1.1), ("program", 0.2),
                   ("call", 0.1), ("call", 0.05)]


def test_driver_host_ms_subtracts_the_programs_spans():
    calls = [{"t0": 0.0, "t1": 1.0, "spans": [(0.1, 0.7)]},
             {"t0": 2.0, "t1": 2.5, "spans": [(2.05, 0.1), (2.2, 0.2)]}]
    ctx = {"kind": "bisect", "calls": calls}
    assert readers.driver_host_ms(ctx, "bisect") == pytest.approx(1e3 * (0.3 + 0.2) / 2)
    assert readers.driver_host_ms(ctx, "sweep") is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded TPU trace")
def test_recorded_tpu_trace():
    tr = Trace.load(DATA)
    assert tr.devices, "no TPU plane in the recorded trace"
    win = tr.window()
    assert win is not None
    busy = tr.busy_s()
    assert 0.0 < busy <= win[1] - win[0]
    launches = tr.launches()
    assert len(launches) >= 3
    assert all(0.0 < b <= e - a + 1e-9 for _, a, e, b in launches)
    assert tr.top_ops(3)
