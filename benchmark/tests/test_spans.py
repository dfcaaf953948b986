"""The per-layer metrics read from the program's spans
(benchmark/spans.py), on hand-made records and calls."""

import importlib.util
import os

import pytest

from benchmark import spans

CALLS = [{"t0": 10.0, "t1": 11.0}, {"t0": 12.0, "t1": 12.6}]


def reader(metric):
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics",
                        f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"test_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_per_call_sums_the_records_inside_each_call():
    # one record each in the calls, one straddling the first call's end
    # and one between the calls: neither counts
    recs = [(10.1, 10.3), (10.9, 11.2), (11.5, 11.6), (12.0, 12.05)]
    ctx = {"kind": "sweep", "calls": CALLS}
    assert spans.per_call_ms(ctx, "sweep", "x", recs) == pytest.approx(1e3 * (0.2 + 0.05) / 2)


def test_per_call_sums_a_questions_rounds():
    recs = [(10.1, 10.2), (10.4, 10.45), (10.7, 10.72), (12.1, 12.3)]
    ctx = {"kind": "bisect", "calls": CALLS}
    assert spans.per_call_ms(ctx, "bisect", "x", recs) == pytest.approx(
        1e3 * ((0.1 + 0.05 + 0.02) + 0.2) / 2)


def test_other_traffic_kind_or_no_records_reads_none():
    ctx = {"kind": "bisect", "calls": CALLS}
    assert spans.per_call_ms(ctx, "sweep", "x", [(10.1, 10.2)]) is None
    assert spans.per_call_ms(ctx, "bisect", "x", []) is None
    assert spans.per_call_ms({"kind": "bisect", "calls": []}, "bisect", "x", []) is None


def test_setup_sums_the_records_closed_before_the_first_call():
    recs = [(1.0, 3.5), (4.0, 4.25), (9.9, 10.1), (10.2, 10.4)]
    ctx = {"kind": "sweep", "calls": CALLS}
    assert spans.setup_s(ctx, "x", recs) == pytest.approx(2.75)
    assert spans.setup_s(ctx, "x", [(10.2, 10.4)]) is None


def test_records_reads_the_programs_recorder():
    from open_simulator_tpu.telemetry.spans import span

    with span("bench_test.stage"):
        pass
    [(a, b)] = spans.records("bench_test.stage")[-1:]
    assert 0.0 <= b - a < 1.0


@pytest.mark.parametrize("metric,kind,name", [
    ("upload_ms.sweep", "sweep", "sweep.upload"),
    ("upload_ms.question", "bisect", "sweep.upload"),
    ("fetch_ms.sweep", "sweep", "sweep.fetch"),
    ("fetch_ms.question", "bisect", "sweep.fetch"),
    ("lane_stats_ms.sweep", "sweep", "sweep.lane_stats"),
    ("lane_stats_ms.question", "bisect", "sweep.lane_stats"),
])
def test_readers_name_their_span_and_kind(monkeypatch, metric, kind, name):
    seen = []
    monkeypatch.setattr(spans, "records",
                        lambda n: seen.append(n) or [(10.1, 10.2)])
    other = "bisect" if kind == "sweep" else "sweep"
    assert reader(metric)({"kind": other, "calls": CALLS}) is None
    assert reader(metric)({"kind": kind, "calls": CALLS}) == pytest.approx(50.0)
    assert seen == [name]


def test_wave_plan_reader_reads_the_setups_span(monkeypatch):
    monkeypatch.setattr(spans, "records",
                        lambda n: [(5.0, 6.5)] if n == "wave_plan" else [])
    assert reader("wave_plan_s")({"kind": "sweep", "calls": CALLS}) == pytest.approx(1.5)
