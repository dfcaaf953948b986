"""Telemetry layer: registry + Prometheus rendering, spans + Chrome
trace, stack instrumentation, and the REST /metrics + /api/explain
surfaces."""

import json
import logging
import threading
import time
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

from open_simulator_tpu import telemetry
from open_simulator_tpu.telemetry.registry import (
    MetricsRegistry,
    PROMETHEUS_CONTENT_TYPE,
)
from open_simulator_tpu.telemetry.spans import SpanRecorder, span


# ---- registry ------------------------------------------------------------


def test_counter_inc_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help", labelnames=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.value(kind="a") == 3
    assert c.value(kind="b") == 1
    assert c.value(kind="missing") == 0
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)


def test_counter_without_labels_and_label_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("plain_total")
    c.inc()
    rendered = reg.render_prometheus()
    assert "plain_total 1" in rendered
    labeled = reg.counter("lab_total", labelnames=("x",))
    with pytest.raises(ValueError):
        labeled.inc()  # must go through .labels()
    with pytest.raises(ValueError):
        labeled.labels(wrong="v")


def test_get_or_create_is_idempotent_and_type_safe():
    reg = MetricsRegistry()
    a = reg.counter("same_total", "h")
    b = reg.counter("same_total", "h")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("same_total")
    with pytest.raises(ValueError):
        reg.counter("same_total", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.counter("bad name")


def test_histogram_bucket_mismatch_rejected():
    reg = MetricsRegistry()
    reg.histogram("hb_seconds", buckets=(0.1, 1.0))
    assert reg.histogram("hb_seconds", buckets=(1.0, 0.1)) is not None  # order-insensitive
    with pytest.raises(ValueError):
        reg.histogram("hb_seconds", buckets=(5.0,))


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("g_val")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6
    gl = reg.gauge("g_lab", labelnames=("d",))
    gl.labels(d="x").set(1.5)
    assert gl.value(d="x") == 1.5


def test_gauge_callback_sampled_at_render_and_survives_errors():
    reg = MetricsRegistry()
    g = reg.gauge("cb_val", labelnames=("k",))
    g.set_callback(lambda: {("a",): 2.0, ("b",): 3.0})
    out = reg.render_prometheus()
    assert 'cb_val{k="a"} 2' in out and 'cb_val{k="b"} 3' in out

    def boom():
        raise RuntimeError("introspection moved")

    g.set_callback(boom)
    out = reg.render_prometheus()  # must not raise
    assert "# TYPE cb_val gauge" in out and 'cb_val{k="a"}' not in out


def test_histogram_buckets_sum_count():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    out = reg.render_prometheus()
    assert 'h_seconds_bucket{le="0.1"} 1' in out
    assert 'h_seconds_bucket{le="1"} 3' in out
    assert 'h_seconds_bucket{le="10"} 4' in out
    assert 'h_seconds_bucket{le="+Inf"} 5' in out
    assert "h_seconds_count 5" in out
    assert "h_seconds_sum 56.05" in out
    assert h.child_stats() == (5, 56.05)


def test_prometheus_text_format_shape_and_escaping():
    reg = MetricsRegistry()
    c = reg.counter("fmt_total", "an \"odd\" help", labelnames=("p",))
    c.labels(p='we"ird\nvalue\\x').inc()
    text = reg.render_prometheus()
    lines = text.strip().splitlines()
    assert lines[0].startswith("# HELP fmt_total")
    assert lines[1] == "# TYPE fmt_total counter"
    # label escaping: backslash, newline, quote
    assert 'p="we\\"ird\\nvalue\\\\x"' in lines[2]
    assert text.endswith("\n")


# ---- spans + chrome trace ------------------------------------------------


def test_spans_nest_and_export_chrome_trace(tmp_path):
    rec = SpanRecorder()
    with span("outer", recorder=rec):
        with span("inner", recorder=rec, detail="x"):
            pass
    records = rec.records()
    by_name = {r.name: r for r in records}
    assert by_name["inner"].depth == 1 and by_name["outer"].depth == 0
    # containment: inner happens inside outer's interval
    o, i = by_name["outer"], by_name["inner"]
    assert o.t0 <= i.t0 and i.t0 + i.dur <= o.t0 + o.dur + 1e-9

    path = tmp_path / "trace.json"
    rec.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert {e["name"] for e in events} == {"outer", "inner"}
    for e in events:
        assert e["ph"] == "X" and "ts" in e and "dur" in e and "pid" in e
    inner_ev = next(e for e in events if e["name"] == "inner")
    assert inner_ev["args"] == {"detail": "x"}


def test_span_closes_on_exception_and_feeds_histogram():
    rec = SpanRecorder()
    h = telemetry.histogram(
        "simon_phase_seconds", labelnames=("phase",))
    before = h.child_stats(phase="failing")[0]
    with pytest.raises(RuntimeError):
        with span("failing", recorder=rec):
            raise RuntimeError("boom")
    assert [r.name for r in rec.records()] == ["failing"]
    assert h.child_stats(phase="failing")[0] == before + 1


def test_recorder_clear_and_bound():
    rec = SpanRecorder(maxlen=4)
    for i in range(10):
        rec.add(f"s{i}", 0.0, 0.001)
    assert len(rec.records()) == 4
    rec.clear()
    assert rec.records() == []


# ---- engine/sched_config rename ------------------------------------------


def test_engine_profile_shim_is_retired():
    """The engine/profile.py deprecation shim (left by the PR-3 rename
    to sched_config.py) is RETIRED: the module must no longer import,
    and the real module keeps exporting the public names. This test
    pins the retirement so the shim cannot quietly come back."""
    import importlib

    import pytest

    from open_simulator_tpu.engine import sched_config

    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("open_simulator_tpu.engine.profile")
    assert callable(sched_config.weight_overrides_from_file)
    assert issubclass(sched_config.SchedulerConfigError, Exception)


# ---- stack instrumentation ----------------------------------------------


def _tiny_body():
    return {
        "cluster": {"yaml": (
            "apiVersion: v1\nkind: Node\nmetadata: {name: m0}\n"
            "status:\n  allocatable: {cpu: '4', memory: 8Gi, pods: '110'}\n")},
        "apps": [{"name": "a", "yaml": (
            "apiVersion: v1\nkind: Pod\nmetadata: {name: p, namespace: default}\n"
            "spec:\n  containers:\n    - name: c\n      resources:\n"
            "        requests: {cpu: 100m}\n")}],
    }


def test_simulate_records_phases_and_compile_cache(node_factory, pod_factory):
    from open_simulator_tpu.core import AppResource, simulate
    from open_simulator_tpu.k8s.loader import ClusterResources

    phase = telemetry.histogram("simon_phase_seconds", labelnames=("phase",))
    sims = telemetry.counter("simon_simulations_total")
    before = {p: phase.child_stats(phase=p)[0]
              for p in ("simulate", "encode", "schedule", "decode")}
    sims_before = sims.value()

    cluster = ClusterResources()
    cluster.nodes = [node_factory("t0")]
    apps = ClusterResources()
    apps.pods = [pod_factory("t-pod")]
    result = simulate(cluster, [AppResource("a", apps)])
    assert len(result.scheduled_pods) == 1

    for p, n0 in before.items():
        assert phase.child_stats(phase=p)[0] == n0 + 1, f"phase {p} not recorded"
    assert sims.value() == sims_before + 1
    # compile-cache accounting saw the schedule phase (hit or miss,
    # depending on what earlier tests compiled)
    cache = telemetry.counter(
        "simon_compile_cache_total", labelnames=("fn", "event"))
    assert (cache.value(fn="schedule_pods", event="hit")
            + cache.value(fn="schedule_pods", event="miss")) >= 1


def test_admission_rejections_counted():
    from open_simulator_tpu.errors import AdmissionError
    from open_simulator_tpu.k8s.loader import ClusterResources
    from open_simulator_tpu.k8s.objects import Node
    from open_simulator_tpu.resilience.admission import admit

    c = telemetry.counter(
        "simon_admission_rejections_total", labelnames=("code",))
    before = c.value(code="E_QUANTITY")
    cluster = ClusterResources()
    cluster.nodes = [Node.from_dict({
        "metadata": {"name": "bad"},
        "status": {"allocatable": {"cpu": "-2", "memory": "1Gi", "pods": "10"}},
    })]
    with pytest.raises(AdmissionError):
        admit(cluster)
    assert c.value(code="E_QUANTITY") == before + 1


def test_retry_outcomes_counted():
    from open_simulator_tpu.resilience.retry import run_with_retries

    c = telemetry.counter("simon_retry_total", labelnames=("outcome",))
    b_retried = c.value(outcome="retried")
    b_recovered = c.value(outcome="recovered")
    b_exhausted = c.value(outcome="exhausted")

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise OSError("transient")
        return "ok"

    assert run_with_retries(flaky, retries=2, sleep=lambda _s: None) == "ok"
    assert c.value(outcome="retried") == b_retried + 1
    assert c.value(outcome="recovered") == b_recovered + 1

    with pytest.raises(OSError):
        run_with_retries(lambda: (_ for _ in ()).throw(OSError("hard")),
                         retries=1, sleep=lambda _s: None)
    assert c.value(outcome="exhausted") == b_exhausted + 1


# ---- REST: /metrics, /api/explain, access log ---------------------------


@pytest.fixture(scope="module")
def telemetry_server():
    from open_simulator_tpu.server.rest import SimulationServer, _make_handler

    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0), _make_handler(SimulationServer()))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()


def _get(url):
    with urllib.request.urlopen(url) as resp:
        return resp.status, resp.headers, resp.read().decode()


def _post(url, payload):
    req = urllib.request.Request(url, data=json.dumps(payload).encode())
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def test_explain_404_before_any_simulation(telemetry_server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(telemetry_server + "/api/explain")
    assert ei.value.code == 404
    assert json.loads(ei.value.read())["code"] == "E_NO_SIMULATION"


def test_metrics_endpoint_serves_core_series(telemetry_server, caplog):
    with caplog.at_level(logging.DEBUG, logger="simon-tpu.http"):
        out = _post(telemetry_server + "/api/deploy-apps", _tiny_body())
        assert not out["unscheduled_pods"]
        # the access log routed method/path/status/duration through the
        # logger; the server thread writes it AFTER flushing the response
        # body, so the client can observe the response first — wait out
        # that handoff instead of racing it
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            access = [r.getMessage() for r in caplog.records
                      if r.name == "simon-tpu.http"]
            if any("POST /api/deploy-apps -> 200" in m and "ms" in m
                   for m in access):
                break
            time.sleep(0.02)
        else:
            raise AssertionError(f"no timed access-log line in {access}")

    status, headers, text = _get(telemetry_server + "/metrics")
    assert status == 200
    assert headers["Content-Type"] == PROMETHEUS_CONTENT_TYPE
    for series in ("simon_http_requests_total", "simon_http_request_seconds",
                   "simon_http_in_flight", "simon_phase_seconds",
                   "simon_simulations_total", "simon_pods_scheduled_total",
                   "simon_admission_rejections_total",
                   "simon_compile_cache_total", "simon_jax_devices"):
        assert series in text, f"missing {series}"
    # the request metric carries the method/path/status labels
    assert 'simon_http_requests_total{method="POST",path="/api/deploy-apps",status="200"}' in text
    # prometheus text format: every non-comment line is "name{...} value"
    import re

    sample = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+$")
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert sample.match(line), f"malformed sample line: {line!r}"


def test_explain_endpoint_over_last_result(telemetry_server):
    body = _tiny_body()
    # one schedulable pod + one impossible pod, so explain has both a
    # candidate breakdown and a failure decode
    body["apps"][0]["yaml"] += (
        "---\n"
        "apiVersion: v1\nkind: Pod\nmetadata: {name: q, namespace: default}\n"
        "spec:\n  containers:\n    - name: c\n      resources:\n"
        "        requests: {cpu: '64'}\n")
    out = _post(telemetry_server + "/api/deploy-apps", body)
    assert out["unscheduled_pods"]
    _status, _h, text = _get(telemetry_server + "/api/explain?top_k=1")
    report = json.loads(text)
    unsched = [p for p in report["pods"] if p["status"] == "unscheduled"]
    assert unsched and unsched[0]["first_failing_op"] == "Insufficient cpu"
    assert unsched[0]["eliminations"] == [{"op": "Insufficient cpu", "nodes": 1}]
    # serving simulations record explain_topk, so scheduled pods carry a
    # candidate breakdown without any re-run
    sched = next(p for p in report["pods"] if p["status"] == "scheduled")
    assert sched["candidates"], "server-side explain must have candidates"
    assert sched["candidates"][0]["node"] == sched["node"]
    assert set(sched["candidates"][0]["parts"]) == set(report["score_parts"])
    # pod filter
    key = unsched[0]["pod"]
    _s, _h, text = _get(telemetry_server + f"/api/explain?pod={key}")
    filtered = json.loads(text)
    assert [p["pod"] for p in filtered["pods"]] == [key]


def test_explain_endpoint_bad_topk(telemetry_server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(telemetry_server + "/api/explain?top_k=abc")
    assert ei.value.code == 400


def test_unknown_paths_collapse_to_other_label(telemetry_server):
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(telemetry_server + "/definitely/not/a/route")
    _s, _h, text = _get(telemetry_server + "/metrics")
    assert 'path="other"' in text
    assert 'path="/definitely/not/a/route"' not in text
