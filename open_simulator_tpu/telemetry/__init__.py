"""Telemetry: metrics registry, nested spans, runtime gauges, explain.

The unified observability layer (ARCHITECTURE.md §8):

  registry.py  dependency-free counters/gauges/histograms + Prometheus
               text exposition (GET /metrics renders the default REGISTRY)
  spans.py     nested host-side phase spans -> simon_phase_seconds +
               Chrome-trace JSON export (--trace-out, loads in Perfetto)
               + simon.<name> events in an active jax.profiler trace
  context.py   causal request tracing (ARCHITECTURE.md §20): the
               X-Simon-Trace-Id contextvar + the always-on black-box
               event ring behind GET /api/trace/<id> and
               `simon-tpu trace show`
  runtime.py   on-demand jax gauges (live buffers, device memory) and
               jit compile-cache hit/miss accounting
  explain.py   per-pod "why this node / why unschedulable" decode of the
               engine's fail_counts + top-k score tensors
  ledger.py    flight recorder: one RunRecord JSON line per simulation
               into an on-disk size-capped ledger (--ledger-dir /
               SIMON_LEDGER_DIR), diffed by `simon-tpu runs`
"""

from open_simulator_tpu.telemetry.registry import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    render_prometheus,
)
from open_simulator_tpu.telemetry.runtime import (  # noqa: F401
    install_runtime_gauges,
    jit_cache_size,
    record_compile_event,
    schedule_phase,
)
from open_simulator_tpu.telemetry.spans import (  # noqa: F401
    RECORDER,
    SpanRecorder,
    export_chrome_trace,
    span,
)
from open_simulator_tpu.telemetry.context import (  # noqa: F401
    BLACKBOX,
    TRACE_HEADER,
    current_trace,
    current_traces,
    ensure_trace,
    new_trace_id,
    trace_scope,
)
from open_simulator_tpu.telemetry import ledger  # noqa: F401
