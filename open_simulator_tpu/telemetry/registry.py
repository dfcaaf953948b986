"""Dependency-free metrics registry (counters, gauges, histograms).

The observability substrate every layer records into: the engine and
simulator stamp phase wall times and compile-cache hits, the resilience
layer counts admission rejections and chaos/retry outcomes, the REST
server counts requests and renders the whole registry as Prometheus text
exposition on ``GET /metrics``. Everything is stdlib: the repo must not
grow a prometheus_client dependency (environment constraint), and the
subset of the text format used here — counter/gauge/histogram with
labels, HELP/TYPE headers, cumulative ``le`` buckets — is all a scraper
needs.

Thread-safety: the REST server serves concurrently (ThreadingHTTPServer),
so every mutation and the render pass hold the registry lock. Metric
*handles* are cheap and cached — ``counter(...)`` is get-or-create, so
hot paths can look metrics up at call time without keeping module
globals in sync.

Trace-safety contract (graftlint GL4): metrics are HOST objects. Never
record from inside jit/scan scope — record decoded outputs after
``np.asarray``/``block_until_ready``, like every call site in this repo
does (see tests/fixtures/lint/gl4_telemetry_ok.py for the pattern).
"""

from __future__ import annotations

import math
import re
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# Prometheus default buckets, trimmed for a simulator whose phases span
# ~100us (cache-hit decode) to minutes (cold compile at north-star shape)
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)

LabelValues = Tuple[str, ...]


def _escape_label_value(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n").replace('"', '\\"')


def _format_value(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _label_str(names: Sequence[str], values: LabelValues,
               extra: Sequence[Tuple[str, str]] = ()) -> str:
    pairs = [f'{n}="{_escape_label_value(v)}"' for n, v in zip(names, values)]
    pairs += [f'{n}="{_escape_label_value(v)}"' for n, v in extra]
    return "{" + ",".join(pairs) + "}" if pairs else ""


class Metric:
    """Base: one named family holding per-label-set children."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = (),
                 lock: Optional[threading.Lock] = None):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r} on {name}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children: Dict[LabelValues, object] = {}
        self._lock = lock or threading.Lock()

    def labels(self, **kv: str):
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, got {tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                self._children[key] = child
            return child

    def _default_child(self):
        """The label-less child, created on first use."""
        if self.labelnames:
            raise ValueError(f"{self.name} has labels {self.labelnames}; "
                             "use .labels(...)")
        with self._lock:
            child = self._children.get(())
            if child is None:
                child = self._make_child()
                self._children[()] = child
            return child

    def _make_child(self):  # pragma: no cover - abstract
        raise NotImplementedError

    def collect_values(self) -> Dict[LabelValues, float]:
        """All scalar children as {label_values: value} in one lock hold —
        the cheap stored-state read path (the ledger's counter snapshot,
        the lifecycle drain summary). Valid for counters and gauges
        (callback gauges are NOT sampled); histogram children have no
        single value and must use child_stats instead."""
        with self._lock:
            return {k: c.v for k, c in self._children.items()}

    def render(self) -> List[str]:
        with self._lock:
            children = list(self._children.items())
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        for values, child in children:
            lines.extend(self._render_child(values, child))
        return lines

    def _render_child(self, values: LabelValues, child) -> List[str]:  # pragma: no cover
        raise NotImplementedError


class _Value:
    __slots__ = ("v",)

    def __init__(self):
        self.v = 0.0


class Counter(Metric):
    kind = "counter"

    def _make_child(self) -> _Value:
        return _Value()

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        child = self._default_child()
        with self._lock:
            child.v += amount

    def _render_child(self, values: LabelValues, child: _Value) -> List[str]:
        return [f"{self.name}{_label_str(self.labelnames, values)} "
                f"{_format_value(child.v)}"]

    def labels(self, **kv: str) -> "_BoundCounter":
        return _BoundCounter(self, super().labels(**kv))

    def value(self, **kv: str) -> float:
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            return child.v if child is not None else 0.0


class _BoundCounter:
    __slots__ = ("_m", "_c")

    def __init__(self, metric: Counter, child: _Value):
        self._m = metric
        self._c = child

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        with self._m._lock:
            self._c.v += amount


class Gauge(Metric):
    """Settable value; or a callback gauge sampled at render time (the
    JAX runtime gauges — live buffers, device memory — use this so the
    cost is paid only when someone scrapes /metrics)."""

    kind = "gauge"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = (),
                 lock: Optional[threading.Lock] = None):
        super().__init__(name, help, labelnames, lock)
        self._callback: Optional[Callable[[], Dict[LabelValues, float]]] = None

    def _make_child(self) -> _Value:
        return _Value()

    def set(self, value: float) -> None:
        child = self._default_child()
        with self._lock:
            child.v = float(value)

    def inc(self, amount: float = 1.0) -> None:
        child = self._default_child()
        with self._lock:
            child.v += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    def set_callback(self, fn: Callable[[], Dict[LabelValues, float]]) -> None:
        """fn() -> {label_values_tuple: value}, sampled on demand at render
        time. A raising callback renders nothing (scrapes must not 500
        because a runtime introspection API moved)."""
        self._callback = fn

    def labels(self, **kv: str) -> "_BoundGauge":
        return _BoundGauge(self, super().labels(**kv))

    def value(self, **kv: str) -> float:
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            return child.v if child is not None else 0.0

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        if self._callback is not None:
            try:
                sampled = self._callback()
            except Exception:  # noqa: BLE001 — scrape survives introspection drift
                sampled = {}
            for values, v in sorted(sampled.items()):
                lines.append(f"{self.name}{_label_str(self.labelnames, values)} "
                             f"{_format_value(v)}")
            return lines
        with self._lock:
            children = list(self._children.items())
        for values, child in children:
            lines.append(f"{self.name}{_label_str(self.labelnames, values)} "
                         f"{_format_value(child.v)}")
        return lines


class _BoundGauge:
    __slots__ = ("_m", "_c")

    def __init__(self, metric: Gauge, child: _Value):
        self._m = metric
        self._c = child

    def set(self, value: float) -> None:
        with self._m._lock:
            self._c.v = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._m._lock:
            self._c.v += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class _HistValue:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets
        self.sum = 0.0
        self.count = 0


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 lock: Optional[threading.Lock] = None):
        super().__init__(name, help, labelnames, lock)
        bks = sorted(float(b) for b in buckets)
        if not bks:
            raise ValueError(f"{name}: histogram needs at least one bucket")
        self.buckets: Tuple[float, ...] = tuple(bks)

    def _make_child(self) -> _HistValue:
        return _HistValue(len(self.buckets))

    def observe(self, value: float) -> None:
        _observe(self, self._default_child(), value)

    def labels(self, **kv: str) -> "_BoundHistogram":
        return _BoundHistogram(self, super().labels(**kv))

    def _render_child(self, values: LabelValues, child: _HistValue) -> List[str]:
        lines = []
        cum = 0
        for b, c in zip(self.buckets, child.counts):
            cum += c
            lines.append(
                f"{self.name}_bucket"
                f"{_label_str(self.labelnames, values, [('le', _format_value(b))])}"
                f" {cum}")
        lines.append(
            f"{self.name}_bucket"
            f"{_label_str(self.labelnames, values, [('le', '+Inf')])}"
            f" {child.count}")
        base = _label_str(self.labelnames, values)
        lines.append(f"{self.name}_sum{base} {_format_value(child.sum)}")
        lines.append(f"{self.name}_count{base} {child.count}")
        return lines

    def child_stats(self, **kv: str) -> Tuple[int, float]:
        """(count, sum) for one label set — the registry-as-source-of-truth
        read path for a histogram child."""
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                return 0, 0.0
            return child.count, child.sum


def _observe(metric: Histogram, child: _HistValue, value: float) -> None:
    v = float(value)
    with metric._lock:
        child.sum += v
        child.count += 1
        for i, b in enumerate(metric.buckets):
            if v <= b:
                child.counts[i] += 1
                break


class _BoundHistogram:
    __slots__ = ("_m", "_c")

    def __init__(self, metric: Histogram, child: _HistValue):
        self._m = metric
        self._c = child

    def observe(self, value: float) -> None:
        _observe(self._m, self._c, value)


class MetricsRegistry:
    """Get-or-create metric families + one-pass Prometheus rendering."""

    def __init__(self):
        self._metrics: Dict[str, Metric] = {}
        self._lock = threading.Lock()
        self.created_at = time.time()

    def _get_or_create(self, cls, name: str, help: str,
                       labelnames: Sequence[str], **kw) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls:
                    raise ValueError(
                        f"metric {name} already registered as {type(m).__name__}")
                if tuple(labelnames) != m.labelnames:
                    raise ValueError(
                        f"metric {name} already registered with labels "
                        f"{m.labelnames}, not {tuple(labelnames)}")
                want_buckets = kw.get("buckets")
                if (want_buckets is not None
                        and tuple(sorted(float(b) for b in want_buckets))
                        != getattr(m, "buckets", None)):
                    raise ValueError(
                        f"histogram {name} already registered with buckets "
                        f"{getattr(m, 'buckets', ())}; observations would land "
                        "in buckets this call site never asked for")
                return m
            m = cls(name, help, labelnames, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def collect(self) -> Iterable[Metric]:
        with self._lock:
            return list(self._metrics.values())

    def counter_samples(self, prefix: str = "") -> Dict[str, float]:
        """Flat snapshot of every counter child as
        {'name{label=value,...}': value} (labels sorted by name; the bare
        metric name when label-less). The run ledger diffs two of these
        snapshots to record which counters moved during a run."""
        out: Dict[str, float] = {}
        for m in self.collect():
            if not isinstance(m, Counter) or not m.name.startswith(prefix):
                continue
            for values, v in m.collect_values().items():
                pairs = sorted(zip(m.labelnames, values))
                key = (m.name + "{" + ",".join(f"{n}={val}" for n, val in pairs)
                       + "}") if pairs else m.name
                out[key] = v
        return out

    def render_prometheus(self) -> str:
        """The full exposition, families in registration order."""
        lines: List[str] = []
        for m in self.collect():
            lines.extend(m.render())
        return "\n".join(lines) + "\n"


# The process-wide default registry: all instrumentation in this repo
# records here, and GET /metrics renders it.
REGISTRY = MetricsRegistry()

PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def counter(name: str, help: str = "", labelnames: Sequence[str] = ()) -> Counter:
    return REGISTRY.counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames: Sequence[str] = ()) -> Gauge:
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return REGISTRY.histogram(name, help, labelnames, buckets=buckets)


def render_prometheus() -> str:
    return REGISTRY.render_prometheus()
