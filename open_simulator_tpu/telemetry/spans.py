"""Nested host-side spans + Chrome-trace (Perfetto) export.

The reference's only timeline is utiltrace's log-if-long alarm
(pkg/simulator/core.go:80-128). Here every phase — encode, compile,
schedule, decode, sweep, chaos events — opens a `span(...)`; closing it
feeds the `simon_phase_seconds` histogram in the default registry and
appends a record to a bounded process-wide recorder, which
`export_chrome_trace` serializes as the Trace Event JSON format that
`chrome://tracing` and Perfetto load (complete "X" events: name/ts/dur in
microseconds, nested by containment per thread). `--trace-out` on the CLI
writes that file after a run.

Spans are host-only and nest via a thread-local stack. Each also opens a
`jax.profiler.TraceAnnotation` named `simon.<name>`, with its attrs as
stats, for its own extent: whenever a profiler session is active (a
`jax.profiler.trace`, `/debug/profile`), the program's phases sit on the
device trace's own clock beside the device operations. With no session
active that costs well under a microsecond, so wrapping millisecond
phases is safe. A span never opens inside traced code (jit, scan, vmap):
it would time tracing, not execution (graftlint GL4).
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from open_simulator_tpu.telemetry import registry as _registry

# one histogram for every phase span, labeled by phase name
PHASE_SECONDS = "simon_phase_seconds"
# counts records the bounded recorder overflowed away (oldest-first) —
# a chrome-trace export after heavy load is a WINDOW, and this counter
# is how /debug/stats says so instead of the window lying by omission
SPANS_DROPPED_TOTAL = "simon_spans_dropped_total"


@dataclass(frozen=True)
class SpanRecord:
    name: str
    t0: float          # perf_counter seconds, process-relative
    dur: float         # seconds
    tid: int
    depth: int
    args: Dict[str, str] = field(default_factory=dict)


class SpanRecorder:
    """Bounded in-memory span sink (process-wide singleton below).

    Always on: the buffer is a deque with a maxlen, so long-lived servers
    pay O(1) memory and `--trace-out` / tests read whatever the recent
    window holds. `clear()` starts a fresh capture (the CLI clears before
    a traced run so the export covers exactly that run).
    """

    def __init__(self, maxlen: int = 65536):
        self._records: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()
        self._local = threading.local()
        self.dropped = 0  # records the deque overflowed away (oldest)

    # ---- stack (thread-local nesting) ---------------------------------

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # ---- recording -----------------------------------------------------

    def add(self, name: str, t0: float, dur: float,
            depth: Optional[int] = None,
            args: Optional[Dict[str, str]] = None) -> None:
        """Append a span record with explicit timing (used both by the
        span() context manager and by after-the-fact annotations like the
        compile-on-cache-miss span, whose interval is only known once the
        jit call returns)."""
        rec = SpanRecord(
            name=name, t0=t0 - self._epoch, dur=dur,
            tid=threading.get_ident(),
            depth=len(self._stack()) if depth is None else depth,
            args=dict(args or {}))
        with self._lock:
            overflowed = (self._records.maxlen is not None
                          and len(self._records) == self._records.maxlen)
            self._records.append(rec)
            if overflowed:
                self.dropped += 1
        if overflowed:
            # overflow was silent at maxlen: the recorder kept the newest
            # window and nothing said records were lost
            _registry.counter(
                SPANS_DROPPED_TOTAL,
                "span records evicted from the bounded recorder (oldest "
                "dropped; the retained window stays the newest spans)",
            ).inc()

    def records(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._records)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
        self._epoch = time.perf_counter()

    # ---- windows -------------------------------------------------------

    def mark(self) -> Tuple[float, float]:
        """(epoch, now-relative) window marker: records_since(mark)
        returns only spans recorded after this point. The run ledger
        marks a run's start; the server marks each POST so GET
        /api/trace can dump just the last request's span tree."""
        return (self._epoch, time.perf_counter() - self._epoch)

    def records_since(self, mark: Optional[Tuple[float, float]]) -> List[SpanRecord]:
        if mark is None:
            return self.records()
        epoch, rel = mark
        if epoch != self._epoch:
            # clear() reset the window since the mark — everything held
            # now started after it
            rel = 0.0
        return [r for r in self.records() if r.t0 >= rel - 1e-9]

    # ---- export --------------------------------------------------------

    def chrome_trace(self, since: Optional[Tuple[float, float]] = None) -> Dict:
        """Trace Event JSON (the `traceEvents` array of complete events).
        Events are emitted start-ordered; nesting falls out of interval
        containment per (pid, tid) row, which the per-thread span stack
        guarantees for spans and the add() caller guarantees for
        synthetic ones."""
        pid = os.getpid()
        events = []
        for rec in sorted(self.records_since(since),
                          key=lambda r: (r.tid, r.t0, -r.dur)):
            ev = {
                "name": rec.name,
                "ph": "X",
                "ts": round(rec.t0 * 1e6, 3),
                "dur": round(rec.dur * 1e6, 3),
                "pid": pid,
                "tid": rec.tid,
                "cat": "simon",
            }
            if rec.args:
                ev["args"] = rec.args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        from open_simulator_tpu.resilience import faults

        payload = self.chrome_trace()

        def write() -> None:
            with open(path, "w", encoding="utf-8") as f:
                json.dump(payload, f)

        # ride the storage fault domain (GL9): retries + the ENOSPC/EIO
        # classification rung, same as ledger/journal writes
        faults.run_io("trace_export", write)
        return path


RECORDER = SpanRecorder()


@functools.lru_cache(maxsize=None)
def _profiler_annotation():
    """jax.profiler.TraceAnnotation, imported on the first span; a null
    context where jax cannot be imported."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return lambda name, **attrs: contextlib.nullcontext()
    return TraceAnnotation


@contextlib.contextmanager
def span(name: str, recorder: Optional[SpanRecorder] = None,
         **attrs: str) -> Iterator[Dict[str, float]]:
    """Time a phase: nested spans build the timeline, every exit observes
    simon_phase_seconds{phase=name}, and a profiler session, when one is
    active, records the event simon.<name> with `attrs` as its stats.
    Exceptions propagate; the span still closes (a failed phase is still
    a timed phase).

    Yields a dict filled with the span's exact {"t0", "dur"} on exit, so
    a caller that must append sibling/child records after the fact (the
    compile-on-cache-miss span) can place them INSIDE this span's
    recorded interval instead of re-measuring around the context manager
    (which would strictly enclose it and break containment nesting)."""
    rec = recorder or RECORDER
    annotation = _profiler_annotation()(f"simon.{name}", **attrs)
    stack = rec._stack()
    depth = len(stack)
    stack.append(name)
    info: Dict[str, float] = {}
    t0 = time.perf_counter()
    try:
        with annotation:
            yield info
    finally:
        dur = time.perf_counter() - t0
        info["t0"] = t0
        info["dur"] = dur
        stack.pop()
        rec.add(name, t0, dur, depth=depth,
                args={str(k): str(v) for k, v in attrs.items()} or None)
        _registry.histogram(
            PHASE_SECONDS, "wall time of simulator phases by span name",
            labelnames=("phase",),
        ).labels(phase=name).observe(dur)


def current_depth(recorder: Optional[SpanRecorder] = None) -> int:
    return len((recorder or RECORDER)._stack())


def export_chrome_trace(path: str,
                        recorder: Optional[SpanRecorder] = None) -> str:
    """Write the recorder's current window as a Chrome-trace JSON file."""
    return (recorder or RECORDER).export_chrome_trace(path)
