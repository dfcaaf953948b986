"""JAX runtime telemetry: on-demand device gauges + jit-cache accounting.

Two surfaces:

* `install_runtime_gauges()` registers callback gauges — live device
  buffer count and per-device memory stats — that sample `jax` only when
  the registry is rendered (a /metrics scrape), so steady-state
  simulation pays nothing. jax is imported lazily inside the callbacks;
  importing this module never pulls the runtime in.

* `jit_cache_size(fn)` reads a jitted function's compilation-cache entry
  count (`PjitFunction._cache_size`, present on current jax). The
  simulate paths diff it across the schedule phase to classify the call
  compile-miss vs cache-hit (`simon_compile_cache_total{event=...}`) and
  to stamp the synthetic "compile" span under "schedule" in the Chrome
  trace. Returns None when the attribute moved — callers degrade to
  recording nothing rather than guessing.

The `simon_compile_cache_total{fn, event}` family is shared with the AOT
executable cache (engine/exec_cache.py), which records under
`fn="batched_schedule"` and adds the `eviction` event to the hit/miss
vocabulary — one series tells the whole compilation-amortization story.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Iterator, Optional, Tuple

from open_simulator_tpu.telemetry import registry as _registry

COMPILE_CACHE_TOTAL = "simon_compile_cache_total"


def jit_cache_size(fn) -> Optional[int]:
    sizer = getattr(fn, "_cache_size", None)
    if sizer is None:
        return None
    try:
        return int(sizer())
    except Exception:  # noqa: BLE001 — introspection drift, not a failure
        return None


def record_compile_event(fn_name: str, before: Optional[int],
                         after: Optional[int]) -> Optional[str]:
    """Classify a schedule phase as compile miss/hit from the jit-cache
    delta and count it. Returns "miss"/"hit" (None when unknowable)."""
    if before is None or after is None:
        return None
    event = "miss" if after > before else "hit"
    _registry.counter(
        COMPILE_CACHE_TOTAL,
        "jit compilation-cache outcomes per schedule phase",
        labelnames=("fn", "event"),
    ).labels(fn=fn_name, event=event).inc()
    return event


@contextlib.contextmanager
def schedule_phase(jit_fn, fn_name: str = "schedule_pods") -> Iterator[None]:
    """The schedule-span wrapper both simulate() and Simulator._run use:
    opens the "schedule" span, diffs jit_fn's compile cache across the
    body to count hit/miss, and on a miss stamps a synthetic "compile"
    span nested inside (epsilon-shrunk so Perfetto's containment nesting
    is unambiguous). That "compile" record goes to the recorder only: a
    profiler event cannot be emitted after the fact, so a profiler trace
    shows simon.schedule without it. The body must block on the device
    result (np.asarray) so the span covers real execution."""
    from open_simulator_tpu.telemetry.spans import RECORDER, span

    before = jit_cache_size(jit_fn)
    with span("schedule") as info:
        yield
    event = record_compile_event(fn_name, before, jit_cache_size(jit_fn))
    if event == "miss":
        # place the compile record strictly INSIDE the schedule span's
        # own recorded interval (info carries the exact t0/dur) so the
        # Chrome-trace containment nesting is unambiguous
        eps = min(1e-6, info["dur"] * 0.25)
        RECORDER.add("compile", info["t0"] + eps,
                     max(info["dur"] - 2 * eps, 0.0))


def _live_buffer_count() -> Dict[Tuple[str, ...], float]:
    import jax

    return {(): float(len(jax.live_arrays()))}


def _device_memory_stats() -> Dict[Tuple[str, ...], float]:
    import jax

    out: Dict[Tuple[str, ...], float] = {}
    blind = []
    for d in jax.devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — CPU devices raise/return None
            stats = None
        if not stats:
            blind.append(d)
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                out[(str(d), key)] = float(stats[key])
    if blind:
        # backends without allocator stats (CPU) still hold arrays —
        # sum live-array nbytes per device so the family is never empty
        # and tier-1 CPU runs see real pressure, labelled distinctly
        # ("live_nbytes": buffers we can see, not an allocator's truth)
        names = {str(d) for d in blind}
        held = _live_nbytes_by_device(jax)
        for dev in names:
            out[(dev, "live_nbytes")] = float(held.get(dev, 0))
    return out


def _live_nbytes_by_device(jax) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for a in jax.live_arrays():
        try:
            dev = str(next(iter(a.devices())))
        except Exception:  # noqa: BLE001 — donated/deleted array mid-walk
            continue
        out[dev] = out.get(dev, 0) + int(getattr(a, "nbytes", 0) or 0)
    return out


def _device_count() -> Dict[Tuple[str, ...], float]:
    import jax

    return {(p,): float(n) for p, n in _count_by_platform(jax.devices()).items()}


def _count_by_platform(devices) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for d in devices:
        out[str(d.platform)] = out.get(str(d.platform), 0) + 1
    return out


def install_runtime_gauges(registry: Optional[_registry.MetricsRegistry] = None) -> None:
    """Idempotent: (re)binds the callback gauges on the given registry."""
    reg = registry or _registry.REGISTRY
    reg.gauge(
        "simon_jax_live_buffers",
        "live jax arrays on this process (sampled at scrape time)",
    ).set_callback(_live_buffer_count)
    reg.gauge(
        "simon_jax_device_memory_bytes",
        "per-device memory stats (allocator stats where the backend has "
        "them; summed live-array nbytes as stat=live_nbytes where not)",
        labelnames=("device", "stat"),
    ).set_callback(_device_memory_stats)
    reg.gauge(
        "simon_jax_devices",
        "visible jax devices by platform",
        labelnames=("platform",),
    ).set_callback(_device_count)
