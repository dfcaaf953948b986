"""What the per-layer metric readers (benchmark/metrics/<metric>.py)
share. Each reader takes the run's context and returns a number, or
None when the run has nothing for it to read."""

from __future__ import annotations

from typing import Dict, Optional


def driver_host_ms(ctx: Dict, kind: str) -> Optional[float]:
    """Mean per call of its host wall time minus the program's "sweep"
    spans inside it: pad and upload, masks, the wave-plan lookup, lane
    statistics, and for a bisection the round loop."""
    if ctx["kind"] != kind or not ctx["calls"]:
        return None
    per = [(c["t1"] - c["t0"]) - sum(d for _, d in c["spans"]) for c in ctx["calls"]]
    return 1e3 * sum(per) / len(per)


def device_ms_per_launch(ctx: Dict, kind: str) -> Optional[float]:
    """Mean device busy time inside each launch of the cell's program,
    from the trace."""
    tr = ctx["trace"]
    if ctx["kind"] != kind or tr is None:
        return None
    launches = tr.launches()
    if not launches:
        return None
    return 1e3 * sum(busy for *_, busy in launches) / len(launches)


def device_idle_pct(ctx: Dict, kind: str) -> Optional[float]:
    """Share of the traced window with no operation on the device."""
    tr = ctx["trace"]
    if ctx["kind"] != kind or tr is None:
        return None
    busy = tr.busy_s()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx["window_s"])
