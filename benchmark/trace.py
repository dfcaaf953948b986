"""Reduction from a profiler trace (``*.xplane.pb``) to device metrics.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per operation run on the chip and ``XLA
Modules`` one event per executable launch. Busy time is the union of
the operation intervals, so overlapping or nested events count once.
Host spans (the benchmark's ``TraceAnnotation`` marks, and the program's
spans mapped onto the trace clock) label the idle gaps.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench.clock"

Interval = Tuple[float, float]   # seconds on the trace clock


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def short(name: str) -> str:
    """An operation's name without its HLO text: "%fusion.12 = f32[...]
    fusion(...)" -> "fusion.12"."""
    return name.split(" = ", 1)[0].lstrip("%")


def family(module: str) -> str:
    """A launch's function without its executable's fingerprint:
    "jit_f(8485634492780914798)" -> "jit_f"."""
    return module.split("(", 1)[0]


class Trace:
    """What the benchmark reads of one trace: per device, operation and
    launch events; host annotations by name."""

    def __init__(self, devices: Dict[str, Dict[str, list]],
                 host: Dict[str, List[Interval]]):
        self.devices = devices    # plane -> {"ops": [(name, a, b)], "modules": [...]}
        self.host = host          # annotation name -> intervals

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                     recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        data = ProfileData.from_file(path)
        devices: Dict[str, Dict[str, list]] = {}
        host: Dict[str, List[Interval]] = defaultdict(list)
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                d = devices.setdefault(plane.name, {"ops": [], "modules": []})
                for line in plane.lines:
                    kind = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                    if kind is None:
                        continue
                    d[kind] = [(short(ev.name), ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            host[ev.name].append(
                                (ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
        return cls(devices, dict(host))

    # ---- reductions ---------------------------------------------------------

    def window(self) -> Optional[Interval]:
        """The traced window: the span of the benchmark's call marks."""
        calls = [iv for name, ivs in self.host.items() if name != MARK
                 for iv in ivs]
        if not calls:
            return None
        return min(a for a, _ in calls), max(b for _, b in calls)

    def busy(self, plane: str, lo: float, hi: float) -> float:
        ops = self.devices[plane]["ops"] or self.devices[plane]["modules"]
        return covered(union([(a, b) for _, a, b in ops]), lo, hi)

    def busy_s(self) -> Optional[float]:
        """Seconds with an operation running, averaged over the chips
        that ran any."""
        win = self.window()
        used = [p for p, d in self.devices.items() if d["ops"] or d["modules"]]
        if win is None or not used:
            return None
        return sum(self.busy(p, *win) for p in used) / len(used)

    def launches(self) -> List[Tuple[str, float, float, float]]:
        """(module, start, end, busy seconds inside) for each launch of
        the function that took the most device time: the cell's own
        program (every variant of it, e.g. with and without a donated
        carry), not the small transfers and helpers around it."""
        out = []
        for plane, d in self.devices.items():
            mods = d["modules"]
            if not mods:
                continue
            tot: Dict[str, float] = defaultdict(float)
            for name, a, b in mods:
                tot[family(name)] += b - a
            main = max(tot, key=tot.get)
            busy = union([(a, b) for _, a, b in d["ops"]])
            for name, a, b in mods:
                if family(name) == main:
                    out.append((name, a, b, covered(busy, a, b) if busy else b - a))
        return out

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """Device operations by total seconds, averaged over chips."""
        tot: Dict[str, float] = defaultdict(float)
        used = [d for d in self.devices.values() if d["ops"]]
        for d in used:
            for name, a, b in d["ops"]:
                tot[name] += b - a
        n = max(len(used), 1)
        return sorted(((name, s / n) for name, s in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, labels: Sequence[Tuple[str, float, float]],
                  k: int = 10) -> List[Tuple[str, float]]:
        """The longest idle stretches of the window on the first busy
        chip, each named by the innermost host span (label, start, end,
        on the trace clock) that covers its middle."""
        win = self.window()
        used = [p for p, d in sorted(self.devices.items()) if d["ops"] or d["modules"]]
        if win is None or not used:
            return []
        d = self.devices[used[0]]
        busy = union([(a, b) for _, a, b in (d["ops"] or d["modules"])])
        out = []
        for a, b in gaps(busy, *win):
            mid = 0.5 * (a + b)
            inside = [(e - s, name) for name, s, e in labels if s <= mid <= e]
            out.append((min(inside)[1] if inside else "between calls", b - a))
        return sorted(out, key=lambda x: -x[1])[:k]

    def clock_offset(self, perf_t0: float) -> Optional[float]:
        """trace time minus perf_counter time, from the clock mark the
        benchmark opened at perf_counter() == perf_t0."""
        marks = self.host.get(MARK)
        if not marks:
            return None
        return marks[0][0] - perf_t0
