"""Per-layer metrics from the program's own spans (``span()`` in
``open_simulator_tpu/telemetry/spans.py``), read from its in-process
recorder after a traced run.

A record's absolute time is the recorder's epoch plus its ``t0``, on the
same ``perf_counter`` as the window's call marks. A program without the
span records nothing under its name, and the readers then return None.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]    # perf_counter seconds: start, end


def records(name: str) -> List[Interval]:
    """The recorder's records named `name`, as absolute intervals."""
    from open_simulator_tpu.telemetry.spans import RECORDER

    epoch = RECORDER.mark()[0]
    return [(epoch + r.t0, epoch + r.t0 + r.dur) for r in RECORDER.records()
            if r.name == name]


def per_call_ms(ctx: Dict, kind: str, name: str,
                recs: Optional[Sequence[Interval]] = None) -> Optional[float]:
    """Mean over the window's calls of the summed time of the `name`
    records that lie inside each call's [t0, t1], in ms: a bisect
    question's rounds sum into one value."""
    if ctx["kind"] != kind or not ctx["calls"]:
        return None
    recs = records(name) if recs is None else recs
    per, found = [], False
    for c in ctx["calls"]:
        inside = [b - a for a, b in recs if c["t0"] <= a and b <= c["t1"]]
        found = found or bool(inside)
        per.append(sum(inside))
    return 1e3 * sum(per) / len(per) if found else None


def setup_s(ctx: Dict, name: str,
            recs: Optional[Sequence[Interval]] = None) -> Optional[float]:
    """Summed seconds of the `name` records that closed before the first
    call of the window began: the set-up's."""
    if not ctx["calls"]:
        return None
    recs = records(name) if recs is None else recs
    first = min(c["t0"] for c in ctx["calls"])
    before = [b - a for a, b in recs if b <= first]
    return sum(before) if before else None
