"""lane_stats_ms.sweep: the program's sweep.lane_stats span (the per-lane
verdicts) per sweep call, mean over the window's calls."""

from benchmark.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "sweep", "sweep.lane_stats")
