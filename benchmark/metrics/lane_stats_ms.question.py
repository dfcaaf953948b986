"""lane_stats_ms.question: the program's sweep.lane_stats span (the per-
lane verdicts), summed over a question's rounds, mean over the window's
calls."""

from benchmark.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "bisect", "sweep.lane_stats")
