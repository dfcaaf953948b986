"""device_idle_pct.question: benchmark/readers.device_idle_pct, bisect calls."""

from benchmark.readers import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx, "bisect")
