"""device_idle_pct.sweep: benchmark/readers.device_idle_pct, sweep calls."""

from benchmark.readers import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx, "sweep")
