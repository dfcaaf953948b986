"""device_ms_per_launch.sweep: benchmark/readers.device_ms_per_launch, sweep calls."""

from benchmark.readers import device_ms_per_launch


def read(ctx):
    return device_ms_per_launch(ctx, "sweep")
