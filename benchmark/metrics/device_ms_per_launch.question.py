"""device_ms_per_launch.question: benchmark/readers.device_ms_per_launch, bisect calls."""

from benchmark.readers import device_ms_per_launch


def read(ctx):
    return device_ms_per_launch(ctx, "bisect")
