"""driver_host_ms.question: benchmark/readers.driver_host_ms over the bisect calls."""

from benchmark.readers import driver_host_ms


def read(ctx):
    return driver_host_ms(ctx, "bisect")
