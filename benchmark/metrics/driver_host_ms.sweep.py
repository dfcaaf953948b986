"""driver_host_ms.sweep: benchmark/readers.driver_host_ms over the sweep calls."""

from benchmark.readers import driver_host_ms


def read(ctx):
    return driver_host_ms(ctx, "sweep")
