"""fetch_ms.sweep: the program's sweep.fetch span (device-to-host copy of
the lane outputs and the finite scan) per sweep call, mean over the
window's calls."""

from benchmark.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "sweep", "sweep.fetch")
