"""fetch_ms.question: the program's sweep.fetch span (device-to-host copy
of the lane outputs and the finite scan), summed over a question's
rounds, mean over the window's calls."""

from benchmark.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "bisect", "sweep.fetch")
