"""upload_ms.question: the program's sweep.upload span (pad and host-to-
device copy of the snapshot) per question, mean over the window's calls."""

from benchmark.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "bisect", "sweep.upload")
