"""wave_plan_s: seconds in the program's wave_plan span during set-up:
the wave-plan build on the warm call's plan-cache miss."""

from benchmark.spans import setup_s


def read(ctx):
    return setup_s(ctx, "wave_plan")
