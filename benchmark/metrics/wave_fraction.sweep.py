"""wave_fraction.sweep: percent of pods the wave plan (engine/waves.py)
runs in batched segments for the cell's snapshot; 0 without a plan."""


def read(ctx):
    return ctx["wave_fraction"] if ctx["kind"] == "sweep" else None
