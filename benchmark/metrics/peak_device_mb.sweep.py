"""peak_device_mb.sweep: peak_bytes_in_use of the fullest chip after the
window, in MB (1e6 bytes)."""


def read(ctx):
    if ctx["kind"] != "sweep" or ctx["peak_bytes"] is None:
        return None
    return ctx["peak_bytes"] / 1e6
