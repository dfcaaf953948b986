"""encode_s: host seconds of the set-up's encode_cluster call (encode/snapshot.py)."""


def read(ctx):
    return ctx["encode_s"]
