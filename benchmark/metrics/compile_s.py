"""compile_s: seconds the executable cache (engine/exec_cache.py) spent
compiling or loading from the persistent cache, summed over its entries."""


def read(ctx):
    return ctx["compile_s"]
