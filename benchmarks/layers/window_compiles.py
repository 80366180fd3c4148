"""Backend dispatch: programs compiled inside the window (shape churn; the
aim is 0), from ``filodb_exec_cache_misses_total``."""


def read(ctx):
    if "filodb_exec_cache_misses_total" not in ctx.m1:
        return None
    return ctx.delta("filodb_exec_cache_misses_total")
