"""Aligned tiles + tile cache: hits over hits + builds in the window."""


def read(ctx):
    hits = ctx.delta("filodb_tile_cache_hits_total")
    builds = ctx.delta("filodb_tile_builds_total")
    if hits + builds <= 0:
        return None
    return 100.0 * hits / (hits + builds)
