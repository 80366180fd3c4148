"""HTTP + parse/plan + engine: collections in the window that held the
interpreter for at least 20 ms (``filodb_gc_stalls_total``; their seconds are
``filodb_gc_stall_seconds_total``): full collections that walk what the store
holds. To be read beside the window's longest ``idle_gaps``. 0 is a reading;
``None`` on a program without the family."""

FAMILY = "filodb_gc_stalls_total"


def read(ctx):
    if FAMILY not in ctx.m1:
        return None
    return ctx.delta(FAMILY)
