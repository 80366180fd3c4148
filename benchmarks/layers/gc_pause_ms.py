"""HTTP + parse/plan + engine: what the garbage collector took of the
interpreter per answered query: ``filodb_gc_pause_seconds_total`` (timed in
``gc.callbacks`` where the collection runs, the three generations summed) over
the queries the node answered in the window. Every request thread waits
through a pause, whichever thread it ran on. ``None`` on a program without the
family, or where no query was answered."""

import stages

FAMILY = "filodb_gc_pause_seconds_total"


def read(ctx):
    n = ctx.delta(stages.QUERIES)
    if FAMILY not in ctx.m1 or n <= 0:
        return None
    return ctx.delta(FAMILY) / n * 1e3
