"""HTTP + parse/plan + engine: how much of the CPU the stage table charges is
the collector's: ``filodb_stage_<S>_gc_seconds_total`` (collector pauses, added
to the innermost stage open on the collecting thread; exact) over
``filodb_stage_<S>_cpu_seconds_total`` (sampled), both over every query-path
stage but the waits, as ``host_cpu_share`` takes them. ``None`` on a program
whose stages have no collector column, or where no CPU was sampled. Stages and
families: ``stages.py``."""

import stages


def read(ctx):
    work = [s for row in stages.ROWS.values() for s in row
            if s not in stages.WAITS]
    if stages.family("query", "gc_seconds_total") not in ctx.m1:
        return None
    cpu = stages.seconds(ctx, work, "cpu_seconds_total")
    if cpu <= 0:
        return None
    return 100.0 * stages.seconds(ctx, work, "gc_seconds_total") / cpu
