"""HTTP + parse/plan + engine: thread CPU seconds over self wall seconds of every
query-path stage but the waits; the rest is waiting for the GIL or a lock.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.cpu_share(ctx)
