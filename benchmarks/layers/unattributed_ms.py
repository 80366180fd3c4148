"""HTTP + parse/plan + engine: self time of query and execute, what no named
stage below them covers, per answered query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "unattributed_ms")
