"""HTTP + parse/plan + engine: self time of series selection (index lookup,
reads) and group keys per answered query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "select_ms")
