"""Backend dispatch: host self time of tile lookup, eligibility, one-hot, pack,
evaluator routing, kernel submission and aggregation per answered query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "dispatch_host_ms")
