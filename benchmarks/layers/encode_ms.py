"""HTTP + parse/plan + engine: self time of the JSON encode per answered query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "encode_ms")
