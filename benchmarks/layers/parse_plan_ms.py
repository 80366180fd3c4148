"""HTTP + parse/plan + engine: self time of parse, plan and the results-cache
stitch per answered query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "parse_plan_ms")
