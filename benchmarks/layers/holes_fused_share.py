"""Backend dispatch: the share of the window's answered queries that the
fused gate served with its second program, the grouped non-dense evaluator
over tiles with holes (``filodb_fused_holes_aggs_total`` over queries
answered). 0 is a reading: every fused answer came from dense tiles.
``None`` on a program that has one fused program and no such counter."""


def read(ctx):
    if not ctx.ok or "filodb_fused_holes_aggs_total" not in ctx.m1:
        return None
    return 100.0 * ctx.delta("filodb_fused_holes_aggs_total") / len(ctx.ok)
