"""Backend dispatch: the share of the window's answered queries that the fused
group-sum path served (``filodb_fused_aggs_total`` over queries answered)."""


def read(ctx):
    if not ctx.ok:
        return None
    return 100.0 * ctx.delta("filodb_fused_aggs_total") / len(ctx.ok)
