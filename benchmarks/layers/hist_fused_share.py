"""Backend dispatch: the share of the window's answered queries that the
fused histogram quantile program served (``filodb_fused_hist_aggs_total``
over queries answered): a ``histogram_quantile`` of a histogram sum of which
only the [steps, groups] answer left the chip. 0 is a reading (the host
served every one); ``None`` on a program that has no such counter."""


def read(ctx):
    if not ctx.ok or "filodb_fused_hist_aggs_total" not in ctx.m1:
        return None
    return 100.0 * ctx.delta("filodb_fused_hist_aggs_total") / len(ctx.ok)
