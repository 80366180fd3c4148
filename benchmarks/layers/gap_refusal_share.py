"""Backend dispatch: the share of the window's answered queries that the
fused group-sum path refused because the selection's tiles have holes
(``filodb_fused_refused_gaps_total`` over queries answered). 0 is a reading:
no query was turned away for a missed scrape. ``None`` on a program that
does not count refusals."""


def read(ctx):
    if not ctx.ok or "filodb_fused_refused_gaps_total" not in ctx.m1:
        return None
    return 100.0 * ctx.delta("filodb_fused_refused_gaps_total") / len(ctx.ok)
