"""HTTP + parse/plan + engine: the node's own clock per query, from
``filodb_query_latency_seconds`` (sum over count) over the window."""


def read(ctx):
    n = ctx.delta("filodb_query_latency_seconds_count")
    if n <= 0:
        return None
    return ctx.delta("filodb_query_latency_seconds_sum") / n * 1e3
