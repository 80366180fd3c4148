"""Batcher: queries per device batch in the window."""


def read(ctx):
    b = ctx.delta("filodb_batcher_batches_total")
    if b <= 0:
        return None
    return ctx.delta("filodb_batcher_queries_total") / b
