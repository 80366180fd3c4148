"""WAL + driver + memstore: flush time per thousand rows ingested in the
window (``filodb_flush_seconds_sum`` over ``filodb_rows_ingested``)."""


def read(ctx):
    rows = ctx.delta("filodb_rows_ingested")
    if rows <= 0:
        return None
    return ctx.delta("filodb_flush_seconds_sum") * 1e3 / (rows / 1000.0)
