"""Rows sent through the gateway that the node acknowledged inside the window
(``filodb_rows_ingested`` at the close less at the open) over its length."""


def read(ctx):
    a = ctx.traffic.acked
    if a is None or a.at_close is None:
        return None
    return (a.at_close - a.at_open) / ctx.seconds
