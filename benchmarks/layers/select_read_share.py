"""HTTP + parse/plan + engine: of the series handles the window's whole-series
selections handed out (``filodb_select_series_total``), the share whose samples
some consumer then read (``filodb_select_series_read_total``). A query that is
answered from a tile-cache entry reads none, so 0 is a reading (and the aim of
the fused path); ``None`` only where no handle was handed out, as on a program
without these counters."""


def read(ctx):
    handles = ctx.delta("filodb_select_series_total")
    if handles <= 0:
        return None
    return 100.0 * ctx.delta("filodb_select_series_read_total") / handles
