"""Kernels: the fused histogram quantile's share of its HBM roofline. The
least time is the bytes the window's answered queries need
(``roofline/histquantile.py``) over the chip's peak bandwidth
(``peaks.json``); it is divided by ALL the device-busy time of the traced
window, as ``sumby_roofline`` is, so the metric survives a PR that replaces
or splits the program. ``None`` where the trace holds no device-busy time
(a program that serves histograms on the host): never 0."""
from roofline import histquantile


def read(ctx):
    t = ctx.trace
    if not t or not t.get("busy_s") or getattr(ctx.world, "les", None) is None:
        return None
    kind = ctx.device["kind"]
    if kind not in ctx.peaks:
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    need = sum(histquantile.bytes_needed(ctx.world, d.req) for d in ctx.ok)
    if need <= 0:
        return None
    least_s = need / ctx.peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
