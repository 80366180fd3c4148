"""Kernels: the grouped counter sum's share of its HBM roofline. The least
time is the bytes the window's answered queries need (``roofline/sumby.py``)
over the chip's peak bandwidth (``peaks.json``); it is divided by ALL the
device-busy time of the traced window, not by one named kernel's events, so
the metric survives a PR that replaces or splits the kernel."""
from roofline import sumby


def read(ctx):
    t = ctx.trace
    if not t or not t.get("busy_s"):
        return None
    kind = ctx.device["kind"]
    if kind not in ctx.peaks:
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    need = sum(sumby.bytes_needed(ctx.world, d.req) for d in ctx.ok)
    if need <= 0:
        return None
    least_s = need / ctx.peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
