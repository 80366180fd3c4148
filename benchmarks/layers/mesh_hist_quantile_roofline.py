"""Kernels: the fused histogram quantile's share of the HBM roofline of ALL
the chips that served it. The least time is the bytes the window's answered
queries need (``roofline/histquantile.py``: the same work whatever
implements it) over the peak bandwidth of one chip (``peaks.json``) times the
device planes of the trace; it is divided by the traced window's device-busy
time, which ``trace_reduce.reduce`` already averages over those planes. That
is ``hist_quantile_roofline``'s reading over the planes: that reader divides
by one chip's peak, so over four chips it reads four times too high, and
cells on a mesh are listed here and not there. ``None`` where nothing ran on
a device, never 0."""
from layers import hist_quantile_roofline


def read(ctx):
    one_chip = hist_quantile_roofline.read(ctx)
    planes = (ctx.trace or {}).get("device_planes")
    if one_chip is None or not planes:
        return None
    return one_chip / planes
