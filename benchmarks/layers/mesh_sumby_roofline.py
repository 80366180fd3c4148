"""Kernels: the grouped counter sum's share of the HBM roofline of ALL the
chips that served it. The least time is the bytes the window's answered
queries need (``roofline/sumby.py``: the same work whatever implements it)
over the peak bandwidth of one chip (``peaks.json``) times the device planes
of the trace; it is divided by the traced window's device-busy time, which
``trace_reduce.reduce`` already averages over those planes. That is
``sumby_roofline``'s reading over the planes: that reader divides by one
chip's peak, so over four chips it reads four times too high, and cells on a
mesh are listed here and not there. ``None`` where nothing ran on a device,
never 0."""
from layers import sumby_roofline


def read(ctx):
    one_chip = sumby_roofline.read(ctx)
    planes = (ctx.trace or {}).get("device_planes")
    if one_chip is None or not planes:
        return None
    return one_chip / planes
