"""Median over every request answered, client clock from the first byte sent
to the last byte read."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.lat_ms, 50)) if ctx.lat_ms.size else None
