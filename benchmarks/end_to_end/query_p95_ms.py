"""95th percentile over every request answered (the same requests as
``query_p50_ms``)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.lat_ms, 95)) if ctx.lat_ms.size else None
