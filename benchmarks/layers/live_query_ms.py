"""HTTP + parse/plan + engine, under ingest: the mean client-side latency of
the at-now panel queries that run beside the scraper (too few per window for a
percentile to be an end-to-end metric; they share the GIL with the write
path, which is why this moves the ingest rate)."""


def read(ctx):
    if not ctx.lat_ms.size:
        return None
    return float(ctx.lat_ms.mean())
