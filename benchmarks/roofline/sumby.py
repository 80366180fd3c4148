"""What a grouped windowed sum has to read, whatever implements it: every
sample of every selected series that any step's window reaches, at 12 bytes (an
8-byte value and a 4-byte timestamp: what the query needs, not what any layout
stores). The output (groups x steps) is negligible beside it."""

import reference

BYTES_PER_SAMPLE = 12


def bytes_needed(world, req):
    q = req.query
    n_series = reference.select(world, q["metric"], q.get("select", {})).size
    span_ms = (req.end_s - req.start_s + q["window_s"]) * 1000
    slots = span_ms // world.dt_ms + 1
    return n_series * slots * BYTES_PER_SAMPLE
