"""What a histogram quantile of a grouped windowed rate has to read, whatever
implements it: every slot of every selected series that any step's window
reaches, at 8 bytes a bucket and a 4-byte timestamp a slot (what the query
needs, not what any layout stores). The output (groups x steps) is negligible
beside it."""

import reference

BYTES_PER_BUCKET = 8
BYTES_PER_TIMESTAMP = 4


def bytes_needed(world, req):
    q = req.query
    n_series = reference.select(world, q["metric"], q.get("select", {})).size
    span_ms = (req.end_s - req.start_s + q["window_s"]) * 1000
    slots = span_ms // world.dt_ms + 1
    return n_series * slots * (len(world.les) * BYTES_PER_BUCKET
                               + BYTES_PER_TIMESTAMP)
