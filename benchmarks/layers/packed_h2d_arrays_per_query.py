"""Backend dispatch: host values that the packed path's launches handed the
device in the window (``filodb_packed_host_arrays_total``: the launch's
arguments that are host values, plus any put made for it), per answered
query. A packed launch hands over two host arrays, one int64 and one f64
block, whatever its batch's size, so the reading is about 2 over
``batch_occupancy`` where every request takes the packed path. ``None`` on a
program that does not count it."""


def read(ctx):
    if not ctx.ok or "filodb_packed_host_arrays_total" not in ctx.m1:
        return None
    return ctx.delta("filodb_packed_host_arrays_total") / len(ctx.ok)
