"""Backend dispatch: the share of the window's answered queries that the
mesh-resident sharded store served (``filodb_mesh_dispatches_total`` over
queries answered): the selection's tiles sharded by series over the chips,
the group sums a ``psum`` over the shard axis. 0 is a reading: the
single-chip path served every query. ``None`` on a program without the
counter."""


def read(ctx):
    if not ctx.ok or "filodb_mesh_dispatches_total" not in ctx.m1:
        return None
    return 100.0 * ctx.delta("filodb_mesh_dispatches_total") / len(ctx.ok)
