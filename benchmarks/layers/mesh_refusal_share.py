"""Backend dispatch: the share of the window's answered queries of the fused
shape that a node with a mesh store served from ONE chip because the store
turned them down (``filodb_mesh_refused_total``, its ``reason`` labels
summed: tiles with holes or a span past int32 ms, a grid that does not fit
int32 ms from the tile base, the exact all-f64 family) over queries answered.
0 is a reading: the mesh store refused nothing. ``None`` on a program that
does not count these refusals."""


def read(ctx):
    if not ctx.ok or "filodb_mesh_refused_total" not in ctx.m1:
        return None
    return 100.0 * ctx.delta("filodb_mesh_refused_total") / len(ctx.ok)
