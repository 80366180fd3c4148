"""Aligned tiles + tile cache: self time of the ``mesh-place`` stage (a
selection's tiles transposed on the host and put across the mesh, when the
store BUILDS a placement, not on a hit) per query the node answered in the
window. 0 is a reading and the expected one: the placement happens in
warm-up, and more than 0 means one was dropped and built again under
traffic. ``None`` on a program without the stage. Families: ``stages.py``."""

import stages


def read(ctx):
    fam = stages.family("mesh-place", "self_seconds_total")
    n = ctx.delta(stages.QUERIES)
    if fam not in ctx.m1 or n <= 0:
        return None
    return ctx.delta(fam) / n * 1e3
