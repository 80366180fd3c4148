"""HTTP + parse/plan + engine: of the window's mesh lowerings that had to
know whether their selection holds histogram columns (query/planner.py
``_hist_selection``: it picks the mesh-resident store, the scatter-gather
aggregate or the local engine), the share that took "none" from the
selection memo's entry (``filodb_plan_selection_facts_hits_total``) and
asked no shard for its partitions (``filodb_plan_selection_facts_walks_total``
counts those that matched the index on every shard and walked every matched
partition's schema). One template over a store nobody writes to hits after
the warm-up; a store under ingest moves a version before every request and
walks, so 0 is a reading; ``None`` where neither rose: a node without a mesh
never asks, and a program without these counters has nothing to read."""


def read(ctx):
    hits = ctx.delta("filodb_plan_selection_facts_hits_total")
    total = hits + ctx.delta("filodb_plan_selection_facts_walks_total")
    if total <= 0:
        return None
    return 100.0 * hits / total
