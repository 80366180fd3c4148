"""HTTP + parse/plan + engine: of the window's whole-series selections over
local shards, the share the selection memo answered
(``filodb_select_memo_hits_total``) and not the loop over partitions
(``filodb_select_memo_misses_total``). A selector that repeats over a store
that does not change hits; one whose handles are read, or that does not
repeat, misses, so 0 is a reading; ``None`` only where no such selection ran,
as on a program without these counters."""


def read(ctx):
    hits = ctx.delta("filodb_select_memo_hits_total")
    total = hits + ctx.delta("filodb_select_memo_misses_total")
    if total <= 0:
        return None
    return 100.0 * hits / total
