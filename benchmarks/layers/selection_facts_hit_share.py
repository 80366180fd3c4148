"""Backend dispatch: of the window's requests that needed what a request
derives from its selection alone (the tile key, the tail bound, the
histogram flag), the share that took it from the selection memo's entry
(``filodb_selection_facts_hits_total``) and made no pass over the series
(``filodb_selection_facts_misses_total`` counts those that did). A selector
that repeats over a store that does not change hits; a selection whose
handles its consumer reads is new every time and misses, so 0 is a reading;
``None`` where neither rose, as on a program without these counters."""


def read(ctx):
    hits = ctx.delta("filodb_selection_facts_hits_total")
    total = hits + ctx.delta("filodb_selection_facts_misses_total")
    if total <= 0:
        return None
    return 100.0 * hits / total
