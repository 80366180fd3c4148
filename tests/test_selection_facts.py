"""What a request derives from its selection alone (query/model.py
``SelectionFacts``: the tile key and its ident, the tail bound, the histogram
flag) is derived once a selection: it rides the selection memo's entry, the
tile-order group ids ride the tile entry, and a second request over a store
that has not changed walks no series.

Pinned here: the facts are what the per-request loops computed, fact for
fact, on every kind of store; a hit makes no pass and answers the same
bytes; the facts go with the entry (a handle read, a version moved, a
partition evicted under its handle: the rewritten snapshot key is what the
tiles go under); a selection without an entry, or whose entry is dropped
mid-request, answers as before; the memo never keeps a tile entry alive;
each grouping has its own tile-order ids and nothing grows without bound; a
selection its consumer reads counts one miss and no hit.
"""

import gc
import json
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.http import prom_json
from filodb_tpu.promql.parser import parse_query_range
from filodb_tpu.query import engine as eng
from filodb_tpu.query import model, tpu
from filodb_tpu.query.engine import (QueryEngine, select_counts,
                                     select_memo, select_raw_series)
from filodb_tpu.query.model import (QueryStats, SelectionFacts,
                                    selection_facts)
from filodb_tpu.query.tpu import TpuBackend

from test_select_handles import (KINDS, REF, T0, _assert_matches,
                                 _column, _filters, _ingest, _store)
from test_select_memo import MOVES, QUERY, TSP, _ref

OPS = ("sum", "avg", "count")
PACKED = "max(max_over_time(reqs_total[5m]))"     # cell 2's shape


@pytest.fixture(autouse=True)
def _empty_memo():
    select_memo.clear()
    yield
    select_memo.clear()


def _counts():
    return select_counts.facts_hits, select_counts.facts_misses


def _delta(before):
    return tuple(a - b for a, b in zip(_counts(), before))


def _select(shard, kind="flushed"):
    return select_raw_series([shard], _filters(kind), 0, 2**62,
                             _column(kind), QueryStats(), full=True)


def _body(shard, be, query=QUERY, tsp=TSP):
    """The answer's bytes, as the HTTP edge encodes it."""
    plan = parse_query_range(query, tsp)
    grid = QueryEngine([shard], backend=be).execute(plan)
    return json.dumps(prom_json.matrix(grid), sort_keys=True).encode()


# --- the facts are what the loops computed -----------------------------------

def _loops(series):
    """The per-request loops of query/tpu.py before the facts rode the
    entry (``_tile_key``, ``_tail_min``, the ``any`` of the engine),
    written out as they were."""
    use_snap = all(s.snapshot_key is not None for s in series)
    if use_snap:
        key = tuple(s.snapshot_key for s in series)
        ident = tuple(s.snapshot_key[:3] + s.snapshot_key[4:]
                      for s in series)
    else:
        key, ident = tuple(id(s) for s in series), None
    bound = None
    for s in series:
        tm = s.tail_first_ts
        if tm is not None and (bound is None or tm < bound):
            bound = tm
    return use_snap, key, ident, bound, any(s.is_hist for s in series)


def _as_tuple(facts):
    return (facts.use_snap, facts.key.parts, facts.ident, facts.tail_min,
            facts.any_hist)


@pytest.mark.parametrize("kind", KINDS)
def test_the_facts_are_what_the_loops_computed(kind):
    """Over handles not yet read, over the same handles read, and over
    plain series built from arrays (no snapshot keys: keyed by identity)."""
    series = _select(_store(kind), kind)
    assert len(series) == 4
    reads = select_counts.reads
    assert _as_tuple(SelectionFacts(series)) == _loops(series)
    assert select_counts.reads == reads         # from the facts: none read
    for s in series:
        s.ts
    assert _as_tuple(SelectionFacts(series)) == _loops(series)
    plain = [model.RawSeries(dict(s.labels), s.ts, s.values, s.is_counter,
                             s.bucket_les) for s in series]
    got = SelectionFacts(plain)
    assert _as_tuple(got) == _loops(plain) and got.ident is None
    # the bound a stale tile entry adds is folded in a request, not kept
    tm = got.tail_min
    for cov in (None, 5, 2**62):
        want = cov if tm is None else tm if cov is None else min(tm, cov)
        assert got.tail_bound(cov) == want


# --- (a) a second request makes no pass, and answers the same bytes ----------

@pytest.mark.parametrize("op", OPS)
def test_a_second_request_makes_no_pass_and_answers_the_same(op):
    shard = _store("flushed")
    be = TpuBackend()
    query = QUERY.replace("sum", op, 1)
    cold = _body(shard, be, query)          # builds the tiles: entry ended
    _body(shard, be, query)                 # a new entry: facts made
    entry, = select_memo._entries.values()
    facts = entry.facts
    assert facts is not None
    for _ in range(3):
        before, reads = _counts(), select_counts.reads
        assert _body(shard, be, query) == cold
        assert _delta(before) == (1, 0)
        assert select_counts.reads == reads
        assert entry.facts is facts         # the same object every time
    assert be.tile_builds == 1 and be.fused_aggs == 5
    # and without the memo at all (every request a miss) the same bytes
    for _ in range(2):
        select_memo.clear()
        before = _counts()
        assert _body(shard, be, query) == cold
        assert _delta(before) == (0, 1)


def test_a_hit_looks_the_tiles_up_by_the_caches_own_key():
    """Two selections of one store make equal keys, which compare tuple by
    tuple; the facts take the cache's own key at the first hit, so later
    lookups end at ``is``."""
    shard = _store("flushed")
    be = TpuBackend()
    for _ in range(3):
        _body(shard, be)
    entry, = select_memo._entries.values()
    tile, = be._tile_cache.values()
    assert entry.facts.key is tile.key
    assert next(iter(be._tile_cache)) is tile.key


# --- (b) the facts go with the entry ----------------------------------------

def test_a_read_of_one_handle_ends_the_facts():
    shard = _store("flushed")
    be = TpuBackend()
    for _ in range(2):
        _body(shard, be)
    entry, = select_memo._entries.values()
    assert entry.facts is not None
    mine = _select(shard)
    assert mine.entry is entry
    mine[1].ts                                  # one touch
    assert entry.facts is None and entry.held is None
    before = _counts()
    assert selection_facts(mine).key == SelectionFacts(mine).key
    assert _delta(before) == (0, 1)             # made, not taken
    assert entry.facts is None                  # nothing left behind
    select_memo.clear()
    before = _counts()
    cold = _body(shard, be)
    assert _delta(before) == (0, 1)
    assert cold == _body(shard, be)


@pytest.mark.parametrize("move", sorted(MOVES))
def test_a_change_of_the_store_ends_the_facts(move, tmp_path):
    """An entry is served and carries its facts; then the store changes.
    The next request makes its facts anew, from the handles of the store as
    it is now, and answers refeval's answer over it."""
    from filodb_tpu.store import FlatFileColumnStore
    prepare, change, wants_store, chunk_rows = MOVES[move]
    cs = FlatFileColumnStore(str(tmp_path / "col")) if wants_store else None
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0,
                            max_chunk_rows=chunk_rows, column_store=cs)
    _ingest(shard, "flushed+tail", 0, 170)
    shard.flush_all()
    _ingest(shard, "flushed+tail", 170, 200)
    if prepare is not None:
        prepare(shard)
    be = TpuBackend()
    plan = parse_query_range(QUERY, TSP)
    raw = plan.inner.raw
    for _ in range(3):      # a page-in overlaps the first build: not kept
        series = select_raw_series([shard], raw.filters, raw.start_ms,
                                   raw.end_ms, None, QueryStats(),
                                   full=True)
    entry = series.entry
    old = selection_facts(series)
    before = _counts()
    again = select_raw_series([shard], raw.filters, raw.start_ms,
                              raw.end_ms, None, QueryStats(), full=True)
    assert selection_facts(again) is old and entry.facts is old
    assert _delta(before) == (1, 0)
    rows_of = change(shard)
    before = _counts()
    got = QueryEngine([shard], backend=be).execute(plan)
    assert _delta(before) == (0, 1)
    assert entry.facts is None and entry.held is None
    _assert_matches(got, _ref(rows_of), 1e-5)
    now = _select(shard, "flushed+tail")
    assert _as_tuple(SelectionFacts(now)) == _loops(now)
    if move in ("flush", "buffer-full-switch"):
        assert SelectionFacts(now).key != old.key   # other chunk counts


def test_evicted_under_its_handle_the_rewritten_key_is_the_tiles(tmp_path):
    """A holder's handles outlive an eviction: the first read takes facts
    and samples again, as one, and rewrites the snapshot key. The tiles go
    under the key made AFTER the build read, and the tail bound the request
    serves under is the one of what was read."""
    from filodb_tpu.store import FlatFileColumnStore
    cs = FlatFileColumnStore(str(tmp_path / "col"))
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100,
                            column_store=cs)
    _ingest(shard, "flushed+tail", 0, 170)
    shard.flush_all(offset=1)
    series = _select(shard, "flushed+tail")
    taken = selection_facts(series)
    assert series.entry.facts is taken
    keys = [s.snapshot_key for s in series]
    _ingest(shard, "flushed+tail", 170, 200)
    shard.flush_all(offset=1)
    assert shard.evict_partitions(cutoff_ts=2**62) == 4
    be = TpuBackend()
    entry, built = be._tile_entry(series, taken)
    assert all(s.filled for s in series)
    assert [s.snapshot_key for s in series] != keys         # rewritten
    assert built is not taken
    assert _as_tuple(built) == _loops(series)
    assert next(iter(be._tile_cache)) is built.key and entry.key is built.key
    assert entry.cov_min_ms == built.tail_min
    assert built.key != taken.key and built.tail_min == taken.tail_min
    assert series.entry.facts is None           # (a version moved: gone)


# --- (c) without an entry, and with one dropped mid-request ------------------

@pytest.mark.parametrize("how", ["plain-list", "dropped-before",
                                 "dropped-mid-request", "remote-shaped"])
def test_a_selection_without_a_live_entry_answers_as_before(how):
    shard = _store("flushed")
    be = TpuBackend()
    steps = np.arange(T0 + 600_000, T0 + 2_300_001, 60_000, dtype=np.int64)
    series = _select(shard)
    gids, gkeys = eng._selection_groups(series, ("job",), ())
    want = be.fused_groupsum(series, "rate", steps, 300_000, 0, gids,
                             len(gkeys))
    assert want is not None
    series = _select(shard)                     # the tile build read those
    assert series.entry is not None and series.entry.held is not None
    facts = None
    before = _counts()
    if how == "plain-list":
        series = list(series)
    elif how == "dropped-before":
        select_memo.drop(series.entry)
    elif how == "dropped-mid-request":
        facts = selection_facts(series)
        select_memo.drop(series.entry)
    else:       # a Selection-like list whose entry is None
        series = eng.Selection(series, None)
    got = be.fused_groupsum(series, "rate", steps, 300_000, 0, gids,
                            len(gkeys), facts)
    assert _delta(before) == (0, 1)
    assert np.array_equal(got[0], want[0], equal_nan=True)
    assert np.array_equal(got[1], want[1])
    assert be.tile_builds == 1                  # the same tiles, by key
    grid = be.periodic_samples(series, model.RangeParams(
        int(steps[0]), 60_000, int(steps[-1])), "rate", 300_000)
    assert grid.values.shape == (4, steps.size)


# --- (d) the memo keeps the key, never the tiles -----------------------------

def test_a_tile_entry_pushed_out_is_freed_and_built_again(monkeypatch):
    shard = _store("flushed")
    be = TpuBackend()
    monkeypatch.setattr(TpuBackend, "_TILE_CACHE_MAX", 1)
    first = _body(shard, be)
    for _ in range(2):
        assert _body(shard, be) == first
    entry, = select_memo._entries.values()
    assert entry.facts is not None and len(entry.groups) == 1
    tile, = be._tile_cache.values()
    tiles = weakref.ref(tile.tiles)
    del tile

    def alive():
        gc.collect()
        return sum(isinstance(o, tpu._TileEntry) for o in gc.get_objects())
    assert alive() == 1
    other = QUERY.replace("reqs_total", 'reqs_total{instance=~"i[01]"}')
    _body(shard, be, other)                     # pushes the first out
    assert alive() == 1 and tiles() is None
    # (the other selection's entry went with its tile build's read)
    assert list(select_memo._entries.values()) == [entry]
    assert entry.facts is not None
    builds, before = be.tile_builds, _counts()
    assert _body(shard, be) == first            # asked for again: rebuilt
    assert be.tile_builds == builds + 1
    assert _delta(before) == (1, 0)             # the facts served the miss


# --- (e) groupings -----------------------------------------------------------

def test_each_grouping_has_its_own_tile_order_ids_and_none_grows():
    shard = _store("jittered")
    be = TpuBackend()
    by_job = QUERY
    by_inst = QUERY.replace("by (job)", "by (instance)")
    for q in (by_job, by_inst) * 3:
        _body(shard, be, q)
    entry, = select_memo._entries.values()
    tile, = be._tile_cache.values()
    assert set(entry.groups) == {(("job",), ()), (("instance",), ())}
    # (the first request's entry, which the tile build's read ended, left
    # its by (job) ids behind: kept until the bound clears them)
    kept = len(tile.gvecs.kept)
    assert 2 <= kept <= 3
    for gids, _ in entry.groups.values():
        kept_of, gvec = tile.gvecs.kept[id(gids)]
        assert kept_of is gids and not gvec.flags.writeable
        assert np.array_equal(gvec, gids[tile.idx])
        assert tile.tile_order(gids) is gvec            # a lookup
    assert tile.idx.dtype == np.int64
    # an array that is not frozen is nobody's: gathered, not kept
    mine = np.arange(4) % 2
    got = tile.tile_order(mine)
    assert np.array_equal(got, mine[tile.idx]) and got.flags.writeable
    assert len(tile.gvecs.kept) == kept
    # a ninth grouping, and a twenty-fourth
    series = _select(shard, "jittered")
    for i in range(3 * model.MAX_GROUPINGS):
        gids, _ = eng._selection_groups(series, ("job", f"l{i}"), ())
        assert np.array_equal(tile.tile_order(gids), gids[tile.idx])
        assert len(tile.gvecs.kept) <= model.MAX_GROUPINGS
        assert len(entry.groups) <= eng._MEMO_MAX_GROUPINGS
    assert eng._MEMO_MAX_GROUPINGS == model.MAX_GROUPINGS == 8


# --- (g) a selection its consumer reads --------------------------------------

@pytest.mark.parametrize("kind", ["flushed", "flushed+tail", "buffer-only"])
def test_a_selection_that_is_read_counts_one_miss_and_no_hit(kind):
    """Cell 2's shape: the packed path reads every handle, so the entry
    dies with the request; the facts are made once and used once."""
    shard = _store(kind)
    be = TpuBackend()
    plan = parse_query_range(PACKED, TSP)
    made = []
    real = SelectionFacts.__init__

    def counting(self, series):
        made.append(len(series))
        real(self, series)
    for _ in range(3):
        before, memo = _counts(), select_counts.memo_hits
        QueryEngine([shard], backend=be).execute(plan)
        assert _delta(before) == (0, 1)
        assert select_counts.memo_hits == memo
    try:
        SelectionFacts.__init__ = counting
        QueryEngine([shard], backend=be).execute(plan)
    finally:
        SelectionFacts.__init__ = real
    # once for the request; once more only by the tile build, after it read
    # (as the key was built twice around a build before)
    assert made in ([4], [4, 4])
    assert len(select_memo) == 0


# --- holders race a drop -----------------------------------------------------

def test_requests_racing_reads_and_drops_answer_the_same_bytes():
    """Four request threads over one selection while a fifth keeps ending
    the entry under them (a handle read; a clear): every answer is the
    answer, whoever's facts a request took."""
    shard = _store("jittered")
    be = TpuBackend()
    want = _body(shard, be)
    stop, failures, answered = threading.Event(), [], [0]

    def ask():
        try:
            while not stop.is_set():
                if _body(shard, be) != want:
                    failures.append("differs")
                answered[0] += 1
        except Exception as e:              # noqa: BLE001 - reported below
            failures.append(repr(e))

    def spoil():
        k = 0
        while not stop.is_set():
            k += 1
            if k % 3:
                _select(shard, "jittered")[k % 4].ts
            else:
                select_memo.clear()
            time.sleep(0.002)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=ask) for _ in range(4)] \
        + [threading.Thread(target=spoil)]
    try:
        for t in threads:
            t.start()
        time.sleep(3.0)
    finally:
        stop.set()
        for t in threads:
            t.join(60)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures[:5]
    assert answered[0] >= 8
    assert _body(shard, be) == want
