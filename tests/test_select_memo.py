"""The selection memo (query/engine.py ``select_memo``): a ``full=True``
selection over local shards that the store has not changed under is
selected once, and the next query for the same (shards, filters, column)
gets the same handles, the rows of ITS range counted by two searches, and
the group ids.

Pinned here: a hit equals the loop, fact for fact, in order and in what it
counts; every change a selection could see moves the shard's version, makes
the next selection a miss and its answer promql/refeval.py's; only facts
are shared (the first read of a handle ends an entry, and whoever holds the
list still answers exactly); a range some lifetime does not cover is not
served; the limits refuse what they refused; the memo stays inside its
caps.
"""

import sys
import threading
import time

import numpy as np
import pytest

from filodb_tpu.core.index import ColumnFilter
from filodb_tpu.core.memstore import TimeSeriesMemStore, TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.promql.refeval import RefSeries, ref_eval
from filodb_tpu.query import engine as eng
from filodb_tpu.query.engine import (QueryEngine, select_counts,
                                     select_memo, select_raw_series)
from filodb_tpu.query.model import (QueryLimitError, QueryLimits,
                                    QueryStats)
from filodb_tpu.query.tpu import TpuBackend

from test_select_handles import (KINDS, N, REF, T0, _assert_matches,
                                 _column, _filters, _ingest, _labels,
                                 _ranges, _rows, _store)

QUERY = "sum(rate(reqs_total[5m])) by (job)"
TSP = TimeStepParams(T0 // 1000 + 600, 60, T0 // 1000 + 2300)
FACTS = ("labels", "is_counter", "is_hist", "snapshot_key", "chunk_len",
         "tail_first_ts", "last_ts")


@pytest.fixture(autouse=True)
def _empty_memo():
    select_memo.clear()
    yield
    select_memo.clear()


def _select(shards, filters, start, end, column=None, **kw):
    """-> (series, stats, was it a hit)."""
    stats = QueryStats()
    hits = select_counts.memo_hits
    misses = select_counts.memo_misses
    got = select_raw_series(shards, filters, start, end, column, stats,
                            full=True, **kw)
    hit = select_counts.memo_hits - hits
    assert hit + select_counts.memo_misses - misses == 1
    return got, stats, bool(hit)


def _cold(shards, filters, start, end, column=None):
    select_memo.clear()
    got, stats, hit = _select(shards, filters, start, end, column)
    assert not hit
    return got, stats


def _same_facts(got, want):
    assert len(got) == len(want)
    for s, w in zip(got, want):
        for fact in FACTS:
            assert getattr(s, fact) == getattr(w, fact), fact
        assert (s.bucket_les is None) == (w.bucket_les is None)


# --- a hit is the loop's answer ----------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_hit_equals_a_cold_selection(kind):
    """Fact for fact and in order, with the loop's ``series_scanned`` and
    ``samples_scanned``, for the named ranges (whole chunks, cuts through
    them, outside them) and forty random ones; then sample for sample."""
    shard = _store(kind)
    f, col = _filters(kind), _column(kind)
    rng = np.random.default_rng(31)
    spans = sorted(_ranges().values())
    for _ in range(40):
        a, b = sorted(int(x) for x in rng.integers(
            T0 - 100_000, T0 + (N + 10) * 10_000, 2))
        spans.append((a, b))
    reads = select_counts.reads
    served = 0
    for start, end in spans:
        want, wstats = _cold([shard], f, start, end, col)
        again, astats, hit = _select([shard], f, start, end, col)
        # a range that leaves a series out by its lifetime is its own
        assert hit == (len(want) == 4), (start, end)
        served += hit
        _same_facts(again, want)
        if hit:
            assert all(s is w for s, w in zip(again, want))   # shared
        assert (astats.series_scanned, astats.samples_scanned) == \
            (wstats.series_scanned, wstats.samples_scanned) == \
            (len(want), wstats.samples_scanned)
    assert served >= 40
    assert select_counts.reads == reads         # counted, never read
    # one entry served every range; its handles read what a cold one reads
    got, _, hit = _select([shard], f, 0, 2**62, col)
    assert hit
    want, _ = _cold([shard], f, 0, 2**62, col)
    for s, w in zip(got, want):
        np.testing.assert_array_equal(s.ts, w.ts)
        np.testing.assert_array_equal(s.values, w.values)


def test_a_hit_counts_handles_and_a_list_is_its_holders_own():
    shard = _store("flushed")
    f = _filters("flushed")
    first, _, _ = _select([shard], f, 0, 2**62)
    handles = select_counts.handles
    again, _, hit = _select([shard], f, 0, 2**62)
    assert hit and select_counts.handles == handles + 4
    again.pop()                                 # the holder's list
    third, _, hit = _select([shard], f, 0, 2**62)
    assert hit and len(third) == len(first) == 4


def test_partial_selections_and_remote_groups_pass_it_by():
    shard = _store("flushed")
    f = _filters("flushed")
    before = (select_counts.memo_hits, select_counts.memo_misses)
    for _ in range(2):
        select_raw_series([shard], f, 0, 2**62, None, QueryStats())
    assert (select_counts.memo_hits, select_counts.memo_misses) == before
    assert len(select_memo) == 0

    class Remote:
        def fetch_raw(self, filters, start_ms, end_ms, column, full):
            return []

    for _ in range(2):
        select_raw_series([shard, Remote()], f, 0, 2**62, None,
                          QueryStats(), full=True)
    assert (select_counts.memo_hits, select_counts.memo_misses) == before
    # a filter off the JSON wire holds its ``in`` values as a list
    wire = [ColumnFilter("_metric_", "in", ["reqs_total"])]
    for _ in range(2):
        assert len(select_raw_series([shard], wire, 0, 2**62, None,
                                     QueryStats(), full=True)) == 4
    assert select_counts.memo_hits == before[0]


# --- every change a selection could see ends the entry -----------------------

def _store_of_200():
    """Rows 0..199 of four series, 170 of them flushed."""
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100)
    _ingest(shard, "flushed+tail", 0, 170)
    shard.flush_all()
    _ingest(shard, "flushed+tail", 170, 200)
    return shard


def _ref(rows_of, query=QUERY, kind="flushed+tail"):
    """refeval over ``{series number: rows it has}``."""
    series = []
    for s, upto in sorted(rows_of.items()):
        ts, vals = _rows(kind, s)
        series.append(RefSeries(_labels("reqs_total", s),
                                [int(t) for t in ts[:upto]],
                                [float(v) for v in vals[:upto]]))
    return ref_eval(query, series, TSP.start_s, TSP.step_s, TSP.end_s)


def _other_metric(shard):
    b = RecordBuilder(DEFAULT_SCHEMAS)
    for t in range(150):
        b.add_sample("prom-counter", _labels("other_total", 9),
                     T0 + t * 10_000, float(t))
    for c in b.containers():
        shard.ingest(c)


def _evict_all(shard):
    shard.flush_all(offset=1)
    assert shard.evict_partitions(cutoff_ts=2**62) >= 4


def _move_ingest(shard):
    _ingest(shard, "flushed+tail", 200, N)
    return {s: N for s in range(4)}


def _move_flush(shard):
    assert shard.flush_all() == 4
    return {s: 200 for s in range(4)}


def _move_switch(shard):
    chunks = [p.num_chunks for p in shard.partitions.values()]
    _ingest(shard, "flushed+tail", 200, N)      # 30 + 40 rows, 35 a chunk
    assert [p.num_chunks for p in shard.partitions.values()] == \
        [n + 2 for n in chunks]
    return {s: N for s in range(4)}


def _move_evict(shard):
    assert shard.evict_partitions(cutoff_ts=2**62) == 4
    assert all(p.odp_pending for p in shard.partitions.values())
    return {s: 200 for s in range(4)}


def _move_page_in(shard):
    # another metric's partition is paged in: one version a shard, so the
    # entry of this metric goes too
    paged = shard.stats.partitions_paged_in
    got = select_raw_series([shard],
                            [ColumnFilter.eq("_metric_", "other_total")],
                            0, 2**62, None, QueryStats(), full=True)
    assert len(got) == 1
    assert shard.stats.partitions_paged_in == paged + 1
    return {s: 200 for s in range(4)}


def _move_new_partition(shard):
    _ingest(shard, "flushed+tail", 0, 200, n_series=5)  # 4 known, 1 new
    assert len(shard.partitions) == 5
    return {s: 200 for s in range(5)}


def _move_remove_part_keys(shard):
    # no column store: eviction drops the series and its part key
    cutoff = T0 + 205 * 10_000
    assert shard.evict_partitions(cutoff_ts=cutoff) == 2
    assert len(shard.partitions) == 2
    return {0: N, 1: N}


def _prepare_removal(shard):
    _ingest(shard, "flushed+tail", 200, N, n_series=2)
    shard.flush_all()


# move -> (what comes before the entry is made, the change itself,
#          column store?, rows a chunk)
MOVES = {
    "ingest": (None, _move_ingest, False, 100),
    "flush": (None, _move_flush, False, 100),
    "buffer-full-switch": (None, _move_switch, False, 35),
    "evict": (lambda sh: sh.flush_all(offset=1), _move_evict, True, 100),
    "page-in": (lambda sh: (_other_metric(sh), _evict_all(sh)),
                _move_page_in, True, 100),
    "new-partition": (None, _move_new_partition, False, 100),
    "remove-part-keys": (_prepare_removal, _move_remove_part_keys, False,
                         100),
}


@pytest.mark.parametrize("move", sorted(MOVES))
def test_a_change_makes_the_next_selection_a_miss(move, tmp_path):
    """Rows 0..199 are in (170 flushed) and an entry is being served. Then
    the store changes. The shard's version has moved when the change
    returns, the next selection runs the loop, and the query over it is
    refeval's over the store as it is now: never the entry's rows."""
    from filodb_tpu.store import FlatFileColumnStore
    before, change, wants_store, chunk_rows = MOVES[move]
    cs = FlatFileColumnStore(str(tmp_path / "col")) if wants_store else None
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0,
                            max_chunk_rows=chunk_rows, column_store=cs)
    _ingest(shard, "flushed+tail", 0, 170)
    shard.flush_all()
    _ingest(shard, "flushed+tail", 170, 200)
    if before is not None:
        before(shard)
    plan = parse_query_range(QUERY, TSP)
    f = plan.inner.raw.filters
    a, b = plan.inner.raw.start_ms, plan.inner.raw.end_ms
    hit = False
    for _ in range(3):      # a page-in overlaps the first build: not kept
        _, _, hit = _select([shard], f, a, b)
    assert hit
    version = shard.version
    rows_of = change(shard)
    assert shard.version > version
    hits, misses = select_counts.memo_hits, select_counts.memo_misses
    got = QueryEngine([shard], backend=TpuBackend()).execute(plan)
    assert (select_counts.memo_hits, select_counts.memo_misses) == \
        (hits, misses + 1)
    _assert_matches(got, _ref(rows_of), 1e-5)


def test_a_partition_made_outside_ingest_moves_the_version_too():
    from filodb_tpu.core.record import PartKey
    shard = _store("empty")
    f = _filters("empty")
    assert [_select([shard], f, 0, 2**62)[2] for _ in range(2)] == \
        [False, True]
    schema = DEFAULT_SCHEMAS.by_name("prom-counter")
    shard.get_or_create_partition(
        PartKey.make(schema, _labels("reqs_total", 7)), T0)
    got, _, hit = _select([shard], f, 0, 2**62)
    assert not hit and len(got) == 5


def test_a_build_that_a_change_overlapped_is_not_kept(monkeypatch):
    """The versions are read before the loop and compared after it."""
    shard = _store("flushed+tail")
    f = _filters("flushed+tail")
    real = TimeSeriesShard.lookup_partitions

    def lookup_then_flush(self, *a):
        out = real(self, *a)
        self.flush_all()        # lands inside the build
        return out

    monkeypatch.setattr(TimeSeriesShard, "lookup_partitions",
                        lookup_then_flush)
    _select([shard], f, 0, 2**62)
    assert len(select_memo) == 0
    monkeypatch.setattr(TimeSeriesShard, "lookup_partitions", real)
    _, _, hit = _select([shard], f, 0, 2**62)
    assert not hit and len(select_memo) == 1


def test_dropping_decode_caches_moves_nothing(tmp_path):
    from filodb_tpu.store import FlatFileColumnStore
    shard = _store("flushed", FlatFileColumnStore(str(tmp_path / "col")))
    f = _filters("flushed")
    t = T0 + 50 * 10_000
    _select([shard], f, t, t + 600_000)     # cuts chunks: decodes them
    version = shard.version
    assert shard.trim_decode_caches(1) > 0
    assert all(p.release_caches() == 0 for p in shard.partitions.values())
    assert shard.version == version
    _, stats, hit = _select([shard], f, t, t + 600_000)
    assert hit and stats.samples_scanned == 4 * 61


def test_remove_shard_ends_its_entries():
    store = TimeSeriesMemStore(DEFAULT_SCHEMAS)
    for num in (0, 1):
        shard = store.setup(REF, num, max_chunk_rows=100)
        _ingest(shard, "flushed+tail", 0, 200)
        shard.flush_all()
    plan = parse_query_range(QUERY, TSP)
    f = plan.inner.raw.filters
    for _ in range(2):
        got, _, hit = _select(store.shards(REF), f, 0, 2**62)
    assert hit and len(got) == 8
    gone = store.get_shard(REF, 1)
    version = gone.version
    store.remove_shard(REF, 1)
    assert gone.version > version
    hits = select_counts.memo_hits
    got = QueryEngine(store.shards(REF), backend=TpuBackend()).execute(plan)
    assert select_counts.memo_hits == hits
    _assert_matches(got, _ref({s: 200 for s in range(4)}), 1e-5)
    # the next selection kept swept the entry that held the removed shard
    _select(store.shards(REF), f, 0, 2**62)
    assert all(gone not in e.shards
               for e in select_memo._entries.values())


# --- only facts are shared ---------------------------------------------------

def test_a_read_ends_the_entry_and_every_holder_still_answers():
    shard = _store_of_200()
    f = _filters("flushed+tail")
    mine, _, _ = _select([shard], f, 0, 2**62)
    yours, _, hit = _select([shard], f, 0, 2**62)
    assert hit and len(select_memo) == 1
    reads = select_counts.reads
    ts = mine[2].ts                             # one touch
    assert len(select_memo) == 0
    assert select_counts.reads == reads + 1
    _ingest(shard, "flushed+tail", 200, N)      # the store moves on
    want, _ = _cold([shard], f, 0, 2**62)
    for s, w in zip(yours, want):
        # the rows the selection saw, to every holder, read once
        np.testing.assert_array_equal(s.ts, w.ts[:200])
        np.testing.assert_array_equal(s.values, w.values[:200])
    assert yours[2].ts is ts and mine[2].ts is ts
    assert select_counts.reads == reads + 4 + 4     # yours, and want's
    # and the next query selects afresh
    _, _, hit = _select([shard], f, 0, 2**62)
    assert not hit


def test_the_packed_path_is_never_memoised_and_the_fused_hit_is():
    shard = _store("flushed")
    be = TpuBackend()
    fused = parse_query_range(QUERY, TSP)
    packed = parse_query_range("max(max_over_time(reqs_total[5m]))", TSP)
    for plan, want_hits in ((packed, [0, 0, 0, 0]), (fused, [0, 0, 1, 1])):
        select_memo.clear()
        seen = []
        for _ in range(4):
            hits = select_counts.memo_hits
            QueryEngine([shard], backend=be).execute(plan)
            seen.append(select_counts.memo_hits - hits)
        # fused: the tile build reads (ends the first entry), the second
        # query's entry is read by nobody, the third and fourth are hits
        assert seen == want_hits


def test_readers_racing_on_one_handle_fill_it_once():
    shard = _store_of_200()
    f = _filters("flushed+tail")
    first, _, _ = _select([shard], f, 0, 2**62)
    lists = [first] + [_select([shard], f, 0, 2**62)[0] for _ in range(3)]
    reads = select_counts.reads
    go = threading.Barrier(4)
    out = [None] * 4

    def touch(i):
        go.wait(timeout=30)
        out[i] = [(s.ts, s.values) for s in lists[i]]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=touch, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert select_counts.reads == reads + 4
    for got in out[1:]:
        for (ts, vals), (ts0, vals0) in zip(got, out[0]):
            assert ts is ts0 and vals is vals0 and ts.size == 200


# --- lifetimes ---------------------------------------------------------------

def test_a_range_a_lifetime_does_not_cover_is_not_served():
    """Series 0-3 live over rows 0..239, series 4 only over rows 0..99 (its
    part key ends there). A range past row 99 matches four series, one
    inside it five: neither is answered from the other's entry."""
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100)
    _ingest(shard, "flushed", 0, 100, n_series=5)
    _ingest(shard, "flushed", 100, N, n_series=4)
    shard.flush_all()
    shard.index.update_end_time(4, T0 + 99 * 10_000)    # stopped ingesting
    f = _filters("flushed")
    t = lambda i: T0 + i * 10_000       # noqa: E731
    late, _, _ = _select([shard], f, t(150), t(200))
    assert len(late) == 4 and len(select_memo) == 0     # this range's alone
    both, _, _ = _select([shard], f, t(50), t(200))
    assert len(both) == 5 and len(select_memo) == 1
    got, stats, hit = _select([shard], f, t(20), t(120))
    assert hit and len(got) == 5
    assert stats.samples_scanned == 4 * 101 + 80
    got, stats, hit = _select([shard], f, t(150), t(200))
    assert not hit and len(got) == 4
    assert stats.samples_scanned == 4 * 51
    # ... and the miss did not take the covered entry's place
    _, _, hit = _select([shard], f, t(50), t(200))
    assert hit
    # before every lifetime: nothing matches, by the loop
    got, _, hit = _select([shard], f, 0, T0 - 1)
    assert not hit and got == []


def test_an_empty_match_is_an_entry_too():
    shard = _store("flushed")
    f = [ColumnFilter.eq("_metric_", "no_such_metric")]
    for want_hit in (False, True):
        got, stats, hit = _select([shard], f, 0, 2**62)
        assert hit == want_hit and got == []
        assert (stats.series_scanned, stats.samples_scanned) == (0, 0)


# --- limits ------------------------------------------------------------------

def test_limits_refuse_the_same_queries_on_a_hit():
    shard = _store("flushed")
    be = TpuBackend()
    plan = parse_query_range(QUERY, TimeStepParams(
        T0 // 1000 + 600, 60, T0 // 1000 + 1800))
    scanned = 4 * 151

    def run(limits):
        hits = select_counts.memo_hits
        try:
            QueryEngine([shard], backend=be, limits=limits).execute(plan)
            said = None
        except QueryLimitError as e:
            said = str(e)
        return said, select_counts.memo_hits - hits

    cold = {}
    cases = {"at-the-sample-limit": QueryLimits(sample_limit=scanned),
             "under-it": QueryLimits(sample_limit=2 * 151 + 1),
             "series": QueryLimits(series_limit=3),
             "at-the-series-limit": QueryLimits(series_limit=4)}
    for name, limits in cases.items():
        select_memo.clear()
        cold[name], hit = run(limits)
        assert not hit
    assert [cold[n] is None for n in cases] == [True, False, False, True]
    select_memo.clear()
    run(None), run(None)
    assert run(None) == (None, 1)               # an entry is being served
    for name, limits in cases.items():
        said, hit = run(limits)
        assert said == cold[name], name         # word for word
        assert hit == (said is None)
    assert run(None) == (None, 1)               # and still is


# --- group ids ---------------------------------------------------------------

def test_group_keys_from_a_hit_are_the_callers_to_change():
    shard = _store("flushed")
    be = TpuBackend()
    plan = parse_query_range(QUERY, TSP)
    want = QueryEngine([shard]).execute(plan)           # the numpy oracle
    got = None
    for _ in range(3):
        got = QueryEngine([shard], backend=be).execute(plan)
    entry, = select_memo._entries.values()
    assert list(entry.groups) == [(("job",), ())]
    for k in got.keys:
        k["job"] = "mine"
        k["more"] = "x"
    hits = select_counts.memo_hits
    again = QueryEngine([shard], backend=be).execute(plan)
    assert select_counts.memo_hits == hits + 1
    assert [dict(k) for k in again.keys] == [dict(k) for k in want.keys]
    np.testing.assert_allclose(again.values, want.values, rtol=1e-5)
    # the shared ids cannot be written through
    series, _, hit = _select([shard], plan.inner.raw.filters, 0, 2**62)
    gids, _ = eng._selection_groups(series, ("job",), ())
    assert hit and not gids.flags.writeable
    # another grouping of the same selection is worked out beside it
    other = parse_query_range("sum(rate(reqs_total[5m])) by (instance)", TSP)
    got = QueryEngine([shard], backend=be).execute(other)
    assert len(got.keys) == 4 and len(entry.groups) == 2


# --- under ingest ------------------------------------------------------------

def test_selectors_never_see_fewer_rows_than_were_acknowledged():
    """Four threads select while a fifth ingests. The version moves before
    ``ingest`` returns, so whatever a selector is handed, entry or loop,
    holds every row acknowledged before it asked."""
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100)
    f = _filters("flushed")
    batches = 60
    acked = [0]
    done = threading.Event()
    failures = []

    def ingest():
        try:
            for t in range(batches):
                _ingest(shard, "flushed", t, t + 1)
                acked[0] = 4 * (t + 1)
                if t % 20 == 19:
                    shard.flush_all()
                time.sleep(0.003)       # room for entries to be served
        finally:
            done.set()

    def select(i):
        n = 0
        while not done.is_set() or n < 5:
            want = acked[0]
            stats = QueryStats()
            got = select_raw_series([shard], f, 0, 2**62, None, stats,
                                    full=True)
            if stats.samples_scanned < want:
                failures.append((want, stats.samples_scanned))
            n += 1
            if i == 0 and n % 50 == 0:  # one of them reads now and then
                rows = sum(s.ts.size for s in got)
                if rows < want:
                    failures.append((want, rows, "read"))

    hits = select_counts.memo_hits
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=select, args=(i,))
                   for i in range(4)]
        threads.append(threading.Thread(target=ingest))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not failures, failures[:5]
    assert acked[0] == 4 * batches
    assert select_counts.memo_hits > hits       # entries were served
    _, stats, _ = _select([shard], f, 0, 2**62)
    assert stats.samples_scanned == 4 * batches


# --- bounds ------------------------------------------------------------------

def test_the_memo_stays_inside_its_caps(monkeypatch):
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100)
    _ingest(shard, "flushed", 0, 120, n_series=24)
    shard.flush_all()
    selectors = [[ColumnFilter.eq("_metric_", "reqs_total"),
                  ColumnFilter.eq("instance", f"i{s}")] for s in range(24)]
    for f in selectors:
        _select([shard], f, 0, 2**62)
        assert len(select_memo) <= eng._MEMO_ENTRIES
    assert len(select_memo) == eng._MEMO_ENTRIES == 16
    # least recently used out first: the last sixteen are served
    for f in selectors[8:]:
        assert _select([shard], f, 0, 2**62)[2]
    assert not _select([shard], selectors[0], 0, 2**62)[2]
    # a selection with more rows than an entry may count is not kept
    select_memo.clear()
    monkeypatch.setattr(eng, "_MEMO_MAX_ROWS", 24 * 120 - 1)
    f = _filters("flushed")
    for _ in range(2):
        got, stats, hit = _select([shard], f, 0, 2**62)
        assert not hit and len(got) == 24 and len(select_memo) == 0
    monkeypatch.setattr(eng, "_MEMO_MAX_ROWS", 24 * 120)
    assert [_select([shard], f, 0, 2**62)[2] for _ in range(2)] == \
        [False, True]
    # ... and holds its timestamps as 32-bit offsets
    entry, = select_memo._entries.values()
    assert entry.offsets.dtype == np.uint32 and entry.offsets.size == 24 * 120
    # the groupings kept with an entry are bounded too
    series, _, _ = _select([shard], f, 0, 2**62)
    for i in range(3 * eng._MEMO_MAX_GROUPINGS):
        eng._selection_groups(series, ("job", f"l{i}"), ())
        assert len(entry.groups) <= eng._MEMO_MAX_GROUPINGS


def test_the_row_cap_is_over_all_entries_together(monkeypatch):
    """One budget of rows for the whole memo: a selection as large as the
    budget is kept (an all-store board's 35 M rows under the 1 << 26 there
    are), and what no longer fits beside a new entry goes, least recently
    used first."""
    assert eng._MEMO_MAX_ROWS == 1 << 26
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100)
    _ingest(shard, "flushed", 0, 120, n_series=24)
    shard.flush_all()
    metric = ColumnFilter.eq("_metric_", "reqs_total")
    one = [[metric, ColumnFilter.eq("instance", f"i{s}")] for s in range(4)]
    half = [[metric, ColumnFilter.regex("instance", pat)]
            for pat in ("i(\\d|1[01])", "i(1[2-9]|2\\d)")]
    monkeypatch.setattr(eng, "_MEMO_MAX_ROWS", 24 * 120)
    for f in one + half:            # 4 x 120 rows, then 2 x 12 x 120
        got, _, hit = _select([shard], f, 0, 2**62)
        assert not hit and len(got) in (1, 12)
        assert sum(e.rows for e in select_memo._entries.values()) \
            <= 24 * 120
    # the two halves fill the budget: the four single series went
    assert [e.rows for e in select_memo._entries.values()] == [12 * 120] * 2
    assert all(_select([shard], f, 0, 2**62)[2] for f in half)
    assert not any(_select([shard], f, 0, 2**62)[2] for f in one[:1])
    # the whole fleet takes the budget alone
    whole = _filters("flushed")
    assert [_select([shard], whole, 0, 2**62)[2] for _ in range(2)] == \
        [False, True]
    assert [e.rows for e in select_memo._entries.values()] == [24 * 120]


def test_timestamps_wider_than_32_bits_are_counted_exactly():
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100)
    b = RecordBuilder(DEFAULT_SCHEMAS)
    stamps = [T0, T0 + 10_000, T0 + (1 << 33), T0 + (1 << 33) + 10_000]
    for s in range(2):
        for i, t in enumerate(stamps):
            b.add_sample("prom-counter", _labels("reqs_total", s), t,
                         float(i))
    for c in b.containers():
        shard.ingest(c)
    f = _filters("flushed")
    for start, end, want in ((0, 2**62, 8), (T0 + 1, T0 + (1 << 33), 4),
                             (T0 + (1 << 33) + 1, 2**62, 2),
                             (T0 + 20_000, T0 + (1 << 33) - 1, 0)):
        for _ in range(2):
            _, stats, hit = _select([shard], f, start, end)
            assert stats.samples_scanned == want, (start, end)
        assert hit
    entry, = select_memo._entries.values()
    assert entry.offsets.dtype == np.int64


# --- where it shows ----------------------------------------------------------

def test_the_counters_are_on_metrics_and_the_cache_is_declared():
    from filodb_tpu.http.server import FiloHttpServer
    from filodb_tpu.lint.caches import cache_inventory
    shard = _store("flushed")
    srv = FiloHttpServer({"timeseries": [shard]}, port=0)
    for _ in range(3):
        _select([shard], _filters("flushed"), 0, 2**62)
    text = srv._metrics_text()
    got = {ln.split()[0]: float(ln.split()[1]) for ln in text.splitlines()
           if ln.startswith("filodb_select_memo")}
    assert got == {
        "filodb_select_memo_hits_total": select_counts.memo_hits,
        "filodb_select_memo_misses_total": select_counts.memo_misses}
    assert select_counts.memo_hits >= 2 and select_counts.memo_misses >= 1
    decl = cache_inventory()["select-memo"]
    assert decl["validated_by"] == {
        "store-version": ("begin", "store", "facts_for")}
    assert TimeSeriesShard._changed.__publishes__ == ("store-version",)
    assert eng._store_versions.__event_source__ == ("store-version",)
