"""A ``full=True`` selection hands out handles: the facts of a partition
taken in one lock acquisition, the samples read when a consumer first
touches them (query/model.py ``RawSeries``, core/memstore.py
``select_facts``).

Pinned here: a handle equals what the eager selection built (read_full +
num_chunks + a label dict + hist_drop_rows, written out below as
``_eager``) on every kind of partition; the fused path reads nothing on a
tile hit; a store that moves between selection and first read still
answers exactly (against promql/refeval.py); ``samples_scanned`` and
``query-sample-limit`` count what they counted.
"""

import numpy as np
import pytest

from filodb_tpu.core.index import ColumnFilter
from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import PartKey, RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.memory.histogram import CustomBuckets
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.promql.refeval import RefSeries, ref_eval
from filodb_tpu.query import engine as eng
from filodb_tpu.query.engine import (QueryEngine, select_counts,
                                     select_raw_series)
from filodb_tpu.query.model import (QueryLimitError, QueryLimits,
                                    QueryStats, RawSeries)
from filodb_tpu.query.tpu import TpuBackend

REF = DatasetRef("timeseries")
T0 = 1_600_000_000_000
N = 240                     # rows a series, 10 s apart
LES = (0.5, 2.0, 8.0, float("inf"))
KINDS = ("flushed", "flushed+tail", "buffer-only", "empty", "jittered",
         "counter-reset", "histogram", "histogram+tail")


def _labels(metric, s):
    return {"_metric_": metric, "_ws_": "demo", "_ns_": "App-0",
            "job": f"job-{s % 2}", "instance": f"i{s}"}


def _rows(kind, s):
    """(ts, values) of series ``s``: counters rising 7*(s+1) a scrape."""
    rng = np.random.default_rng(1000 + s)
    ts = T0 + np.arange(N, dtype=np.int64) * 10_000
    if kind == "jittered" and s % 2:
        ts = ts + rng.integers(-2_000, 2_001, N)
    vals = np.cumsum(np.full(N, 7.0 * (s + 1)))
    if kind == "counter-reset":
        cut = 90 + 10 * s
        vals[cut:] -= vals[cut - 1]
    return ts, vals


def _ingest(shard, kind, lo, hi, n_series=4):
    b = RecordBuilder(DEFAULT_SCHEMAS)
    scheme = CustomBuckets(LES)
    for s in range(n_series):
        ts, vals = _rows(kind, s)
        for t in range(lo, hi):
            if kind.startswith("histogram"):
                counts = (np.array([1, 3, 7, 10]) * (s + 1)
                          * ((t % 100) + 1)).astype(np.int64)  # resets
                b.add_sample("prom-histogram", _labels("lat", s),
                             int(ts[t]), counts[-1] * 0.05,
                             float(counts[-1]), (scheme, counts))
            else:
                b.add_sample("prom-counter", _labels("reqs_total", s),
                             int(ts[t]), float(vals[t]))
    for c in b.containers():
        shard.ingest(c)


def _store(kind, column_store=None):
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100,
                            column_store=column_store)
    if kind == "empty":
        schema = DEFAULT_SCHEMAS.by_name("prom-counter")
        for s in range(4):
            shard.get_or_create_partition(
                PartKey.make(schema, _labels("reqs_total", s)), T0)
        return shard
    if kind in ("flushed+tail", "histogram+tail"):
        _ingest(shard, kind, 0, 170)
        shard.flush_all()
        _ingest(shard, kind, 170, N)     # 200 in chunks, 40 in the buffer
    else:
        _ingest(shard, kind, 0, N)
        if kind != "buffer-only":       # 200 rows switch on their own
            shard.flush_all()
    return shard


def _filters(kind):
    metric = "lat" if kind.startswith("histogram") else "reqs_total"
    return [ColumnFilter.eq("_metric_", metric)]


def _column(kind):
    return "h" if kind.startswith("histogram") else None


def _eager(shard, filters, start_ms, end_ms, column):
    """What ``select_raw_series(full=True)`` built before handles: every
    field from the partition's own readers, and the in-range count by two
    searches over the timestamps read."""
    out, scanned = [], 0
    for part in shard.lookup_partitions(filters, start_ms, end_ms):
        names = [c.name for c in part.schema.columns]
        ci = names.index(column or part.schema.value_column)
        col = part.schema.columns[ci]
        ts, vals, chunk_len = part.read_full(ci)
        drops = None
        if vals.ndim == 2 and col.is_counter_like:
            drops = part.hist_drop_rows(ci)
            drops = drops[drops < ts.size]
        out.append(dict(
            labels=dict(part.part_key.labels), ts=ts, values=vals,
            chunk_len=chunk_len, drops=drops,
            snapshot_key=(shard.ref.dataset, shard.shard_num, part.part_id,
                          part.num_chunks, ci),
            is_counter=col.is_counter_like))
        scanned += int(np.searchsorted(ts, end_ms, side="right")
                       - np.searchsorted(ts, start_ms, side="left"))
    return out, scanned


@pytest.mark.parametrize("kind", KINDS)
def test_handle_equals_the_eager_selection(kind):
    shard = _store(kind)
    want, _ = _eager(shard, _filters(kind), 0, 2**62, _column(kind))
    reads = select_counts.reads
    got = select_raw_series([shard], _filters(kind), 0, 2**62,
                            _column(kind), QueryStats(), full=True)
    assert len(got) == len(want) == 4
    for s, w in zip(got, want):
        # the facts, before anything is read
        assert s.snapshot_key == w["snapshot_key"]
        assert s.chunk_len == w["chunk_len"]
        assert dict(s.labels) == w["labels"]
        assert s.is_counter == w["is_counter"]
        assert s.is_hist == (w["values"].ndim == 2)
        cl, n = w["chunk_len"], w["ts"].size
        assert s.tail_first_ts == (int(w["ts"][cl]) if cl < n else None)
        assert s.last_ts == (int(w["ts"][-1]) if n else None)
    assert select_counts.reads == reads         # ... nothing was
    for s, w in zip(got, want):
        np.testing.assert_array_equal(s.ts, w["ts"])
        np.testing.assert_array_equal(s.values, w["values"])
        assert s.values.dtype == w["values"].dtype
        if w["drops"] is None:
            assert s.hist_drop_rows is None
        else:
            np.testing.assert_array_equal(s.hist_drop_rows, w["drops"])
        if kind.startswith("histogram"):
            np.testing.assert_array_equal(s.bucket_les, np.array(LES))
    assert select_counts.reads == reads + 4     # once a handle


def test_histogram_kinds_do_carry_resets():
    """The histogram fixtures above are only worth their name if the drop
    tables they compare are not empty."""
    for kind in ("histogram", "histogram+tail"):
        shard = _store(kind)
        got = select_raw_series([shard], _filters(kind), 0, 2**62, "h",
                                full=True)
        assert all(s.hist_drop_rows.size >= 2 for s in got)


def test_an_array_built_series_derives_the_same_facts():
    ts = T0 + np.arange(10, dtype=np.int64) * 10_000
    s = RawSeries({"a": "b"}, ts, np.arange(10.0), chunk_len=6)
    assert (s.tail_first_ts, s.last_ts, s.is_hist) == (int(ts[6]),
                                                       int(ts[-1]), False)
    whole = RawSeries({"a": "b"}, ts, np.ones((10, 4)))
    assert (whole.tail_first_ts, whole.is_hist) == (None, True)
    none = RawSeries({}, ts[:0], np.zeros(0), chunk_len=0)
    assert (none.tail_first_ts, none.last_ts) == (None, None)


# --- samples_scanned and the limits -----------------------------------------

def _ranges():
    t = lambda i: T0 + i * 10_000       # noqa: E731
    return {
        "everything": (0, 2**62),
        "inside-one-chunk": (t(20), t(60)),
        "across-chunks": (t(50), t(150)),
        "chunks-into-tail": (t(150), t(220)),
        "tail-only": (t(205), t(230)),
        "exactly-one-sample": (t(100), t(100)),
        "between-samples": (t(100) + 1, t(101) - 1),
        "before-all": (0, T0 - 1),
        "after-all": (t(N) + 5_000, 2**62),
        "edge-of-a-chunk": (t(99), t(100)),
    }


@pytest.mark.parametrize("span", sorted(_ranges()))
@pytest.mark.parametrize("kind", ("flushed+tail", "jittered",
                                  "buffer-only"))
def test_samples_scanned_is_the_eager_count(kind, span):
    shard = _store(kind)
    start, end = _ranges()[span]
    _, want = _eager(shard, _filters(kind), start, end, None)
    stats = QueryStats()
    reads = select_counts.reads
    got = select_raw_series([shard], _filters(kind), start, end, None,
                            stats, full=True)
    assert stats.samples_scanned == want
    assert stats.series_scanned == len(got)
    assert select_counts.reads == reads     # counted from the facts


def test_facts_cut_a_writer_in_mid_append():
    """``_buf_rows`` vouches for whole rows only: a timestamp segment the
    writer has appended but not yet counted is no row of the handle."""
    shard = _store("flushed+tail")
    part = next(iter(shard.partitions.values()))
    before = part.select_facts(1, 0, 2**62)
    part._ts_buf.append(np.array([T0 + 10**9], dtype=np.int64))
    assert part.select_facts(1, 0, 2**62) == before


@pytest.mark.parametrize("backend", ("fused", "general"))
def test_sample_limit_refuses_what_it_refused(backend):
    shard = _store("flushed")
    tsp = TimeStepParams(T0 // 1000 + 600, 60, T0 // 1000 + 1800)
    q = ("sum(rate(reqs_total[5m])) by (job)" if backend == "fused"
         else "rate(reqs_total[5m])")
    plan = parse_query_range(q, tsp)
    be = TpuBackend()
    engine = QueryEngine([shard], backend=be)
    engine.execute(plan)
    scanned = engine.stats.samples_scanned
    _, want = _eager(shard, _filters("flushed"),
                     (T0 // 1000 + 300) * 1000, (T0 // 1000 + 1800) * 1000,
                     None)
    assert scanned == want == 4 * 151
    # at the limit: served; one under it: refused before any dispatch,
    # with the count the eager loop had reached at its third series
    QueryEngine([shard], backend=be,
                limits=QueryLimits(sample_limit=scanned)).execute(plan)
    fused, reads = be.fused_aggs, select_counts.reads
    with pytest.raises(QueryLimitError) as e:
        QueryEngine([shard], backend=be,
                    limits=QueryLimits(sample_limit=2 * 151 + 1)
                    ).execute(plan)
    assert str(e.value) == (f"query would scan more than {2 * 151 + 1} "
                            f"samples (scanned {3 * 151} so far)")
    assert (be.fused_aggs, select_counts.reads) == (fused, reads)
    with pytest.raises(QueryLimitError) as e:
        QueryEngine([shard], backend=be,
                    limits=QueryLimits(series_limit=3)).execute(plan)
    assert str(e.value) == ("query matched 4 series, exceeding the limit "
                            "of 3")


# --- the fused path asks for facts only --------------------------------------

def test_fused_path_reads_on_a_miss_and_not_on_a_hit():
    shard = _store("flushed")
    be = TpuBackend()
    plan = parse_query_range(
        "sum(rate(reqs_total[5m])) by (job)",
        TimeStepParams(T0 // 1000 + 600, 60, T0 // 1000 + 1800))
    want = QueryEngine([shard]).execute(plan)       # the numpy oracle
    handles, reads = select_counts.handles, select_counts.reads
    first = QueryEngine([shard], backend=be).execute(plan)
    assert (be.fused_aggs, be.tile_builds, be.tile_hits) == (1, 1, 0)
    assert select_counts.handles == handles + 4
    assert select_counts.reads == reads + 4          # the build read them
    again = QueryEngine([shard], backend=be).execute(plan)
    assert (be.fused_aggs, be.tile_builds, be.tile_hits) == (2, 1, 1)
    assert select_counts.handles == handles + 8
    assert select_counts.reads == reads + 4          # the hit read nothing
    for got in (first, again):
        assert [dict(k) for k in got.keys] == [dict(k) for k in want.keys]
        np.testing.assert_allclose(got.values, want.values, rtol=1e-5)


def test_aligned_path_reads_nothing_on_a_hit_either():
    shard = _store("flushed")
    be = TpuBackend()
    plan = parse_query_range(
        "rate(reqs_total[5m])",
        TimeStepParams(T0 // 1000 + 600, 60, T0 // 1000 + 1800))
    want = QueryEngine([shard]).execute(plan)
    QueryEngine([shard], backend=be).execute(plan)
    reads = select_counts.reads
    got = QueryEngine([shard], backend=be).execute(plan)
    assert be.tile_hits == 1 and select_counts.reads == reads
    np.testing.assert_allclose(got.values, want.values, rtol=1e-5)


def test_the_counters_are_on_metrics():
    from filodb_tpu.http.server import FiloHttpServer
    shard = _store("flushed")
    srv = FiloHttpServer({"timeseries": [shard]}, port=0)
    select_raw_series([shard], _filters("flushed"), 0, 2**62, None,
                      full=True)[0].ts
    text = srv._metrics_text()
    got = {ln.split()[0]: float(ln.split()[1]) for ln in text.splitlines()
           if ln.startswith("filodb_select_series")}
    assert got["filodb_select_series_total"] == select_counts.handles >= 4
    assert got["filodb_select_series_read_total"] == select_counts.reads >= 1


# --- the store moves between selection and first read --------------------------

def _ref_answer(kind, query, tsp, upto):
    series = []
    for s in range(4):
        ts, vals = _rows(kind, s)
        series.append(RefSeries(_labels("reqs_total", s),
                                [int(t) for t in ts[:upto]],
                                [float(v) for v in vals[:upto]]))
    return ref_eval(query, series, tsp.start_s, tsp.step_s, tsp.end_s)


def _assert_matches(got, want, rtol):
    assert got.num_series == len(want) > 0
    for i, k in enumerate(got.keys):
        np.testing.assert_allclose(
            got.values[i], np.array(want[tuple(sorted(k.items()))]),
            rtol=rtol, equal_nan=True)


def _flush(shard, kind):
    shard.flush_all()


def _ingest_more(shard, kind):
    _ingest(shard, kind, 200, N)


def _flush_and_ingest(shard, kind):
    shard.flush_all()
    _ingest(shard, kind, 200, N)
    shard.flush_all()


def _evict(shard, kind):
    shard.flush_all(offset=1)
    assert shard.evict_partitions(cutoff_ts=2**62) == 4
    assert all(p.num_chunks == 0 for p in shard.partitions.values())


MOVES = {"flush": _flush, "ingest": _ingest_more,
         "flush+ingest": _flush_and_ingest, "evict": _evict}


@pytest.mark.parametrize("query", ("sum(rate(reqs_total[5m])) by (job)",
                                   "rate(reqs_total[5m])"))
@pytest.mark.parametrize("backend", ("tpu", "oracle"))
@pytest.mark.parametrize("move", sorted(MOVES))
@pytest.mark.parametrize("kind", ("flushed+tail", "jittered",
                                  "counter-reset"))
def test_a_store_that_moves_under_the_handles_answers_exactly(
        kind, move, backend, query, monkeypatch, tmp_path):
    """Rows 0..199 are in when the selection runs (170 in chunks); then
    the store flushes, takes rows 200..239 (inside the query's range),
    both, or evicts every partition to the column store, BEFORE anything
    reads a sample. The answer is refeval's over the rows the selection
    saw: never a tile under a key its samples do not match, never fewer
    rows than were in."""
    from filodb_tpu.store import FlatFileColumnStore
    cs = FlatFileColumnStore(str(tmp_path / "col")) \
        if move == "evict" else None
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100,
                            column_store=cs)
    _ingest(shard, kind, 0, 170)
    shard.flush_all()
    _ingest(shard, kind, 170, 200)
    tsp = TimeStepParams(T0 // 1000 + 600, 60, T0 // 1000 + 2300)
    want = _ref_answer(kind, query, tsp, upto=200)
    real, moved = eng.select_raw_series, []

    def select_then_move(*a, **kw):
        out = real(*a, **kw)
        if not moved:
            moved.append(MOVES[move](shard, kind))
        return out

    monkeypatch.setattr(eng, "select_raw_series", select_then_move)
    be = TpuBackend() if backend == "tpu" else None
    reads = select_counts.reads
    got = QueryEngine([shard], backend=be).execute(
        parse_query_range(query, tsp))
    assert moved and select_counts.reads == reads + 4
    _assert_matches(got, want, 1e-5 if be is not None else 1e-9)
    # and the next query, of the store as it is now, sees everything
    monkeypatch.setattr(eng, "select_raw_series", real)
    upto = N if "ingest" in move else 200
    want = _ref_answer(kind, query, tsp, upto=upto)
    got = QueryEngine([shard], backend=be).execute(
        parse_query_range(query, tsp))
    _assert_matches(got, want, 1e-5 if be is not None else 1e-9)


def test_an_evicted_partition_retakes_facts_with_its_samples(tmp_path):
    """Evicted and paged back in under the handle, a partition's chunk
    list is another: the handle's key, prefix length and tail facts are
    taken again WITH the samples, so the tiles built from them go under
    the key of what they hold."""
    from filodb_tpu.store import FlatFileColumnStore
    shard = TimeSeriesShard(
        REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=100,
        column_store=FlatFileColumnStore(str(tmp_path / "col")))
    _ingest(shard, "flushed", 0, 170)
    shard.flush_all(offset=1)
    got = select_raw_series([shard], _filters("flushed"), 0, 2**62, None,
                            full=True)
    keys = [s.snapshot_key for s in got]
    _ingest(shard, "flushed", 170, 200)
    _evict(shard, "flushed")
    for s, key in zip(got, keys):
        assert s.snapshot_key == key                # facts stand unread
        ts = s.ts
        part = shard.partitions[key[2]]
        assert s.snapshot_key == key[:3] + (part.num_chunks,) + key[4:]
        assert ts.size == 200 and s.chunk_len == 200
        assert (s.tail_first_ts, s.last_ts) == (None, int(ts[-1]))
        np.testing.assert_array_equal(s.values, part.read_full(1)[1])
    assert shard.stats.partitions_paged_in == 4
