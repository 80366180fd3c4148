"""The fused histogram quantile: ``histogram_quantile(q, sum by (job)
(rate|increase(h[w])))`` over native histogram columns served by ONE cached
device program over bucket-axis tiles (``tilestore.HistTiles``,
``hist_quantile_groupsum``), against the engine's host path over the same
store (``periodic_samples`` -> ``_aggregate_hist_sum`` ->
``histogram_quantile``), which stays the plain reference of the program.

A small fleet of this file's own: 4 jobs x 4 instances, 120 scrapes 10 s
apart, the Prometheus client's 12 default bounds, Poisson(20) observations a
scrape under a per-job log-normal; each case changes the samples one way.
The query asks 9 steps of 60 s from tick 60 with a 5 m window, so the first
window starts at tick 30.

The tolerance: every step after the int32 relative timestamps is f64, and
the program and the host sum a group's rates in another order; the quantile
divides the rank's rounding by the bucket's share of the total (at least a
thousandth here), so 1e-12 holds a few hundred f64 ulps of the
interpolation (the ``hist-quantile`` claim certifies 256).
"""

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.memory.histogram import CustomBuckets
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.query import engine as eng
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query import tpu
from filodb_tpu.query.model import RawSeries, SelectionFacts

REF = DatasetRef("timeseries")
T0 = 1_600_000_000_000
N, JOBS, INST = 120, 4, 4
LES = (.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, float("inf"))
LES_AT_ZERO = (0.0, .01, .1, 1, float("inf"))
START, STEP, END = 600, 60, 1080        # s after T0: ticks 60 .. 108
WINDOW_TICK = 30                        # the first window's first tick
RTOL = 1e-12
QUERY = 'histogram_quantile({q}, sum({fn}(lat{{_ns_="App-0"}}[5m])) by (job))'


def _counts(rng, les, median, n=N):
    """[n, B] cumulative bucket counts of Poisson(20) observations a
    scrape under a log-normal of ``median``."""
    obs = rng.lognormal(np.log(median), 0.8, (n, 20))
    obs[rng.random((n, 20)) < 0.1] = np.inf         # not observed
    per = (obs[..., None] <= np.asarray(les)).sum(axis=1)
    return np.cumsum(per, axis=0).astype(np.float64)


def _reset(c, k):
    """Every bucket falls to 0 at tick k and counts on from there."""
    c[k:] -= c[k]


def _fleet(case, seed=20261015):
    """[(labels, ts ms [n], counts [n, B], les)] of the case."""
    rng = np.random.default_rng(seed)
    les = LES_AT_ZERO if case == "first-bound-nonpositive" else LES
    out = []
    for j in range(JOBS):
        for i in range(INST):
            n = len(out)
            median = 0.02 * 3.0 ** j
            if case == "rank-in-inf" and j == 1:
                median = 100.0
            c = _counts(rng, les, median)
            if case == "first-bound-nonpositive" and j == 0:
                c = np.cumsum(np.broadcast_to(
                    np.array([15, 16, 18, 19, 20.0]), (N, 5)), axis=0)
            ts = T0 + 10_000 * np.arange(N, dtype=np.int64)
            if case != "dense" and n % 2:
                ts = ts + rng.integers(-2000, 2001, N)
            keep = np.ones(N, bool)
            if case == "reset-all" and n == 4:
                _reset(c, 70)
            if case == "reset-at-zero-point" and n in (2, 6):
                _reset(c, WINDOW_TICK)
            if case == "reset-before-zero-point" and n in (2, 6):
                _reset(c, WINDOW_TICK - 1)
            if case == "partial-drop" and n == 6:
                # one bucket falls below tick 74's, +Inf grows on
                c[75:, 6] -= c[75, 6] - c[74, 6] + 1.0
                assert c[75, 6] >= c[75, 5] and c[75, -1] > c[74, -1]
            if case == "holes" and n % 4 == 3:
                keep[[40, 41, 42, 43, 90]] = False
            if case == "group-without-samples" and j == 3:
                keep[60:95] = False
            if case == "total-zero" and j == 2:
                c[60:100] = c[60]             # no observation for 400 s
            out.append(({"_metric_": "lat", "_ws_": "demo", "_ns_": "App-0",
                         "job": f"job-{j}", "instance": f"i-{n:02d}"},
                        ts[keep], c[keep], les))
    return out


def _shard(fleet, flush=True, extra=()):
    """A shard holding the fleet in chunks (``flush``), and ``extra``
    fleets' rows after it in the write buffer."""
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=120)
    for rows, do_flush in ((fleet, flush),) + tuple((e, False)
                                                    for e in extra):
        b = RecordBuilder(DEFAULT_SCHEMAS)
        for labels, ts, counts, les in rows:
            scheme = CustomBuckets(les)
            for t, row in zip(ts.tolist(), counts):
                b.add_sample("prom-histogram", labels, t, 0.0,
                             float(row[-1]), (scheme, row.astype(np.int64)))
        for c in b.containers():
            shard.ingest(c)
        if do_flush:
            shard.flush_all()
    return shard


@pytest.fixture(autouse=True)
def _fresh_memo():
    eng.select_memo.clear()
    yield
    eng.select_memo.clear()


def _plan(q=0.99, fn="rate", start=START, end=END):
    return parse_query_range(QUERY.format(q=q, fn=fn), TimeStepParams(
        T0 // 1000 + start, STEP, T0 // 1000 + end))


def _both(shard, plan):
    """-> (device answer, backend, host answer) over the same store."""
    be = tpu.TpuBackend(batcher=None)
    got = eng.QueryEngine([shard], backend=be).execute(plan)
    eng.select_memo.clear()
    want = eng.QueryEngine([shard]).execute(plan)
    return got, be, want


def _same(got, want):
    assert got.keys == want.keys
    np.testing.assert_array_equal(got.steps, want.steps)
    np.testing.assert_allclose(got.values, want.values, rtol=RTOL, atol=0)


CASES = ["dense", "jitter", "holes", "reset-all", "partial-drop",
         "reset-at-zero-point", "reset-before-zero-point",
         "group-without-samples", "total-zero", "rank-in-inf",
         "first-bound-nonpositive"]
_SHARDS = {}


def _case_shard(case):
    if case not in _SHARDS:
        _SHARDS[case] = _shard(_fleet(case))
    return _SHARDS[case]


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
@pytest.mark.parametrize("fn", ["rate", "increase"])
@pytest.mark.parametrize("case", CASES)
def test_device_answers_as_the_host(case, fn, q):
    got, be, want = _both(_case_shard(case), _plan(q, fn))
    assert be.fused_hist_aggs == 1, be.fused_hist_refused
    _same(got, want)
    assert np.isfinite(want.values).any()


@pytest.mark.parametrize("case, nan", [
    ("group-without-samples", "job-3"), ("total-zero", "job-2")])
def test_a_group_with_no_point_or_no_observation_is_nan(case, nan):
    got, _, want = _both(_case_shard(case), _plan(0.5))
    row = [k["job"] for k in got.keys].index(nan)
    assert np.isnan(got.values[row]).any()
    assert np.isfinite(np.delete(got.values, row, axis=0)).all()
    _same(got, want)


def test_rank_in_inf_gives_second_highest_bound():
    got, _, _ = _both(_case_shard("rank-in-inf"), _plan(0.9))
    row = [k["job"] for k in got.keys].index("job-1")
    assert (got.values[row] == LES[-2]).all()


def test_first_bound_at_zero_is_the_answer():
    got, _, _ = _both(_case_shard("first-bound-nonpositive"), _plan(0.5))
    row = [k["job"] for k in got.keys].index("job-0")
    assert (got.values[row] == 0.0).all()


def _corr(tiles):
    """The tiles' correction as [slots, series, buckets]."""
    c = np.asarray(tiles.t_corr)
    return c.reshape(c.shape[0], tiles.num_buckets, -1).transpose(0, 2, 1)


@pytest.mark.parametrize("at", [WINDOW_TICK, WINDOW_TICK - 1])
def test_resets_count_from_the_first_window(at):
    """FiloDB reads the buckets from the first window's start: a reset on
    or before its first sample is not one the query saw, so the program
    takes the tile's correction there off both boundary values. Without
    that the first window's rate extrapolates to another zero point."""
    case = ("reset-at-zero-point" if at == WINDOW_TICK
            else "reset-before-zero-point")
    shard = _case_shard(case)
    series = [RawSeries(l, ts, c, True, np.asarray(les))
              for l, ts, c, les in _fleet(case)]
    tiles, _ = tst.build_aligned_tiles(series)
    # the tile's correction at the first window's first sample is the
    # whole histogram before the reset, in series 2 and 6
    corr = _corr(tiles)[WINDOW_TICK]
    assert corr[2].max() > 0 and corr[6].max() > 0 and corr[0].max() == 0
    got, _, want = _both(shard, _plan(0.5, "increase", START - 300,
                                      END - 300))
    _same(got, want)


def test_partial_drop_adds_back_the_whole_histogram():
    series = [RawSeries(l, ts, c, True, np.asarray(les))
              for l, ts, c, les in _fleet("partial-drop")]
    tiles, _ = tst.build_aligned_tiles(series)
    corr = _corr(tiles)[:, 6]                       # [N, B]
    want = series[6].values[74]
    np.testing.assert_array_equal(corr[75], want)
    np.testing.assert_array_equal(corr[74], 0.0)


def test_drop_table_is_taken_where_the_chunk_has_one():
    """A drop the table names is a reset even where no bucket fell."""
    fleet = _fleet("dense")
    labels, ts, c, les = fleet[0]
    plain = RawSeries(labels, ts, c, True, np.asarray(les))
    told = RawSeries(labels, ts, c, True, np.asarray(les),
                     hist_drop_rows=np.array([50]))
    t_plain, _ = tst.build_aligned_tiles([plain])
    t_told, _ = tst.build_aligned_tiles([told])
    assert not _corr(t_plain).any()
    np.testing.assert_array_equal(_corr(t_told)[50, 0], c[49])


# -- the route -------------------------------------------------------------

def test_route_counts_apart_from_the_counter_group_sums():
    got, be, want = _both(_case_shard("dense"), _plan())
    assert (be.fused_hist_aggs, be.fused_aggs, be.fused_refused) == (1, 0, 0)
    assert sum(be.fused_hist_refused.values()) == 0


def test_two_bucket_schemes_are_refused_as_tiles():
    fleet = _fleet("dense")
    labels, ts, c, _ = fleet[5]
    fleet[5] = (labels, ts, c, LES[:6] + (0.75,) + LES[7:])
    got, be, want = _both(_shard(fleet), _plan())
    assert be.fused_hist_aggs == 0 and be.fused_hist_refused["tiles"] == 1
    _same(got, want)


def test_a_window_in_the_write_buffer_is_refused_as_tail():
    fleet = _fleet("dense")
    head = [(l, ts[:100], c[:100], les) for l, ts, c, les in fleet]
    tail = [(l, ts[100:], c[100:], les) for l, ts, c, les in fleet]
    got, be, want = _both(_shard(head, extra=(tail,)), _plan())
    assert be.fused_hist_aggs == 0 and be.fused_hist_refused["tail"] == 1
    _same(got, want)


def test_a_cpu_node_without_the_flag_is_refused_as_cpu(monkeypatch):
    monkeypatch.setattr(tpu, "FUSED_GROUPSUM_INTERPRET", False)
    got, be, want = _both(_case_shard("dense"), _plan())
    assert be.fused_hist_aggs == 0 and be.fused_hist_refused["cpu"] == 1
    _same(got, want)


def test_a_grid_past_int32_ms_is_refused_as_grid():
    """Hourly scrapes over 30 days: the tile spans more than int32 ms, and
    a grid at its end leaves int32 ms from the tile base."""
    hour = 3_600_000
    rng = np.random.default_rng(9)
    fleet = [({"_metric_": "lat", "_ws_": "demo", "_ns_": "App-0",
               "job": f"job-{n % 2}", "instance": f"i-{n}"},
              T0 + hour * np.arange(720, dtype=np.int64),
              _counts(rng, LES, 0.05, 720), LES) for n in range(4)]
    plan = parse_query_range(
        'histogram_quantile(0.5, sum(rate(lat{_ns_="App-0"}[3h])) by (job))',
        TimeStepParams((T0 + 700 * hour) // 1000, 3600,
                       (T0 + 710 * hour) // 1000))
    got, be, want = _both(_shard(fleet), plan)
    assert be.fused_hist_aggs == 0 and be.fused_hist_refused["grid"] == 1
    _same(got, want)


def test_one_executable_serves_every_q_and_request():
    shard = _case_shard("jitter")
    be = tpu.TpuBackend(batcher=None)
    engine = eng.QueryEngine([shard], backend=be)
    engine.execute(_plan(0.5))
    before = be.executable_cache_stats()
    for q, shift in ((0.9, 0), (0.99, 60), (0.5, 120), (0.75, 180)):
        engine.execute(_plan(q, start=START + shift, end=END + shift))
    after = be.executable_cache_stats()
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 4
    assert be.fused_hist_aggs == 5 and be.tile_builds == 1


# -- counters keep their tiles, keys and route ------------------------------

def _counter_series():
    rng = np.random.default_rng(3)
    ts = T0 + 10_000 * np.arange(N, dtype=np.int64)
    return [RawSeries({"_metric_": "c", "job": f"job-{i % 2}",
                       "le": le}, ts,
                      np.cumsum(rng.integers(0, 9, N)).astype(float),
                      True)
            for i, le in enumerate(["0.1", "1", "+Inf"] * 2)]


def test_counter_selections_build_the_tiles_they_built():
    series = _counter_series()
    tiles, idx = tst.build_aligned_tiles(series)
    assert type(tiles) is tst.AlignedTiles and idx == list(range(6))
    assert np.asarray(tiles.vals).shape == (6, N)
    np.testing.assert_array_equal(np.asarray(tiles.vals),
                                  np.stack([s.values for s in series]))
    facts = SelectionFacts(series)
    assert facts.les is None and not facts.any_hist
    assert facts.key.parts == tuple(id(s) for s in series)


def test_classic_le_quantile_stays_on_the_counter_fused_path():
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=120)
    b = RecordBuilder(DEFAULT_SCHEMAS)
    rng = np.random.default_rng(5)
    for j in range(2):
        base = np.cumsum(rng.integers(0, 9, (N, 3)), axis=1)
        vals = np.cumsum(base, axis=0).astype(float)
        for k, le in enumerate(["0.1", "1", "+Inf"]):
            labels = {"_metric_": "lat_bucket", "_ws_": "demo",
                      "_ns_": "App-0", "job": f"job-{j}", "le": le}
            for t in range(N):
                b.add_sample("prom-counter", labels, T0 + 10_000 * t,
                             float(vals[t, k]))
    for c in b.containers():
        shard.ingest(c)
    shard.flush_all()
    plan = parse_query_range(
        'histogram_quantile(0.9, sum(rate(lat_bucket{_ns_="App-0"}[5m])) '
        'by (le, job))',
        TimeStepParams(T0 // 1000 + START, STEP, T0 // 1000 + END))
    got, be, want = _both(shard, plan)
    assert be.fused_aggs == 1 and be.fused_hist_aggs == 0
    assert sum(be.fused_hist_refused.values()) == 0
    assert got.keys == want.keys
    # the counter fused path sums f32 rates
    np.testing.assert_allclose(got.values, want.values, rtol=1e-5)
