"""TPU/JAX backend parity tests: every device function must match the numpy
oracle (runs on the 8-device virtual CPU mesh; the same code path runs on
real TPU)."""

import numpy as np
import pytest

from filodb_tpu.query import rangefn as rf
from filodb_tpu.query.model import RangeParams, RawSeries
from filodb_tpu.query.tpu import DEVICE_FUNCS, TpuBackend, pack_series


def make_series(n_series=5, n_samples=300, seed=0, counter=False,
                with_nans=False, irregular=False):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_series):
        if irregular:
            dts = rng.integers(5_000, 15_000, n_samples)
        else:
            dts = np.full(n_samples, 10_000)
        ts = 1_600_000_000_000 + np.cumsum(dts).astype(np.int64)
        if counter:
            vals = np.cumsum(rng.uniform(0, 100, n_samples))
            # inject resets
            if s % 2 == 1:
                vals[n_samples // 2 :] -= vals[n_samples // 2] * 0.9
        else:
            vals = rng.normal(100, 25, n_samples)
        if with_nans:
            vals = vals.copy()
            vals[rng.integers(0, n_samples, n_samples // 20)] = np.nan
        out.append(RawSeries({"instance": f"i{s}"}, ts,
                             np.asarray(vals, dtype=np.float64),
                             is_counter=counter))
    return out


PARAMS = RangeParams(1_600_001_000_000, 60_000, 1_600_003_000_000)
WINDOW = 300_000

ALL_FUNCS = sorted(DEVICE_FUNCS - {"last_over_time"})


@pytest.mark.parametrize("func", ALL_FUNCS)
def test_device_matches_oracle(func):
    counter = func in ("rate", "increase", "irate", "resets")
    series = make_series(counter=counter, with_nans=True, irregular=True)
    args = (0.9,) if func == "quantile_over_time" else ()
    backend = TpuBackend()
    from filodb_tpu.query.engine import periodic_samples
    oracle = periodic_samples(series, PARAMS, func, WINDOW, args)
    got = backend.periodic_samples(series, PARAMS, func, WINDOW, args)
    assert got is not None, f"{func} fell back to oracle"
    assert got.values.shape == oracle.values.shape
    np.testing.assert_allclose(got.values, oracle.values, rtol=1e-9,
                               atol=1e-9, equal_nan=True,
                               err_msg=f"mismatch for {func}")


@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_packed_rate_one_program_per_step_bucket(func):
    """A lone irregular-cadence query pads its step count to a power of
    two like a batched one: 17, 23 and 31 steps are ONE program of the
    packed executable table (one miss, two hits), each right against
    the oracle."""
    from filodb_tpu.query.engine import periodic_samples
    series = make_series(counter=func != "delta", with_nans=True,
                         irregular=True)
    backend = TpuBackend()
    before = backend.executable_cache_stats()
    for nsteps in (17, 23, 31):
        # 30 s steps: each grid clips to 64 < n <= 128 samples a series,
        # one sample bucket, so only the step bucket is in question
        params = RangeParams(PARAMS.start_ms, 30_000,
                             PARAMS.start_ms + (nsteps - 1) * 30_000)
        got = backend.periodic_samples(series, params, func, WINDOW)
        oracle = periodic_samples(series, params, func, WINDOW)
        assert got.values.shape == (len(series), nsteps)
        np.testing.assert_allclose(got.values, oracle.values, rtol=1e-9,
                                   atol=1e-9, equal_nan=True)
    after = backend.executable_cache_stats()
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 2


def test_pack_series_drops_nans():
    series = make_series(n_series=2, with_nans=True)
    ts, vals, lens = pack_series(series)
    assert ts.shape == vals.shape
    assert not np.isnan(vals[0, : lens[0]]).any()
    # padded tail has sentinel timestamps
    if lens[0] < ts.shape[1]:
        assert ts[0, lens[0]] > 1 << 59


def test_offset_parity():
    series = make_series(counter=True)
    backend = TpuBackend()
    from filodb_tpu.query.engine import periodic_samples
    oracle = periodic_samples(series, PARAMS, "rate", WINDOW, (),
                              offset_ms=600_000)
    got = backend.periodic_samples(series, PARAMS, "rate", WINDOW, (),
                                   offset_ms=600_000)
    # rate rides the tilestore f32-hybrid path (exact delta, f32
    # extrapolation factor): ~3e-7 relative vs the f64 oracle
    np.testing.assert_allclose(got.values, oracle.values, rtol=1e-5,
                               equal_nan=True)


def test_histograms_fall_back():
    s = RawSeries({"a": "b"}, np.array([1000], dtype=np.int64),
                  np.ones((1, 4)), bucket_les=np.array([1.0, 2, 4, np.inf]))
    backend = TpuBackend()
    assert backend.periodic_samples([s], PARAMS, "rate", WINDOW) is None


def test_engine_with_tpu_backend_e2e():
    """QueryEngine wired with the TPU backend produces oracle-equal results."""
    from filodb_tpu.core.memstore import TimeSeriesShard
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
    from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
    from filodb_tpu.query.engine import QueryEngine

    shard = TimeSeriesShard(DatasetRef("ts"), DEFAULT_SCHEMAS, 0)
    b = RecordBuilder(DEFAULT_SCHEMAS)
    t0 = 1_600_000_000
    for s in range(6):
        labels = {"_metric_": "reqs_total", "_ws_": "w", "_ns_": "n",
                  "instance": f"i{s}"}
        v = 0.0
        for t in range(360):
            v += 7.0 * (s + 1)
            b.add_sample("prom-counter", labels, (t0 + t * 10) * 1000, v)
    for c in b.containers():
        shard.ingest(c)
    shard.flush_all()

    plan = parse_query_range("sum(rate(reqs_total[5m]))",
                             TimeStepParams(t0 + 600, 60, t0 + 3000))
    oracle_res = QueryEngine([shard]).execute(plan)
    backend = TpuBackend()
    tpu_res = QueryEngine([shard], backend=backend).execute(plan)
    # rate rides the tilestore f32-hybrid path: ~3e-7 relative vs oracle
    np.testing.assert_allclose(tpu_res.values, oracle_res.values, rtol=1e-5,
                               equal_nan=True)
    # steady increase of 7*(s+1) per 10s across 6 series
    expected = sum(0.7 * (s + 1) for s in range(6))
    np.testing.assert_allclose(tpu_res.values[0], expected, rtol=1e-5)
    # the whole sum(rate(...)) ran inside the fused group-sum program —
    # no [S, T] per-series intermediate
    assert backend.fused_aggs == 1

    # grouped + avg/count variants ride the same fused path
    for q in ("sum(rate(reqs_total[5m])) by (instance)",
              "avg(rate(reqs_total[5m]))",
              "count(rate(reqs_total[5m]))"):
        plan = parse_query_range(q, TimeStepParams(t0 + 600, 60, t0 + 3000))
        want = QueryEngine([shard]).execute(plan)
        got = QueryEngine([shard], backend=backend).execute(plan)
        assert [dict(k) for k in got.keys] == [dict(k) for k in want.keys]
        np.testing.assert_allclose(got.values, want.values, rtol=1e-5,
                                   equal_nan=True)
    assert backend.fused_aggs == 4
