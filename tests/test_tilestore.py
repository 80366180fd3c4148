"""Aligned tile store parity tests: the shared-column fast path must match
the numpy oracle (rangefn) on jittered, gappy, resetting, boundary-exact
series — and fall back cleanly when series don't align.

(Reference oracle: query/src/test rangefn specs — RateFunctionsSpec,
AggrOverTimeFunctionsSpec golden semantics.)"""

import numpy as np
import pytest

from filodb_tpu.query import rangefn as rf
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query.model import RangeParams, RawSeries
from filodb_tpu.query.tpu import TpuBackend

PARAMS = RangeParams(300_000, 60_000, 1_500_000)
WINDOW = 300_000
DT = 10_000


def _mk(seed, n_series=6, n=150, counter=False, gaps=0.0, jitter=2000,
        resets=False):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_series):
        ts = np.arange(1, n + 1, dtype=np.int64) * DT \
            + rng.integers(-jitter, jitter + 1, n)
        ts = np.sort(ts)
        if counter:
            vals = np.cumsum(rng.uniform(0, 5, n))
            if resets and i % 2 == 0:
                cut = rng.integers(n // 3, 2 * n // 3)
                vals[cut:] = np.cumsum(rng.uniform(0, 5, n - cut))
        else:
            vals = rng.normal(10, 3, n)
        if gaps > 0:
            keep = rng.random(n) > gaps
            keep[0] = keep[-1] = True
            ts, vals = ts[keep], vals[keep]
        out.append(RawSeries({"i": str(i)}, ts, vals, is_counter=counter))
    return out


def _oracle(series, func, params=PARAMS, window=WINDOW, scalar=None):
    return np.vstack([
        rf.evaluate(func, s.ts, s.values, params.start_ms, params.step_ms,
                    params.end_ms, window, scalar=scalar)
        for s in series])


def _device(series, func, params=PARAMS, window=WINDOW, args=()):
    r = TpuBackend().periodic_samples(series, params, func, window,
                                      func_args=args)
    assert r is not None
    return r.values


ALL_FUNCS = sorted(tst.ALIGNED_FUNCS - {"last_sample"})

# rate/increase/delta ride the f32-hybrid fast path: int32 timestamps and
# f64 boundary deltas keep the numerator EXACT (large counters can't
# cancel), but the extrapolation factor runs in f32 — a few f32 ulps
# (~3e-7 relative) vs the f64 oracle. Documented tolerance; every other
# function stays exact-f64 at 1e-9.
_COUNTER_RTOL = 1e-5


def _rtol(func):
    return _COUNTER_RTOL if func in ("rate", "increase", "delta") else 1e-9


@pytest.mark.parametrize("func", ALL_FUNCS)
def test_aligned_parity_jittered(func):
    series = _mk(1, counter=True, resets=True)
    tiles, idx = tst.build_aligned_tiles(series)
    assert tiles is not None and len(idx) == len(series)
    got = _device(series, func)
    want = _oracle(series, func)
    np.testing.assert_allclose(got, want, rtol=_rtol(func), equal_nan=True)


@pytest.mark.parametrize("func", ["rate", "sum_over_time", "changes",
                                  "count_over_time", "last_over_time",
                                  "first_over_time", "stddev_over_time"])
def test_aligned_parity_with_gaps(func):
    series = _mk(2, counter=(func == "rate"), gaps=0.3)
    tiles, idx = tst.build_aligned_tiles(series)
    assert tiles is not None and len(idx) == len(series)
    got = _device(series, func)
    want = _oracle(series, func)
    np.testing.assert_allclose(got, want, rtol=_rtol(func), equal_nan=True)


def test_boundary_exact_samples():
    """Samples exactly at wstart/wend must be included (closed window)."""
    ts = np.array([300_000, 360_000, 420_000, 600_000], dtype=np.int64)
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    series = [RawSeries({"i": "0"}, ts, vals)]
    params = RangeParams(600_000, 60_000, 720_000)
    got = _device(series, "sum_over_time", params, window=300_000)
    want = _oracle(series, "sum_over_time", params, window=300_000)
    np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)


def test_counter_reset_correction_matches():
    series = _mk(3, counter=True, resets=True, gaps=0.2)
    got = _device(series, "increase")
    want = _oracle(series, "increase")
    np.testing.assert_allclose(got, want, rtol=_COUNTER_RTOL,
                               equal_nan=True)


def test_irregular_series_fall_back():
    """Random (non-cadenced) timestamps: build must reject them and the
    backend must still produce oracle-parity results via the general path."""
    rng = np.random.default_rng(4)
    series = []
    for i in range(4):
        ts = np.sort(rng.integers(10_000, 1_500_000, 120)).astype(np.int64)
        ts = np.unique(ts)
        series.append(RawSeries({"i": str(i)}, ts,
                                rng.normal(10, 3, ts.size)))
    tiles, idx = tst.build_aligned_tiles(series)
    assert tiles is None or len(idx) < len(series)
    got = _device(series, "avg_over_time")
    want = _oracle(series, "avg_over_time")
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_irregular_rate_family_takes_packed_path(func):
    """Irregular series build no aligned tiles: the packed endpoint
    evaluator serves them and must match the oracle."""
    rng = np.random.default_rng(11)
    series = []
    for i in range(3):
        ts = np.unique(np.sort(rng.integers(10_000, 1_500_000, 120))
                       ).astype(np.int64)
        vals = np.cumsum(rng.uniform(0, 5, ts.size))
        if i == 0:
            vals[ts.size // 2:] = np.cumsum(
                rng.uniform(0, 5, ts.size - ts.size // 2))   # reset
        series.append(RawSeries({"i": str(i)}, ts, vals, is_counter=True))
    from filodb_tpu.query import tilestore as tst2
    tiles, idx = tst2.build_aligned_tiles(series)
    assert tiles is None or len(idx) < len(series)
    got = _device(series, func)
    want = _oracle(series, func)
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


def test_mixed_alignment_falls_back_to_general():
    series = _mk(5, n_series=3)
    rng = np.random.default_rng(6)
    ts = np.unique(np.sort(rng.integers(10_000, 1_500_000, 200)))
    series.append(RawSeries({"i": "x"}, ts.astype(np.int64),
                            rng.normal(10, 3, ts.size)))
    got = _device(series, "max_over_time")
    want = _oracle(series, "max_over_time")
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


def test_last_sample_with_stale_markers_falls_back():
    ts = np.arange(1, 61, dtype=np.int64) * DT
    vals = np.full(60, 5.0)
    vals[30] = np.nan                      # stale marker
    series = [RawSeries({"i": "0"}, ts, vals)]
    params = RangeParams(DT * 31, DT, DT * 35)
    got = _device(series, "last_sample", params, window=DT * 5)
    want = _oracle(series, "last_sample", params, window=DT * 5)
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


def test_tile_cache_reused_across_queries():
    series = _mk(7)
    be = TpuBackend()
    be.periodic_samples(series, PARAMS, "sum_over_time", WINDOW)
    assert len(be._tile_cache) == 1
    be.periodic_samples(series, PARAMS, "avg_over_time", WINDOW)
    assert len(be._tile_cache) == 1       # same snapshot, no rebuild


@pytest.mark.parametrize("func", ["stddev_over_time", "stdvar_over_time",
                                  "z_score"])
def test_variance_large_offset_no_cancellation(func):
    """Variance via shifted squares must survive a large mean offset
    (round-1 advisor: E[x^2]-mean^2 diverged ~1e-7 and z_score NaN'd).

    Values ~1e8 with O(1) spread: the naive form loses all 8 digits of
    the variance; the shifted form keeps full precision."""
    rng = np.random.default_rng(11)
    series = []
    for i in range(4):
        ts = np.arange(1, 151, dtype=np.int64) * DT
        vals = 1e8 + rng.normal(0.0, 2.0, 150)
        series.append(RawSeries({"i": str(i)}, ts, vals))
    got = _device(series, func)
    want = _oracle(series, func)
    # z_score's numerator (last - mean) cancels at 1e8 scale in BOTH
    # paths; allow for op-ordering noise there
    rtol = 5e-6 if func == "z_score" else 1e-6
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-9,
                               equal_nan=True)
    # sanity: results are finite wherever the oracle is
    assert np.isnan(got).sum() == np.isnan(want).sum()


def test_transposed_counter_eval_matches_row_major():
    """The slot-major f32-hybrid fast path (evaluate_counters_t) must
    match the exact row-major evaluator to f32-epilogue precision on
    gappy jittered tiles — identical NaN pattern, ~1e-5 relative."""
    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(11)
    S, N, dt = 24, 96, 10_000
    base = 1_600_000_000_000
    valid = rng.random((S, N)) > 0.15
    valid[3] = False
    valid[4, : N // 2] = False
    ts_true = (base + np.arange(N)[None, :] * dt
               + rng.integers(-2000, 2000, (S, N))).astype(np.float64)
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    vals[7, 40:] *= 0.2          # a counter reset
    tiles = tst.AlignedTiles([{} for _ in range(S)], base, dt, valid,
                             ts_true, vals)
    steps = base + 400_000 + np.arange(37) * 60_000
    for func in ("rate", "increase", "delta"):
        want = np.asarray(tst.evaluate_aligned(tiles, func, steps,
                                               300_000))
        got = np.asarray(tst.evaluate_counters_t(tiles, func, steps,
                                                 300_000)).T
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9,
                                   equal_nan=True, err_msg=func)
        assert np.array_equal(np.isnan(got), np.isnan(want)), func


def test_transposed_counter_wide_grid_exact_fallback():
    """Grids that don't fit int32 ms relative to the tile base take the
    exact all-f64 path — bit-identical to the row-major evaluator."""
    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(12)
    S, N, dt = 8, 64, 10_000
    base = 1_600_000_000_000
    ts_true = (base + np.arange(N)[None, :] * dt
               + rng.integers(-2000, 2000, (S, N))).astype(np.float64)
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    tiles = tst.AlignedTiles([{} for _ in range(S)], base, dt,
                             np.ones((S, N), bool), ts_true, vals)
    # grid ends ~25 days past base: beyond int32 ms -> exact path
    steps = base + np.int64(26 * 86_400_000) + np.arange(5) * 60_000
    want = np.asarray(tst.evaluate_aligned(tiles, "rate", steps, 300_000))
    got = np.asarray(tst.evaluate_counters_t(tiles, "rate", steps,
                                             300_000)).T
    np.testing.assert_array_equal(got, want)


def test_fast_path_large_counter_exact_delta():
    """Counters at 1e15 with O(1) increments: the f64 boundary delta must
    stay exact (a pure-f32 value channel would cancel catastrophically —
    f32 ulp at 1e15 is ~1e8, dwarfing the real increase)."""
    from filodb_tpu.query import rangefn as rf
    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(13)
    S, N, dt = 4, 128, 10_000
    base = 1_600_000_000_000
    ts_true = (base + np.arange(N)[None, :] * dt
               + rng.integers(-2000, 2000, (S, N))).astype(np.float64)
    vals = 1e15 + np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    tiles = tst.AlignedTiles([{} for _ in range(S)], base, dt,
                             np.ones((S, N), bool), ts_true, vals)
    steps = base + 400_000 + np.arange(20) * 60_000
    got = np.asarray(tst.evaluate_counters_t(tiles, "rate", steps,
                                             300_000)).T
    want = np.vstack([
        rf.evaluate("rate", ts_true[s].astype(np.int64), vals[s],
                    int(steps[0]), 60_000, int(steps[-1]), 300_000)
        for s in range(S)])
    np.testing.assert_allclose(got, want, rtol=1e-5, equal_nan=True)
    # rates are O(0.5/s); garbage from f32 cancellation would be O(1e8/300)
    assert np.nanmax(np.abs(got)) < 10.0


def test_dense_alias_keeps_semantics():
    """Fully-valid tiles alias ff/bf to the raw channels; results must not
    change vs a near-dense tile evaluated the general way."""
    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(5)
    S, N, dt = 8, 64, 10_000
    base = 1_600_000_000_000
    ts_true = (base + np.arange(N)[None, :] * dt
               + rng.integers(-2000, 2000, (S, N))).astype(np.float64)
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    dense = tst.AlignedTiles([{} for _ in range(S)], base, dt,
                             np.ones((S, N), bool), ts_true, vals)
    assert dense._dense
    # force the general (non-alias) fills by faking density off
    general = tst.AlignedTiles([{} for _ in range(S)], base, dt,
                               np.ones((S, N), bool), ts_true, vals)
    general._dense = False
    steps = base + 400_000 + np.arange(19) * 60_000
    for func in ("rate", "sum_over_time", "last_over_time"):
        a = np.asarray(tst.evaluate_aligned(dense, func, steps, 300_000))
        b = np.asarray(tst.evaluate_aligned(general, func, steps, 300_000))
        np.testing.assert_array_equal(a, b, err_msg=func)


def test_transposed_dense_fast_path_matches():
    """Dense tiles drop the ps/ch arrays (arithmetic counts) — results
    must still match the general row-major evaluator exactly."""
    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(17)
    S, N, dt = 16, 128, 10_000
    base = 1_600_000_000_000
    ts_true = (base + np.arange(N)[None, :] * dt
               + rng.integers(-2000, 2000, (S, N))).astype(np.float64)
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    vals[5, 60:] *= 0.1          # reset
    tiles = tst.AlignedTiles([{} for _ in range(S)], base, dt,
                             np.ones((S, N), bool), ts_true, vals)
    assert tiles._dense
    assert "ps_ones" not in tst._tiles_arrays_t(tiles, "rate")
    # query grid pokes beyond both edges to exercise the clamps
    steps = base - 120_000 + np.arange(40) * 60_000
    for func in ("rate", "increase", "delta"):
        want = np.asarray(tst.evaluate_aligned(tiles, func, steps,
                                               300_000))
        got = np.asarray(tst.evaluate_counters_t(tiles, func, steps,
                                                 300_000)).T
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9,
                                   equal_nan=True, err_msg=func)
        assert np.array_equal(np.isnan(got), np.isnan(want)), func


def test_slide_path_bitwise_matches_gather_fast_path():
    """Regular in-bounds grids over dense tiles dispatch to the stride-
    permuted slide evaluator; results must be BITWISE identical to the
    gather fast path (same ops, different read pattern), and irregular
    or out-of-range grids must fall back."""
    import functools

    import jax
    import jax.numpy as jnp

    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(23)
    S, N, dt = 16, 288, 10_000
    base = 1_600_000_000_000
    ts_true = (base + np.arange(N)[None, :] * dt
               + rng.integers(-2000, 2000, (S, N))).astype(np.float64)
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    vals[3, 100:] *= 0.1          # reset
    tiles = tst.AlignedTiles([{} for _ in range(S)], base, dt,
                             np.ones((S, N), bool), ts_true, vals)
    steps = np.arange(base + 400_000, base + 2_000_000, 60_000,
                      dtype=np.int64)
    for func in ("rate", "increase", "delta"):
        got = np.asarray(tst.evaluate_counters_t(tiles, func, steps,
                                                 300_000))
        assert (("slide", func, steps.size, 6) in tst._EVAL_T_JIT), func
        arrs = tst._tiles_arrays_fast(tiles, func)
        ref = np.asarray(jax.jit(functools.partial(
            tst._eval_counter_fast, func, steps.size))(
                arrs, jnp.asarray(np.int64(N)), jnp.asarray(np.int64(base)),
                jnp.asarray(np.int64(dt)),
                jnp.asarray(np.int64(steps[0] - 300_000)),
                jnp.asarray(np.int64(steps[0])),
                jnp.asarray(np.int64(60_000))))
        assert got.dtype == ref.dtype == np.float32
        np.testing.assert_array_equal(got, ref, err_msg=func)
    # grid past the tile end and a non-multiple step both fall back
    # (no new slide jit entries) yet still produce results
    before = {k for k in tst._EVAL_T_JIT if k[0] == "slide"}
    over = np.arange(base + 400_000, base + N * dt + 600_000, 60_000,
                     dtype=np.int64)
    r = np.asarray(tst.evaluate_counters_t(tiles, "rate", over, 300_000))
    assert np.isfinite(r).any()
    odd = np.arange(base + 400_000, base + 2_000_000, 61_000,
                    dtype=np.int64)
    np.asarray(tst.evaluate_counters_t(tiles, "rate", odd, 300_000))
    assert {k for k in tst._EVAL_T_JIT if k[0] == "slide"} == before
