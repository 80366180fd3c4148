"""The fused histogram quantile on the mesh-resident store: a histogram
cohort (``tilestore.HistTiles``) placed across four of the CPU's virtual
devices (``shardstore.ShardedHistTiles``: each device holds every bucket of
its own series, each channel in the f32 parts its values need) answers
``histogram_quantile(q, sum by (g) (rate|increase(h[w])))`` with ONE sharded
program (the one-chip program's evaluator and partial sums on every device,
a ``psum`` of the [T, G, B] sums and counts over the shard axis) and the
quantile of those sums on the host.

Held here: the answer against the one-chip program over the same tiles
(``hist_quantile_groupsum``; only the psum's order differs, 1e-12 relative)
and against the host path (``periodic_samples`` -> ``_aggregate_hist_sum``
-> ``histogram_quantile``, numpy f64) within 1e-9, the histogram cell's
limit; the route (``mesh_dispatches`` and ``fused_hist_aggs`` rise, a
refused placement falls back to one chip and is counted by reason, a flush
drops the placement); one host array a request; and that splitting the
one-chip program into its partials and its epilogue left its bits as they
were. The "large" case's counts pass 2**24 and keep two f32 parts a
channel, the "tenths" case's have a fraction and keep three.

A fleet of this file's own: 16 series (13 where the padding rows are the
case) of the Prometheus client's 12 default bounds, 120 scrapes 10 s apart;
the query asks 9 steps of 60 s from tick 60 with a 5 m window.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.memory.histogram import CustomBuckets
from filodb_tpu.obs import devprof
from filodb_tpu.parallel.mesh import make_mesh
from filodb_tpu.parallel.shardstore import (ShardedHistTiles,
                                            ShardedTileEvaluator)
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.query import engine as eng
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query import tpu
from filodb_tpu.query.model import RangeParams, RawSeries, clip_series

BASE, DT, W, STEP = 1_600_000_000_000, 10_000, 300_000, 60_000
N, T = 120, 9
LES = (.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, np.inf)
QS = (0.5, 0.9, 0.99, 0.999, 1 / 3)
TO_ONE_CHIP = 1e-12
TO_HOST = 1e-9
# group 0 lives on the first device alone (its series 0-3 of 16 or 13)
GIDS = (0, 0, 0, 0, 1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2, 3)
CASES = ["dense", "jitter", "resets", "padding", "empty-group", "large",
         "tenths"]


def _frozen(a):
    a = np.asarray(a)
    a.setflags(write=False)
    return a


def _fleet(case, seed=20261016):
    """-> ([RawSeries], gids, G) of the case: Poisson(30) observations a
    scrape under a log-normal whose median grows with the group."""
    rng = np.random.default_rng(seed)
    les = np.asarray(LES)
    S = 13 if case == "padding" else 16
    G = 5 if case == "empty-group" else 4
    out = []
    for i in range(S):
        obs = rng.lognormal(np.log(0.02 * 3.0 ** GIDS[i]), 0.8, (N, 30))
        obs[rng.random((N, 30)) < 0.1] = np.inf        # not observed
        counts = np.cumsum((obs[..., None] <= les).sum(axis=1),
                           axis=0).astype(np.float64)
        if case == "large":
            counts += 2.0 ** 26 + 3.0 * i
        if case == "tenths":
            counts += 0.1 * (i + 1)
        ts = BASE + np.arange(N, dtype=np.int64) * DT
        if case != "dense" and i % 2:
            ts = ts + rng.integers(-2000, 2001, N)
        if case == "resets" and i in (2, 9):
            counts[70:] -= counts[70]                   # every bucket to 0
        if case == "resets" and i == 5:
            counts[80:, 6] -= counts[80, 6] - counts[79, 6] + 1.0
        out.append(RawSeries({"g": str(GIDS[i]), "i": str(i)}, ts, counts,
                             is_counter=True, bucket_les=les))
    return out, _frozen(np.asarray(GIDS[:S])), G


def _steps(shift=0):
    return BASE + 600_000 + shift + np.arange(T, dtype=np.int64) * STEP


def _mesh(shape=(4, 1)):
    return make_mesh(n_shard_groups=shape[0], time_parallel=shape[1],
                     devices=jax.devices()[:shape[0] * shape[1]])


def _host(series, steps, func, q, G):
    """The host path's [T, G] (NaN for a group no series is in)."""
    first, last = int(steps[0]), int(steps[-1])
    grid = eng.periodic_samples(clip_series(series, first - W, last),
                                RangeParams(first, STEP, last), func, W)
    res = eng.histogram_quantile(eng._aggregate_hist_sum(grid, ("g",), ()), q)
    out = np.full((steps.size, G), np.nan)
    for key, row in zip(res.keys, res.values):
        out[:, int(dict(key)["g"])] = row
    return out


def _mesh_answer(st, func, steps, gids, G, q):
    """The placement's bucket sums, synced and cut, then its quantile."""
    sums = np.asarray(st.dispatch_hist_quantile(func, steps, W, gids, G))
    return st.quantile(sums[:steps.size], q)


def _close(got, want, rtol):
    assert got.shape == want.shape
    assert np.array_equal(np.isnan(got), np.isnan(want))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=0)


_TILES = {}


def _tiles(case):
    if case not in _TILES:
        series, gids, G = _fleet(case)
        tiles, idx = tst.build_aligned_tiles(series)
        assert idx == list(range(len(series))) and tiles._dense
        _TILES[case] = series, gids, G, tiles
    return _TILES[case]


# -- the answer -------------------------------------------------------------

@pytest.mark.parametrize("q", QS)
@pytest.mark.parametrize("func", ["rate", "increase"])
@pytest.mark.parametrize("case", CASES)
def test_mesh_answers_as_one_chip_and_as_the_host(case, func, q):
    series, gids, G, tiles = _tiles(case)
    steps = _steps()
    st = ShardedTileEvaluator(_mesh()).place(tiles)
    assert isinstance(st, ShardedHistTiles)
    got = _mesh_answer(st, func, steps, gids, G, q)
    one = np.asarray(tst.hist_quantile_groupsum(
        tiles, func, steps, W, tst.fused_group_ids(tiles, gids), G, q))
    _close(got, one, TO_ONE_CHIP)
    _close(got, _host(series, steps, func, q, G), TO_HOST)
    assert np.isfinite(got[:, :4]).all()
    if case == "empty-group":
        assert np.isnan(got[:, 4]).all()


@pytest.mark.parametrize("shape", [(4, 1), (2, 2), (1, 4)])
def test_any_mesh_shape_gives_the_answer(shape):
    """Series over the first axis, output steps over the second: the step
    offset of a device of the time axis is its own."""
    series, gids, G, tiles = _tiles("jitter")
    steps = _steps(STEP)
    st = ShardedTileEvaluator(_mesh(shape)).place(tiles)
    got = _mesh_answer(st, "rate", steps, gids, G, 0.99)
    _close(got, _host(series, steps, "rate", 0.99, G), TO_HOST)


@pytest.mark.parametrize("case,parts", [("jitter", 1), ("padding", 1),
                                        ("large", 2), ("tenths", 3)])
def test_each_device_holds_every_bucket_of_its_own_series(case, parts):
    """Device d's block of each channel is [cap, BP, S_l] over the series
    d*S_l .. (d+1)*S_l - 1 (zeros for the padding rows and buckets), its
    f32 parts summing to the f64 values: as many as those need (the
    corrections of these fleets are integers below 2**24: one part)."""
    series, _, _, tiles = _tiles(case)
    st = ShardedTileEvaluator(_mesh()).place(tiles)
    S, B, S_l = len(series), len(LES), st.S_l
    assert st.S_pad == 4 * S_l and st.cap == 128 and st.BP == 16
    for placed, ch in ((st._cv, tiles.t_cv), (st._corr, tiles.t_corr)):
        assert len(placed) == (parts if ch is tiles.t_cv else 1)
        want = np.asarray(ch).reshape(N, B, S)
        for shard in zip(*(p.addressable_shards for p in placed)):
            d = (shard[0].index[2].start or 0) // S_l
            block = sum(np.asarray(s.data).astype(np.float64)
                        for s in shard)
            assert block.shape == (st.cap, st.BP, S_l)
            for s in range(S_l):
                g = d * S_l + s
                np.testing.assert_array_equal(
                    block[:N, :B, s], want[:, :, g] if g < S else 0.0)
            assert not block[N:].any() and not block[:, B:].any()


# -- the route --------------------------------------------------------------

def _backend(mesh=True):
    return tpu.TpuBackend(batcher=None, mesh_eval=(
        ShardedTileEvaluator(_mesh()) if mesh else None))


def _ask(be, series, gids, G, steps, q=0.99):
    return be.fused_hist_quantile(series, "rate", steps, W, 0, gids, G, q)


def test_a_request_is_one_mesh_dispatch_and_one_fused_answer():
    series, gids, G = _fleet("jitter")
    be = _backend()
    for k in range(3):
        got = _ask(be, series, gids, G, _steps(k * STEP))
        _close(got, _host(series, _steps(k * STEP), "rate", 0.99, G),
               TO_HOST)
        assert (be.mesh_dispatches, be.fused_hist_aggs) == (k + 1, k + 1)
    assert sum(be.mesh_refused.values()) == 0
    assert sum(be.fused_hist_refused.values()) == 0
    assert be.mesh_eval.placements == 1 and be.fused_aggs == 0


@pytest.mark.parametrize("flag", [True, False])
def test_holed_tiles_fall_back_to_one_chip_counted_as_tiles(monkeypatch,
                                                            flag):
    """A scrape missing in one series: the placement refuses the tiles and
    the one-chip program serves, or, on a CPU node without the interpreted
    kernels, the host."""
    monkeypatch.setattr(tpu, "FUSED_GROUPSUM_INTERPRET", flag)
    series, gids, G = _fleet("jitter")
    s = series[6]
    keep = np.ones(N, bool)
    keep[[47, 48]] = False
    series[6] = RawSeries(s.labels, s.ts[keep], s.values[keep],
                          is_counter=True, bucket_les=s.bucket_les)
    be = _backend()
    got = _ask(be, series, gids, G, _steps())
    assert be.mesh_refused == {"tiles": 1, "grid": 0, "family": 0}
    assert be.mesh_dispatches == 0 and be.mesh_eval.placements == 0
    if flag:
        assert be.fused_hist_aggs == 1
        _close(got, _host(series, _steps(), "rate", 0.99, G), TO_HOST)
    else:
        assert got is None and be.fused_hist_refused["cpu"] == 1


def test_a_grid_past_int32_ms_is_refused_as_grid():
    """26 days of hourly steps before the data: the windows leave int32 ms
    from the tile base, for the mesh and for the one-chip program."""
    series, gids, G = _fleet("dense")
    be = _backend()
    steps = BASE - 26 * 86_400_000 + np.arange(0, 27 * 24) * 3_600_000
    assert _ask(be, series, gids, G, steps) is None
    assert be.mesh_refused["grid"] == 1 and be.mesh_dispatches == 0
    assert be.fused_hist_refused["grid"] == 1


def test_a_cpu_node_with_a_mesh_serves_from_the_mesh(monkeypatch):
    monkeypatch.setattr(tpu, "FUSED_GROUPSUM_INTERPRET", False)
    series, gids, G = _fleet("dense")
    be = _backend()
    assert _ask(be, series, gids, G, _steps()) is not None
    assert (be.mesh_dispatches, be.fused_hist_aggs) == (1, 1)
    plain = _backend(mesh=False)
    assert _ask(plain, series, gids, G, _steps()) is None
    assert plain.fused_hist_refused["cpu"] == 1


def test_a_flush_drops_the_placement_and_counts_it():
    """No donated append for histograms: a refresh over the new tiles drops
    the placement, counts an eviction, and the next request places anew."""
    series, _, _, tiles = _tiles("jitter")
    ev = ShardedTileEvaluator(_mesh())
    st = ev.place(tiles)
    longer = [RawSeries(s.labels, np.append(s.ts, s.ts[-1] + DT),
                        np.vstack([s.values, s.values[-1:] + 1.0]),
                        is_counter=True, bucket_les=s.bucket_les)
              for s in series]
    grown, _ = tst.build_aligned_tiles(longer)
    assert not ev.refresh(tiles, grown)
    assert (ev.evictions, ev.snapshot()["resident"]) == (1, 0)
    assert ev.place(grown) is not st and ev.placements == 2
    assert not ev.refresh(object(), grown)          # nothing placed: no count
    assert ev.evictions == 1


def test_a_request_hands_over_one_host_array(monkeypatch):
    """Once the grouping has been seen, the program is handed the request's
    int32 plan and nothing else from the host: one buffer a device."""
    calls = []
    real = devprof.ProfiledExecutable.__call__

    def spy(self, *args):
        calls.append(args)
        return real(self, *args)
    monkeypatch.setattr(devprof.ProfiledExecutable, "__call__", spy)
    series, gids, G = _fleet("jitter")
    be = _backend()
    _ask(be, series, gids, G, _steps())
    for q in (0.99, 0.5):
        del calls[:]
        before = devprof.put_counts.h2d_puts
        _ask(be, series, gids, G, _steps(STEP), q)
        assert devprof.put_counts.h2d_puts - before == 4
        args, = calls
        host = [a for a in jax.tree_util.tree_leaves(args)
                if not isinstance(a, jax.Array)]
        assert len(host) == 1 and host[0].dtype == np.int32 \
            and host[0].ndim == 1


# -- through the engine -----------------------------------------------------

def _shard(series):
    shard = TimeSeriesShard(DatasetRef("timeseries"), DEFAULT_SCHEMAS, 0,
                            max_chunk_rows=N)
    b = RecordBuilder(DEFAULT_SCHEMAS)
    scheme = CustomBuckets(LES)
    for s in series:
        labels = {"_metric_": "lat", "_ws_": "demo", **s.labels}
        for t, row in zip(s.ts.tolist(), s.values):
            b.add_sample("prom-histogram", labels, t, 0.0, float(row[-1]),
                         (scheme, row.astype(np.int64)))
    for c in b.containers():
        shard.ingest(c)
    shard.flush_all()
    return shard


@pytest.mark.parametrize("q", [0.5, 0.99])
def test_the_engine_takes_the_mesh_for_a_promql_board(q):
    eng.select_memo.clear()
    shard = _shard(_fleet("resets")[0])
    plan = parse_query_range(
        f'histogram_quantile({q}, sum(rate(lat{{_ws_="demo"}}[5m])) by (g))',
        TimeStepParams(BASE // 1000 + 600, 60, BASE // 1000 + 1080))
    be = _backend()
    got = eng.QueryEngine([shard], backend=be).execute(plan)
    eng.select_memo.clear()
    want = eng.QueryEngine([shard]).execute(plan)
    eng.select_memo.clear()
    assert (be.mesh_dispatches, be.fused_hist_aggs) == (1, 1)
    assert got.keys == want.keys
    np.testing.assert_allclose(got.values, want.values, rtol=TO_HOST, atol=0)


# -- the one-chip program, split --------------------------------------------

def _parent_program(func, nsteps, G, arrs, consts, les, q, grid, ids):
    """The one-chip program's body before the split, as it was."""
    num_slots, base, dt = consts[0], consts[1], consts[2]
    w0s, w0e, step = grid[0], grid[1], grid[2]
    rates = tst._eval_counter_fast(func, nsteps, arrs, num_slots, base, dt,
                                   w0s, w0e, step)
    member = ids[None, :] == jnp.arange(G, dtype=jnp.int32)[:, None]
    ok = ~jnp.isnan(rates)[:, None] & member[None, :, None, :]
    sums = jnp.sum(jnp.where(ok, rates[:, None], 0.0), axis=3,
                   dtype=jnp.float64)
    cnts = jnp.sum(ok, axis=3, dtype=jnp.int32)
    return tst._bucket_quantile(q, les, jnp.where(cnts > 0, sums, jnp.nan))


@pytest.mark.parametrize("q", QS)
@pytest.mark.parametrize("case", ["resets", "empty-group"])
def test_the_split_program_gives_the_parents_bits(case, q):
    _, gids, G, tiles = _tiles(case)
    steps = _steps()
    ids = tst.fused_group_ids(tiles, gids)
    got = np.asarray(tst.hist_quantile_groupsum(tiles, "rate", steps, W, ids,
                                                G, q))
    grid = np.array([int(steps[0]) - W, int(steps[0]), STEP], np.int64)
    want = np.asarray(jax.jit(
        lambda *a: _parent_program("rate", T, G, *a))(
            tst._tiles_arrays_hist(tiles), tiles.t_consts(), tiles.t_les(),
            tiles.t_q(q), grid, ids))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
