"""Device-resident sharded tile serving (parallel/shardstore.py).

The multi-chip correctness pins: the sharded evaluator families are
BIT-FOR-BIT the single-device tilestore dispatch at every device count
(1/2/4/8 over the conftest virtual mesh), the grouped collective
matches the host oracle, and the donated cross-flush refresh serves
exactly what a from-scratch rebuild would."""

import numpy as np
import pytest

import jax

from filodb_tpu.parallel.mesh import make_mesh
from filodb_tpu.parallel.shardstore import (ShardedTileEvaluator,
                                            ShardedTiles, _append_step)
from filodb_tpu.query import tilestore as tst

BASE = 1_000_000_000_000
DT = 10_000
W = 300_000
STEP = 60_000


def _tiles(S=13, N=200, seed=3, jitter=2000, resets=False):
    rng = np.random.default_rng(seed)
    ts = (BASE + np.arange(N, dtype=np.float64)[None, :] * DT
          + rng.integers(-jitter, jitter + 1, (S, N)))
    incs = rng.uniform(0, 5, (S, N))
    vals = np.cumsum(incs, axis=1)
    if resets:
        # a mid-tile counter reset per series
        vals[:, N // 2:] = np.cumsum(incs[:, N // 2:], axis=1)
    return tst.AlignedTiles([{"i": str(i)} for i in range(S)], BASE, DT,
                            np.ones((S, N), bool), ts, vals)


def _steps(n=24, start=400_000):
    return BASE + start + np.arange(n, dtype=np.int64) * STEP


def _mesh(ndev, time_parallel=1):
    devs = jax.devices()[:ndev]
    return make_mesh(n_shard_groups=ndev // time_parallel,
                     time_parallel=time_parallel, devices=devs)


@pytest.mark.parametrize("ndev,tp", [(1, 1), (2, 1), (4, 2), (8, 2)])
@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_counter_parity_bitwise_across_device_counts(ndev, tp, func):
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(ndev, tp))
    st = ev.place(tiles)
    assert st is not None
    steps = _steps()
    ref = np.asarray(tst.evaluate_counters_t(tiles, func, steps, W))
    got = np.asarray(st.eval_counters(func, steps, W))
    assert got.dtype == ref.dtype
    assert np.array_equal(got, ref, equal_nan=True)


@pytest.mark.parametrize("ndev,tp", [(4, 1), (4, 2)])
def test_placed_store_is_sharded_not_replicated(ndev, tp):
    """A replicated store gives the same per-series answers as a sharded
    one, so parity cannot see it: pin the placement itself. The spec
    names the shard axis and every device holds S_pad / n_shard series
    of each resident channel."""
    mesh = _mesh(ndev, tp)
    st = ShardedTileEvaluator(mesh).place(_tiles(S=13))
    s_axis = mesh.axis_names[0]
    n_shard = mesh.shape[s_axis]
    for chan in (st._tsr, st._v, st._cv):
        assert chan.sharding.spec == jax.sharding.PartitionSpec(None, s_axis)
        assert len(chan.addressable_shards) == ndev
        for sh in chan.addressable_shards:
            assert sh.data.shape == (st.cap, st.S_pad // n_shard)


def test_counter_parity_instant_and_offset():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(8, 2))
    st = ev.place(tiles)
    one = _steps(1)                      # instant-query shape (T=1)
    ref = np.asarray(tst.evaluate_counters_t(tiles, "rate", one, W))
    got = np.asarray(st.eval_counters("rate", one, W))
    assert np.array_equal(got, ref, equal_nan=True)
    steps = _steps(16)
    ref = np.asarray(tst.evaluate_counters_t(tiles, "rate", steps, W,
                                             offset_ms=60_000))
    got = np.asarray(st.eval_counters("rate", steps, W,
                                      offset_ms=60_000))
    assert np.array_equal(got, ref, equal_nan=True)


def test_batch_parity_bitwise():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(4, 2))
    st = ev.place(tiles)
    steps = _steps()
    fam = tst.counters_batch_family(tiles, "rate", steps, W, 0)
    w0e = int(steps[0])
    w0s_l = [w0e - W + k * STEP for k in range(3)]
    w0e_l = [w0e + k * STEP for k in range(3)]
    ref = np.asarray(tst.evaluate_counters_t_batch(
        tiles, "rate", fam, steps.size, STEP, w0s_l, w0e_l))
    got = np.asarray(st.eval_counters_batch("rate", steps.size, STEP,
                                            w0s_l, w0e_l))
    assert np.array_equal(got[:3], ref[:3, :steps.size],
                          equal_nan=True)


@pytest.mark.parametrize("func", ["sum_over_time", "avg_over_time",
                                  "count_over_time", "last_over_time",
                                  "stddev_over_time"])
def test_aligned_family_parity_bitwise(func):
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(8, 2))
    st = ev.place(tiles)
    steps = _steps()
    ref = np.asarray(tst.evaluate_aligned(tiles, func, steps, W))
    got = np.asarray(st.eval_aligned(tiles, func, steps, W))
    assert np.array_equal(got, ref, equal_nan=True)


def test_aligned_batch_parity_bitwise():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(2, 1))
    st = ev.place(tiles)
    steps = _steps()
    w0e = int(steps[0])
    w0s_l = [w0e - W, w0e - W + STEP]
    w0e_l = [w0e, w0e + STEP]
    ref = np.asarray(tst.evaluate_aligned_batch(
        tiles, "sum_over_time", steps.size, STEP, w0s_l, w0e_l))
    got = np.asarray(st.eval_aligned_batch(tiles, "sum_over_time",
                                           steps.size, STEP, w0s_l,
                                           w0e_l))
    assert np.array_equal(got[:2], ref[:2], equal_nan=True)


def _host_grouped(ref, gids, G, agg):
    out = np.full((G, ref.shape[0]), np.nan)
    for g in range(G):
        rows = ref[:, gids == g]
        ok = ~np.isnan(rows)
        any_ok = ok.any(axis=1)
        if agg == "sum":
            v = np.where(ok, rows, 0.0).sum(axis=1)
        elif agg == "count":
            v = ok.sum(axis=1).astype(float)
        elif agg == "avg":
            v = np.where(ok, rows, 0.0).sum(axis=1) / ok.sum(axis=1)
        elif agg == "min":
            v = np.nanmin(np.where(ok, rows, np.nan), axis=1)
        else:
            v = np.nanmax(np.where(ok, rows, np.nan), axis=1)
        out[g] = np.where(any_ok, v, np.nan)
    return out


@pytest.mark.parametrize("agg", ["sum", "count", "avg", "min", "max"])
def test_grouped_collective_matches_host_oracle(agg):
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(8, 2))
    st = ev.place(tiles)
    steps = _steps()
    gids = np.arange(13) % 3
    ref = np.asarray(tst.evaluate_counters_t(tiles, "rate", steps, W)
                     ).astype(np.float64)
    want = _host_grouped(ref, gids, 3, agg)
    got = st.eval_grouped("rate", steps, W, gids, 3, agg)
    assert np.allclose(got, want, rtol=1e-5, equal_nan=True)


def test_grouped_pair_matches_fused_contract():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(4, 1))
    st = ev.place(tiles)
    steps = _steps()
    gids = np.arange(13) % 3
    sums, cnts = st.eval_grouped_pair("rate", steps, W, gids, 3)
    assert sums.shape == (steps.size, 3) and cnts.shape == sums.shape
    ref = np.asarray(tst.evaluate_counters_t(tiles, "rate", steps, W)
                     ).astype(np.float64)
    want = _host_grouped(ref, gids, 3, "sum")
    wantc = _host_grouped(ref, gids, 3, "count")
    assert np.allclose(sums.T[wantc > 0], want[wantc > 0], rtol=1e-5)
    assert np.array_equal(cnts.T, np.where(np.isnan(wantc), 0, wantc))


# ---------------------------------------------------------------------------
# eligibility gates
# ---------------------------------------------------------------------------

def test_non_dense_tiles_not_placed():
    S, N = 8, 64
    valid = np.ones((S, N), bool)
    valid[0, 5] = False
    ts = BASE + np.arange(N, dtype=np.float64)[None, :] * DT \
        + np.zeros((S, 1))
    tiles = tst.AlignedTiles([{"i": str(i)} for i in range(S)], BASE, DT,
                             valid, ts, np.ones((S, N)))
    assert not ShardedTiles.tiles_eligible(tiles)
    assert ShardedTileEvaluator(_mesh(2)).place(tiles) is None


def test_query_fits_rejects_wide_grid():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(2))
    st = ev.place(tiles)
    wide = np.array([BASE + 400_000, BASE + (1 << 32)], dtype=np.int64)
    assert not st.query_fits(wide, W, 0)
    assert st.query_fits(_steps(), W, 0)


# ---------------------------------------------------------------------------
# the donated refresh
# ---------------------------------------------------------------------------

def _extend(tiles, k, seed=11, reset_at=None):
    """A fresh AlignedTiles extending ``tiles`` by k appended slots."""
    rng = np.random.default_rng(seed)
    S = len(tiles.keys)
    N = tiles.num_slots
    ts_old = np.asarray(tiles.ts)
    v_old = np.asarray(tiles.channel("v"))
    new_ts = (BASE + (N + np.arange(k, dtype=np.float64))[None, :] * DT
              + rng.integers(-2000, 2001, (S, k)))
    incs = rng.uniform(0, 5, (S, k))
    new_v = v_old[:, -1:] + np.cumsum(incs, axis=1)
    if reset_at is not None:
        new_v[:, reset_at:] = np.cumsum(incs[:, reset_at:], axis=1)
    return tst.AlignedTiles(list(tiles.keys), BASE, DT,
                            np.ones((S, N + k), bool),
                            np.concatenate([ts_old, new_ts], axis=1),
                            np.concatenate([v_old, new_v], axis=1))


def test_donated_refresh_matches_fresh_rebuild_bitwise():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(4, 2))
    st = ev.place(tiles)
    tiles2 = _extend(tiles, 32)
    assert ev.refresh(tiles, tiles2)
    assert ev.snapshot()["donated_refreshes"] == 1
    st2 = ev.place(tiles2)          # the refreshed placement, reused
    assert st2 is st
    steps = _steps(30)
    ref = np.asarray(tst.evaluate_counters_t(tiles2, "rate", steps, W))
    got = np.asarray(st2.eval_counters("rate", steps, W))
    assert np.array_equal(got, ref, equal_nan=True)
    # the old placement key is gone: old tiles re-place from scratch
    assert id(tiles) not in ev._placed


def test_donated_refresh_with_counter_reset_in_appended_span():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(2, 1))
    st = ev.place(tiles)
    tiles2 = _extend(tiles, 24, reset_at=8)
    assert ev.refresh(tiles, tiles2)
    steps = _steps(28)
    ref = np.asarray(tst.evaluate_counters_t(tiles2, "rate", steps, W)
                     ).astype(np.float64)
    got = np.asarray(ev.place(tiles2).eval_counters(
        "rate", steps, W)).astype(np.float64)
    # the correction carry is mathematically identical; rounding order
    # of the cumsum may differ, so pin to tight tolerance here
    assert np.allclose(got, ref, rtol=1e-9, equal_nan=True)


def test_refresh_incompatible_falls_back():
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(2, 1))
    st = ev.place(tiles)
    assert st is not None
    # different series set: refuse
    other = _tiles(S=14, seed=9)
    assert not ev.refresh(tiles, other)
    # beyond capacity: refuse (capacity is the pow2 of the build size)
    big = _extend(tiles, st.cap)     # n_filled + k_pad > cap
    st2 = ev.place(tiles)
    assert st2 is None or not st2.append_slots(big)


def test_placement_dropped_when_tiles_die():
    ev = ShardedTileEvaluator(_mesh(2, 1))
    tiles = _tiles(S=5, N=64)
    st = ev.place(tiles)
    assert st is not None and len(ev._placed) == 1
    del tiles
    import gc
    gc.collect()
    assert len(ev._placed) == 0


def test_append_step_is_donated():
    """The zero-copy property itself: the donated input buffer is
    consumed by the append (reading it afterwards raises), and the
    output reuses its sharding."""
    mesh = _mesh(2, 1)
    from jax.sharding import NamedSharding, PartitionSpec as P
    col = NamedSharding(mesh, P(None, mesh.axis_names[0]))
    import jax.numpy as jnp
    tsr = jax.device_put(jnp.zeros((64, 8), jnp.int32), col)
    v = jax.device_put(jnp.ones((64, 8)), col)
    cv = jax.device_put(jnp.ones((64, 8)), col)
    new_tsr = jax.device_put(jnp.ones((8, 8), jnp.int32), col)
    new_v = jax.device_put(jnp.full((8, 8), 2.0), col)
    t2, v2, c2 = _append_step(tsr, v, cv, new_tsr, new_v, np.int64(32))
    assert v2.sharding == col
    with pytest.raises(RuntimeError):
        _ = np.asarray(v)           # donated: buffer deleted


# ---------------------------------------------------------------------------
# backend integration: mesh-shaped batches + dispatch routing
# ---------------------------------------------------------------------------

def test_backend_routes_counters_through_mesh_and_matches():
    from filodb_tpu.query.model import RangeParams, RawSeries
    from filodb_tpu.query.tpu import TpuBackend

    rng = np.random.default_rng(0)
    series = []
    for i in range(9):
        ts = BASE + np.arange(128, dtype=np.int64) * DT
        series.append(RawSeries({"i": str(i)}, ts,
                                np.cumsum(rng.uniform(0, 5, 128)),
                                is_counter=True))
    params = RangeParams(BASE + 400_000, STEP, BASE + 400_000 + 23 * STEP)
    plain = TpuBackend(batcher=None)
    ref = plain.periodic_samples(series, params, "rate", W)
    meshed = TpuBackend(batcher=None,
                        mesh_eval=ShardedTileEvaluator(_mesh(8, 2)))
    got = meshed.periodic_samples(series, params, "rate", W)
    assert meshed.mesh_dispatches >= 1
    assert np.array_equal(got.values, ref.values, equal_nan=True)


def test_backend_mesh_batch_run_parity():
    """The mesh-shaped micro-batch: _aligned_run with 3 members through
    the sharded batch evaluator splits back bit-for-bit the members'
    single dispatches."""
    from filodb_tpu.query.tpu import TpuBackend

    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(4, 2))
    st = ev.place(tiles)
    be = TpuBackend(batcher=None, mesh_eval=ev)
    steps = _steps()
    fam = tst.counters_batch_family(tiles, "rate", steps, W, 0)
    members = []
    for k in range(3):
        s = steps + k * STEP
        members.append((int(s[0]) - W, int(s[0]), s, tiles, ()))
    res = be._aligned_run(tiles, "rate", fam, steps.size, STEP, W, 0,
                          st, members)
    for k in range(3):
        want = np.asarray(tst.evaluate_counters_t(
            tiles, "rate", steps + k * STEP, W)).T
        assert np.array_equal(res.get(k), want, equal_nan=True)


@pytest.mark.parametrize("gids_are", ["frozen", "writable"])
def test_frozen_group_ids_are_put_on_the_devices_once(gids_are,
                                                      monkeypatch):
    """A read-only tile-order vector names its device copy by identity (the
    backend's tile entry hands one out per grouping); any other array is
    padded and put every time, as before. Same program, same answer."""
    from filodb_tpu.parallel import shardstore
    from filodb_tpu.query.model import MAX_GROUPINGS
    tiles = _tiles()
    ev = ShardedTileEvaluator(_mesh(4, 1))
    st = ev.place(tiles)
    gids = np.arange(13) % 3
    want = st.eval_grouped_pair("rate", _steps(), W, gids.copy(), 3)
    if gids_are == "frozen":
        gids.setflags(write=False)
    puts = []
    real = jax.device_put

    def counting(x, *args, **kw):
        puts.append(getattr(x, "shape", None))
        return real(x, *args, **kw)
    monkeypatch.setattr(shardstore.jax, "device_put", counting)
    frozen = gids_are == "frozen"
    for k in range(3):
        got = st.eval_grouped_pair("rate", _steps(), W, gids, 3)
        assert np.array_equal(got[0], want[0]) \
            and np.array_equal(got[1], want[1])
        assert puts == [(st.S_pad,)] * (1 if frozen else k + 1)
    assert len(st._gids.kept) == int(frozen)
    # the other grouped program takes the same copy
    st.eval_grouped("rate", _steps(), W, gids, 3, "sum")
    assert len(puts) == (1 if frozen else 4)
    # bounded: a ninth grouping does not grow it
    for i in range(3 * MAX_GROUPINGS):
        other = (np.arange(13) + i) % 3
        other.setflags(write=False)
        st.dispatch_grouped_pair("rate", _steps(), W, other, 3)
        assert len(st._gids.kept) <= MAX_GROUPINGS
    # the donated refresh brings tiles, and so tile-order vectors, of its
    # own: the copies go, and the same vector is put again
    del puts[:]
    assert ev.refresh(tiles, _extend(tiles, 32))
    assert st._gids.kept == {}
    st.dispatch_grouped_pair("rate", _steps(), W, gids, 3)
    assert puts.count((st.S_pad,)) == 1 \
        and len(st._gids.kept) == int(frozen)


def test_fused_groupsum_rides_resident_collective():
    from filodb_tpu.query.model import RawSeries
    from filodb_tpu.query.tpu import TpuBackend

    rng = np.random.default_rng(1)
    series = []
    for i in range(8):
        ts = BASE + np.arange(128, dtype=np.int64) * DT
        series.append(RawSeries({"i": str(i)}, ts,
                                np.cumsum(rng.uniform(0, 5, 128)),
                                is_counter=True))
    be = TpuBackend(batcher=None,
                    mesh_eval=ShardedTileEvaluator(_mesh(4, 1)))
    steps = _steps(16)
    gids = np.arange(8) % 2
    res = be.fused_groupsum(series, "rate", steps, W, 0, gids, 2)
    assert res is not None
    sums, cnts = res
    assert sums.shape == (16, 2) and (cnts > 0).any()
    assert be.fused_aggs == 1 and be.mesh_dispatches == 1
