"""One host array a request: the four fused device programs (the Pallas
group sum over dense tiles, the grouped program over tiles with holes, the
histogram quantile, the mesh store's grouped pair) are handed the request's
grid and nothing else from the host once the selection's grouping has been
seen. What is constant for a tile cohort (num_slots, base_ms, dt_ms, the
bucket bounds and each quantile asked of it), a mesh placement (n_filled,
base_ms, dt_ms) or a grouping (the padded group ids) waits on the device, and every call of a cached
executable adds the buffers it made from host values to
``filodb_host_to_device_puts_total`` (four for one array on four devices).

Each case also computes its answer in the layout the programs had before,
every scalar and the ids handed over from the host, with the scalars taken
from the tiles and the grid here, and asks for the same bits: a constant
that is stale, or in the wrong place, is a wrong answer. The Pallas kernel
runs in interpret mode (``FUSED_GROUPSUM_INTERPRET``, tests/conftest.py), the
mesh on four of the virtual devices.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.obs import devprof
from filodb_tpu.parallel.mesh import make_mesh
from filodb_tpu.parallel.shardstore import ShardedTileEvaluator, ShardedTiles
from filodb_tpu.query import engine as eng
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query.model import RangeParams, RawSeries, clip_series
from filodb_tpu.query.tpu import TpuBackend

BASE, DT, W, STEP = 1_600_000_000_000, 10_000, 300_000, 60_000
S, N, G, T = 24, 200, 3, 12
LES = (.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, np.inf)
PATHS = ["pallas", "holes", "hist", "mesh"]


def _frozen(a):
    a = np.asarray(a)
    a.setflags(write=False)
    return a


def _counters(n=N, holes=False, seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(S):
        ts = (BASE + np.arange(n, dtype=np.int64) * DT
              + rng.integers(-2000, 2001, n))
        vals = 1e9 + np.cumsum(rng.uniform(0, 5, n))
        keep = np.ones(n, bool)
        if holes and i % 4 == 3:
            keep[rng.choice(np.arange(2, n - 2), 4, replace=False)] = False
        out.append(RawSeries({"g": str(i % G), "i": str(i)}, ts[keep],
                             vals[keep], is_counter=True))
    return out


def _histograms(n=N, seed=6):
    rng = np.random.default_rng(seed)
    les = np.asarray(LES)
    out = []
    for i in range(S):
        obs = rng.lognormal(np.log(0.02 * 2.0 ** (i % G)), 0.8, (n, 40))
        counts = np.cumsum((obs[..., None] <= les).sum(axis=1), axis=0
                           ).astype(np.float64)
        ts = (BASE + np.arange(n, dtype=np.int64) * DT
              + rng.integers(-2000, 2001, n))
        out.append(RawSeries({"g": str(i % G), "i": str(i)}, ts, counts,
                             is_counter=True, bucket_les=les))
    return out


def _steps(shift=0):
    return BASE + 600_000 + shift + np.arange(T, dtype=np.int64) * STEP


def _backend(path):
    if path != "mesh":
        return TpuBackend(batcher=None)
    mesh = make_mesh(n_shard_groups=4, time_parallel=1,
                     devices=jax.devices()[:4])
    return TpuBackend(batcher=None, mesh_eval=ShardedTileEvaluator(mesh))


def _request(be, path, series, gids, steps, q=0.9):
    if path == "hist":
        return be.fused_hist_quantile(series, "rate", steps, W, 0, gids, G, q)
    return be.fused_groupsum(series, "rate", steps, W, 0, gids, G)


@pytest.fixture
def calls(monkeypatch):
    """Every call of a cached executable: (executable, its arguments)."""
    seen = []
    real = devprof.ProfiledExecutable.__call__

    def spy(self, *args):
        seen.append((self, args))
        return real(self, *args)
    monkeypatch.setattr(devprof.ProfiledExecutable, "__call__", spy)
    return seen


def _parent(path, be, series, gids, steps, exe, args, q=0.9):
    """The answer in the parent's layout: the grid and the tiles' (or the
    placement's) scalars as host values, the ids as host int32, ``q`` as an
    f64. The Pallas program and the mesh's are the same programs handed the
    old arguments; over holes and histograms the program bodies are the
    parent's as they were (the grid int64[6] unpacked in place)."""
    entry, _ = be._tile_entry(series, eng.selection_facts(series))
    tiles = entry.tiles
    w0e = int(steps[0])
    grid6 = np.array([w0e - W, w0e, STEP, tiles.num_slots, tiles.base_ms,
                      tiles.dt_ms], np.int64)
    gvec = np.asarray(gids)[entry.idx].astype(np.int32)
    if path == "pallas":
        v_p, base, params, ids = args
        host = np.full(ids.shape, -1, np.int32)
        host[:S] = gvec
        sums, cnts = exe.fn(v_p, base, params, host)
        return np.asarray(sums)[:T], np.asarray(cnts)[:T]
    if path == "holes":
        def old(arrs, g6, ids):
            w0s, w0e, step, num_slots, base, dt = g6
            out = tst._eval_counter_fast("rate", T, arrs, num_slots, base, dt,
                                         w0s, w0e, step)
            ok = ~jnp.isnan(out)
            onehot = tst._group_onehot(ids, G)
            dot = functools.partial(jnp.dot,
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            return (dot(jnp.where(ok, out, jnp.float32(0.0)), onehot),
                    dot(ok.astype(jnp.float32), onehot))
        sums, cnts = jax.jit(old)(args[0], grid6, gvec)
        return np.asarray(sums)[:T], np.asarray(cnts)[:T]
    if path == "hist":
        def old(arrs, g6, ids, les, q):
            w0s, w0e, step, num_slots, base, dt = g6
            rates = tst._eval_counter_fast("rate", T, arrs, num_slots, base,
                                           dt, w0s, w0e, step)
            member = ids[None, :] == jnp.arange(G, dtype=jnp.int32)[:, None]
            ok = ~jnp.isnan(rates)[:, None] & member[None, :, None, :]
            sums = jnp.sum(jnp.where(ok, rates[:, None], 0.0), axis=3,
                           dtype=jnp.float64)
            cnts = jnp.sum(ok, axis=3, dtype=jnp.int32)
            return tst._bucket_quantile(q, les,
                                        jnp.where(cnts > 0, sums, jnp.nan))
        return np.asarray(jax.jit(old)(args[0], grid6, gvec,
                                       np.asarray(LES), np.float64(q)))
    st = be.mesh_eval.place(tiles)
    old = jax.jit(lambda tsr, vv, g, n, base, dt, w0s, w0e, step: exe.fn(
        tsr, vv, g, jnp.stack([n, base, dt]), jnp.stack([w0s, w0e, step])))
    sums, cnts = old(args[0], args[1], args[2], np.int64(st.n_filled),
                     np.int64(st.base_ms), np.int64(st.dt_ms),
                     np.int64(w0e - W), np.int64(w0e), np.int64(STEP))
    return np.asarray(sums)[:T], np.asarray(cnts)[:T]


def _same(got, want):
    for g, w in zip(got, want) if isinstance(got, tuple) else [(got, want)]:
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("path", PATHS)
def test_a_request_hands_over_one_host_array(calls, path):
    be = _backend(path)
    series = (_histograms() if path == "hist"
              else _counters(holes=path == "holes"))
    gids = _frozen(np.arange(S) % G)
    assert _request(be, path, series, gids, _steps()) is not None
    ndev = 4 if path == "mesh" else 1
    for shift in (STEP, 3 * STEP):
        del calls[:]
        before = devprof.put_counts.h2d_puts
        got = _request(be, path, series, gids, _steps(shift))
        assert devprof.put_counts.h2d_puts - before == ndev
        (exe, args), = calls
        host = [a for a in jax.tree_util.tree_leaves(args)
                if not isinstance(a, jax.Array)]
        assert len(host) == 1 and type(host[0]) is np.ndarray, host
        _same(got, _parent(path, be, series, gids, _steps(shift), exe,
                           args))
    assert (be.mesh_dispatches > 0) == (path == "mesh")
    if path == "holes":
        assert be.fused_holes_aggs == 3
    if path == "hist":
        assert be.fused_hist_aggs == 3


def _extend(tiles, k, seed=11):
    """``tiles`` and ``k`` more slots of every series."""
    rng = np.random.default_rng(seed)
    n0 = tiles.num_slots
    ts = (BASE + (n0 + np.arange(k, dtype=np.float64))[None, :] * DT
          + rng.integers(-2000, 2001, (S, k)))
    v = np.asarray(tiles.channel("v"))
    new_v = v[:, -1:] + np.cumsum(rng.uniform(0, 5, (S, k)), axis=1)
    return tst.AlignedTiles(list(tiles.keys), BASE, DT,
                            np.ones((S, n0 + k), bool),
                            np.concatenate([np.asarray(tiles.ts), ts], 1),
                            np.concatenate([v, new_v], 1))


@pytest.mark.parametrize("change", ["append_slots", "tile-rebuild"])
def test_resident_constants_follow_the_store(calls, change):
    """The grid of the last request reaches slots that only the grown store
    holds: with n_filled or num_slots of the old one the windows there
    would clip, and the sums would differ."""
    gids = _frozen(np.arange(S) % G)
    late = _steps(N * DT - 600_000 - T * STEP + 20 * DT)
    if change == "append_slots":
        mesh = make_mesh(n_shard_groups=4, time_parallel=1,
                         devices=jax.devices()[:4])
        ev = ShardedTileEvaluator(mesh)
        series = _counters()
        tiles, _ = tst.build_aligned_tiles(series)
        st = ev.place(tiles)
        st.eval_grouped_pair("rate", _steps(), W, gids, G)
        grown = _extend(tiles, 32)
        assert ev.refresh(tiles, grown) and ev.place(grown) is st
        assert np.asarray(st._consts).tolist() == [N + 32, BASE, DT]
        got = st.eval_grouped_pair("rate", late, W, gids, G)
        want = ShardedTiles(mesh, grown).eval_grouped_pair(
            "rate", late, W, gids, G)
    else:
        be = TpuBackend(batcher=None)
        assert _request(be, "holes", _counters(holes=True), gids,
                        _steps()) is not None
        longer = _counters(N + 32, holes=True)
        got = _request(be, "holes", longer, gids, late)
        entry, _ = be._tile_entry(longer, eng.selection_facts(longer))
        assert np.asarray(entry.tiles.t_consts()).tolist() \
            == [N + 32, BASE, DT]
        want = _request(TpuBackend(batcher=None), "holes", longer, gids, late)
    assert np.isfinite(got[0]).all() and (got[1] > 0).all()
    _same(got, want)


def _host_quantile(series, steps, q):
    """The host path's answer: per-series bucket rates, their sums by g,
    the quantile (numpy f64)."""
    first, last = int(steps[0]), int(steps[-1])
    rates = eng.periodic_samples(clip_series(series, first - W, last),
                                 RangeParams(first, STEP, last), "rate", W)
    return eng.histogram_quantile(eng._aggregate_hist_sum(rates, ("g",), ()),
                                  q).values.T


def test_two_quantiles_over_one_hist_tiles_are_one_executable(calls):
    be = TpuBackend(batcher=None)
    series = _histograms()
    gids = _frozen(np.arange(S) % G)
    steps = _steps(STEP)
    got = {0.5: _request(be, "hist", series, gids, steps, 0.5)}
    misses = be.executable_cache_stats()["misses"]
    del calls[:]
    got[0.99] = _request(be, "hist", series, gids, steps, 0.99)
    assert be.executable_cache_stats()["misses"] == misses
    assert len({id(exe) for exe, _ in calls}) == 1
    assert not np.array_equal(got[0.5], got[0.99])
    for q, answer in got.items():
        np.testing.assert_allclose(answer, _host_quantile(series, steps, q),
                                   rtol=1e-12, atol=0)
    exe, args = calls[0]
    _same(got[0.99], _parent("hist", be, series, gids, steps, exe, args,
                             q=0.99))
