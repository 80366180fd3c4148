"""One array down: each fused counter program (the grouped program over
dense tiles and over tiles with holes, the mesh store's grouped pair)
returns its sums and counts stacked in ONE device array,
[2, T, G] in the dtype it had (f32 on one chip, f64 on the mesh), and the
backend's ``device-sync`` pulls that one buffer: a request adds 1 to
``filodb_device_to_host_arrays_total`` and the bytes of two [T, G] grids to
``filodb_device_to_host_bytes_total``, as before. The histogram paths
already synced one array a request and still do.

The (sums, cnts) the backend returns are the halves of the program's output
to the bit, and the bits of the programs with two outputs (two dots; a
psum each on the mesh). Through the engine the answers are what the
existing parity tests assert, within their tolerances. The one-device
program runs on the CPU (``FUSED_GROUPSUM_INTERPRET``, tests/conftest.py),
the mesh on four of the virtual devices.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.obs import devprof
from filodb_tpu.parallel.mesh import make_mesh
from filodb_tpu.parallel.shardstore import ShardedTileEvaluator
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query.batcher import transfer_counts
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.model import RawSeries
from filodb_tpu.query.tpu import TpuBackend

BASE, DT, W, STEP = 1_600_000_000_000, 10_000, 300_000, 60_000
S, N, G, T = 24, 200, 3, 12
LES = (.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, np.inf)
COUNTER_PATHS = ["dense", "holes", "mesh"]


def _frozen(a):
    a = np.asarray(a)
    a.setflags(write=False)
    return a


def _counters(holes=False, seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(S):
        ts = (BASE + np.arange(N, dtype=np.int64) * DT
              + rng.integers(-2000, 2001, N))
        vals = 1e9 + np.cumsum(rng.uniform(0, 5, N))
        keep = np.ones(N, bool)
        if holes and i % 4 == 3:
            keep[rng.choice(np.arange(2, N - 2), 4, replace=False)] = False
        out.append(RawSeries({"g": str(i % G), "i": str(i)}, ts[keep],
                             vals[keep], is_counter=True))
    return out


def _histograms(seed=6):
    rng = np.random.default_rng(seed)
    les = np.asarray(LES)
    out = []
    for i in range(S):
        obs = rng.lognormal(np.log(0.02 * 2.0 ** (i % G)), 0.8, (N, 40))
        counts = np.cumsum((obs[..., None] <= les).sum(axis=1), axis=0
                           ).astype(np.float64)
        ts = (BASE + np.arange(N, dtype=np.int64) * DT
              + rng.integers(-2000, 2001, N))
        out.append(RawSeries({"g": str(i % G), "i": str(i)}, ts, counts,
                             is_counter=True, bucket_les=les))
    return out


def _steps(shift=0):
    return BASE + 600_000 + shift + np.arange(T, dtype=np.int64) * STEP


def _mesh():
    return make_mesh(n_shard_groups=4, time_parallel=1,
                     devices=jax.devices()[:4])


def _backend(mesh):
    if not mesh:
        return TpuBackend(batcher=None)
    return TpuBackend(batcher=None, mesh_eval=ShardedTileEvaluator(_mesh()))


@pytest.fixture
def calls(monkeypatch):
    """Every call of a cached executable: (executable, arguments, the
    device output it returned)."""
    seen = []
    real = devprof.ProfiledExecutable.__call__

    def spy(self, *args):
        out = real(self, *args)
        seen.append((self, args, out))
        return out
    monkeypatch.setattr(devprof.ProfiledExecutable, "__call__", spy)
    return seen


def _two_outputs(path, args):
    """The program's answer as it was with two outputs: on one chip the two
    dots as two results, on the mesh a psum each, from the same
    arguments."""
    if path in ("dense", "holes"):
        def old(arrs, consts, grid, ids):
            out = tst._eval_counter_fast("rate", T, arrs, consts[0],
                                         consts[1], consts[2], grid[0],
                                         grid[1], grid[2])
            ok = ~jnp.isnan(out)
            onehot = tst._group_onehot(ids, G)
            dot = functools.partial(jnp.dot,
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            return (dot(jnp.where(ok, out, jnp.float32(0.0)), onehot),
                    dot(ok.astype(jnp.float32), onehot))
        sums, cnts = jax.jit(old)(*args)
        return np.asarray(sums), np.asarray(cnts)
    mesh = _mesh()
    s_axis, t_axis = mesh.axis_names[:2]

    def body(tsr, vv, gids, consts, grid):
        n, base, dt = consts[0], consts[1], consts[2]
        local = tst._eval_counter_fast("rate", T, {"tsr": tsr, "ff_v": vv},
                                       n, base, dt, grid[0], grid[1],
                                       grid[2])
        member = gids[None, :] == jnp.arange(G)[:, None]
        ok = ~jnp.isnan(local)[:, None, :] & member[None]
        sums = jnp.sum(jnp.where(ok, local[:, None, :], 0.0), axis=2,
                       dtype=jnp.float64)
        cnts = jnp.sum(ok, axis=2, dtype=jnp.int32).astype(jnp.float64)
        return jax.lax.psum(sums, s_axis), jax.lax.psum(cnts, s_axis)
    old = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, s_axis), P(None, s_axis), P(s_axis), P(), P()),
        out_specs=(P(t_axis, None), P(t_axis, None))))
    sums, cnts = old(*args)
    return np.asarray(sums)[:T], np.asarray(cnts)[:T]


@pytest.mark.parametrize("path", COUNTER_PATHS)
def test_a_fused_request_syncs_one_array(calls, path):
    be = _backend(path == "mesh")
    series = _counters(holes=path == "holes")
    gids = _frozen(np.arange(S) % G)
    assert be.fused_groupsum(series, "rate", _steps(), W, 0, gids,
                             G) is not None
    itemsize = 8 if path == "mesh" else 4
    for shift in (STEP, 3 * STEP):
        del calls[:]
        arrays, nbytes = transfer_counts.d2h_arrays, transfer_counts.d2h_bytes
        sums, cnts = be.fused_groupsum(series, "rate", _steps(shift), W, 0,
                                       gids, G)
        assert transfer_counts.d2h_arrays - arrays == 1
        # the bytes two [T, G] grids were
        assert transfer_counts.d2h_bytes - nbytes == 2 * T * G * itemsize
        (_, args, out), = calls
        assert isinstance(out, jax.Array) and out.shape == (2, T, G)
        out = np.asarray(out)
        assert out.dtype == np.dtype(np.float64 if path == "mesh"
                                     else np.float32)
        # two views of one host buffer, the halves of the program's output
        assert sums.base is not None and sums.base is cnts.base
        np.testing.assert_array_equal(sums, out[0])
        np.testing.assert_array_equal(cnts, out[1])
        assert (cnts > 0).any()
        want_s, want_c = _two_outputs(path, args)
        assert sums.dtype == want_s.dtype
        np.testing.assert_array_equal(sums, want_s)
        np.testing.assert_array_equal(cnts, want_c)
    assert (be.mesh_dispatches > 0) == (path == "mesh")
    assert be.fused_holes_aggs == (3 if path == "holes" else 0)


@pytest.mark.parametrize("mesh", [False, True], ids=["one-chip", "mesh"])
def test_a_histogram_request_still_syncs_one_array(mesh):
    be = _backend(mesh)
    series = _histograms()
    gids = _frozen(np.arange(S) % G)
    assert be.fused_hist_quantile(series, "rate", _steps(), W, 0, gids, G,
                                  0.9) is not None
    arrays = transfer_counts.d2h_arrays
    for shift in (STEP, 3 * STEP):
        assert be.fused_hist_quantile(series, "rate", _steps(shift), W, 0,
                                      gids, G, 0.9) is not None
    assert transfer_counts.d2h_arrays - arrays == 2
    assert (be.mesh_dispatches > 0) == mesh
    assert be.fused_hist_aggs == 3


def test_the_mesh_store_eval_grouped_pair_keeps_its_contract():
    """``eval_grouped_pair`` (the test-facing wrapper) still hands back
    (sums, cnts) [T, G] f64, the halves of ``dispatch_grouped_pair``'s
    one buffer."""
    tiles, _ = tst.build_aligned_tiles(_counters())
    st = ShardedTileEvaluator(_mesh()).place(tiles)
    gids = np.arange(S) % G
    sums, cnts = st.eval_grouped_pair("rate", _steps(), W, gids, G)
    out = np.asarray(st.dispatch_grouped_pair("rate", _steps(), W, gids, G))
    assert out.shape[0] == 2 and sums.shape == cnts.shape == (T, G)
    assert sums.dtype == cnts.dtype == np.float64
    np.testing.assert_array_equal(sums, out[0, :T])
    np.testing.assert_array_equal(cnts, out[1, :T])


# -- through the engine ------------------------------------------------------

REF = DatasetRef("timeseries")
T0 = 1_600_000_000


def _shard(holes):
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0)
    rng = np.random.default_rng(9)
    b = RecordBuilder(DEFAULT_SCHEMAS)
    for s in range(16):
        labels = {"_metric_": "reqs_total", "_ws_": "demo", "_ns_": "App-0",
                  "job": f"job-{s % 4}", "instance": f"i{s}"}
        v = 0.0
        for t in range(360):
            v += rng.uniform(0, 5)
            if holes and s % 4 == 3 and t in (100, 101, 200):
                continue
            b.add_sample("prom-counter", labels, (T0 + t * 10) * 1000, v)
    for c in b.containers():
        shard.ingest(c)
    shard.flush_all()
    return shard


@pytest.mark.parametrize("path", COUNTER_PATHS)
@pytest.mark.parametrize("op", ["sum", "avg", "count"])
def test_engine_answers_hold_their_parity(path, op):
    shard = _shard(holes=path == "holes")
    be = _backend(path == "mesh")
    q = f"{op}(rate(reqs_total[5m])) by (job)"
    tsp = TimeStepParams(T0 + 600, 60, T0 + 3000)
    arrays = transfer_counts.d2h_arrays
    got = QueryEngine([shard], backend=be).execute(parse_query_range(q, tsp))
    want = QueryEngine([shard], backend=None).execute(
        parse_query_range(q, tsp))
    assert be.fused_aggs == 1 and transfer_counts.d2h_arrays - arrays == 1
    assert be.fused_holes_aggs == (path == "holes")
    assert be.mesh_dispatches == (path == "mesh")
    rows = {tuple(sorted(k.items())): got.values[i]
            for i, k in enumerate(got.keys)}
    assert len(rows) == len(want.keys) == 4
    for i, k in enumerate(want.keys):
        np.testing.assert_allclose(rows[tuple(sorted(k.items()))],
                                   want.values[i], rtol=1e-5,
                                   equal_nan=True)
