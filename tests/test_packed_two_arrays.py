"""Two host arrays a packed launch: the general (packed) path hands the
device one int64 block (each row's timestamps, then its sample count, first
window start, first window end and step) and one f64 block (each row's
values, then the batch's scalar), for a batch of one member as for many,
and the jitted entry slices them on the device into the arguments of the
unchanged ``_window_gather`` / ``_window_endpoint`` bodies.

Every case computes its answer in the layout the launch had before as
well (``_separate_args_run``: timestamps, values and counts as three host
arrays, the grid as three scalars for a lone member or three uploaded
vectors for a batch, the scalar a Python float) and asks for the same
bits, and counts ``filodb_packed_host_arrays_total``: two a launch.
"""

import json
import threading
import urllib.parse
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from filodb_tpu.obs import devprof
from filodb_tpu.query import tpu
from filodb_tpu.query.batcher import MicroBatcher
from filodb_tpu.query.model import RangeParams, RawSeries, clip_series
from filodb_tpu.query.tpu import TpuBackend

BASE = 1_600_000_000_000
W = 300_000
GATHER = ["max_over_time", "min_over_time"]
ENDPOINT = ["rate", "avg_over_time"]


def _raw(S, n, seed, counter=False):
    """``S`` irregular series of ``n`` samples (8-12 s apart), a few
    NaN stale markers among them."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(S):
        ts = BASE + np.cumsum(rng.integers(8_000, 12_001, n)).astype(
            np.int64)
        vals = (np.cumsum(rng.uniform(0, 4, n)) if counter
                else rng.uniform(0, 100, n))
        vals[rng.integers(0, n, 2)] = np.nan
        out.append(RawSeries({"i": str(i)}, ts, vals, is_counter=counter))
    return out


def _member(func, S, n, seed, shift_steps, step_ms, nsteps, n_pad=512):
    """One packed member as ``TpuBackend._general`` makes it, over ``n``
    samples padded to ``n_pad`` columns (members of one batch share N)."""
    series = _raw(S, n, seed, counter=func == "rate")
    w0e = BASE + 600_000 + shift_steps * 60_000
    w0s = w0e - W
    ts, vals, lens = tpu.pack_series(series)
    assert ts.shape[1] <= n_pad
    pad = n_pad - ts.shape[1]
    ts = np.pad(ts, ((0, 0), (0, pad)), constant_values=tpu._TS_PAD)
    vals = np.pad(vals, ((0, 0), (0, pad)))
    w_bound = TpuBackend._window_sample_bound(series, W, n_pad) \
        if func in tpu._GATHER_FUNCS else 0
    return tpu._PackedMember(ts, vals, lens, w0s, w0e, step_ms, nsteps,
                             w_bound)


def _separate_args_run(func, t_bucket, scalar, members):
    """The launch as it was before the two blocks, on the same kernel
    bodies: -> each member's [S, nsteps] answer."""
    if len(members) == 1:
        m = members[0]
        S, N = m.ts.shape
        s_bucket = tpu._next_pow2(S, 8)
        ts = np.full((s_bucket, N), tpu._TS_PAD, dtype=np.int64)
        vals = np.zeros((s_bucket, N), dtype=np.float64)
        lens = np.zeros(s_bucket, dtype=np.int32)
        ts[:S], vals[:S], lens[:S] = m.ts, m.vals, m.lens
        grid = (np.int64(m.w0s), np.int64(m.w0e), np.int64(m.step))
    else:
        offs = np.cumsum([0] + [m.ts.shape[0] for m in members])
        s_bucket = tpu._next_pow2(int(offs[-1]), 8)
        N = members[0].ts.shape[1]
        ts = np.full((s_bucket, N), tpu._TS_PAD, dtype=np.int64)
        vals = np.zeros((s_bucket, N), dtype=np.float64)
        lens = np.zeros(s_bucket, dtype=np.int32)
        w0s = np.zeros(s_bucket, dtype=np.int64)
        w0e = np.ones(s_bucket, dtype=np.int64)
        step = np.ones(s_bucket, dtype=np.int64)
        for m, o in zip(members, offs):
            sl = slice(int(o), int(o) + m.ts.shape[0])
            ts[sl], vals[sl], lens[sl] = m.ts, m.vals, m.lens
            w0s[sl], w0e[sl], step[sl] = m.w0s, m.w0e, m.step
        grid = (jnp.asarray(w0s), jnp.asarray(w0e), jnp.asarray(step))
    if func in tpu._GATHER_FUNCS:
        w_bound = max(m.w_bound for m in members)
        out = tpu._window_gather(func, w_bound, ts, vals, lens, *grid,
                                 t_bucket, scalar)
    else:
        out = tpu._window_endpoint(func, ts, vals, lens, *grid, t_bucket,
                                   scalar)
    host = np.asarray(out)
    answers, o = [], 0
    for m in members:
        answers.append(host[o:o + m.ts.shape[0], :m.nsteps])
        o += m.ts.shape[0]
    return answers


def _launch_answers(backend, func, t_bucket, scalar, members):
    """``_packed_run_inner`` -> (each member's answer, host arrays it
    handed the device)."""
    before = devprof.put_counts.packed_arrays
    res = backend._packed_run_inner(func, t_bucket, scalar, members)
    got = [res.get(i) for i in range(len(members))]
    return got, devprof.put_counts.packed_arrays - before


def _same_bits(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == np.float64
        assert g.tobytes() == w.tobytes()


# members: (series, samples, seed, shift in steps, step ms, steps); every
# member's N is 512, their fill 150-500 samples, their grids differ
BATCHES = {
    "batch-of-1": [(1, 390, 1, 0, 60_000, 13)],
    "batch-of-1-no-pad": [(8, 500, 2, 3, 300_000, 16)],
    "batch-of-2": [(1, 390, 3, 0, 300_000, 13), (8, 260, 4, 5, 60_000, 9)],
    "batch-of-5": [(1, 390, 5, 0, 300_000, 13), (8, 390, 6, 2, 300_000, 13),
                   (3, 150, 7, 1, 30_000, 16), (1, 500, 8, 7, 60_000, 1),
                   (4, 300, 9, 4, 120_000, 11)],
}


@pytest.mark.parametrize("func", GATHER + ENDPOINT)
@pytest.mark.parametrize("batch", list(BATCHES))
def test_launch_equals_separate_args_bit_for_bit(func, batch):
    members = [_member(func, *spec) for spec in BATCHES[batch]]
    backend = TpuBackend(batcher=None)
    got, arrays = _launch_answers(backend, func, 16, 0.0, members)
    _same_bits(got, _separate_args_run(func, 16, 0.0, members))
    assert arrays == 2


@pytest.mark.parametrize("scalars", [(0.3, 0.9), (-0.5, 1.5)],
                         ids=["inside", "outside"])
def test_quantile_scalar_rides_the_f64_block(scalars):
    """Two batches of ``quantile_over_time``, each with its own scalar
    (the batch key holds it): the f64 block's last column carries it."""
    backend = TpuBackend(batcher=None)
    specs = BATCHES["batch-of-2"]
    members = [_member("quantile_over_time", *spec) for spec in specs]
    outs = []
    for q in scalars:
        got, arrays = _launch_answers(backend, "quantile_over_time", 16, q,
                                      members)
        _same_bits(got, _separate_args_run("quantile_over_time", 16, q,
                                           members))
        assert arrays == 2
        outs.append(got)
    assert outs[0][0].tobytes() != outs[1][0].tobytes()


def test_blocks_layout_and_padding_rows():
    """Rows in member order, then pad rows: ``_TS_PAD`` timestamps, no
    samples, a 1 ms grid, zero values; the scalar in every row."""
    members = [_member("max_over_time", *spec)
               for spec in BATCHES["batch-of-2"]]
    ib, fb, offs = tpu._launch_blocks(members, 0.75)
    assert (ib.dtype, ib.shape) == (np.int64, (16, 512 + 4))
    assert (fb.dtype, fb.shape) == (np.float64, (16, 512 + 1))
    assert offs == [0, 1, 9]
    for m, o, e in zip(members, offs, offs[1:]):
        np.testing.assert_array_equal(ib[o:e, :512], m.ts)
        np.testing.assert_array_equal(ib[o:e, 512], m.lens)
        assert (ib[o:e, 513:] == (m.w0s, m.w0e, m.step)).all()
        np.testing.assert_array_equal(fb[o:e, :512], m.vals)
    assert (ib[9:, :512] == tpu._TS_PAD).all()
    assert (ib[9:, 512:] == (0, 0, 1, 1)).all()
    assert (fb[9:, :512] == 0).all()
    assert (fb[:, 512] == 0.75).all()


@pytest.mark.parametrize("func", GATHER + ENDPOINT)
def test_one_executable_for_a_lone_member_and_a_batch(func):
    """A batch of one and a batch of two that pad to the same series
    bucket take ONE executable: one first sight, then hits."""
    backend = TpuBackend(batcher=None)
    lone = [_member(func, 3, 390, 11, 0, 60_000, 13)]
    pair = [_member(func, 2, 390, 12, 1, 60_000, 13),
            _member(func, 4, 390, 13, 2, 60_000, 13)]
    for m in pair:
        m.w_bound = lone[0].w_bound     # one window bound, one program
    for members in (lone, pair, lone, pair):
        backend._packed_run_inner(func, 16, 0.0, members).get(0)
    assert backend.exec_cache_misses == 1
    assert backend.exec_cache_hits == 3
    (key,) = backend._exec_keys
    assert key == ("packed", func, 8, 512, 16, lone[0].w_bound)


def _params(k, nsteps=13, step=300_000):
    start = BASE + 900_000 + k * 60_000
    return RangeParams(start, step, start + (nsteps - 1) * step)


def _separate_args_query(series, func, params, func_args=()):
    """What the backend's general path answered with the launch it had
    before: the same clip, pack and bound, the separate arguments."""
    steps = params.steps
    w0e = int(steps[0])
    series = clip_series(series, w0e - W, int(steps[-1]))
    ts, vals, lens = tpu.pack_series(series)
    w_bound = TpuBackend._window_sample_bound(series, W, ts.shape[1]) \
        if func in tpu._GATHER_FUNCS else 0
    m = tpu._PackedMember(ts, vals, lens, w0e - W, w0e, params.step_ms,
                          steps.size, w_bound)
    scalar = float(func_args[0]) if func_args else 0.0
    return _separate_args_run(func, tpu._next_pow2(steps.size, 8), scalar,
                              [m])[0]


@pytest.mark.parametrize("func,func_args", [
    ("max_over_time", ()), ("min_over_time", ()), ("rate", ()),
    ("avg_over_time", ()), ("quantile_over_time", (0.9,))])
def test_public_lone_query(func, func_args):
    """``periodic_samples`` with no batcher: one launch, two arrays, the
    separate-argument launch's bits."""
    series = _raw(5, 420, 21, counter=func == "rate")
    backend = TpuBackend(batcher=None)
    before = devprof.put_counts.packed_arrays
    got = backend.periodic_samples(series, _params(0), func, W, func_args)
    assert devprof.put_counts.packed_arrays - before == 2
    want = _separate_args_query(series, func, _params(0), func_args)
    assert got.values.tobytes() == want.tobytes()


@pytest.mark.parametrize("func", ["max_over_time", "rate"])
def test_public_batched_queries(func):
    """Eight concurrent queries through the executor-queued batcher: two
    host arrays for every batch it dispatched, each answer the
    separate-argument launch's bits."""
    series = _raw(8, 420, 31, counter=func == "rate")
    backend = TpuBackend(batcher=MicroBatcher(use_executor=True,
                                              max_batch=8))
    barrier = threading.Barrier(8)
    outs = {}

    def worker(k):
        barrier.wait()
        outs[k] = backend.periodic_samples(series, _params(k), func,
                                           W).values
    before = devprof.put_counts.packed_arrays
    ths = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    batches = backend.batcher.stats.snapshot()["batches"]
    assert devprof.put_counts.packed_arrays - before == 2 * batches
    for k in range(8):
        want = _separate_args_query(series, func, _params(k))
        assert outs[k].tobytes() == want.tobytes(), k


def test_metrics_exposes_the_counter():
    """``/metrics`` carries ``filodb_packed_host_arrays_total`` beside
    ``filodb_host_to_device_puts_total``: two for every packed launch."""
    from filodb_tpu.standalone.server import FiloServer
    t0 = 1_600_000_000
    srv = FiloServer({"num-shards": 2, "port": 0}).start()
    try:
        srv.seed_dev_data(n_samples=360, n_instances=4, start_ms=t0 * 1000)

        def metrics():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
                lines = r.read().decode().splitlines()
            return {ln.split(" ")[0]: float(ln.rsplit(" ", 1)[1])
                    for ln in lines if ln and not ln.startswith("#")}
        m0 = metrics()
        qs = urllib.parse.urlencode(dict(
            query="min_over_time(http_requests_total[3m])", start=t0 + 300,
            end=t0 + 900, step=67, cache="false"))
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
            f"query_range?{qs}", timeout=120).read())
        assert body["status"] == "success" and body["data"]["result"]
        m1 = metrics()
    finally:
        srv.stop()
    names = list(m1)
    assert names.index("filodb_packed_host_arrays_total") \
        == names.index("filodb_host_to_device_puts_total") + 1
    launches = m1["filodb_batcher_batches_total"] \
        - m0["filodb_batcher_batches_total"]
    assert launches >= 1
    assert m1["filodb_packed_host_arrays_total"] \
        - m0["filodb_packed_host_arrays_total"] == 2 * launches
