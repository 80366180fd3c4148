"""Micro-batcher parity + mechanics (serving fast path, PR 3).

The acceptance contract: batched and unbatched execution must be
bit-for-bit on the same inputs, on CPU. Covers the aligned
tilestore families (slide/fast counters + the general evaluator), the
packed general path (series-axis stacking with per-row window
vectors), the executor-queued TPU-style path and the CPU inline path,
failure propagation, and the occupancy counters /metrics reads."""

import threading

import numpy as np
import pytest

from filodb_tpu.query.batcher import (DeviceExecutor, MicroBatcher,
                                      SplitResult)
from filodb_tpu.query.model import RangeParams, RawSeries
from filodb_tpu.query.tpu import TpuBackend

BASE = 1_600_000_000_000


def _series(n=300, S=5, regular=True, counter=True, seed=0,
            snap=True):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(S):
        if regular:
            ts = BASE + np.arange(n, dtype=np.int64) * 10_000
        else:
            ts = BASE + np.cumsum(
                rng.integers(8_000, 12_000, n)).astype(np.int64)
        vals = np.cumsum(rng.random(n) * 4).astype(np.float64)
        out.append(RawSeries(
            {"i": str(s)}, ts, vals, is_counter=counter,
            snapshot_key=("ds", 0, s, 7, 0) if snap else None,
            chunk_len=n if snap else -1))
    return out


def _params(k, nsteps=16, step=60_000):
    start = BASE + 600_000 + k * step
    return RangeParams(start, step, start + (nsteps - 1) * step)


def _run_concurrent(backend, series, func, window_ms, n=8, nsteps=16):
    """Fire n same-shape queries concurrently through the backend;
    returns {k: values}."""
    outs = {}
    lock = threading.Lock()
    barrier = threading.Barrier(n)

    def worker(k):
        barrier.wait()
        g = backend.periodic_samples(series, _params(k, nsteps=nsteps),
                                     func, window_ms)
        with lock:
            outs[k] = g.values
    ths = [threading.Thread(target=worker, args=(k,)) for k in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    return outs


@pytest.mark.parametrize("use_executor", [False, True],
                         ids=["cpu-inline", "executor-queued"])
@pytest.mark.parametrize("func,regular,window_ms", [
    ("rate", True, 300_000),           # aligned slide/fast family
    ("avg_over_time", True, 600_000),  # aligned general evaluator
    ("rate", False, 300_000),          # packed endpoint family
    ("max_over_time", False, 300_000),  # packed gather family
    ("sum_over_time", False, 300_000),  # packed prefix-sum family
])
def test_batched_equals_unbatched_bit_for_bit(func, regular, window_ms,
                                              use_executor):
    series = _series(regular=regular)
    # references: batcher disabled -> single-query kernel paths only
    ref_backend = TpuBackend(batcher=MicroBatcher(enabled=False))
    refs = {k: ref_backend.periodic_samples(
        series, _params(k), func, window_ms).values for k in range(8)}
    backend = TpuBackend(batcher=MicroBatcher(
        use_executor=use_executor, max_batch=8))
    for _ in range(3):      # repeat: batch composition varies per run
        outs = _run_concurrent(backend, series, func, window_ms)
        for k in range(8):
            assert np.array_equal(outs[k], refs[k], equal_nan=True), \
                (func, regular, use_executor, k)
    snap = backend.batcher.stats.snapshot()
    assert snap["queries"] >= 24
    assert snap["occupancy_max"] >= 1


@pytest.mark.parametrize("func,regular,func_args", [
    ("rate", True, ()),                 # aligned counter family
    ("avg_over_time", True, ()),        # aligned general evaluator
    ("avg_over_time", True, (0.5,)),    # aligned, never joins a batch
    ("rate", False, ()),                # packed endpoint family
    ("max_over_time", False, ()),       # packed gather family
], ids=["aligned-counter", "aligned-gauge", "aligned-func-args",
        "packed-rate", "packed-gather"])
def test_lone_paths_are_one_path(func, regular, func_args):
    """No batcher, a disabled batcher and an enabled batcher with one
    client run the same body: a batch of one. Equal bytes, one
    ``device-dispatch``, and the same ``device-sync`` spans (a packed
    batch of one syncs on the dispatching thread, inside
    ``device-dispatch``; handing the host array out is a second, empty
    span, as it is under the batcher)."""
    from filodb_tpu.obs import trace as obs_trace

    series = _series(regular=regular)
    seen = []
    for batcher in (None, MicroBatcher(enabled=False), MicroBatcher()):
        backend = TpuBackend(batcher=batcher)
        trace = obs_trace.Trace()
        with obs_trace.activate(trace):
            got = backend.periodic_samples(series, _params(0), func,
                                           300_000, func_args)
        names = [sp.name for sp in trace.spans]
        dispatch = [sp for sp in trace.spans
                    if sp.name == "device-dispatch"]
        assert len(dispatch) == 1
        assert dispatch[0].tags["batch"] == 1
        assert dispatch[0].tags["path"] == \
            ("aligned" if regular else "packed")
        seen.append((got.values.tobytes(), names.count("device-sync")))
    assert seen[0] == seen[1] == seen[2]
    assert seen[0][1] == (1 if regular else 2)


def test_batched_queries_actually_batch():
    """With the executor-queued mode and a barrier start, most of the
    8 concurrent same-shape queries must share dispatches."""
    series = _series()
    backend = TpuBackend(batcher=MicroBatcher(use_executor=True,
                                              max_batch=8))
    for _ in range(3):
        _run_concurrent(backend, series, "rate", 300_000)
    snap = backend.batcher.stats.snapshot()
    assert snap["batched_queries"] > 0
    assert snap["occupancy_max"] >= 2
    assert snap["batches"] < snap["queries"]


def test_mixed_shapes_do_not_share_batches():
    """Queries with different step counts resolve to different batch
    keys and still match their unbatched references."""
    series = _series()
    ref_backend = TpuBackend(batcher=MicroBatcher(enabled=False))
    backend = TpuBackend(batcher=MicroBatcher(use_executor=True))
    refs, outs = {}, {}
    lock = threading.Lock()
    barrier = threading.Barrier(8)
    for k in range(8):
        nsteps = 16 if k % 2 == 0 else 31
        refs[k] = ref_backend.periodic_samples(
            series, _params(k, nsteps=nsteps), "rate", 300_000).values

    def worker(k):
        barrier.wait()
        nsteps = 16 if k % 2 == 0 else 31
        g = backend.periodic_samples(series, _params(k, nsteps=nsteps),
                                     "rate", 300_000)
        with lock:
            outs[k] = g.values
    ths = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    for k in range(8):
        assert np.array_equal(outs[k], refs[k], equal_nan=True), k


def test_shape_bucketing_is_invisible():
    """Pow2 S/T bucketing pads with sentinel rows/steps: results for
    non-pow2 series counts and step counts equal the oracle-free
    reference computed series-by-series."""
    series = _series(S=5, regular=False, counter=False)
    backend = TpuBackend(batcher=MicroBatcher(enabled=False))
    for nsteps in (3, 10, 17):
        g = backend.periodic_samples(series, _params(0, nsteps=nsteps),
                                     "sum_over_time", 300_000)
        assert g.values.shape == (5, nsteps)
        one = backend.periodic_samples(series[:1],
                                       _params(0, nsteps=nsteps),
                                       "sum_over_time", 300_000)
        assert np.array_equal(g.values[:1], one.values, equal_nan=True)
    assert backend.executable_cache_stats()["misses"] >= 1


def test_batch_failure_fails_all_members():
    b = MicroBatcher(use_executor=True)
    b.enter()
    b.enter()           # simulate a second in-flight query thread
    errs = []
    barrier = threading.Barrier(4)

    def run_batch(members):
        raise RuntimeError("kernel exploded")

    def worker(i):
        barrier.wait()
        try:
            b.submit("k", i, run_batch)
        except RuntimeError as e:
            errs.append(str(e))
    ths = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert len(errs) == 4
    b.exit()
    b.exit()


def test_split_result_single_sync():
    calls = []

    class FakeDev:
        def __array__(self, dtype=None):
            calls.append(1)
            return np.arange(6, dtype=np.float64).reshape(3, 2)

    sr = SplitResult(FakeDev(), 3)
    got = [sr.get(i) for i in range(3)]
    assert len(calls) == 1          # one device->host sync per batch
    assert np.array_equal(got[1], [2.0, 3.0])


def test_executor_owns_submissions_in_order():
    ex = DeviceExecutor()
    seen = []
    done = threading.Event()
    for i in range(5):
        ex.submit(lambda i=i: seen.append(i))
    ex.submit(done.set)
    assert done.wait(5)
    assert seen == [0, 1, 2, 3, 4]
    ex.stop()


@pytest.mark.parametrize("dead", [(), (2,)], ids=["all-grow", "one-dead"])
def test_stale_tiles_never_serve_past_the_prefix_they_were_built_from(dead):
    """A flush publishes new chunks and changes the snapshot key; the
    previous snapshot's tiles keep serving while the rebuild runs. Built
    when they covered everything (cov_min_ms None), they must still stop
    at the first sample a series has gained since: the newly flushed
    samples are not in them. (chip_smoke.py's at-now queries caught the
    whole grid being served from the stale tiles — rates up to 89% low
    for one query.) A series that stopped early and has not grown holds
    nothing back: the tiles still serve up to the others' growth."""
    from filodb_tpu.query.engine import periodic_samples
    n0, n1, W = 120, 150, 300_000
    full = _series(n=n1, S=4, snap=False)

    def snap(n, num_chunks):
        lens = [60 if i in dead else n for i in range(len(full))]
        return [RawSeries(s.labels, s.ts[:k], s.values[:k], True,
                          snapshot_key=("ds", 0, i, num_chunks, 0),
                          chunk_len=k)
                for i, (s, k) in enumerate(zip(full, lens))]
    backend = TpuBackend(batcher=MicroBatcher())
    first = RangeParams(BASE + 600_000, 60_000, BASE + (n0 - 2) * 10_000)
    backend.periodic_samples(snap(n0, 7), first, "rate", W)
    assert backend.tile_builds == 1
    # the rebuild never lands: the stale entry is what serves
    backend.batcher.executor.submit = lambda *a, **k: None
    served = []
    dispatch = backend._aligned_dispatch
    backend._aligned_dispatch = lambda tiles, func, steps, *a: (
        served.append(steps.size) or dispatch(tiles, func, steps, *a))
    now = RangeParams(BASE + 600_000, 60_000, BASE + (n1 - 1) * 10_000)
    new = snap(n1, 8)
    got = backend.periodic_samples(new, now, "rate", W).values
    assert backend.tile_builds == 1
    want = periodic_samples(new, now, "rate", W).values
    np.testing.assert_allclose(got, want, rtol=1e-5, equal_nan=True)
    # the tiles served every step whose window ends before sample n0
    steps = np.arange(now.start_ms, now.end_ms + 1, now.step_ms)
    assert served == [int((steps < BASE + n0 * 10_000).sum())]
