"""Device tile store lifecycle: tiles are published per store snapshot
(keyed dataset/shard/part/num_chunks), reused across queries with zero
rebuilds, survive ingest into write buffers (tail steps spliced from the
live path), and are invalidated by flushes.

(Reference model: chunks are immutable once encoded —
memstore/TimeSeriesPartition.scala:248 encodeOneChunkset; queries read
buffers + chunks through one API.)"""

import time

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.tpu import TpuBackend

REF = DatasetRef("timeseries")
T0 = 1_600_000_000


def _ingest(shard, n_samples, t_start_s, n_series=4, metric="reqs_total"):
    b = RecordBuilder(DEFAULT_SCHEMAS)
    for s in range(n_series):
        labels = {"_metric_": metric, "_ws_": "demo", "_ns_": "App-0",
                  "job": "api", "instance": f"i{s}"}
        for t in range(n_samples):
            ts = (t_start_s + t * 10) * 1000
            b.add_sample("prom-counter", labels, ts,
                         10.0 * (s + 1) * (ts - T0 * 1000) / 10_000.0)
    for c in b.containers():
        shard.ingest(c)


def _run(engine, q, start, end, step=60):
    plan = parse_query_range(q, TimeStepParams(start, step, end))
    return engine.execute(plan)


def test_second_identical_query_zero_tile_builds():
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0)
    _ingest(shard, 360, T0)
    shard.flush_all()
    backend = TpuBackend()
    engine = QueryEngine([shard], backend=backend)
    r1 = _run(engine, "rate(reqs_total[5m])", T0 + 600, T0 + 3000)
    builds = backend.tile_builds
    assert builds >= 1
    r2 = _run(engine, "rate(reqs_total[5m])", T0 + 600, T0 + 3000)
    assert backend.tile_builds == builds          # ZERO new builds
    np.testing.assert_array_equal(r1.values, r2.values)
    # a different grid over the same snapshot also reuses the tiles
    _run(engine, "rate(reqs_total[5m])", T0 + 900, T0 + 2400, step=30)
    assert backend.tile_builds == builds


def test_ingest_tail_does_not_invalidate_tiles_and_is_correct():
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=10_000)
    _ingest(shard, 300, T0)
    shard.flush_all()
    backend = TpuBackend()
    engine = QueryEngine([shard], backend=backend)
    _run(engine, "rate(reqs_total[5m])", T0 + 600, T0 + 2900)
    builds = backend.tile_builds
    # new samples land in write buffers; published chunks unchanged
    _ingest(shard, 30, T0 + 3000)
    got = _run(engine, "rate(reqs_total[5m])", T0 + 600, T0 + 3290)
    assert backend.tile_builds == builds          # tiles NOT rebuilt
    oracle = QueryEngine([shard], backend=None)
    want = _run(oracle, "rate(reqs_total[5m])", T0 + 600, T0 + 3290)
    # align by labels
    gmap = {tuple(sorted(k.items())): got.values[i]
            for i, k in enumerate(got.keys)}
    for i, k in enumerate(want.keys):
        np.testing.assert_allclose(gmap[tuple(sorted(k.items()))],
                                   want.values[i], rtol=1e-5,
                                   equal_nan=True)


def test_flush_publishes_new_tiles():
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=10_000)
    _ingest(shard, 300, T0)
    shard.flush_all()
    backend = TpuBackend()
    engine = QueryEngine([shard], backend=backend)
    _run(engine, "rate(reqs_total[5m])", T0 + 600, T0 + 2900)
    builds = backend.tile_builds
    _ingest(shard, 30, T0 + 3000)
    shard.flush_all()                              # publishes new chunks
    r = _run(engine, "rate(reqs_total[5m])", T0 + 600, T0 + 3290)
    # the previous snapshot's tiles serve while the rebuild runs on the
    # executor thread: wait for it to land before counting
    deadline = time.monotonic() + 30
    while backend.tile_builds == builds and time.monotonic() < deadline:
        time.sleep(0.05)
    assert backend.tile_builds == builds + 1       # rebuilt once
    assert np.isfinite(r.values).any()


def test_http_second_query_zero_builds():
    import json
    import urllib.request

    from filodb_tpu.http.server import FiloHttpServer

    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0)
    _ingest(shard, 360, T0)
    shard.flush_all()
    backend = TpuBackend()
    # results cache off: the second query must reach the DEVICE tile
    # cache (a results-cache hit would short-circuit above it)
    srv = FiloHttpServer({"timeseries": [shard]}, backend=backend,
                         port=0, results_cache_mb=0)
    srv.start()
    try:
        url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
               f"query_range?query=rate(reqs_total%5B5m%5D)"
               f"&start={T0 + 600}&end={T0 + 3000}&step=60")
        r1 = json.load(urllib.request.urlopen(url))
        assert r1["status"] == "success" and r1["data"]["result"]
        builds = backend.tile_builds
        assert builds >= 1
        r2 = json.load(urllib.request.urlopen(url))
        # wall-clock span timings legitimately differ run to run
        r1["stats"].pop("timings", None)
        r2["stats"].pop("timings", None)
        assert r2 == r1
        assert backend.tile_builds == builds       # ZERO builds on repeat
    finally:
        srv.stop()
