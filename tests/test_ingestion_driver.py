"""Ingestion driver tests: steady-state ingest with rotating group
flushes, checkpoint watermark recovery, shard status FSM transitions.

(Parity model: coordinator/src/test IngestionStreamSpec +
IngestionActor.scala:174-345 recovery protocol.)"""

import tempfile
import time

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.ingest import (IngestionDriver, LogIngestionStream,
                               MemoryIngestionStream)
from filodb_tpu.parallel.shardmapper import ShardMapper, ShardStatus
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.store import FlatFileColumnStore

REF = DatasetRef("timeseries")
T0 = 1_600_000_000


def _publish(stream, n_batches=10, rows_per_batch=20, t0_s=T0):
    """n_batches containers of counter samples for 2 series."""
    t = 0
    for i in range(n_batches):
        b = RecordBuilder(DEFAULT_SCHEMAS)
        for _ in range(rows_per_batch // 2):
            for s in range(2):
                b.add_sample(
                    "prom-counter",
                    {"_metric_": "reqs_total", "_ws_": "demo",
                     "_ns_": "App-0", "instance": f"i{s}"},
                    (t0_s + t * 10) * 1000, float((t + 1) * (s + 1)))
            t += 1
        for c in b.containers():
            stream.append(c)


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def _query(shard, start=T0 + 100, end=T0 + 900, step=60):
    plan = parse_query_range("rate(reqs_total[5m])",
                             TimeStepParams(start, step, end))
    return QueryEngine([shard]).execute(plan)


def test_steady_state_ingest_and_flush():
    stream = MemoryIngestionStream()
    mapper = ShardMapper(1)
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                            max_chunk_rows=64)
    drv = IngestionDriver(shard, stream, mapper=mapper,
                          flush_every_records=2).start()
    assert _wait(lambda: mapper.status(0) is ShardStatus.ACTIVE)
    _publish(stream, n_batches=10, rows_per_batch=20)
    assert _wait(lambda: drv.next_offset == 10)
    assert shard.stats.rows_ingested == 200
    assert shard.stats.flushes_done >= 4          # rotating group flushes
    # checkpoints recorded against ingested offsets
    assert shard.checkpoints and max(shard.checkpoints.values()) <= 9
    drv.stop()
    assert shard.recovery_watermark() == 9        # final flush_all


def test_recovery_replays_from_watermark(tmp_path):
    cs = FlatFileColumnStore(str(tmp_path / "col"))
    stream_path = str(tmp_path / "stream.log")

    # -- "process 1": ingest 10 batches, flush through offset 5, crash
    stream1 = LogIngestionStream(stream_path, DEFAULT_SCHEMAS)
    _publish(stream1, n_batches=10)
    shard1 = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                             max_chunk_rows=64, column_store=cs)
    for sd in stream1.read(0, 6):
        shard1.ingest(sd.container, sd.offset)
    shard1.flush_all(offset=5)                    # watermark = 5
    # rows 6..9 were never ingested -> lost with the "crash"

    # -- "process 2": bootstrap + driver recovery replays 6..9
    cs2 = FlatFileColumnStore(str(tmp_path / "col"))
    shard2 = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                             max_chunk_rows=64, column_store=cs2)
    shard2.bootstrap_from_store()
    assert shard2.recovery_watermark() == 5
    stream2 = LogIngestionStream(stream_path, DEFAULT_SCHEMAS)
    mapper = ShardMapper(1)
    statuses = []
    drv = IngestionDriver(shard2, stream2, mapper=mapper,
                          flush_every_records=3,
                          on_event=lambda s, st, p: statuses.append(st))
    drv.start()
    assert _wait(lambda: mapper.status(0) is ShardStatus.ACTIVE)
    assert drv.next_offset == 10
    assert ShardStatus.RECOVERY in statuses       # FSM went through recovery
    drv.stop()

    # the recovered shard answers the same query as an oracle that saw
    # every sample exactly once
    oracle = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, max_chunk_rows=64)
    stream3 = LogIngestionStream(stream_path, DEFAULT_SCHEMAS)
    for sd in stream3.read(0, 100):
        oracle.ingest(sd.container, sd.offset)
    want, got = _query(oracle), _query(shard2)
    assert got.num_series == want.num_series == 2
    wmap = {k["instance"]: want.values[i] for i, k in enumerate(want.keys)}
    for i, k in enumerate(got.keys):
        np.testing.assert_allclose(got.values[i], wmap[k["instance"]],
                                   rtol=1e-9, equal_nan=True)


def test_recovery_idempotent_replay_below_group_checkpoints(tmp_path):
    """Groups flush at different offsets; replay from the min watermark
    re-delivers rows some groups already flushed — the OOO guard must
    drop them (no duplicated samples)."""
    cs = FlatFileColumnStore(str(tmp_path / "col"))
    stream_path = str(tmp_path / "stream.log")
    stream1 = LogIngestionStream(stream_path, DEFAULT_SCHEMAS)
    _publish(stream1, n_batches=10)

    shard1 = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                             max_chunk_rows=64, column_store=cs)
    for sd in stream1.read(0, 5):
        shard1.ingest(sd.container, sd.offset)
    shard1.flush_group(0, offset=4)
    shard1.flush_group(1, offset=4)
    for sd in stream1.read(5, 3):
        shard1.ingest(sd.container, sd.offset)
    shard1.flush_group(0, offset=7)               # group 0 ahead of group 1
    # watermark = min(7, 4) = 4; crash here

    cs2 = FlatFileColumnStore(str(tmp_path / "col"))
    shard2 = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                             max_chunk_rows=64, column_store=cs2)
    shard2.bootstrap_from_store()
    assert shard2.recovery_watermark() == 4
    drv = IngestionDriver(shard2, LogIngestionStream(stream_path,
                                                     DEFAULT_SCHEMAS),
                          flush_every_records=100)
    drv.start()
    assert _wait(lambda: drv.next_offset == 10)
    drv.stop()

    # every series has each timestamp exactly once.  Go through the real
    # read path (lookup_partitions) so ODP shells page their persisted
    # history back in — a shell whose replayed rows were all beyond its
    # persisted end stays unpaged until a query touches it.
    total_expected = 10 * 20  # all batches
    parts = shard2.lookup_partitions([], 0, 2**62)
    assert len(parts) == 2
    n_rows = 0
    for p in parts:
        ts, _, _ = p.read_full(1)
        assert np.all(np.diff(ts) > 0)            # strictly increasing
        n_rows += ts.size
    assert n_rows == total_expected


def test_ingest_batch_records_knob_replays_equivalently():
    """The WAL read batch (ingest-batch-records, was hardcoded at 64)
    must not change WHAT gets ingested — tiny and huge batches deliver
    the same rows, checkpoints, and query results."""
    shards = {}
    for batch in (2, 256):
        stream = MemoryIngestionStream()
        _publish(stream, n_batches=10, rows_per_batch=20)
        shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                                max_chunk_rows=64)
        drv = IngestionDriver(shard, stream, flush_every_records=3,
                              ingest_batch_records=batch)
        drv.start()
        assert _wait(lambda: drv.next_offset == 10)
        drv.stop()
        assert shard.stats.rows_ingested == 200
        assert shard.recovery_watermark() == 9
        shards[batch] = shard
    small, big = shards[2], shards[256]
    assert small.ingest_watermark_ms == big.ingest_watermark_ms
    want, got = _query(small), _query(big)
    assert want.num_series == got.num_series == 2
    wmap = {k["instance"]: want.values[i]
            for i, k in enumerate(want.keys)}
    for i, k in enumerate(got.keys):
        np.testing.assert_array_equal(got.values[i],
                                      wmap[k["instance"]])


def test_ingest_batch_records_recovery_replay(tmp_path):
    """Recovery replay honours the knob too: a 1-record batch replays
    to the same state as the default."""
    stream_path = str(tmp_path / "stream.log")
    stream1 = LogIngestionStream(stream_path, DEFAULT_SCHEMAS)
    _publish(stream1, n_batches=8)
    results = []
    for batch in (1, 64):
        shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                                max_chunk_rows=64)
        drv = IngestionDriver(
            shard, LogIngestionStream(stream_path, DEFAULT_SCHEMAS),
            flush_every_records=100, ingest_batch_records=batch)
        drv.start()
        assert _wait(lambda: drv.next_offset == 8)
        drv.stop()
        results.append((shard.stats.rows_ingested,
                        shard.ingest_watermark_ms))
    assert results[0] == results[1]


# -- an idle driver that its stream wakes (idle_wait_s) ------------------------

class _CountedReads(MemoryIngestionStream):
    """A stream that counts its polls."""
    reads = 0

    def read(self, from_offset, max_records=64):
        self.reads += 1
        return super().read(from_offset, max_records)


def _idle_driver(stream, **kw):
    shard = TimeSeriesShard(REF, DEFAULT_SCHEMAS, 0, num_groups=2,
                            max_chunk_rows=64)
    mapper = ShardMapper(1)
    drv = IngestionDriver(shard, stream, mapper=mapper, **kw).start()
    assert _wait(lambda: mapper.status(0) is ShardStatus.ACTIVE)
    return shard, drv


@pytest.mark.parametrize("make", [
    MemoryIngestionStream,
    lambda: LogIngestionStream(tempfile.mkdtemp() + "/s/stream.log",
                               DEFAULT_SCHEMAS)], ids=["memory", "log"])
def test_an_append_wakes_the_idle_driver_at_once(make):
    """A sleep of 30 s that an append ends: nothing else explains rows
    ingested inside two."""
    stream = make()
    shard, drv = _idle_driver(stream, idle_wait_s=30.0, flush_interval_s=60)
    try:
        time.sleep(0.1)                 # the driver is asleep by now
        _publish(stream, n_batches=3, rows_per_batch=20)
        assert _wait(lambda: drv.next_offset == 3, timeout=2.0)
        assert shard.stats.rows_ingested == 60
        time.sleep(0.1)
        _publish(stream, n_batches=2, rows_per_batch=20)    # and again
        assert _wait(lambda: drv.next_offset == 5, timeout=2.0)
    finally:
        t = time.monotonic()
        drv.stop()
        assert time.monotonic() - t < 2.0       # stop wakes it too


@pytest.mark.parametrize("idle_wait_s,least,most", [(None, 8, 10**9),
                                                    (5.0, 0, 1)])
def test_an_idle_driver_that_is_woken_does_not_poll(idle_wait_s, least,
                                                    most):
    """0.4 s of an empty stream: a polling driver reads it every 20 ms,
    a woken one not at all (once, if it was still on its way to sleep)."""
    stream = _CountedReads()
    _, drv = _idle_driver(stream, idle_wait_s=idle_wait_s)
    try:
        before = stream.reads
        time.sleep(0.4)
        assert least <= stream.reads - before <= most
    finally:
        drv.stop()


def test_an_idle_driver_is_up_when_its_flush_is_due():
    """Rows ingested and a flush interval of 0.3 s: the time-based flush
    is not held back by an idle wait of 30 s."""
    stream = MemoryIngestionStream()
    shard, drv = _idle_driver(stream, idle_wait_s=30.0, flush_interval_s=0.3)
    try:
        _publish(stream, n_batches=2, rows_per_batch=20)
        assert _wait(lambda: drv.next_offset == 2, timeout=2.0)
        done = shard.stats.flushes_done
        # one group an interval, round-robin: two more within 1.5 s
        assert _wait(lambda: shard.stats.flushes_done >= done + 2,
                     timeout=1.5)
    finally:
        drv.stop()


@pytest.mark.parametrize("nodes,want", [(1, 0.5), (2, None)])
def test_a_node_on_its_own_wakes_its_drivers(tmp_path, nodes, want):
    from filodb_tpu.standalone.server import FiloServer
    cfg = {"num-shards": 4, "port": 0, "grpc-port": None,
           "stream-dir": str(tmp_path / "streams"),
           "data-dir": str(tmp_path / "data")}
    if nodes > 1:
        cfg.update({"num-nodes": nodes, "node-ordinal": 0,
                    "peers": {"node1": "http://127.0.0.1:1"}})
    srv = FiloServer(cfg).start()
    try:
        assert srv.drivers
        for shard, drv in srv.drivers.items():
            assert drv.idle_wait_s == want
            assert (srv.streams[shard].on_append is not None) \
                == (want is not None)
    finally:
        srv.stop()
