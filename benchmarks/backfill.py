"""Columnar history into the node's normal shard entry.

For each shard ONE ``RecordContainer`` whose ``arrays()`` are the seeded numpy
columns and whose ``runs()`` is one ``[i, j, PartKey]`` per series, handed to
``TimeSeriesShard.ingest`` and flushed: O(1) Python per series. Series are
routed by the same hash the gateway uses
(``ingestion_shard(pk.shard_key_hash(..), pk.part_hash(), spread, shards)``).

A world of histograms goes in the ``prom-histogram`` schema: columns
``sum``, ``count`` (the ``+Inf`` bucket) and ``h`` in the per-row form the
container takes from the gateway (``gateway/influx.py`` ``input_records``):
one ``(scheme, bucket counts)`` a sample, so O(rows) Python for ``h``.

This BYPASSES the gateway and the WAL, so it is set-up only and is never
inside a measured window: every write a cell measures goes through the
gateway's TCP port. A shard has one writer at a time: this runs on the node
child's main thread after ``FiloServer.start()`` and before the gateway is
sent its first line (until a shard's stream holds a record its driver only
polls and never ingests or flushes).
"""

import time

import numpy as np

from filodb_tpu.core.record import PartKey, RecordContainer, ingestion_shard
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, PartitionSchema
from filodb_tpu.memory.histogram import CustomBuckets


def backfill(server, world):
    """-> {"rows", "series", "ingest_s", "flush_s", "shard_series"}."""
    cfg = server.config
    schema = DEFAULT_SCHEMAS.by_name(world.schema)
    part_schema = PartitionSchema()
    shards = {s.shard_num: s for s in server.store.shards(server.ref)}
    n = world.n_hist
    t0 = time.monotonic()
    by_shard = {}
    for i, labels in enumerate(world.labels):
        pk = PartKey.make(schema, labels)
        spread = server.spread_provider.spread_for_labels(
            labels, part_schema.non_metric_shard_key_columns)
        shard = ingestion_shard(pk.shard_key_hash(part_schema),
                                pk.part_hash(), spread, cfg["num-shards"])
        by_shard.setdefault(shard, []).append((i, pk))
    rows = 0
    for shard, members in sorted(by_shard.items()):
        idx = np.fromiter((i for i, _ in members), dtype=np.int64)
        c = RecordContainer(schema)
        c.timestamps = np.ascontiguousarray(world.ts[idx, :n]).reshape(-1)
        c._runs = [[k * n, (k + 1) * n, pk]
                   for k, (_, pk) in enumerate(members)]
        c._arrays_cache = (idx.size * n, c.timestamps,
                           _columns(world, idx, n))
        got = shards[shard].ingest(c)
        if got != idx.size * n:
            raise RuntimeError(f"shard {shard} took {got} of "
                               f"{idx.size * n} backfilled rows")
        rows += got
    t1 = time.monotonic()
    for shard in shards.values():
        shard.flush_all()
    return {"rows": rows, "series": world.n_series, "ingest_s": t1 - t0,
            "flush_s": time.monotonic() - t1,
            "shard_series": {str(s): len(m) for s, m in by_shard.items()}}


def _columns(world, idx, n):
    """The data columns of the rows ``[idx, :n]``, row-major."""
    if world.les is None:
        return [np.ascontiguousarray(world.vals[idx, :n]).reshape(-1)]
    scheme = CustomBuckets(tuple(float(le) for le in world.les))
    counts = world.vals[idx, :n].reshape(-1, len(world.les)).astype(np.int64)
    return [np.ascontiguousarray(world.sums[idx, :n]).reshape(-1),
            counts[:, -1].astype(np.float64),
            [(scheme, row) for row in counts]]
