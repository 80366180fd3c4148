"""Gateway TCP server: influx line protocol in, per-shard streams out.

(Reference: gateway/src/main/scala/filodb/gateway/GatewayServer.scala —
Netty TCP server :60 parsing influx lines, computing shardKeyHash/
partKeyHash and routing via shardMapper.ingestionShard :120,164, batching
per-shard RecordBuilders, publishing containers to Kafka via
KafkaContainerSink.  Here "Kafka" is the per-shard LogIngestionStream and
the server is a stdlib ThreadingTCPServer — the ingest edge is host-side
I/O, not device work.)

Wire protocol: newline-delimited influx lines; `#`-prefixed lines are
comments.  Batches are published per shard every ``batch_lines`` lines or
when a connection closes, preserving per-connection ordering per shard.
"""

from __future__ import annotations

import socket
import socketserver
import threading
from typing import Dict, List, Optional

from filodb_tpu.core.record import RecordBuilder, ingestion_shard
from filodb_tpu.ingest import health as ingest_health
from filodb_tpu.lint.locks import guarded_by
from filodb_tpu.lint.threads import thread_root
from filodb_tpu.core.record import PartKey
from filodb_tpu.core.schemas import PartitionSchema, Schemas
from filodb_tpu.gateway.influx import input_records, parse_line
from filodb_tpu.ingest.stream import IngestionStream
from filodb_tpu.obs import trace as obs_trace


@guarded_by("_stats_lock", "lines_ingested", "lines_rejected",
            "batches_dropped", "_routes")
class GatewayServer:
    """TCP ingest edge, one instance per gateway process.

    Line/drop counters ride ``_stats_lock``: producer threads (one per
    TCP connection) and the HTTP ingest edge (``/api/v1/ingest/influx``
    handler threads) both route lines through this object."""

    def __init__(self, streams: Dict[int, IngestionStream], schemas: Schemas,
                 num_shards: int, spread: int = 1, port: int = 0,
                 host: str = "127.0.0.1", batch_lines: int = 256,
                 ws: str = "demo", ns: str = "App-0",
                 spread_provider=None):
        self.streams = streams
        self.schemas = schemas
        self.num_shards = num_shards
        self.spread = spread
        # per-shard-key overrides; the planner prunes with the SAME
        # provider so ingest and query always agree (SpreadProvider)
        self.spread_provider = spread_provider
        self.batch_lines = batch_lines
        self.ws, self.ns = ws, ns
        self.part_schema = PartitionSchema()
        # (line identity, field names) -> [(schema name, PartKey, shard)]
        # per sample of the line
        self._routes: Dict = {}
        self._stats_lock = threading.Lock()
        self.lines_ingested = 0
        self.lines_rejected = 0
        # batches dropped while ingest is degraded to read-only (the
        # fire-and-forget TCP edge has no backpressure channel — counted
        # loss beats a crashed producer thread; HTTP ingest gets a 503)
        self.batches_dropped = 0
        gateway = self

        class Handler(socketserver.StreamRequestHandler):
            # per-connection producer thread (ThreadingTCPServer spawn
            # the AST engine cannot see)
            @thread_root("gateway-producer")
            def handle(self):
                builders: Dict[int, RecordBuilder] = {}
                lines = iter(self.rfile)
                more = True
                while more:
                    # one stage span per published batch, not per line
                    with obs_trace.span("gateway-parse"):
                        more = gateway._route_batch(lines, builders)
                    gateway._publish(builders)

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server((host, port), Handler)
        self._thread: Optional[threading.Thread] = None

    # -- routing -----------------------------------------------------------
    _ROUTE_CACHE_MAX = 2_000_000

    def _route_batch(self, lines, builders: Dict[int, RecordBuilder]
                     ) -> bool:
        """Read, parse and route lines until ``batch_lines`` were
        accepted; False once ``lines`` is exhausted."""
        pending = 0
        for raw in lines:
            line = raw.decode("utf-8", errors="replace").strip()
            if not line or line.startswith("#"):
                continue
            if self._route_line(line, builders):
                pending += 1
                if pending >= self.batch_lines:
                    return True
        return False

    def _route_line(self, line: str, builders: Dict[int, RecordBuilder]
                    ) -> bool:
        """Parse one line, append each resulting sample to its shard's
        builder (GatewayServer.scala:120 shardKeyHash->ingestionShard).

        A line's identity (measurement + tags) and its field names
        decide the schema, part key and shard of each of its samples,
        and never change: they are resolved once per series
        (``_routes``); the line itself is always parsed by
        ``parse_line``/``input_records``."""
        try:
            rec = parse_line(line)
            samples = input_records(rec, self.ws, self.ns)
        except ValueError:
            with self._stats_lock:
                self.lines_rejected += 1
            return False
        key = (rec.ident, tuple(rec.fields))
        with self._stats_lock:
            routes = self._routes.get(key)
        if routes is None:
            routes = [(name,) + self._route(name, labels)
                      for name, labels, _, _ in samples]
            with self._stats_lock:
                if len(self._routes) >= self._ROUTE_CACHE_MAX:
                    self._routes.clear()
                self._routes[key] = routes
        for (schema_name, pk, shard), sample in zip(routes, samples):
            b = builders.get(shard)
            if b is None:
                b = builders[shard] = RecordBuilder(self.schemas)
            b.add_keyed(schema_name, pk, sample[2], *sample[3])
        with self._stats_lock:
            self.lines_ingested += 1
        return True

    def _route(self, schema_name: str, labels: Dict[str, str]):
        """(part key, ingestion shard) of one series."""
        pk = PartKey.make(self.schemas.by_name(schema_name), labels)
        if self.spread_provider is not None:
            spread = self.spread_provider.spread_for_labels(
                labels, self.part_schema.non_metric_shard_key_columns)
        else:
            spread = self.spread
        shard = ingestion_shard(pk.shard_key_hash(self.part_schema),
                                pk.part_hash(), spread, self.num_shards)
        return pk, shard

    def _publish(self, builders: Dict[int, RecordBuilder],
                 raise_on_error: bool = False) -> None:
        """Flush per-shard builders into their streams (KafkaContainerSink).

        Write-path out-of-space degrades instead of crashing the
        producer thread: the process flips to ingest-read-only
        (ingest/health.py), and while degraded this edge DROPS batches
        (counted) except for the rate-limited probe write that detects
        recovery. ``raise_on_error=True`` (the HTTP ingest edge) raises
        :class:`~filodb_tpu.ingest.health.IngestReadOnly` instead so
        the caller can answer 503 + Retry-After."""
        health = ingest_health.GLOBAL
        if health.read_only() and not health.should_probe():
            # containers() drains the builders — the batch is lost
            # either way (dropped here, or retried wholesale by the
            # HTTP caller after its 503)
            dropped = sum(len(b.containers()) for b in builders.values())
            if dropped:
                with self._stats_lock:
                    self.batches_dropped += 1
            if raise_on_error:
                raise health.reject()
            return
        wrote = False
        for shard, b in builders.items():
            stream = self.streams.get(shard)
            if stream is None:
                continue
            for cont in b.containers():
                try:
                    stream.append(cont)
                    wrote = True
                except OSError as e:
                    if health.note_write_error(e, "gateway publish"):
                        with self._stats_lock:
                            self.batches_dropped += 1
                        if raise_on_error:
                            raise health.reject() from e
                        return
                    raise
        if wrote:
            health.note_write_ok()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "GatewayServer":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="gateway-server", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    @property
    def port(self) -> int:
        return self._server.server_address[1]


def send_lines(host: str, port: int, lines: List[str],
               timeout: float = 10.0) -> None:
    """Small client for tests/tools: push influx lines to a gateway."""
    with socket.create_connection((host, port), timeout=timeout) as s:
        payload = ("\n".join(lines) + "\n").encode()
        s.sendall(payload)
