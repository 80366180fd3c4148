"""FiloServer: the standalone node binary.

Wires config -> memstore shards -> shard mapper -> TPU query backend ->
HTTP API, mirroring the v2 startup path (standalone/NewFiloServerMain.scala:21:
start memstore, discovery, ingestion, http) without Akka: shard state is a
local ShardMapper FSM; the distributed query path is the mesh executor.

Config keys follow conf/timeseries-dev-source.conf naming where sensible:
  dataset, num-shards, groups-per-shard, max-chunks-size, port.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Dict, Optional

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.http.server import FiloHttpServer
from filodb_tpu.lint.threads import thread_root
from filodb_tpu.obs import process as obs_process
from filodb_tpu.parallel.shardmapper import (ShardMapper,
                                             assign_shards_evenly,
                                             shards_for_ordinal)
from filodb_tpu.query.model import QueryLimits

DEFAULTS = {
    "dataset": "timeseries",
    "num-shards": 4,
    "groups-per-shard": 8,
    "max-chunks-size": 400,
    "port": 8080,
    "node-id": "node0",
    # spread used for shard-key routing (filodb-defaults.conf:319
    # default-spread); must match the ingest-side spread
    "default-spread": 1,
    # lower agg(rangefunc(...)) onto the device mesh when >1 jax device
    "mesh-enabled": False,
    # with mesh-enabled: serve eligible aligned-tile cohorts from
    # device-RESIDENT sharded tiles (shard_map slot-major evaluators,
    # donated zero-copy refreshes) instead of single-device dispatch
    "mesh-tile-serving": True,
    # chunk/partkey/checkpoint persistence root; None = memory-only
    # (conf/timeseries-filodb-server.conf store path equivalent)
    "data-dir": None,
    # streaming ingestion: per-shard durable stream logs (the Kafka
    # partition analogue, conf/timeseries-dev-source.conf sourceconfig);
    # None = no streaming ingestion (direct/test ingest only)
    "stream-dir": None,
    # influx line-protocol ingest edge (GatewayServer.scala); None = off,
    # 0 = ephemeral port
    "gateway-port": None,
    # flush cadence: one flush group every interval, rotating round-robin
    # (flush-interval in the reference source config)
    "flush-interval-s": 2.0,
    "flush-every-records": None,
    # raw retention in seconds; queries reaching further back split to the
    # downsample tier (LongTimeRangePlanner). Requires data-dir (the ds
    # tier reads downsampler-job output from the ColumnStore). None = off.
    "raw-retention-s": None,
    # downsample resolutions in ms (conf multi-resolution config)
    "downsample-resolutions": [300_000, 3_600_000],
    # emit downsample records during flush (ShardDownsampler.scala:40);
    # requires data-dir. The batch job remains for backfill + histograms.
    "flush-downsample": False,
    # per-shard resident-sample budget; exceeded -> evict least-recently
    # written partitions to ODP shells (headroom task). 0 = no cap.
    "max-resident-samples": 0,
    # per-query guardrails (filodb-defaults.conf sample-limit equivalent;
    # 0 = unlimited). Over-limit queries return HTTP 422.
    "query-sample-limit": 1_000_000,
    "query-series-limit": 100_000,
    # degraded-mode execution (parallel/resilience.py): default per-query
    # deadline budget (overridable per request via &timeout=), bounded
    # retries on peer transport failures, and per-peer circuit breakers
    # (open after N consecutive failures; half-open probe after the
    # reset window). Partial responses stay opt-in per request
    # (&allow_partial=true).
    "query-timeout-s": 30.0,
    # serving fast path (query/batcher.py + query/plancache.py):
    # micro-batch gather window for concurrent same-shape queries (the
    # continuous-batching admission layer in front of the TPU backend),
    # max queries per device dispatch, and the parsed-plan LRU size
    # (0 disables the respective piece)
    "batch-gather-window-ms": 1.0,
    "batch-max": 8,
    "batch-enabled": True,
    "plan-cache-size": 256,
    # incremental range-query results cache (query/resultcache.py):
    # byte budget for cached per-step matrix extents (0 disables) and
    # the freshness hot window — steps within this many ms of now (or
    # above a shard's ingest watermark) are never served from cache.
    # Per-request escape hatch: &cache=false.
    "results-cache-mb": 64,
    "results-cache-hot-window-ms": 10_000,
    # WAL read batch per ingest poll (was hardcoded at 64); also the
    # recovery replay batch size
    "ingest-batch-records": 64,
    # host decode/merge cache byte budget per shard (0 = unbounded);
    # trimmed on the flush path — fully-persisted partitions' decoded
    # duplicates are released first (filodb_decode_cache_bytes gauge)
    "decode-cache-mb": 0,
    # observability (filodb_tpu.obs): distributed tracing is OFF by
    # default (zero overhead, byte-identical responses); when enabled,
    # fresh queries sample at trace-sample-rate and finished traces land
    # in the /debug/traces ring (&explain=trace forces + inlines one).
    # Queries slower than slow-query-ms leave a structured record at
    # /debug/slow_queries (0 = off); /debug/queries lists in-flight.
    "trace-enabled": False,
    "trace-sample-rate": 1.0,
    "trace-max-traces": 256,
    "slow-query-ms": 1000.0,
    # tail-sampling retention: with tracing enabled, EVERY request
    # records into a pending trace and the sample-rate coin only
    # decides uninteresting outcomes — errors, QoS-shed rungs, and
    # queries slower than trace-slow-ms are ALWAYS retained. None
    # defaults the slow threshold to slow-query-ms, so slowlog entries
    # always link a resolvable trace id.
    "trace-slow-ms": None,
    # trace export: POST retained traces as OTLP/JSON batches to this
    # sink URL (None = off) through the breaker+backoff stack; the
    # queue is bounded drop-oldest (export lag never blocks serving)
    "trace-export-url": None,
    "trace-export-batch": 64,
    "trace-export-interval-s": 2.0,
    "trace-export-queue": 1024,
    # wall-clock sampling profiler (obs/profiler.py): OFF by default
    # (no sampler thread, no metric families, byte-identical /metrics);
    # when on, /debug/profile serves folded stacks + top self-time and
    # filodb_profile_self_seconds_total{root,func} rides the registry
    "profiler-enabled": False,
    "profiler-hz": 29.0,
    "profiler-max-stacks": 4096,
    "profiler-top-n": 20,
    # self-monitoring (obs/selfmon.py): a per-process loop snapshots
    # the metrics registry in-process every interval and ingests the
    # samples into the reserved __selfmon__ dataset through the normal
    # ingest path (WAL + driver replay when stream-dir is set; direct
    # ingest + flush otherwise), tagged to the reserved __selfmon__
    # tenant (background priority, forced charges). PromQL over our own
    # telemetry: /promql/__selfmon__/api/v1/query_range?query=...
    "self-monitor": False,
    "self-monitor-interval-s": 5.0,
    # direct-ingest mode flush cadence (ticks between flushes; the
    # internal shard's ingest watermark — the results cache's
    # freshness input — advances on flush)
    "self-monitor-flush-ticks": 4,
    # -- recording rules & alerting (filodb_tpu/rules) ----------------
    # rules-file: a Prometheus-style YAML/JSON rule-group file;
    # "rules" accepts the same structure inline ({"groups": [...]}) —
    # handy for tests and generated configs. Groups evaluate in-process
    # as standing queries (background priority, forced-charge
    # __rules__ tenant, step-aligned tail recomputes through the
    # results cache); recorded series + synthetic ALERTS land in the
    # reserved __rules__ dataset via the selfmon write-back rail
    # (durable WAL + driver replay under stream-dir). Under the
    # supervisor every worker loads the config but only the lowest
    # ALIVE ordinal evaluates (re-elected on bus worker-exit).
    "rules-file": None,
    "rules": None,
    # steps per evaluation window: each tick queries the last N
    # interval-aligned steps so the results cache serves the warm
    # prefix and only the newest step recomputes
    "rules-eval-span-steps": 8,
    # alert webhook receiver (Alertmanager-webhook-shaped POSTs,
    # retried with backoff through a per-receiver circuit breaker);
    # None = no notifications
    "rules-webhook-url": None,
    # group-commit fsync for the durable ingest streams (ROADMAP
    # follow-up: per-append fsync stalls on shared container disks).
    # Appends fsync at most every this-many ms (or 1MB unsynced);
    # 0 = strict fsync-per-append. The durability window is bounded by
    # this knob; stream close / checkpoint sync() force the tail out.
    "stream-group-commit-ms": 5.0,
    # storage-integrity knob: quarantined-record loss a shard tolerates
    # before degrading to read-only (queries keep serving, flagged in
    # /__health "integrity"). 0 = ANY quarantined record trips it — the
    # zero-silent-loss default; raise it only when replay-through-
    # damage is preferred over read-only (fsck can repair offline).
    "integrity-max-quarantined-records": 0,
    # admission control: query endpoints admit at most this many
    # in-flight evaluations (excess parks on a semaphore); 0 = off.
    # The wait is BOUNDED: a slot that does not free within
    # admission-wait-s answers 429 + Retry-After (never a silent hang)
    "max-inflight-queries": 4,
    "admission-wait-s": 5.0,
    # -- tenant QoS / brownout control (query/qos.py) -----------------
    # Per-tenant query budgets in estimated cost units/second (tenant =
    # X-Filo-Tenant header / &tenant= param, by convention the
    # workspace; "default" otherwise). 0 = budgets off (the pre-QoS
    # edge). Burst is the bucket depth (0 = 10x rate); per-tenant
    # overrides: {tenant: rate} or {tenant: [rate, burst]} (rate 0 =
    # that tenant is unlimited). Over-budget queries degrade down the
    # ladder (stale-cache -> downsample -> partial -> 429) unless
    # qos-shed-degraded is false; the coarsen rung targets at most
    # qos-degrade-max-steps evaluation steps.
    "qos-tenant-rate": 0,
    "qos-tenant-burst": 0,
    "qos-tenant-overrides": {},
    "qos-shed-degraded": True,
    "qos-degrade-max-steps": 64,
    "peer-retry-attempts": 3,
    "peer-retry-base-delay-s": 0.05,
    "breaker-failure-threshold": 3,
    "breaker-reset-s": 5.0,
    # multi-process cluster (coordinator/v2 FiloDbClusterDiscovery.scala:50
    # ordinal->shards; explicit peer list like the akka-bootstrapper's
    # explicit-list mode): this node owns shards_for_ordinal(node-ordinal);
    # peers maps node ids ("node0"...) -> base URLs for leaf dispatch
    "num-nodes": 1,
    "node-ordinal": 0,
    "peers": {},
    # seed discovery (akka-bootstrapper AkkaBootstrapper.scala:31): when
    # "peers" is empty, resolve them at startup —
    #   {"mode": "dns-srv", "srv-name": "_filodb._tcp.ns.svc"} or
    #   {"mode": "consul", "url": "http://consul:8500", "service": "filodb"}
    # "advertise-url" identifies THIS node among the discovered seeds
    # (ordinals follow the sorted seed list on every node).
    "discovery": None,
    "advertise-url": None,
    # HA buddy replica cluster (HighAvailabilityPlanner.scala:31): maps a
    # node id to the SAME-ordinal node of a replica cluster ingesting the
    # same streams; queries route a DOWN node's shards to its buddy
    "buddy-peers": {},
    # cross-cluster federation: _ws_ value -> base URL of the cluster
    # owning that workspace (MultiPartitionPlanner.scala:53); workspaces
    # in local-partitions are served here and never forwarded
    "partitions": {},
    "local-partitions": [],
    # per-shard-key spread overrides {"ws,ns": spread}
    # (core/SpreadProvider.scala; doc/sharding.md "Spread")
    "spread-overrides": {},
    # cardinality quotas (ratelimit QuotaSource, filodb-defaults.conf:277):
    # default quota per prefix depth [root, ws, ns, metric]; 0 = unlimited.
    # Per-prefix overrides: {"ws,ns": quota}. Breaches drop new series.
    "card-default-quotas": [0, 0, 0, 0],
    "card-quotas": {},
    "failure-detect-interval-s": 0.5,
    "failure-detect-threshold": 3,
    # per-tenant cardinality gauges published on a timer
    # (TenantIngestionMetering.scala; 0 = off)
    "tenant-metering-interval-s": 60,
    # gRPC query service port (PromQLGrpcServer.scala; 0 = ephemeral,
    # None = off). ON by default: this is the data plane — leaf dispatch
    # and pushdown ride protobuf + NibblePack over persistent channels;
    # base64-JSON HTTP remains the control plane and the fallback. Fixed
    # peer addrs can be given via "grpc-peers" {node_id: "host:port"};
    # otherwise each node advertises its ephemeral port in its health
    # body and peers learn it through the failure detector's gossip.
    "grpc-port": 0,
    "grpc-peers": {},
    "grpc-partitions": {},
    # elastic recovery (ShardManager.scala:28 assignShardsToNodes): when a
    # peer stays DOWN this many seconds past detection, survivors adopt
    # its shards — bootstrap from the ColumnStore, replay the stream from
    # the checkpoint watermark, then serve them. None = survive-only
    # (buddy failover still applies). Requires the shared data-dir /
    # stream-dir deployment (the Cassandra/Kafka analogue).
    "shard-reassign-grace-s": None,
    # elastic membership (parallel/membership.py): POST /admin/drain
    # walks this node's shards through planned make-before-break
    # handoff, rejoining nodes defer shards a peer still serves and
    # receive them back through the same protocol, and topology epochs
    # + stale-routing retries keep routing/caches coherent. False falls
    # back to the legacy on_node_up hard cutover.
    "elastic-membership": True,
    # per-shard handoff budget: flush + successor bootstrap/replay +
    # ACTIVE advertisement must fit, or the shard rolls back to the
    # draining owner
    "handoff-timeout-s": 30.0,
    # metadata/cardinality peer fan-out concurrency (was hard-coded 8);
    # 0 = auto-size from the host core count. Surfaced in /metrics as
    # filodb_peer_fanout_workers.
    "peer-fanout-workers": 0,
    # -- process-sharded serving tier (standalone/supervisor.py) ------
    # These keys are normally derived by the supervisor, which forks N
    # worker processes per host — each an ordinal-owned shard-group
    # node with PRIVATE plan/executable/results caches, batcher, and
    # device executor — behind ONE public port.
    #   worker-id:    this process's worker ordinal (None = standalone)
    #   accept-port:  shared public port; bound here with SO_REUSEPORT
    #                 so the kernel balances accepted connections
    #                 across workers
    #   accept-fd:    inherited listening-socket fd (the fd-passing
    #                 fallback where SO_REUSEPORT is unavailable; the
    #                 supervisor binds once and every worker accepts
    #                 on the shared socket)
    #   bus-port:     the supervisor's local control plane
    #                 (standalone/bus.py): topology / schema /
    #                 watermark events fan out to every sibling so
    #                 per-process caches stay coherent with membership
    "worker-id": None,
    "accept-port": None,
    "accept-host": "127.0.0.1",
    "accept-fd": None,
    "bus-port": None,
    # cadence of this worker's watermark/backfill gossip on the bus
    # (the detector's health-body gossip remains the backstop)
    "bus-watermark-interval-s": 0.25,
}


def bind_reuseport(host: str, port: int):
    """A listening socket on (host, port) with SO_REUSEPORT, or None
    when the platform doesn't support it (the supervisor then falls
    back to binding once and passing the fd to every worker)."""
    import socket as _socket
    if not hasattr(_socket, "SO_REUSEPORT"):
        return None
    s = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    try:
        s.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEPORT, 1)
        s.bind((host, int(port)))
        s.listen(128)
    except OSError:
        s.close()
        raise
    return s


class FiloServer:
    def __init__(self, config: Optional[Dict] = None,
                 backend: Optional[object] = None):
        self.config = {**DEFAULTS, **(config or {})}
        self.ref = DatasetRef(self.config["dataset"])
        column_store = None
        if self.config.get("data-dir"):
            from filodb_tpu.store import FlatFileColumnStore
            column_store = FlatFileColumnStore(self.config["data-dir"])
        self.store = TimeSeriesMemStore(DEFAULT_SCHEMAS,
                                        column_store=column_store)
        self.mapper = ShardMapper(self.config["num-shards"])
        self.backend = backend
        self.http: Optional[FiloHttpServer] = None
        self.streams: Dict[int, object] = {}
        # ONE driver map for primary, adopted, and handed-back shards:
        # the per-shard single-writer invariant is "at most one entry
        # here, cluster-wide, per shard" (membership + chaos pin it)
        self.drivers: Dict[int, object] = {}
        self.gateway = None
        self.detector = None
        self.membership = None
        self.node_id: str = self.config["node-id"]
        self.owned_shards: list = []
        # rejoin deferral: ordinal shards a peer still served at startup
        # (it adopted them while this node was down); created only when
        # the peer hands them back through /admin/adopt
        self.deferred_shards: set = set()
        # elastic-recovery bookkeeping: origin node -> shards THIS node
        # adopted (crash or planned); node -> original assignment
        self._adopted: Dict[str, list] = {}
        self._reassign_lock = threading.Lock()
        self._original_shards: Dict[str, list] = {}
        self._gw_streams: Dict[int, object] = {}
        # process-sharded serving: the worker's control-plane client
        # (standalone/bus.py) + the watermark-gossip tick that rides it
        self.bus_client = None
        self._bus_tick_stop = threading.Event()
        self._bus_tick_thread: Optional[threading.Thread] = None
        # holds a share of the process's interpreter instruments
        # (obs/process.py) from start() to stop()
        self._instruments = False
        # self-monitoring (obs/selfmon.py): loop + its internal
        # dataset's dedicated stream/driver (None when off)
        self.selfmon = None
        self._selfmon_stream = None
        self._selfmon_driver = None
        # recording rules & alerting (filodb_tpu/rules): engine +
        # the reserved __rules__ dataset's stream/driver (None when no
        # rules are configured)
        self.rules = None
        self._rules_stream = None
        self._rules_driver = None

    def _make_qos_budgets(self):
        """Per-tenant token-bucket budgets from the qos-* knobs (None
        semantics live in TenantBudgets.enabled: rate 0 and no
        overrides = budgets off, the pre-QoS edge)."""
        from filodb_tpu.query.qos import TenantBudgets
        return TenantBudgets(
            default_rate=float(self.config.get("qos-tenant-rate", 0)
                               or 0),
            default_burst=float(self.config.get("qos-tenant-burst", 0)
                                or 0),
            overrides=dict(self.config.get("qos-tenant-overrides")
                           or {}))

    def _make_tracer(self):
        from filodb_tpu.obs.trace import Tracer, TraceExporter
        slow_ms = self.config.get("trace-slow-ms")
        if slow_ms is None:
            # tail retention inherits the slowlog threshold, so every
            # slow-query record links a retained (resolvable) trace
            slow_ms = self.config.get("slow-query-ms", 1000.0)
        exporter = None
        url = self.config.get("trace-export-url")
        if url:
            exporter = TraceExporter(
                str(url),
                batch_max=int(self.config.get("trace-export-batch", 64)),
                interval_s=float(self.config.get(
                    "trace-export-interval-s", 2.0)),
                queue_max=int(self.config.get(
                    "trace-export-queue", 1024))).start()
        return Tracer(
            enabled=bool(self.config.get("trace-enabled", False)),
            sample_rate=float(self.config.get("trace-sample-rate", 1.0)),
            max_traces=int(self.config.get("trace-max-traces", 256)),
            node=self.node_id,
            slow_ms=float(slow_ms or 0.0),
            exporter=exporter)

    def _make_profiler(self):
        from filodb_tpu.obs.profiler import SamplingProfiler
        if not self.config.get("profiler-enabled", False):
            return None
        return SamplingProfiler(
            hz=float(self.config.get("profiler-hz", 29.0)),
            max_stacks=int(self.config.get("profiler-max-stacks", 4096)),
            top_n=int(self.config.get("profiler-top-n", 20))).start()

    def _make_shard(self, shard: int):
        """One shard's full construction — tracker with quota overrides,
        flush-downsampler, store setup + bootstrap. Shared by startup and
        elastic adoption so adopted shards cannot silently diverge."""
        from filodb_tpu.core.cardinality import CardinalityTracker
        tracker = CardinalityTracker(
            tuple(self.config.get("card-default-quotas", ())))
        for pfx, quota in dict(
                self.config.get("card-quotas") or {}).items():
            tracker.set_quota([p for p in pfx.split(",") if p],
                              int(quota))
        # shard-registry maps (card_trackers/streams/drivers + the HTTP
        # shard-list publish) are mutated from adopt/release/handback
        # worker threads concurrently — every mutation rides
        # _reassign_lock (graftlint thread-unguarded-shared-state);
        # reads stay lock-free GIL-atomic snapshots
        with self._reassign_lock:
            self.card_trackers[shard] = tracker
        fds = None
        if self.config.get("flush-downsample") \
                and self.store.column_store is not None:
            from filodb_tpu.downsample.flush import FlushDownsampler
            fds = FlushDownsampler(
                self.store.column_store, self.config["dataset"], shard,
                DEFAULT_SCHEMAS,
                resolutions=tuple(self.config["downsample-resolutions"]))
        return self.store.setup(
            self.ref, shard,
            num_groups=self.config["groups-per-shard"],
            max_chunk_rows=self.config["max-chunks-size"],
            bootstrap=self.store.column_store is not None,
            card_tracker=tracker,
            flush_downsampler=fds)

    def start(self) -> "FiloServer":
        # GIL convoy mitigation on the serving path: handler threads do
        # short bursts of socket I/O between compute; with CPython's
        # default 5ms switch interval every GIL reacquisition after a
        # send/recv can stall a full interval behind a compute-bound
        # thread. A ~1ms interval keeps request threads interleaving.
        swi = self.config.get("gil-switch-interval-ms")
        if swi:
            import sys as _sys
            _sys.setswitchinterval(float(swi) / 1000.0)
        n = self.config["num-shards"]
        num_nodes = int(self.config.get("num-nodes", 1))
        ordinal = int(self.config.get("node-ordinal", 0))
        # seed discovery (akka-bootstrapper analogue): resolve the peer
        # map + this node's ordinal from DNS-SRV/Consul when no explicit
        # peer list is configured
        disc = self.config.get("discovery")
        if disc and not self.config.get("peers"):
            from filodb_tpu.parallel.discovery import discover_peers
            all_nodes = discover_peers(disc)
            adv = self.config.get("advertise-url")
            if adv is None:
                raise ValueError(
                    "discovery needs advertise-url to identify this "
                    "node among the discovered seeds")
            me = [nid for nid, url in all_nodes.items()
                  if url.rstrip("/") == adv.rstrip("/")]
            if len(me) != 1:
                raise ValueError(
                    f"advertise-url {adv!r} matched {len(me)} "
                    f"discovered seeds {sorted(all_nodes.values())}")
            ordinal = int(me[0].removeprefix("node"))
            num_nodes = len(all_nodes)
            self.config["num-nodes"] = num_nodes
            self.config["node-ordinal"] = ordinal
            self.config["peers"] = {nid: url for nid, url
                                    in all_nodes.items()
                                    if nid != me[0]}
        if num_nodes > 1:
            self.node_id = f"node{ordinal}"
            self.owned_shards = shards_for_ordinal(ordinal, num_nodes, n)
        else:
            self.node_id = self.config["node-id"]
            self.owned_shards = list(range(n))
        from filodb_tpu.core.cardinality import CardinalityTracker
        from filodb_tpu.core.spread import SpreadProvider
        self.spread_provider = SpreadProvider(
            int(self.config.get("default-spread", 1)),
            dict(self.config.get("spread-overrides") or {}))
        self.card_trackers = {}
        # rejoin deferral (parallel/membership.py): before creating a
        # shard this node owns by ordinal, ask the peers whether one of
        # them still SERVES it (it adopted the shard while this node
        # was down). Deferred shards are neither created nor ingested
        # here — the temporary owner hands them back make-before-break
        # through /admin/adopt, closing the dual-writer window the
        # legacy hard cutover opened.
        peer_claims: Dict[int, tuple] = {}
        elastic = bool(self.config.get("elastic-membership", True))
        if num_nodes > 1 and elastic:
            probe_peers = {k: v for k, v in
                           dict(self.config.get("peers") or {}).items()
                           if k != self.node_id}
            if probe_peers:
                from filodb_tpu.parallel.membership import \
                    probe_peer_claims
                peer_claims = probe_peer_claims(probe_peers,
                                                self.owned_shards)
        self.deferred_shards = set(peer_claims)
        for shard in self.owned_shards:
            if shard in self.deferred_shards:
                continue
            self._make_shard(shard)
        if num_nodes > 1:
            for i in range(num_nodes):
                owned_i = shards_for_ordinal(i, num_nodes, n)
                self._original_shards[f"node{i}"] = list(owned_i)
                for shard in owned_i:
                    self.mapper.assign(shard, f"node{i}")
        else:
            assign_shards_evenly(self.mapper, [self.node_id])
        streaming = bool(self.config.get("stream-dir"))
        # peer shards start ACTIVE optimistically; the failure detector
        # flips them DOWN when health checks fail. Own shards activate
        # immediately only without streaming (the ingestion drivers take
        # them through RECOVERY -> ACTIVE otherwise).
        owned = set(self.owned_shards) - self.deferred_shards
        for shard in range(n) if num_nodes > 1 else self.owned_shards:
            if shard in self.deferred_shards:
                continue
            if shard in owned and streaming:
                continue
            self.mapper.activate(shard)
        # deferred shards are owned by their claimer until handed back
        from filodb_tpu.parallel.shardmapper import ShardStatus
        for shard, (claimer, st) in sorted(peer_claims.items()):
            self.mapper.assign(shard, claimer)
            try:
                self.mapper.update(shard, ShardStatus(st), claimer)
            except ValueError:
                self.mapper.update(shard, ShardStatus.ACTIVE, claimer)
        if self.backend is None:
            # a backend that fails to construct fails the node: there is
            # no route from here to a numpy-oracle-only node
            from filodb_tpu.query.batcher import MicroBatcher
            from filodb_tpu.query.tpu import TpuBackend
            self.backend = TpuBackend(batcher=MicroBatcher(
                gather_window_s=float(self.config.get(
                    "batch-gather-window-ms", 1.0)) / 1000.0,
                max_batch=int(self.config.get("batch-max", 8)),
                enabled=bool(self.config.get("batch-enabled", True))))
        mesh_ex = None
        if self.config.get("mesh-enabled"):
            import jax

            from filodb_tpu.parallel.mesh import MeshExecutor, make_mesh
            if len(jax.devices()) > 1:
                mesh_ex = MeshExecutor(make_mesh())
        if mesh_ex is not None and self.backend is not None \
                and self.config.get("mesh-tile-serving", True):
            # multi-chip serving path: eligible aligned-tile cohorts
            # live sharded across the mesh and the slot-major
            # evaluators dispatch from the resident tiles (zero-copy
            # donated refreshes across flushes) — parallel/shardstore
            from filodb_tpu.parallel.shardstore import \
                ShardedTileEvaluator
            self.backend.mesh_eval = ShardedTileEvaluator(mesh_ex.mesh)
        ds_stores: Dict[str, object] = {}
        retention_ms = 0
        if (self.config.get("raw-retention-s")
                and self.store.column_store is not None):
            from filodb_tpu.downsample import DownsampledTimeSeriesStore
            retention_ms = int(self.config["raw-retention-s"]) * 1000
            ds_stores[self.ref.dataset] = DownsampledTimeSeriesStore(
                self.store.column_store, self.ref.dataset, n,
                resolutions=tuple(self.config["downsample-resolutions"]))
        peers = {k: v for k, v in
                 dict(self.config.get("peers") or {}).items()
                 if k != self.node_id}
        from filodb_tpu.parallel.resilience import (BreakerRegistry,
                                                    PeerResilience,
                                                    RetryPolicy)
        resilience = PeerResilience(
            retry=RetryPolicy(
                max_attempts=int(self.config.get(
                    "peer-retry-attempts", 3)),
                base_delay_s=float(self.config.get(
                    "peer-retry-base-delay-s", 0.05))),
            breakers=BreakerRegistry(
                failure_threshold=int(self.config.get(
                    "breaker-failure-threshold", 3)),
                reset_timeout_s=float(self.config.get(
                    "breaker-reset-s", 5.0))))
        self.http = FiloHttpServer(
            {self.ref.dataset: self.store.shards(self.ref)},
            backend=self.backend, shard_mapper=self.mapper,
            mesh_executor=mesh_ex,
            spread=int(self.config.get("default-spread", 1)),
            port=self.config["port"],
            ds_store_by_dataset=ds_stores,
            raw_retention_ms=retention_ms,
            query_limits=QueryLimits(
                series_limit=int(self.config.get("query-series-limit", 0)),
                sample_limit=int(self.config.get("query-sample-limit", 0))),
            spread_provider=self.spread_provider,
            node_id=self.node_id, peers=peers,
            buddies=dict(self.config.get("buddy-peers") or {}),
            partitions=dict(self.config.get("partitions") or {}),
            local_partitions=list(
                self.config.get("local-partitions") or ()),
            grpc_peers={k: v for k, v in dict(
                self.config.get("grpc-peers") or {}).items()
                if k != self.node_id},
            grpc_partitions=dict(
                self.config.get("grpc-partitions") or {}),
            query_timeout_s=float(self.config.get("query-timeout-s",
                                                  30.0)),
            resilience=resilience,
            plan_cache_size=int(self.config.get("plan-cache-size", 256)),
            results_cache_mb=float(
                self.config.get("results-cache-mb", 64)),
            results_cache_hot_window_ms=float(
                self.config.get("results-cache-hot-window-ms", 10_000)),
            max_inflight_queries=int(self.config.get(
                "max-inflight-queries", 4)),
            admission_wait_s=float(self.config.get(
                "admission-wait-s", 5.0)),
            qos_budgets=self._make_qos_budgets(),
            qos_degrade_max_steps=int(self.config.get(
                "qos-degrade-max-steps", 64)),
            qos_shed_degraded=bool(self.config.get(
                "qos-shed-degraded", True)),
            tracer=self._make_tracer(),
            profiler=self._make_profiler(),
            slow_query_ms=float(self.config.get("slow-query-ms",
                                                1000.0)),
            peer_fanout_workers=int(self.config.get(
                "peer-fanout-workers", 0) or 0),
            worker_id=self.config.get("worker-id"))
        # process-sharded serving: the shared public accept edge —
        # either this worker binds the public port itself with
        # SO_REUSEPORT (kernel balances connections across workers) or
        # it accepts on a listening socket inherited from the
        # supervisor (fd-passing fallback). Both feed the same handler
        # machinery as the private per-worker port.
        self.accept_port = None
        accept_sock = None
        if self.config.get("accept-fd") is not None:
            import socket as _socket
            accept_sock = _socket.socket(
                fileno=int(self.config["accept-fd"]))
            self.accept_port = accept_sock.getsockname()[1]
        elif self.config.get("accept-port"):
            accept_sock = bind_reuseport(
                str(self.config.get("accept-host", "127.0.0.1")),
                int(self.config["accept-port"]))
            if accept_sock is None:
                raise RuntimeError(
                    "accept-port configured but SO_REUSEPORT is "
                    "unavailable on this platform — run the supervisor "
                    "with fd passing (it detects this automatically)")
            self.accept_port = accept_sock.getsockname()[1]
        if accept_sock is not None:
            self.http.add_listener(accept_sock)
        # elastic membership: wire the planned-handoff coordinator
        # BEFORE the HTTP edge starts serving, so an adopt/hand-back
        # request arriving the instant the health endpoint answers
        # (the peer's failure detector reacts fast) finds it ready
        from filodb_tpu.parallel.membership import MembershipManager
        self.membership = MembershipManager(
            self, handoff_timeout_s=float(
                self.config.get("handoff-timeout-s", 30.0)))
        self.http.membership = self.membership
        self.http.start()
        self.grpc_server = None
        if self.config.get("grpc-port") is not None:
            from filodb_tpu.grpcsvc import GrpcQueryServer
            self.grpc_server = GrpcQueryServer(
                self.http, port=int(self.config["grpc-port"])).start()
            self.http.grpc_server = self.grpc_server   # /metrics gauge
        if peers:
            from filodb_tpu.parallel.cluster import FailureDetector
            shards_by_node = {node: self.mapper.shards_for_node(node)
                              for node in peers}
            grace = self.config.get("shard-reassign-grace-s")
            self.detector = FailureDetector(
                self.mapper, peers, shards_by_node,
                interval_s=float(self.config.get(
                    "failure-detect-interval-s", 0.5)),
                threshold=int(self.config.get(
                    "failure-detect-threshold", 3)),
                reassign_grace_s=(float(grace) if grace is not None
                                  else None),
                on_node_down=self._on_node_down,
                on_node_up=self._on_node_up,
                grpc_peer_sink=self.http.grpc_peers,
                peer_state_sink=self.http.peer_watermarks).start()
            # the health body advertises this node's down-view (quorum
            # input) and served-shard statuses (gossip) to its peers
            self.http.detector = self.detector
        # process-sharded serving: connect the control plane. Local
        # mapper transitions are published to siblings the instant they
        # commit (per-process plan/results caches must not serve
        # extents keyed on a stale topology for a detector-poll
        # interval), sibling transitions are applied to the local
        # mapper (whose subscribers invalidate the caches), schema
        # invalidations broadcast host-wide, and watermark/backfill
        # gossip ticks faster than the health-body path.
        if self.config.get("bus-port"):
            from filodb_tpu.standalone.bus import BusClient
            bc = BusClient(int(self.config["bus-port"]),
                           int(self.config.get("worker-id") or 0),
                           self.node_id)
            bc.on("topology", self._bus_apply_topology)
            bc.on("schema", self._bus_apply_schema)
            bc.on("watermarks", self._bus_apply_watermarks)
            bc.on("worker-exit", self._bus_apply_worker_exit)
            bc.on("worker-up", self._bus_apply_worker_up)
            self.bus_client = bc.start()
            self.http.bus_client = bc
            self.mapper.subscribe(self._bus_publish_topology)
            tick_s = float(self.config.get(
                "bus-watermark-interval-s", 0.25))
            if tick_s > 0:
                self._bus_tick_thread = threading.Thread(
                    target=self._bus_watermark_run, args=(tick_s,),
                    daemon=True, name="bus-watermark-tick")
                self._bus_tick_thread.start()
        self.tenant_metering = None
        meter_s = float(self.config.get("tenant-metering-interval-s", 0))
        if meter_s > 0 and self.card_trackers:
            from filodb_tpu.core.metering import TenantMetering
            self.tenant_metering = TenantMetering(
                self.card_trackers, interval_s=meter_s).start()
            self.http.tenant_metering = self.tenant_metering
        # host-level series from day one: RSS/fds/threads/GC/uptime +
        # filodb_build_info ride every exposition build (and therefore
        # the self-monitoring ingest below)
        # ... and what the interpreter itself costs a request: the
        # collection timer and the interpreter probe, once a process
        obs_process.register_process_collector()
        obs_process.acquire_instruments()
        self._instruments = True
        if streaming:
            self._start_ingestion()
        if self.config.get("self-monitor"):
            self._start_selfmon()
        if self.config.get("rules") or self.config.get("rules-file"):
            self._start_rules()
        # serving-path GC hygiene: move the (large, permanent) startup
        # object graph out of the collector's reach and make full
        # collections 10x rarer — a gen-2 sweep over jax/XLA module
        # state stalls every in-flight query (how long, and how often,
        # is filodb_gc_pause_seconds_total{generation="2"} and
        # filodb_gc_stalls_total). The freeze precedes any ingest: what
        # the store holds afterwards is NOT frozen, and a full
        # collection walks it
        if self.config.get("gc-freeze", True):
            import gc
            gc.collect()
            gc.freeze()
            t0, t1, t2 = gc.get_threshold()
            gc.set_threshold(t0, t1, max(t2, 100))
        return self

    # -- control plane (standalone/bus.py) --------------------------------
    def _bus_publish_topology(self, ev) -> None:
        """ShardMapper subscriber: ship every locally-witnessed FSM
        transition to the siblings. BusClient.publish() is a no-op on
        the bus reader thread (the apply→republish loop breaker) and on
        transport failure (detector gossip re-converges)."""
        bc = self.bus_client
        if bc is None:
            return
        bc.publish({"type": "topology", "shard": int(ev.shard),
                    "status": ev.status.value, "node": ev.node,
                    "epoch": self.mapper.topology_epoch})

    def _bus_apply_topology(self, ev: Dict) -> None:
        """A sibling witnessed a shard FSM transition: converge the
        local mapper (idempotent — the mapper bumps its epoch only when
        the ownership edge actually rewires), which fires this worker's
        own subscribers and therefore the plan/results-cache
        invalidation. Already-converged events are dropped without
        touching the caches."""
        from filodb_tpu.parallel.shardmapper import ShardStatus
        try:
            shard = int(ev.get("shard", -1))
            st = ShardStatus(str(ev.get("status")))
        except (TypeError, ValueError):
            return
        if not (0 <= shard < self.mapper.num_shards):
            return
        node = ev.get("node")
        if self.mapper.status(shard) is st \
                and self.mapper.node_of(shard) == node:
            return
        self.mapper.update(shard, st, node)

    def _bus_apply_schema(self, ev: Dict) -> None:
        if self.http is not None:
            self.http.invalidate_plan_cache(
                str(ev.get("reason") or "schema-bus"))

    def _bus_apply_watermarks(self, ev: Dict) -> None:
        """Sibling watermark/backfill gossip → the same per-peer sink
        the failure detector fills from health bodies, so the results
        cache's freshness horizon tracks sibling ingest at bus latency
        instead of poll latency."""
        origin = str(ev.get("origin") or "")
        if not origin or origin == self.node_id or self.http is None:
            return
        def _ints(raw):
            try:
                return {int(k): int(v) for k, v in (raw or {}).items()}
            except (TypeError, ValueError):
                return {}
        self.http.peer_watermarks[origin] = {
            "watermarks": _ints(ev.get("watermarks")),
            "epochs": _ints(ev.get("backfill_epochs")),
            "topo_epoch": int(ev.get("topo_epoch") or 0),
        }

    @staticmethod
    def _worker_ordinal(node: str) -> Optional[int]:
        try:
            return int(node.removeprefix("node"))
        except ValueError:
            return None

    def _bus_apply_worker_exit(self, ev: Dict) -> None:
        node = str(ev.get("node") or "")
        if self.detector is not None and node:
            self.detector.note_peer_exit(node)
        # single-owner rule scheduling: a dead sibling triggers
        # re-election (the next-lowest ALIVE ordinal takes over at the
        # next interval boundary — no duplicated tick by construction)
        ordinal = self._worker_ordinal(node)
        if self.rules is not None and ordinal is not None:
            self.rules.note_worker_exit(ordinal)

    def _bus_apply_worker_up(self, ev: Dict) -> None:
        node = str(ev.get("node") or "")
        if self.detector is not None and node:
            self.detector.note_peer_up(node)
        ordinal = self._worker_ordinal(node)
        if self.rules is not None and ordinal is not None:
            self.rules.note_worker_up(ordinal)

    def _bus_gossip_once(self) -> None:
        """One watermark/backfill gossip beat onto the bus (the same
        per-shard fields the health body advertises)."""
        watermarks: Dict[str, int] = {}
        epochs: Dict[str, int] = {}
        for lst in self.http.shards_by_dataset.values():
            for i, s in enumerate(lst):
                n = getattr(s, "shard_num", i)
                wm = getattr(s, "ingest_watermark_ms", None)
                if wm is not None:
                    watermarks[str(n)] = int(wm)
                epochs[str(n)] = int(getattr(
                    s, "ingest_backfill_epoch", 0) or 0)
        self.bus_client.publish({
            "type": "watermarks", "watermarks": watermarks,
            "backfill_epochs": epochs,
            "topo_epoch": self.mapper.topology_epoch})

    @thread_root("bus-watermark-tick")
    def _bus_watermark_run(self, interval_s: float) -> None:
        while not self._bus_tick_stop.wait(interval_s):
            try:
                self._bus_gossip_once()
            except Exception:   # noqa: BLE001 — gossip must not die
                pass

    def _start_ingestion(self) -> None:
        """Streaming path: per-shard durable stream logs + ingestion
        drivers (recovery -> active), plus the optional influx gateway
        (NewFiloServerMain.start: memstore, ingestion, http)."""
        import os

        from filodb_tpu.ingest import LogIngestionStream
        stream_dir = self.config["stream-dir"]
        n = self.config["num-shards"]
        gc_s = float(self.config.get("stream-group-commit-ms", 0)) / 1000
        for shard in self.owned_shards:
            if shard in self.deferred_shards:
                continue        # a peer still serves it (single-writer)
            path = os.path.join(stream_dir, f"shard={shard}", "stream.log")
            stream = LogIngestionStream(
                path, DEFAULT_SCHEMAS, group_commit_s=gc_s)
            with self._reassign_lock:
                self.streams[shard] = stream
        for shard in sorted(self.streams):
            drv = self._make_driver(shard, self.streams[shard])
            with self._reassign_lock:
                self.drivers[shard] = drv
            drv.start()
        if self.config.get("gateway-port") is not None:
            from filodb_tpu.gateway.server import GatewayServer
            # the gateway is the producer edge: in multi-node mode it
            # publishes to EVERY shard's stream (kafka/KafkaContainerSink
            # writes all partitions), not just this node's consumer set.
            # One gateway process per stream set — frames are appended
            # whole, but two gateways on one log would interleave.
            gw_streams = dict(self.streams)
            if int(self.config.get("num-nodes", 1)) > 1:
                for shard in range(n):
                    if shard not in gw_streams:
                        path = os.path.join(stream_dir, f"shard={shard}",
                                            "stream.log")
                        gw_streams[shard] = LogIngestionStream(
                            path, DEFAULT_SCHEMAS, group_commit_s=gc_s)
            self._gw_streams = gw_streams
            self.gateway = GatewayServer(
                gw_streams, DEFAULT_SCHEMAS, num_shards=n,
                spread=int(self.config.get("default-spread", 1)),
                spread_provider=self.spread_provider,
                port=int(self.config["gateway-port"])).start()
            if self.http is not None:
                # remote-ingest edge with backpressure: the HTTP
                # /api/v1/ingest/influx route publishes through the
                # same builders/streams as the TCP gateway
                self.http.gateway = self.gateway

    # -- reserved internal datasets (selfmon + rules write-back) ----------
    def _setup_internal_dataset(self, dataset: str, subdir: str):
        """One internal shard per process for a reserved dataset,
        numbered by worker ordinal so a supervisor fleet sharing
        data/stream dirs never collides. The shard gets its OWN
        CardinalityTracker — internal series are invisible to user
        cardinality accounting and quotas. With a stream-dir the
        producer appends to a dedicated WAL and a normal
        IngestionDriver replays it (recovery included: derived series
        survive restarts); memory-only deployments ingest directly and
        flush explicitly so the freshness watermark still advances.
        Returns ``(shard, stream, driver)`` (stream/driver None without
        a stream-dir)."""
        import os

        from filodb_tpu.core.cardinality import CardinalityTracker
        wid = self.config.get("worker-id")
        shard_num = int(wid or 0)
        ref = DatasetRef(dataset)
        shard = self.store.setup(
            ref, shard_num,
            num_groups=2,
            max_chunk_rows=self.config["max-chunks-size"],
            bootstrap=self.store.column_store is not None,
            card_tracker=CardinalityTracker())
        self.http.shards_by_dataset[dataset] = self.store.shards(ref)
        stream = driver = None
        if self.config.get("stream-dir"):
            from filodb_tpu.ingest import (IngestionDriver,
                                           LogIngestionStream)
            path = os.path.join(self.config["stream-dir"], subdir,
                                f"shard={shard_num}", "stream.log")
            stream = LogIngestionStream(
                path, DEFAULT_SCHEMAS,
                group_commit_s=float(self.config.get(
                    "stream-group-commit-ms", 0)) / 1000)
            driver = IngestionDriver(
                shard, stream, mapper=None,
                flush_interval_s=float(self.config.get(
                    "flush-interval-s", 2.0)),
                ingest_batch_records=int(self.config.get(
                    "ingest-batch-records", 64)))
            driver.start()
        return shard, stream, driver

    # -- self-monitoring (obs/selfmon.py) ---------------------------------
    def _start_selfmon(self) -> None:
        """Wire the reserved ``__selfmon__`` dataset and start the
        loop (see _setup_internal_dataset for the shard/WAL model;
        internal series are stamped with a ``worker`` label and each
        worker serves its own via a strictly-local planner)."""
        from filodb_tpu.obs.selfmon import SELFMON_DATASET, SelfMonitor
        wid = self.config.get("worker-id")
        shard, stream, driver = self._setup_internal_dataset(
            SELFMON_DATASET, "selfmon")
        self._selfmon_stream = stream
        self._selfmon_driver = driver
        self.selfmon = SelfMonitor(
            self.http.build_exposition, shard,
            schemas=DEFAULT_SCHEMAS, stream=stream,
            interval_s=float(self.config.get(
                "self-monitor-interval-s", 5.0)),
            node=self.node_id,
            worker_id=int(wid) if wid is not None else None,
            flush_every_ticks=int(self.config.get(
                "self-monitor-flush-ticks", 4)))
        self.http.selfmon = self.selfmon
        self.selfmon.start()

    # -- recording rules & alerting (filodb_tpu/rules) --------------------
    def _start_rules(self) -> None:
        """Load the rule groups and start the scheduler.

        Evaluations run through ``FiloHttpServer.rule_eval_range`` —
        the normal plan-cache/results-cache/QoS path under the reserved
        ``__rules__`` tenant; recorded series and ALERTS state series
        write back through the shared IngestWriteBack rail into the
        reserved ``__rules__`` dataset (same shard/WAL model as
        selfmon). Under the supervisor every worker builds the engine
        from the propagated config, but only the lowest ALIVE worker
        ordinal evaluates; the bus ``worker-exit``/``worker-up``
        lifecycle events re-elect (wired in the bus handlers above)."""
        from filodb_tpu.obs.writeback import IngestWriteBack
        from filodb_tpu.rules import (RULES_DATASET, RulesEngine,
                                      WebhookNotifier, load_groups,
                                      load_rules_file)
        if self.config.get("rules"):
            groups = load_groups(self.config["rules"])
        else:
            groups = load_rules_file(self.config["rules-file"])
        if not groups:
            return
        shard, stream, driver = self._setup_internal_dataset(
            RULES_DATASET, "rules")
        self._rules_stream = stream
        self._rules_driver = driver
        notifier = None
        url = self.config.get("rules-webhook-url")
        if url:
            notifier = WebhookNotifier(url).start()
        wid = self.config.get("worker-id")
        self.rules = RulesEngine(
            groups,
            evaluator=self.http.rule_eval_range,
            writeback=IngestWriteBack(shard, schemas=DEFAULT_SCHEMAS,
                                      stream=stream),
            default_dataset=self.config["dataset"],
            node=self.node_id,
            worker_id=int(wid) if wid is not None else None,
            num_workers=int(self.config.get("num-nodes", 1) or 1),
            span_steps=int(self.config.get("rules-eval-span-steps", 8)),
            notifier=notifier,
            # supervised workers stand by until their own worker-up
            # broadcast (single-owner handover in one bus beat);
            # bus-less processes are announced from birth
            announced=not self.config.get("bus-port"))
        self.http.rules = self.rules
        # topology/schema invalidations reach the engine's rule-plan
        # cache through the plan cache's listener chain (the same chain
        # the results cache rides) — see the @cache_registry inventory
        self.http.plan_cache.add_invalidation_listener(
            self.rules.invalidate_plans)
        self.rules.start()

    # -- elastic recovery (shard reassignment on node loss) ---------------
    # ShardManager.scala:28 assignShardsToNodes / IngestionActor.scala:297
    # recovery protocol: every survivor independently computes the same
    # round-robin table; the shard's new owner bootstraps index + chunks
    # from the ColumnStore, replays the shared stream log from the
    # checkpoint watermark (RECOVERY with progress), then serves it.

    def _make_driver(self, shard: int, stream):
        """One ingestion driver, unstarted — shared by startup, crash
        adoption, planned adoption, and handoff rollback so a shard's
        writer is always built the same way."""
        from filodb_tpu.ingest import IngestionDriver
        # a node on its own is its streams' only writer (its gateway,
        # _start_ingestion) and wakes a driver at every append; with
        # peers another node's gateway appends to this node's logs and
        # a driver can only poll for that
        alone = int(self.config.get("num-nodes", 1)) == 1
        return IngestionDriver(
            self.store.get_shard(self.ref, shard), stream,
            mapper=self.mapper,
            idle_wait_s=0.5 if alone else None,
            flush_every_records=self.config.get("flush-every-records"),
            flush_interval_s=float(self.config.get("flush-interval-s",
                                                   2.0)),
            max_resident_samples=int(
                self.config.get("max-resident-samples", 0)),
            ingest_batch_records=int(
                self.config.get("ingest-batch-records", 64)),
            max_decode_cache_bytes=int(float(
                self.config.get("decode-cache-mb", 0)) * (1 << 20)),
            max_quarantined_records=int(self.config.get(
                "integrity-max-quarantined-records", 0)))

    def _restart_driver(self, shard: int) -> None:
        """Handoff rollback: the successor never went ACTIVE — resume
        ingesting locally from the checkpoint watermark (the recovery
        replay covers the stopped window; the shard never left this
        node's serving set)."""
        if not self.config.get("stream-dir"):
            return
        import os

        from filodb_tpu.ingest import LogIngestionStream
        stream = self.streams.get(shard)
        if stream is None:
            path = os.path.join(self.config["stream-dir"],
                                f"shard={shard}", "stream.log")
            stream = LogIngestionStream(
                path, DEFAULT_SCHEMAS,
                group_commit_s=float(self.config.get(
                    "stream-group-commit-ms", 0)) / 1000)
            with self._reassign_lock:
                self.streams[shard] = stream
        drv = self._make_driver(shard, stream)
        with self._reassign_lock:
            self.drivers[shard] = drv
        drv.start()

    def _on_node_down(self, node: str) -> None:
        import threading

        from filodb_tpu.parallel.cluster import reassign_dead_shards
        from filodb_tpu.parallel.shardmapper import ShardStatus
        dead = sorted(self.mapper.shards_for_node(node))
        if not dead:
            return
        survivors = [self.node_id] + (self.detector.alive_peers()
                                      if self.detector else [])
        table = reassign_dead_shards(dead, survivors)
        with self._reassign_lock:
            self._adopted[node] = []
        mine = []
        for sh, owner in table.items():
            self.mapper.assign(sh, owner)
            if owner == self.node_id:
                self.mapper.update(sh, ShardStatus.RECOVERY, owner)
                mine.append(sh)
            else:
                # another survivor adopts it; hold RECOVERY until the
                # adopter's health body advertises it (the detector's
                # status gossip promotes it ACTIVE) — queries routed
                # meanwhile carry a partial-result warning instead of
                # silently missing the bootstrapping shard
                self.mapper.update(sh, ShardStatus.RECOVERY, owner)

        @thread_root("crash-adopt")
        def adopt_all():
            # off the detector's poll thread: ColumnStore bootstrap can
            # take long, and health checks must keep running meanwhile
            if self.membership is not None:
                self.membership.note_crash_adoption()
            for sh in mine:
                with self._reassign_lock:
                    if node not in self._adopted:
                        return           # owner came back mid-adoption
                try:
                    self._adopt_shard(sh)
                    with self._reassign_lock:
                        if node in self._adopted:
                            self._adopted[node].append(sh)
                            continue
                    # owner recovered while we bootstrapped: hand back
                    self._release_shard(sh)
                except Exception:
                    self._release_shard(sh)      # drop partial state
                    self.mapper.update(sh, ShardStatus.ERROR,
                                       self.node_id)
        threading.Thread(target=adopt_all, daemon=True,
                         name=f"adopt-{node}").start()

    def _on_node_up(self, node: str) -> None:
        import threading

        from filodb_tpu.parallel.shardmapper import ShardStatus
        if self.membership is not None \
                and self.config.get("elastic-membership", True):
            # planned hand-back: each adopted shard replays and flips
            # ACTIVE on its home node BEFORE this node releases it —
            # the same make-before-break handoff the drain path runs,
            # replacing the legacy hard cutover below
            self.membership.handback(node)
            return
        with self._reassign_lock:
            mine = self._adopted.pop(node, [])
        # legacy hard cutover: hand every reassigned shard back to its
        # original owner at once (each node recomputes identically; the
        # returned node re-bootstraps from the shared store + streams
        # on its own startup). Held in RECOVERY until the owner's
        # health body advertises the shard — the detector's status
        # gossip promotes it, so queries carry a partial-result warning
        # instead of silently missing data while the owner bootstraps
        for sh in self._original_shards.get(node, []):
            self.mapper.assign(sh, node)
            self.mapper.update(sh, ShardStatus.RECOVERY, node)

        @thread_root("crash-release")
        def release_all():
            # off the poll thread: driver stops join + flush (the same
            # reason adoption runs in the background)
            for sh in mine:
                self._release_shard(sh)
        if mine:
            threading.Thread(target=release_all, daemon=True,
                             name=f"release-{node}").start()

    def _adopt_shard(self, shard: int, on_event=None,
                     register=None) -> None:
        import os

        from filodb_tpu.parallel.shardmapper import ShardStatus
        with self._reassign_lock:
            self.deferred_shards.discard(shard)   # hand-back on rejoin
        self._make_shard(shard)
        # publish the widened local shard list to the HTTP layer (atomic
        # rebind; request handlers read the dict per request) BEFORE
        # claiming ownership in the mapper: a query planned in between
        # would see "owned by me" with no local shard and silently drop
        # it — published-but-unclaimed just routes to the previous
        # owner (planned handoff) or stays DOWN (crash path) instead
        with self._reassign_lock:
            self.http.shards_by_dataset[self.ref.dataset] = \
                self.store.shards(self.ref)
        self.mapper.update(shard, ShardStatus.RECOVERY, self.node_id)
        if self.config.get("stream-dir"):
            from filodb_tpu.ingest import LogIngestionStream
            path = os.path.join(self.config["stream-dir"],
                                f"shard={shard}", "stream.log")
            stream = LogIngestionStream(
                path, DEFAULT_SCHEMAS,
                group_commit_s=float(self.config.get(
                    "stream-group-commit-ms", 0)) / 1000)
            with self._reassign_lock:
                self.streams[shard] = stream  # gateway routes to it too
            drv = self._make_driver(shard, stream)
            if on_event is not None:
                # planned adoption: membership clears the read redirect
                # when the replay completes (driver flips ACTIVE)
                drv.on_event = on_event
            if register is None:
                with self._reassign_lock:
                    self.drivers[shard] = drv
                drv.start()
            elif register(drv):
                # planned adoption: registration is the single-writer
                # gate — it is refused (atomically with the abort path)
                # when the handoff was cancelled mid-bootstrap, so a
                # writer never starts after the draining owner resumed
                drv.start()
        else:
            self.mapper.update(shard, ShardStatus.ACTIVE, self.node_id)

    def _release_shard(self, shard: int) -> None:
        # registry pops ride _reassign_lock; the blocking teardown
        # (driver stop() joins its thread, stream close() syncs the
        # log tail) runs strictly outside it
        with self._reassign_lock:
            drv = self.drivers.pop(shard, None)
            stream = self.streams.pop(shard, None)
            self.card_trackers.pop(shard, None)
        if drv is not None:
            drv.stop()
        if stream is not None and self._gw_streams.get(shard) \
                is not stream:
            # close by OBJECT identity: if the local gateway publishes
            # through this very stream (a draining node keeps its
            # producer edge alive), only drop the consumer reference
            try:
                stream.close()
            except OSError:
                pass
        self.store.remove_shard(self.ref, shard)
        with self._reassign_lock:
            self.http.shards_by_dataset[self.ref.dataset] = \
                self.store.shards(self.ref)
        if self.membership is not None:
            self.membership.note_release()

    def seed_dev_data(self, n_samples: int = 360, n_instances: int = 4,
                      start_ms: Optional[int] = None) -> int:
        """Dev loop seed (dev-gateway.sh + TestTimeseriesProducer)."""
        from filodb_tpu.gateway.producer import (TestTimeseriesProducer,
                                                 ingest_builders)
        producer = TestTimeseriesProducer(
            DEFAULT_SCHEMAS, num_shards=self.config["num-shards"])
        if start_ms is None:
            start_ms = (int(time.time()) - n_samples * 10) * 1000
        owned = set(self.owned_shards) - set(self.deferred_shards)

        def _mine(builders):
            return {sh: b for sh, b in builders.items() if sh in owned}
        rows = 0
        rows += ingest_builders(self.store, self.ref,
                                _mine(producer.gauges(start_ms, n_samples,
                                                      n_instances)))
        rows += ingest_builders(self.store, self.ref,
                                _mine(producer.counters(start_ms, n_samples,
                                                        n_instances)))
        rows += ingest_builders(self.store, self.ref,
                                _mine(producer.histograms(start_ms,
                                                          n_samples)))
        self.store.flush_all(self.ref)
        return rows

    def stop(self) -> None:
        if self._instruments:
            self._instruments = False
            obs_process.release_instruments()
        if self.rules is not None:
            self.rules.stop()
        if self._rules_driver is not None:
            self._rules_driver.stop()
        if self._rules_stream is not None:
            try:
                self._rules_stream.close()
            except OSError:
                pass
        if self.selfmon is not None:
            self.selfmon.stop()
        if self._selfmon_driver is not None:
            self._selfmon_driver.stop()
        if self._selfmon_stream is not None:
            try:
                self._selfmon_stream.close()
            except OSError:
                pass
        self._bus_tick_stop.set()
        if self._bus_tick_thread is not None:
            self._bus_tick_thread.join(timeout=5)
        if self.bus_client is not None:
            self.bus_client.stop()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop()
        if getattr(self, "tenant_metering", None) is not None:
            self.tenant_metering.stop()
        if self.detector is not None:
            self.detector.stop()
        if self.gateway is not None:
            self.gateway.stop()
        for drv in list(self.drivers.values()):
            drv.stop()
        for stream in self.streams.values():
            stream.close()
        for shard, stream in self._gw_streams.items():
            # close by OBJECT identity: an adopted shard put a different
            # stream object in self.streams for the same path
            if stream is not self.streams.get(shard):
                stream.close()
        if self.http:
            if self.http.profiler is not None:
                self.http.profiler.stop()
            if self.http.tracer is not None \
                    and self.http.tracer.exporter is not None:
                self.http.tracer.exporter.stop()
            self.http.stop()

    @property
    def port(self) -> int:
        return self.http.port if self.http else -1


@thread_root("main-idle")
def _main_idle() -> None:
    # the main thread parks here for the life of the process; a
    # registered root so the sampling profiler attributes it instead
    # of counting a permanently-asleep thread as unattributed
    while True:
        time.sleep(3600)


def _device_or_exit() -> Dict:
    """The device this node runs on, as jax reports it. The CPU backend
    is served only where the environment asks for it and for nothing
    else (``JAX_PLATFORMS=cpu`` exactly — ``tpu,cpu`` is a fallback list,
    not a request for the CPU); a node that was meant for an accelerator
    and came up on anything else exits with the reason instead of
    serving from the host."""
    import os

    import jax
    want = os.environ.get("JAX_PLATFORMS", "")
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"filodb-tpu server: no JAX device "
                 f"(JAX_PLATFORMS={want!r}): {e}")
    platform = devs[0].platform
    if platform == "cpu" and want.strip().lower() != "cpu":
        sys.exit("filodb-tpu server: JAX found no accelerator and came up "
                 f"on the CPU (JAX_PLATFORMS={want!r}); set "
                 "JAX_PLATFORMS=cpu, and nothing else in it, to run a CPU "
                 "node on purpose")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="filodb-tpu-server")
    p.add_argument("--config", help="JSON config file")
    p.add_argument("--port", type=int)
    p.add_argument("--num-shards", type=int)
    p.add_argument("--dataset")
    p.add_argument("--data-dir")
    p.add_argument("--stream-dir")
    p.add_argument("--gateway-port", type=int)
    p.add_argument("--self-monitor", action="store_true", default=None,
                   help="ingest this node's own metrics into the "
                        "reserved __selfmon__ dataset (PromQL over "
                        "our own telemetry)")
    p.add_argument("--rules-file",
                   help="Prometheus-style recording/alerting rule "
                        "file evaluated in-process (validate with "
                        "python -m filodb_tpu.rules --check)")
    p.add_argument("--seed-dev-data", action="store_true",
                   help="generate dev series on startup")
    args = p.parse_args(argv)
    config: Dict = {}
    if args.config:
        with open(args.config) as f:
            config.update(json.load(f))
    for k in ("port", "num_shards", "dataset", "data_dir", "stream_dir",
              "gateway_port", "self_monitor", "rules_file"):
        v = getattr(args, k)
        if v is not None:
            config[k.replace("_", "-")] = v
    from filodb_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()       # before the first JAX use
    device = _device_or_exit()
    server = FiloServer(config).start()
    if args.seed_dev_data or config.get("seed-dev-data"):
        rows = server.seed_dev_data(
            n_samples=int(config.get("seed-samples", 360)),
            n_instances=int(config.get("seed-instances", 4)),
            start_ms=config.get("seed-start-ms"))
        print(f"seeded {rows} dev samples", file=sys.stderr)
    # machine-readable startup line (test harness / dev scripts read this)
    gw = server.gateway.port if server.gateway is not None else None
    gp = server.grpc_server.port if getattr(server, "grpc_server", None) \
        is not None else None
    line = {"port": server.port, "gateway_port": gw, "grpc_port": gp,
            "device": device}
    if getattr(server, "accept_port", None) is not None:
        line["accept_port"] = server.accept_port
        line["worker_id"] = server.config.get("worker-id")
    print(json.dumps(line), flush=True)
    print(f"filodb-tpu server listening on :{server.port}", file=sys.stderr)
    try:
        _main_idle()
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
