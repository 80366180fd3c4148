"""End-to-end latency under load: the gatling-equivalent harness
(gatling/src/test FiloDBSimulation; conf/promperf-*.conf).

Starts a REAL standalone node (subprocess: gateway TCP ingest -> durable
streams -> ingestion drivers -> HTTP), seeds a working set, then drives
a CONCURRENCY SWEEP (1/8/32/64 in-flight clients) of query_range
traffic while the gateway keeps ingesting live samples. Clients hold
persistent HTTP/1.1 keep-alive connections (gatling's default — the
server speaks HTTP/1.1 so the per-request TCP handshake + thread spawn
disappears from steady-state serving). Reports client-observed p50/p95
latency and qps per level, the serving fast path's micro-batcher
occupancy (scraped from /metrics deltas), and the server span timings
(parse/plan/exec + plan-cache disposition) from the final response.

Headline fields (value/p95_ms/qps) come from the 8-client level for
continuity with earlier BENCH rounds.

A second DASHBOARD scenario re-issues the same query texts with a
sliding window from 8 clients — the refresh pattern the results cache
(query/resultcache.py) targets — and reports cache-off vs warm-cache
qps/p50 plus the hit ratio and cached-steps-served scraped from
/metrics ("dashboard" in the output JSON).

A third WORKER SWEEP drives the process-sharded serving tier
(standalone/supervisor.py): for 1/2/4/N worker processes behind one
SO_REUSEPORT public port, a fixed closed-loop client level measures
e2e qps/p50 plus per-worker qps and batcher occupancy (scraped from
each worker's private /metrics), and pins byte-identity of the data
section against the 1-worker deployment ("worker_sweep" in the output
JSON). The GIL plateau only breaks with real cores: on a 1-core rig
the sweep documents the overhead floor, on a >=4-core host it is the
>=3x acceptance measurement.

Prints ONE JSON line.
"""

import http.client
import json
import os
import pathlib
import select
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
T0 = 1_600_000_000
N_INSTANCES = 16
SEED_SAMPLES = 360             # 1h at 10s (the dev-seed
# producer is a Python loop; bigger sets take minutes to seed)
LEVELS = (1, 8, 32, 64)
HEADLINE_LEVEL = 8
QUERIES = [
    "rate(http_requests_total[5m])",
    "sum(rate(http_requests_total[5m])) by (instance)",
    "avg_over_time(heap_usage[10m])",
    "max(heap_usage) by (instance)",
]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


class KeepAliveClient:
    """One persistent HTTP/1.1 keep-alive connection per client thread,
    speaking raw sockets with pre-built request bytes — what native
    load generators (wrk, gatling) do, so the harness measures the
    SERVER, not Python's http.client object machinery."""

    def __init__(self, port: int):
        self.port = port
        self.sock = None
        self.buf = b""

    def _connect(self):
        self.sock = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=120)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def get_raw(self, path, **params) -> bytes:
        qs = urllib.parse.urlencode(params, doseq=True)
        req = (f"GET {path}?{qs} HTTP/1.1\r\n"
               f"Host: 127.0.0.1\r\nAccept-Encoding: identity\r\n\r\n"
               ).encode()
        for attempt in (0, 1):
            if self.sock is None:
                self._connect()
            try:
                self.sock.sendall(req)
                return self._read_response()
            except OSError:
                # server closed the idle connection: reconnect once
                self.close()
                if attempt:
                    raise

    def _read_response(self) -> bytes:
        # headers
        while b"\r\n\r\n" not in self.buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise OSError("connection closed mid-response")
            self.buf += chunk
        head, self.buf = self.buf.split(b"\r\n\r\n", 1)
        clen = 0
        for ln in head.split(b"\r\n")[1:]:
            k, _, v = ln.partition(b":")
            if k.lower() == b"content-length":
                clen = int(v.strip())
                break
        while len(self.buf) < clen:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise OSError("connection closed mid-body")
            self.buf += chunk
        body, self.buf = self.buf[:clen], self.buf[clen:]
        if not head.startswith(b"HTTP/1.1 200") \
                and not head.startswith(b"HTTP/1.0 200"):
            raise AssertionError(head.split(b"\r\n", 1)[0] + b" "
                                 + body[:120])
        return body

    def get(self, path, **params):
        return json.loads(self.get_raw(path, **params))

    def close(self):
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None
            self.buf = b""

def _scrape_metric(client, name):
    try:
        body = client.get_raw("/metrics").decode()
    except (OSError, AssertionError):
        return 0.0
    for ln in body.splitlines():
        # family with labels or bare (label-less gauges print no braces)
        if ln.startswith(f"filodb_{name}{{") \
                or ln.startswith(f"filodb_{name} "):
            try:
                return float(ln.rsplit(" ", 1)[1])
            except ValueError:
                return 0.0
    return 0.0


def _scrape_histogram(client, name):
    """{le_seconds: cumulative_count} + total count for one histogram
    family from /metrics (filodb_<name>_bucket lines)."""
    try:
        body = client.get_raw("/metrics").decode()
    except (OSError, AssertionError):
        return {}, 0
    buckets = {}
    count = 0
    for ln in body.splitlines():
        if ln.startswith(f"filodb_{name}_bucket{{le="):
            le_s = ln.split('le="', 1)[1].split('"', 1)[0]
            le = float("inf") if le_s == "+Inf" else float(le_s)
            buckets[le] = float(ln.rsplit(" ", 1)[1])
        elif ln.startswith(f"filodb_{name}_count"):
            count = float(ln.rsplit(" ", 1)[1])
    return buckets, count


def _hist_quantiles(b0, c0, b1, c1, qs=(0.5, 0.95, 0.99)):
    """Quantiles (ms) from the DELTA of two cumulative-bucket
    snapshots — i.e. what a PromQL histogram_quantile(rate(...)) would
    report for the measurement window (linear interpolation within the
    winning bucket)."""
    les = sorted(b1)
    deltas = []
    prev = 0.0
    for le in les:
        cum = b1[le] - b0.get(le, 0.0)
        deltas.append(cum - prev)
        prev = cum
    total = c1 - c0
    if total <= 0:
        return {q: float("nan") for q in qs}
    out = {}
    for q in qs:
        rank = q * total
        cum = 0.0
        lo = 0.0
        val = les[-1]
        for le, d in zip(les, deltas):
            if cum + d >= rank:
                hi = le if le != float("inf") else lo
                frac = (rank - cum) / d if d else 0.0
                val = lo + (hi - lo) * frac
                break
            cum += d
            lo = le
        out[q] = val * 1000.0
    return out


def measure():
    tmp = tempfile.mkdtemp(prefix="filodb-e2e-")
    port, gw_port = _free_port(), _free_port()
    cfg = {
        "num-shards": 4, "port": port, "gateway-port": gw_port,
        "data-dir": os.path.join(tmp, "data"),
        "stream-dir": os.path.join(tmp, "streams"),
        "flush-interval-s": 1.0,
        "seed-dev-data": True, "seed-start-ms": T0 * 1000,
        "seed-samples": SEED_SAMPLES, "seed-instances": N_INSTANCES,
        "query-sample-limit": 0, "query-series-limit": 0,
    }
    cfg_path = os.path.join(tmp, "server.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    # the node inherits JAX_PLATFORMS from this process's environment
    # (this parent never imports JAX, so the child can have the chip)
    proc = subprocess.Popen(
        [sys.executable, "-m", "filodb_tpu.standalone.server",
         "--config", cfg_path],
        cwd=str(REPO), stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)
    try:
        buf = b""
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and b"\n" not in buf:
            r, _, _ = select.select([proc.stdout], [], [], 1.0)
            if r:
                ch = proc.stdout.read1(4096)
                if not ch:
                    raise RuntimeError("server died during startup")
                buf += ch
        line = json.loads(buf.split(b"\n", 1)[0])
        assert line["port"] == port

        def one_query(client, i, want_timings=False):
            q = QUERIES[i % len(QUERIES)]
            span = 900 + (i % 4) * 600           # 15-45m windows
            start = T0 + 600 + (i * 37) % 600
            t0 = time.perf_counter()
            raw = client.get_raw("/promql/timeseries/api/v1/query_range",
                                 query=q, start=start, end=start + span,
                                 step=60)
            dt = time.perf_counter() - t0
            # a load generator verifies status without re-parsing every
            # 18KB body on the measurement path (gatling checks do the
            # same); timings are parsed on a sample of responses
            assert raw.startswith(b'{"status":"success"') \
                or raw.startswith(b'{"status": "success"'), raw[:120]
            if not want_timings:
                return dt, {}
            body = json.loads(raw)
            return dt, body.get("stats", {}).get("timings", {})

        # live ingest load: a writer streams new samples via the gateway.
        # Started BEFORE compile warmup so the warmup also covers the
        # write-buffer-tail splice shapes live ingest creates (the tail
        # steps take the packed kernel path with their own shape set).
        stop = threading.Event()

        def writer():
            t = SEED_SAMPLES
            while not stop.is_set():
                lines = []
                ts_ns = (T0 + t * 10) * 1_000_000_000
                for s in range(N_INSTANCES):
                    lines.append(
                        f"http_requests_total,instance=i{s} "
                        f"counter={(t + 1) * (s + 1)} {ts_ns}")
                try:
                    with socket.create_connection(
                            ("127.0.0.1", gw_port), timeout=10) as sk:
                        sk.sendall(("\n".join(lines) + "\n").encode())
                except OSError:
                    pass
                t += 1
                time.sleep(0.05)         # ~640 samples/s live
        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        time.sleep(1.5)          # at least one flush: tails exist

        # warm compile caches per query shape before measuring — the
        # sequential kernel shapes, the micro-batched (vmapped)
        # batch-width buckets each concurrency level will hit, and the
        # live-tail splice shapes
        warm = KeepAliveClient(port)
        for rep in range(3):
            for i in range(len(QUERIES)):
                one_query(warm, i + 4 * rep)
        for burst in (3, 8):
            for qi in range(len(QUERIES)):
                ths = []
                for c in range(burst):
                    def wfire(cc=c, qq=qi):
                        cl = KeepAliveClient(port)
                        one_query(cl, qq + 4 * cc)
                        cl.close()
                    ths.append(threading.Thread(target=wfire))
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()

        def run_level(clients, duration_s=2.5):
            """Fixed-DURATION closed-loop level (wrk-style): every
            client loops until the shared deadline, so one slow client
            can't skew qps by leaving the others idle at the end."""
            lats, timings = [], []
            lock = threading.Lock()
            t_end = [0.0]

            def client_loop(cid):
                # ramp-up: stagger connects so a level's start isn't a
                # thundering herd of simultaneous TCP handshakes (load
                # generators ramp users in; the herd would only measure
                # the accept loop)
                time.sleep(cid * 0.002)
                cl = KeepAliveClient(port)
                i = 0
                while time.perf_counter() < t_end[0]:
                    dt, tm = one_query(cl, cid * 100_000 + i,
                                       want_timings=(i % 16 == 15))
                    i += 1
                    with lock:
                        lats.append(dt)
                        if tm:
                            timings.append(tm)
                cl.close()

            b0 = _scrape_metric(warm, "batcher_batches_total")
            q0 = _scrape_metric(warm, "batcher_queries_total")
            hb0, hc0 = _scrape_histogram(warm, "query_latency_seconds")
            t0 = time.perf_counter()
            t_end[0] = t0 + duration_s
            threads = [threading.Thread(target=client_loop, args=(c,))
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            b1 = _scrape_metric(warm, "batcher_batches_total")
            q1 = _scrape_metric(warm, "batcher_queries_total")
            hb1, hc1 = _scrape_histogram(warm, "query_latency_seconds")
            lats_ms = np.asarray(lats) * 1000
            occ = (q1 - q0) / (b1 - b0) if b1 > b0 else 1.0
            # server-side quantiles derived from the /metrics histogram
            # delta over this level — the scrapeable answer to the same
            # question the client-side percentiles measure (bucket
            # resolution, so expect agreement to the bucket width)
            hq = _hist_quantiles(hb0, hc0, hb1, hc1)
            return {
                "clients": clients,
                "queries": len(lats),
                "e2e_qps": round(len(lats) / wall, 1),
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(lats_ms, 95)), 2),
                "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
                "hist_p50_ms": round(hq[0.5], 2),
                "hist_p95_ms": round(hq[0.95], 2),
                "hist_p99_ms": round(hq[0.99], 2),
                "batcher_occupancy": round(occ, 2),
            }, (timings[-1] if timings else {})

        sweep = []
        last_timings = {}
        headline = None
        for level in LEVELS:
            res, tm = run_level(level)
            sweep.append(res)
            if tm:
                last_timings = tm
            if level == HEADLINE_LEVEL:
                headline = res

        # -- dashboard scenario: N clients re-issuing the SAME queries
        # with a sliding window (the refresh-every-few-seconds pattern
        # the results cache targets). The window slides one step per
        # SLIDE_S of wall time, shared by all clients — like a real
        # dashboard, where the refresh interval is shorter than the
        # step, most refreshes repeat the previous window exactly and
        # a slide recomputes only the newest step(s). Measured twice
        # over the same server: &cache=false (full recompute per
        # refresh) vs cache on, with hit ratio + cached-steps-served
        # scraped from /metrics deltas.
        SLIDE_S = 0.5

        def dashboard_query(client, cid, t_base, use_cache):
            q = QUERIES[cid % len(QUERIES)]
            slide = int((time.perf_counter() - t_base) / SLIDE_S)
            start = T0 + 600 + (slide % 30) * 60
            params = dict(query=q, start=start, end=start + 1800,
                          step=60)
            if not use_cache:
                params["cache"] = "false"
            t0 = time.perf_counter()
            raw = client.get_raw(
                "/promql/timeseries/api/v1/query_range", **params)
            dt = time.perf_counter() - t0
            assert raw.startswith(b'{"status":"success"'), raw[:120]
            return dt

        def run_dashboard(clients, use_cache, duration_s=2.5):
            lats = []
            lock = threading.Lock()
            t_end = [0.0]
            t_base = [0.0]

            def client_loop(cid):
                time.sleep(cid * 0.002)
                cl = KeepAliveClient(port)
                while time.perf_counter() < t_end[0]:
                    dt = dashboard_query(cl, cid, t_base[0], use_cache)
                    with lock:
                        lats.append(dt)
                cl.close()

            t0 = time.perf_counter()
            t_base[0] = t0
            t_end[0] = t0 + duration_s
            threads = [threading.Thread(target=client_loop, args=(c,))
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            lats_ms = np.asarray(lats) * 1000
            return {
                "queries": len(lats),
                "qps": round(len(lats) / wall, 1),
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(lats_ms, 95)), 2),
            }

        def rc_counters():
            return {k: _scrape_metric(warm, f"result_cache_{k}_total")
                    for k in ("hits", "partial_hits", "misses",
                              "cached_steps_served",
                              "computed_steps_served")}

        # two levels: 1 client measures unloaded serving latency (the
        # p50 win), 8 closed-loop clients measure throughput on the
        # saturated 1-core rig (where p50 is queueing-dominated in both
        # modes and understates the service-time ratio)
        dash_levels = []
        for dash_clients in (1, 8):
            # cold baseline: every refresh recomputes the whole range
            dash_off = run_dashboard(dash_clients, use_cache=False)
            # warm the extents, then measure steady-state cache serving
            run_dashboard(dash_clients, use_cache=True, duration_s=1.0)
            c0 = rc_counters()
            dash_on = run_dashboard(dash_clients, use_cache=True)
            c1 = rc_counters()
            served = (c1["hits"] - c0["hits"]
                      + c1["partial_hits"] - c0["partial_hits"])
            lookups = served + c1["misses"] - c0["misses"]
            cached_steps = (c1["cached_steps_served"]
                            - c0["cached_steps_served"])
            total_steps = cached_steps + (c1["computed_steps_served"]
                                          - c0["computed_steps_served"])
            dash_levels.append({
                "clients": dash_clients,
                "cache_off": dash_off,
                "cache_warm": dash_on,
                "hit_ratio": round(served / lookups, 3)
                if lookups else 0.0,
                "cached_steps_served": int(cached_steps),
                "cached_step_ratio": round(cached_steps / total_steps,
                                           3) if total_steps else 0.0,
                "qps_speedup": round(dash_on["qps"] / dash_off["qps"],
                                     2) if dash_off["qps"] else 0.0,
                "p50_speedup": round(
                    dash_off["p50_ms"] / dash_on["p50_ms"], 2)
                if dash_on["p50_ms"] else 0.0,
            })
        dashboard = {
            "levels": dash_levels,
            "hit_ratio": dash_levels[-1]["hit_ratio"],
            "cached_steps_served": sum(l["cached_steps_served"]
                                       for l in dash_levels),
            # headline: throughput under load, latency unloaded
            "qps_speedup": dash_levels[-1]["qps_speedup"],
            "p50_speedup": dash_levels[0]["p50_speedup"],
        }
        stop.set()
        wt.join(timeout=5)
        headline = headline or sweep[-1]

        return {
            "metric": "e2e_query_p50_ms",
            "value": headline["p50_ms"],
            "unit": "ms",
            "p95_ms": headline["p95_ms"],
            "p99_ms": headline["p99_ms"],
            "hist_p50_ms": headline["hist_p50_ms"],
            "hist_p95_ms": headline["hist_p95_ms"],
            "hist_p99_ms": headline["hist_p99_ms"],
            "qps": headline["e2e_qps"],
            "clients": headline["clients"],
            "queries": headline["queries"],
            "live_ingest": True,
            "keep_alive": True,
            "batcher_occupancy": headline["batcher_occupancy"],
            "sweep": sweep,
            "dashboard": dashboard,
            "server_spans_last": last_timings,
        }
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()


# -- worker sweep: the process-sharded serving tier ------------------------

SWEEP_SAMPLES = 180         # 30min at 10s — enough for the 15-45m windows
SWEEP_INSTANCES = 8
SWEEP_SHARDS = 4
SWEEP_CLIENTS = 16
SWEEP_QUERIES = [
    "rate(http_requests_total[5m])",
    "sum(rate(http_requests_total[5m])) by (instance)",
    "avg_over_time(heap_usage[10m])",
    "max(heap_usage) by (instance)",
]


def _sweep_corpus(stream_dir):
    """Test-owned WAL producer plane (the Kafka analogue): every worker
    consumes its own shard-group's streams regardless of fleet size."""
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
    from filodb_tpu.gateway.producer import TestTimeseriesProducer
    from filodb_tpu.ingest import LogIngestionStream
    prod = TestTimeseriesProducer(DEFAULT_SCHEMAS,
                                  num_shards=SWEEP_SHARDS)
    streams = {}
    for sh in range(SWEEP_SHARDS):
        path = os.path.join(stream_dir, f"shard={sh}", "stream.log")
        streams[sh] = LogIngestionStream(path, DEFAULT_SCHEMAS)
    for builders in (prod.gauges(T0 * 1000, SWEEP_SAMPLES,
                                 SWEEP_INSTANCES),
                     prod.counters(T0 * 1000, SWEEP_SAMPLES,
                                   SWEEP_INSTANCES)):
        for sh, b in builders.items():
            for c in b.containers():
                streams[sh].append(c)
    for s in streams.values():
        s.close()


def _spawn_supervisor(cfg):
    cfg_dir = tempfile.mkdtemp(prefix="filodb-sweep-cfg-")
    cfg_path = os.path.join(cfg_dir, "sup.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "filodb_tpu.standalone.supervisor",
         "--config", cfg_path],
        cwd=str(REPO), stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)
    buf = b""
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline and b"\n" not in buf:
        r, _, _ = select.select([proc.stdout], [], [], 1.0)
        if r:
            ch = proc.stdout.read1(4096)
            if not ch:
                raise RuntimeError("supervisor died during startup")
            buf += ch
    return proc, json.loads(buf.split(b"\n", 1)[0])


def _sweep_query(client, i, cache=True):
    q = SWEEP_QUERIES[i % len(SWEEP_QUERIES)]
    span = 900 + (i % 4) * 600
    start = T0 + 600 + (i * 37) % 300
    params = dict(query=q, start=start, end=start + span, step=60)
    if not cache:
        params["cache"] = "false"
    t0 = time.perf_counter()
    raw = client.get_raw("/promql/timeseries/api/v1/query_range",
                         **params)
    dt = time.perf_counter() - t0
    assert raw.startswith(b'{"status":"success"'), raw[:120]
    return dt, raw


def _worker_counts(port):
    """Per-worker counters scraped off a PRIVATE port."""
    cl = KeepAliveClient(port)
    out = {
        "queries": _scrape_metric(cl, "query_latency_seconds_count"),
        "batches": _scrape_metric(cl, "batcher_batches_total"),
        "batched": _scrape_metric(cl, "batcher_queries_total"),
    }
    cl.close()
    return out


def measure_worker_sweep():
    import shutil
    cores = os.cpu_count() or 1
    levels = sorted({1, 2, 4, cores} & set(range(1, max(cores, 4) + 1)))
    out_levels = []
    golden = None
    for workers in levels:
        tmp = tempfile.mkdtemp(prefix=f"filodb-sweep-w{workers}-")
        _sweep_corpus(os.path.join(tmp, "streams"))
        cfg = {
            "num-shards": SWEEP_SHARDS, "port": _free_port(),
            "serving-workers": workers,
            "supervisor-port": 0,
            "run-dir": os.path.join(tmp, "run"),
            "data-dir": os.path.join(tmp, "data"),
            "stream-dir": os.path.join(tmp, "streams"),
            "flush-interval-s": 0.5,
            "max-chunks-size": 100,
            "query-sample-limit": 0, "query-series-limit": 0,
            # the production data plane: sibling leaf dispatch rides
            # protobuf+NibblePack over persistent channels (ports
            # advertised via health gossip)
            "grpc-port": 0,
            # admission sized for the level so the GLOBAL quota is not
            # the bottleneck under SWEEP_CLIENTS closed-loop clients
            "max-inflight-queries": max(8, 2 * workers),
        }
        proc, line = _spawn_supervisor(cfg)
        try:
            pub = line["port"]
            worker_ports = [w["port"] for w in line["workers"]]
            want = 2 * SWEEP_INSTANCES

            # replay + full results
            probe = KeepAliveClient(pub)
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                try:
                    _, raw = _sweep_query(probe, 0, cache=False)
                    if raw.count(b'"metric"') >= SWEEP_INSTANCES:
                        break
                except (OSError, AssertionError):
                    probe.close()
                time.sleep(0.3)
            time.sleep(2.0)         # settle: chunks + watermarks

            # warm EVERY worker's compile/plan caches, entry and peer
            # paths alike (per-process caches: each interpreter pays
            # its own warmup)
            for port in worker_ports:
                wcl = KeepAliveClient(port)
                for rep in range(2):
                    for i in range(len(SWEEP_QUERIES)):
                        _sweep_query(wcl, i + 4 * rep)
                wcl.close()
            for rep in range(2 * workers):
                for i in range(len(SWEEP_QUERIES)):
                    _sweep_query(probe, i + 4 * rep)

            # byte-identity vs the 1-worker deployment (data section;
            # the stats tail carries wall-clock timings)
            _, raw = _sweep_query(probe, 0, cache=False)
            data = raw.partition(b',"stats":')[0]
            if golden is None:
                golden = data
            identical = data == golden
            probe.close()

            # fixed closed-loop level through the PUBLIC port
            lats = []
            lock = threading.Lock()
            t_end = [0.0]

            def client_loop(cid):
                time.sleep(cid * 0.002)
                cl = KeepAliveClient(pub)
                i = 0
                while time.perf_counter() < t_end[0]:
                    dt, _ = _sweep_query(cl, cid * 100_000 + i)
                    i += 1
                    with lock:
                        lats.append(dt)
                cl.close()

            before = {p: _worker_counts(p) for p in worker_ports}
            t0 = time.perf_counter()
            t_end[0] = t0 + 2.5
            threads = [threading.Thread(target=client_loop, args=(c,))
                       for c in range(SWEEP_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            after = {p: _worker_counts(p) for p in worker_ports}
            per_worker = {}
            for idx, p in enumerate(worker_ports):
                dq = after[p]["queries"] - before[p]["queries"]
                db = after[p]["batches"] - before[p]["batches"]
                dbq = after[p]["batched"] - before[p]["batched"]
                per_worker[str(idx)] = {
                    "qps": round(dq / wall, 1),
                    "batcher_occupancy": round(dbq / db, 2)
                    if db > 0 else 1.0,
                }
            lats_ms = np.asarray(lats) * 1000
            out_levels.append({
                "workers": workers,
                "clients": SWEEP_CLIENTS,
                "queries": len(lats),
                "e2e_qps": round(len(lats) / wall, 1),
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(lats_ms, 95)), 2),
                "byte_identical": identical,
                "per_worker": per_worker,
            })
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
            shutil.rmtree(tmp, ignore_errors=True)
    base_qps = out_levels[0]["e2e_qps"] if out_levels else 0.0
    best = max(out_levels, key=lambda l: l["e2e_qps"]) \
        if out_levels else None
    return {
        "cores": cores,
        "levels": out_levels,
        "byte_identical": all(l["byte_identical"] for l in out_levels),
        "best_workers": best["workers"] if best else 0,
        "qps_speedup_vs_1worker": round(best["e2e_qps"] / base_qps, 2)
        if best and base_qps else 0.0,
    }


# -- noisy-neighbor scenario (tenant QoS, query/qos.py) ---------------------
# One abusive tenant hammering monster scans next to N interactive
# tenants issuing cheap dashboard queries, measured twice over identical
# servers: QoS off (the abuser's scans head-of-line block everyone) vs
# QoS on (the abuser throttles to its budget / degrades; interactive
# latency stays near the unloaded baseline). The headline number is
# interactive p99 under load vs the same server unloaded.

NOISY_INTERACTIVE_Q = dict(query="sum(rate(heap_usage[1m]))",
                           start=T0 + 600, end=T0 + 900, step=30)
# two monster shapes: sort(...) is results-cache-UNCACHEABLE (order
# depends on the grid bounds), so every issue is a full recompute —
# the worst-case scan QoS must throttle; the plain rate(...) matrix is
# cacheable, so under QoS the brownout's stale rung can answer it
NOISY_ABUSE_QS = [
    dict(query='sort(rate({_metric_=~"heap_usage|http_requests_total"}'
               '[10m]))',
         start=T0 + 600, end=T0 + SEED_SAMPLES * 10 - 10, step=10),
    dict(query='rate({_metric_=~"heap_usage|http_requests_total"}'
               '[10m])',
         start=T0 + 600, end=T0 + SEED_SAMPLES * 10 - 10, step=10),
]
NOISY_ABUSE_BUDGET = [50, 2000]         # rate units/s, burst


def _spawn_node(cfg):
    cfg_dir = tempfile.mkdtemp(prefix="filodb-noisy-cfg-")
    cfg_path = os.path.join(cfg_dir, "node.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "filodb_tpu.standalone.server",
         "--config", cfg_path],
        cwd=str(REPO), stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)
    buf = b""
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline and b"\n" not in buf:
        r, _, _ = select.select([proc.stdout], [], [], 1.0)
        if r:
            ch = proc.stdout.read1(4096)
            if not ch:
                raise RuntimeError("node died during startup")
            buf += ch
    return proc, json.loads(buf.split(b"\n", 1)[0])


def measure_noisy_neighbor(interactive_clients=3, abuse_clients=1,
                           duration_s=3.0):
    out = {"interactive_clients": interactive_clients,
           "abuse_clients": abuse_clients,
           "abuse_budget": NOISY_ABUSE_BUDGET}
    for mode in ("qos_off", "qos_on"):
        port = _free_port()
        cfg = {
            "num-shards": 4, "port": port, "gateway-port": None,
            "seed-dev-data": True, "seed-start-ms": T0 * 1000,
            "seed-samples": SEED_SAMPLES,
            "seed-instances": N_INSTANCES,
            "query-sample-limit": 0, "query-series-limit": 0,
            "max-inflight-queries": 8,
            "admission-wait-s": 2.0,
            "grpc-port": None,
        }
        if mode == "qos_on":
            cfg["qos-tenant-overrides"] = {
                "abuser": NOISY_ABUSE_BUDGET}
        proc, _line = _spawn_node(cfg)
        try:
            # unloaded interactive baseline (p50/p99 with no abuser)
            def interactive_once(cl):
                t0 = time.perf_counter()
                raw = cl.get_raw(
                    "/promql/timeseries/api/v1/query_range",
                    tenant="interactive", **NOISY_INTERACTIVE_Q)
                dt = time.perf_counter() - t0
                assert raw.startswith(b'{"status":"success"'), raw[:120]
                return dt

            cl = KeepAliveClient(port)
            interactive_once(cl)                # warm compile
            # warm the cacheable abuse shape's extent under an
            # UNBUDGETED tenant (the realistic dashboard world): the
            # abuser's brownout then serves the stale rung for it
            cl.get_raw("/promql/timeseries/api/v1/query_range",
                       tenant="warmup", **NOISY_ABUSE_QS[1])
            cl.close()

            lats, abuse_out = [], {"clean": 0, "shed": 0,
                                   "throttled": 0, "failed": 0}
            lock = threading.Lock()
            t_end = [0.0]

            def interactive_loop(cid):
                c = KeepAliveClient(port)
                while time.perf_counter() < t_end[0]:
                    dt = interactive_once(c)
                    with lock:
                        lats.append(dt)
                c.close()

            def abuse_loop():
                c = KeepAliveClient(port)
                i = 0
                while time.perf_counter() < t_end[0]:
                    i += 1
                    # the keep-alive client asserts 200; a 429 raises
                    # with the status line + body head in the message
                    try:
                        raw = c.get_raw(
                            "/promql/timeseries/api/v1/query_range",
                            tenant="abuser",
                            **NOISY_ABUSE_QS[i % len(NOISY_ABUSE_QS)])
                    except AssertionError as e:
                        # body fully drained before the raise: the
                        # keep-alive connection stays usable
                        with lock:
                            if "429" in str(e):
                                abuse_out["throttled"] += 1
                            else:
                                abuse_out["failed"] += 1
                        continue
                    with lock:
                        if b'shed(' in raw:
                            abuse_out["shed"] += 1
                        else:
                            abuse_out["clean"] += 1
                c.close()

            def run_phase(with_abuse):
                """Interactive percentiles at the SAME interactive
                concurrency, with/without the abuser — the unloaded
                baseline must carry the identical client-side load so
                the ratio isolates the NEIGHBOR, not the GIL."""
                lats.clear()
                threads = [threading.Thread(target=interactive_loop,
                                            args=(c,))
                           for c in range(interactive_clients)]
                if with_abuse:
                    threads += [threading.Thread(target=abuse_loop)
                                for _ in range(abuse_clients)]
                t_end[0] = time.perf_counter() + duration_s
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                lats_ms = np.asarray(lats) * 1000
                return {
                    "p50_ms":
                        round(float(np.percentile(lats_ms, 50)), 2),
                    "p99_ms":
                        round(float(np.percentile(lats_ms, 99)), 2),
                    "queries": len(lats),
                }

            base = run_phase(with_abuse=False)
            loaded = run_phase(with_abuse=True)
            out[mode] = {
                "interactive_unloaded_p50_ms": base["p50_ms"],
                "interactive_unloaded_p99_ms": base["p99_ms"],
                "interactive_loaded_p50_ms": loaded["p50_ms"],
                "interactive_loaded_p99_ms": loaded["p99_ms"],
                "interactive_queries": loaded["queries"],
                "abuse": dict(abuse_out),
                "interactive_p99_vs_unloaded": round(
                    loaded["p99_ms"] / max(base["p99_ms"], 1e-9), 2),
            }
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
    return out


# -- self-monitoring overhead (obs/selfmon.py) ------------------------------
# Identical servers, identical closed-loop client load, measured with
# the self-monitoring pipeline OFF vs ON at the default interval: the
# loop's registry walk + RecordBuilder + ingest must cost <=2% qps/p99
# (the PR acceptance bound). The ON server also reports how much
# internal telemetry it banked meanwhile (ticks/samples), so the
# overhead number is tied to real self-ingest volume.

def measure_selfmon_overhead(clients=8, duration_s=2.5,
                             interval_s=5.0, trials=3):
    """INTERLEAVED best-of-``trials`` per mode: both servers (loop off
    / loop on) are alive for the whole measurement and trials
    alternate off/on/off/on — single-trial qps on a 1-core
    oversubscribed dev rig swings +/-20% run to run (warm-up compiles,
    GC, container neighbors), and a serial off-then-on design
    confounds that drift with the effect being measured. Best trial
    per mode (min-of-N convention) is the comparator."""
    out = {"clients": clients, "interval_s": interval_s,
           "trials": trials}
    procs = {}
    ports = {}
    try:
        for mode in ("selfmon_off", "selfmon_on"):
            port = _free_port()
            cfg = {
                "num-shards": 4, "port": port, "gateway-port": None,
                "seed-dev-data": True, "seed-start-ms": T0 * 1000,
                "seed-samples": SEED_SAMPLES,
                "seed-instances": N_INSTANCES,
                "query-sample-limit": 0, "query-series-limit": 0,
                "max-inflight-queries": 8,
                "grpc-port": None,
            }
            if mode == "selfmon_on":
                cfg["self-monitor"] = True
                cfg["self-monitor-interval-s"] = interval_s
            procs[mode], _line = _spawn_node(cfg)
            ports[mode] = port

        def one(cl, i):
            t0 = time.perf_counter()
            raw = cl.get_raw(
                "/promql/timeseries/api/v1/query_range",
                query="rate(http_requests_total[5m])",
                start=T0 + 600 + (i % 8) * 10,
                end=T0 + 900 + (i % 8) * 10, step=30)
            dt = time.perf_counter() - t0
            assert raw.startswith(b'{"status":"success"'), raw[:120]
            return dt

        for mode in ("selfmon_off", "selfmon_on"):
            warm = KeepAliveClient(ports[mode])
            for i in range(8):      # compile every query shape
                one(warm, i)
            warm.close()
        # settle the loop: the FIRST ticks create the internal series
        # (index inserts + first flush) — a one-time transient, not the
        # steady state being measured. Wait ~2 ticks so measurement
        # sees the append-only regime.
        time.sleep(min(2.2 * interval_s, 12.0))

        def run_trial(port):
            lats = []
            lock = threading.Lock()
            t_end = time.perf_counter() + duration_s

            def loop(cid):
                c = KeepAliveClient(port)
                i = 0
                while time.perf_counter() < t_end:
                    dt = one(c, cid * 13 + i)
                    i += 1
                    with lock:
                        lats.append(dt)
                c.close()
            threads = [threading.Thread(target=loop, args=(c,))
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            lats_ms = np.asarray(lats) * 1000
            return {
                "qps": round(len(lats) / duration_s, 1),
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
                "queries": len(lats),
            }

        runs = {"selfmon_off": [], "selfmon_on": []}
        for t in range(max(1, trials)):
            # alternate within-round order: rig drift inside a round
            # (GC, neighbors warming) must not systematically favor
            # one mode
            order = ("selfmon_off", "selfmon_on") if t % 2 == 0 \
                else ("selfmon_on", "selfmon_off")
            for mode in order:
                runs[mode].append(run_trial(ports[mode]))
        for mode, rs in runs.items():
            # trial 0 is warm-up on both sides (residual compiles, page
            # cache): drop it, then MEAN the steady trials — a ratio of
            # means is far more stable than a ratio of extremes on a
            # rig whose per-trial qps swings +/-20%
            steady = rs[1:] if len(rs) > 1 else rs
            entry = {
                "qps": round(sum(r["qps"] for r in steady)
                             / len(steady), 1),
                "p50_ms": round(sum(r["p50_ms"] for r in steady)
                                / len(steady), 2),
                "p99_ms": round(sum(r["p99_ms"] for r in steady)
                                / len(steady), 2),
                "queries": sum(r["queries"] for r in steady),
            }
            entry["all_qps"] = [r["qps"] for r in rs]
            entry["all_p99_ms"] = [r["p99_ms"] for r in rs]
            if mode == "selfmon_on":
                cl = KeepAliveClient(ports[mode])
                entry["selfmon"] = _scrape_metric(
                    cl, "selfmon_samples_ingested_total")
                entry["selfmon_ticks"] = _scrape_metric(
                    cl, "selfmon_ticks_total")
                # the noise-free overhead number: the loop's own tick
                # histogram gives mean collect+ingest wall time; duty
                # cycle = tick_s / interval_s bounds the steady-state
                # qps cost independent of client-side trial noise
                tick_sum = _scrape_metric(cl, "selfmon_tick_seconds_sum")
                tick_n = _scrape_metric(cl, "selfmon_tick_seconds_count")
                if tick_n:
                    entry["tick_ms_avg"] = round(
                        1000 * tick_sum / tick_n, 2)
                    entry["duty_cycle"] = round(
                        (tick_sum / tick_n) / interval_s, 5)
                cl.close()
            out[mode] = entry
    finally:
        for proc in procs.values():
            proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
    if out.get("selfmon_off", {}).get("qps"):
        off, on = out["selfmon_off"], out["selfmon_on"]
        out["qps_ratio_on_vs_off"] = round(
            on["qps"] / max(off["qps"], 1e-9), 4)
        out["p99_ratio_on_vs_off"] = round(
            on["p99_ms"] / max(off["p99_ms"], 1e-9), 4)
    return out


def measure_profiler_overhead(clients=8, duration_s=2.5, hz=29.0,
                              trials=3):
    """Sampling-profiler cost under the 8-client dashboard load, same
    interleaved best-of-``trials`` design as the selfmon harness: two
    servers (profiler off / on at the default hz) alive for the whole
    measurement, trials alternating. Besides client-side qps/p99, the
    sampler's own tick histogram gives the noise-free number: duty
    cycle = mean tick cost x hz. The /debug/profile report closes the
    attribution acceptance (fraction of samples landing on a declared
    thread root)."""
    out = {"clients": clients, "hz": hz, "trials": trials}
    procs = {}
    ports = {}
    try:
        for mode in ("profiler_off", "profiler_on"):
            port = _free_port()
            cfg = {
                "num-shards": 4, "port": port, "gateway-port": None,
                "seed-dev-data": True, "seed-start-ms": T0 * 1000,
                "seed-samples": SEED_SAMPLES,
                "seed-instances": N_INSTANCES,
                "query-sample-limit": 0, "query-series-limit": 0,
                "max-inflight-queries": 8,
                "grpc-port": None,
            }
            if mode == "profiler_on":
                cfg["profiler-enabled"] = True
                cfg["profiler-hz"] = hz
            procs[mode], _line = _spawn_node(cfg)
            ports[mode] = port

        def one(cl, i):
            t0 = time.perf_counter()
            raw = cl.get_raw(
                "/promql/timeseries/api/v1/query_range",
                query="rate(http_requests_total[5m])",
                start=T0 + 600 + (i % 8) * 10,
                end=T0 + 900 + (i % 8) * 10, step=30)
            dt = time.perf_counter() - t0
            assert raw.startswith(b'{"status":"success"'), raw[:120]
            return dt

        for mode in ("profiler_off", "profiler_on"):
            warm = KeepAliveClient(ports[mode])
            for i in range(8):      # compile every query shape
                one(warm, i)
            warm.close()

        def run_trial(port):
            lats = []
            lock = threading.Lock()
            t_end = time.perf_counter() + duration_s

            def loop(cid):
                c = KeepAliveClient(port)
                i = 0
                while time.perf_counter() < t_end:
                    dt = one(c, cid * 13 + i)
                    i += 1
                    with lock:
                        lats.append(dt)
                c.close()
            threads = [threading.Thread(target=loop, args=(c,))
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            lats_ms = np.asarray(lats) * 1000
            return {
                "qps": round(len(lats) / duration_s, 1),
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
                "queries": len(lats),
            }

        runs = {"profiler_off": [], "profiler_on": []}
        for t in range(max(1, trials)):
            order = ("profiler_off", "profiler_on") if t % 2 == 0 \
                else ("profiler_on", "profiler_off")
            for mode in order:
                runs[mode].append(run_trial(ports[mode]))
        for mode, rs in runs.items():
            steady = rs[1:] if len(rs) > 1 else rs
            entry = {
                "qps": round(sum(r["qps"] for r in steady)
                             / len(steady), 1),
                "p50_ms": round(sum(r["p50_ms"] for r in steady)
                                / len(steady), 2),
                "p99_ms": round(sum(r["p99_ms"] for r in steady)
                                / len(steady), 2),
                "queries": sum(r["queries"] for r in steady),
            }
            entry["all_qps"] = [r["qps"] for r in rs]
            entry["all_p99_ms"] = [r["p99_ms"] for r in rs]
            if mode == "profiler_on":
                cl = KeepAliveClient(ports[mode])
                tick_sum = _scrape_metric(
                    cl, "profiler_tick_seconds_sum")
                tick_n = _scrape_metric(
                    cl, "profiler_tick_seconds_count")
                if tick_n:
                    entry["ticks"] = int(tick_n)
                    entry["tick_us_avg"] = round(
                        1e6 * tick_sum / tick_n, 1)
                    # ticks fire hz times per second: the sampler's
                    # steady-state CPU share is tick cost x hz
                    entry["duty_cycle"] = round(
                        (tick_sum / tick_n) * hz, 6)
                rep = json.loads(cl.get_raw("/debug/profile"))
                entry["samples"] = rep["data"]["samples"]
                entry["attribution_fraction"] = \
                    rep["data"]["attribution_fraction"]
                entry["roots"] = {
                    k: v for k, v in sorted(
                        rep["data"]["roots"].items(),
                        key=lambda kv: -kv[1])[:8]}
                cl.close()
            out[mode] = entry
    finally:
        for proc in procs.values():
            proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
    if out.get("profiler_off", {}).get("qps"):
        off, on = out["profiler_off"], out["profiler_on"]
        out["qps_ratio_on_vs_off"] = round(
            on["qps"] / max(off["qps"], 1e-9), 4)
        out["p99_ratio_on_vs_off"] = round(
            on["p99_ms"] / max(off["p99_ms"], 1e-9), 4)
    return out


def measure_rules_overhead(clients=8, duration_s=2.5,
                           rule_interval_s=1.0):
    """The dashboard-conversion win (recording rules, filodb_tpu/rules):
    the SAME dashboard aggregate measured two ways on one live server —
    (a) as a direct warm-cache query over the raw counters, and (b) as
    a one-series read of the recording rule's precomputed output from
    /promql/__rules__. The rule converts the per-user rate() work into
    O(rules) background ticks, so (b) should serve at >= the direct
    warm-cache qps while the standing cost is the rule-tick duty cycle
    (reported from the engine's own filodb_rule_tick_seconds
    histogram, noise-free)."""
    out = {"clients": clients, "rule_interval_s": rule_interval_s}
    # seed AT wall-now: rule ticks evaluate at now and must see data
    now_s = int(time.time())
    seed_start = (now_s - SEED_SAMPLES * 10) * 1000
    port = _free_port()
    cfg = {
        "num-shards": 4, "port": port, "gateway-port": None,
        "seed-dev-data": True, "seed-start-ms": seed_start,
        "seed-samples": SEED_SAMPLES, "seed-instances": N_INSTANCES,
        "query-sample-limit": 0, "query-series-limit": 0,
        "max-inflight-queries": 8, "grpc-port": None,
        # old steps settle fast so consecutive rule ticks are
        # cache-warm tail recomputes
        "results-cache-hot-window-ms": 2_000.0,
        "rules-eval-span-steps": 8,
        "rules": {"groups": [{
            "name": "bench", "interval": rule_interval_s, "rules": [
                {"record": "bench:req:rate5m",
                 "expr": "sum(rate(http_requests_total[5m]))"}]}]},
    }
    proc, _line = _spawn_node(cfg)
    try:
        # let the engine tick a few times (first ticks create the
        # internal series — a one-time transient)
        time.sleep(4 * rule_interval_s)

        # both paths use the BENCH_r08 dashboard methodology: a
        # SLIDING window (refresh interval shorter than the step, so
        # most refreshes repeat the window and a slide recomputes only
        # the tail). The direct path's tail recompute re-runs rate()
        # over every instance's counter; the recorded path's tail is
        # one precomputed series — that asymmetry IS the conversion.
        SLIDE_S = 0.5
        t_base = time.perf_counter()
        d_base = now_s - 3000

        def one_direct(cl):
            slide = int((time.perf_counter() - t_base) / SLIDE_S)
            start = d_base + (slide % 20) * 60
            t0 = time.perf_counter()
            raw = cl.get_raw(
                "/promql/timeseries/api/v1/query_range",
                query="sum(rate(http_requests_total[5m]))",
                start=start, end=start + 1800, step=60)
            dt = time.perf_counter() - t0
            assert raw.startswith(b'{"status":"success"'), raw[:120]
            return dt

        def one_recorded(cl):
            # the recorded series' natural dashboard: the window
            # slides with the wall clock at the rule's own cadence
            now = int(time.time())
            t0 = time.perf_counter()
            raw = cl.get_raw(
                "/promql/__rules__/api/v1/query_range",
                query="bench:req:rate5m",
                start=now - 90, end=now - 2,
                step=max(1, int(rule_interval_s)))
            dt = time.perf_counter() - t0
            assert raw.startswith(b'{"status":"success"'), raw[:120]
            return dt

        def run_level(one):
            lats = []
            lock = threading.Lock()
            t_end = [0.0]

            def loop(cid):
                time.sleep(cid * 0.002)
                cl = KeepAliveClient(port)
                while time.perf_counter() < t_end[0]:
                    dt = one(cl)
                    with lock:
                        lats.append(dt)
                cl.close()
            t0 = time.perf_counter()
            t_end[0] = t0 + duration_s
            threads = [threading.Thread(target=loop, args=(c,))
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            lats_ms = np.asarray(lats) * 1000
            return {"queries": len(lats),
                    "qps": round(len(lats) / wall, 1),
                    "p50_ms": round(float(np.percentile(lats_ms, 50)),
                                    2),
                    "p99_ms": round(float(np.percentile(lats_ms, 99)),
                                    2)}

        warm = KeepAliveClient(port)
        for _ in range(4):          # compile + warm both shapes
            one_direct(warm)
            one_recorded(warm)
        # interleaved trials, warm-up dropped (the selfmon-bench
        # methodology: single trials swing +/-20% on a 1-core rig)
        runs = {"direct_warm_cache": [], "recorded_series": []}
        for t in range(3):
            order = (("direct_warm_cache", one_direct),
                     ("recorded_series", one_recorded)) if t % 2 == 0 \
                else (("recorded_series", one_recorded),
                      ("direct_warm_cache", one_direct))
            for name, fn in order:
                runs[name].append(run_level(fn))
        for name, rs in runs.items():
            steady = rs[1:] if len(rs) > 1 else rs
            out[name] = {
                "qps": round(sum(r["qps"] for r in steady)
                             / len(steady), 1),
                "p50_ms": round(sum(r["p50_ms"] for r in steady)
                                / len(steady), 2),
                "p99_ms": round(sum(r["p99_ms"] for r in steady)
                                / len(steady), 2),
                "all_qps": [r["qps"] for r in rs],
            }
        out["qps_ratio_recorded_vs_direct"] = round(
            out["recorded_series"]["qps"]
            / max(out["direct_warm_cache"]["qps"], 1e-9), 3)
        # the standing cost, from the engine's own histogram: mean
        # tick wall seconds / interval = duty cycle
        tick_sum = _scrape_metric(warm, "rule_tick_seconds_sum")
        tick_n = _scrape_metric(warm, "rule_tick_seconds_count")
        if tick_n:
            out["rule_ticks"] = int(tick_n)
            out["tick_ms_avg"] = round(1000 * tick_sum / tick_n, 2)
            out["rule_duty_cycle"] = round(
                (tick_sum / tick_n) / rule_interval_s, 5)
        out["rule_samples_written"] = _scrape_metric(
            warm, "rule_samples_written_total")
        warm.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
    return out


def main():
    # focused runs: `python bench_e2e.py profiler_overhead ...` runs
    # only the named measure_* sections (a full run takes minutes; the
    # per-PR BENCH files usually pin one section)
    sections = sys.argv[1:]
    if sections:
        out = {}
        for name in sections:
            fn = globals().get(f"measure_{name}")
            if fn is None:
                raise SystemExit(f"unknown section {name!r}")
            out[name] = fn()
        print(json.dumps(out))
        return
    out = measure()
    try:
        out["worker_sweep"] = measure_worker_sweep()
    except Exception as e:  # noqa: BLE001 — the sweep must not void
        out["worker_sweep"] = {"error": repr(e)}    # the main bench
    try:
        out["noisy_neighbor"] = measure_noisy_neighbor()
    except Exception as e:  # noqa: BLE001
        out["noisy_neighbor"] = {"error": repr(e)}
    try:
        out["selfmon_overhead"] = measure_selfmon_overhead()
    except Exception as e:  # noqa: BLE001
        out["selfmon_overhead"] = {"error": repr(e)}
    try:
        out["rules_overhead"] = measure_rules_overhead()
    except Exception as e:  # noqa: BLE001
        out["rules_overhead"] = {"error": repr(e)}
    try:
        out["profiler_overhead"] = measure_profiler_overhead()
    except Exception as e:  # noqa: BLE001
        out["profiler_overhead"] = {"error": repr(e)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
