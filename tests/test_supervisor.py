"""Unit coverage for the process-sharded serving tier's supervisor-side
pieces: worker config derivation (global admission split, cache budget
split, peer wiring, single gateway), the control-plane bus (fan-out,
exclusion of the sender, the apply→republish loop breaker), exposition
merging, the fan-out concurrency knob, and the deterministic response
ordering that cross-topology byte-identity rests on."""

import json
import threading
import time

import pytest

from filodb_tpu.obs.metrics import (ExpositionBuilder, merge_expositions,
                                    parse_exposition)
from filodb_tpu.standalone.bus import (BusClient, SupervisorBus,
                                       wait_connected)
from filodb_tpu.standalone.supervisor import split_quota, worker_config


# -- launchers stay off JAX: a parent that touched it would hold the chip --

@pytest.mark.parametrize("module", [
    "filodb_tpu.standalone.supervisor", "chip_smoke"])
def test_launcher_import_does_not_import_jax(module):
    """The supervisor and the chip smoke start node processes that need
    the chip; importing them must leave JAX alone."""
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parent.parent
    res = subprocess.run(
        [sys.executable, "-c",
         f"import sys, {module}; sys.exit('jax' in sys.modules)"],
        cwd=str(repo), capture_output=True, timeout=120)
    assert res.returncode == 0, res.stderr.decode()[-2000:]


# -- admission quota: global across workers, not Nx ------------------------

def test_split_quota_preserves_aggregate_bound():
    assert split_quota(6, 4) == [2, 2, 1, 1]
    assert sum(split_quota(6, 4)) == 6          # the aggregate pin
    assert split_quota(8, 4) == [2, 2, 2, 2]
    assert split_quota(4, 4) == [1, 1, 1, 1]
    assert split_quota(7, 3) == [3, 2, 2]
    assert sum(split_quota(7, 3)) == 7


def test_split_quota_edge_cases():
    # 0 = admission control off, stays off per worker
    assert split_quota(0, 4) == [0, 0, 0, 0]
    # budget below fleet size: documented lower bound of 1 per worker
    # (a zero-quota worker could never answer)
    assert split_quota(2, 4) == [1, 1, 1, 1]
    assert split_quota(5, 1) == [5]


def test_worker_config_derivation():
    base = {"num-shards": 8, "max-inflight-queries": 6,
            "results-cache-mb": 64, "gateway-port": 0,
            "serving-workers": 4, "supervisor-port": 0,
            "run-dir": "/x", "stream-dir": "/s"}
    ports = [9001, 9002, 9003, 9004]
    cfgs = [worker_config(base, i, 4, ports, 8080, 7000)
            for i in range(4)]
    for i, cfg in enumerate(cfgs):
        assert cfg["num-nodes"] == 4
        assert cfg["node-ordinal"] == i
        assert cfg["worker-id"] == i
        assert cfg["port"] == ports[i]
        assert cfg["accept-port"] == 8080
        assert cfg["bus-port"] == 7000
        assert cfg["peers"] == {f"node{j}": f"http://127.0.0.1:{p}"
                                for j, p in enumerate(ports)}
        # supervisor-only keys must not leak into the worker
        assert "serving-workers" not in cfg
        assert "run-dir" not in cfg
    # admission is GLOBAL: per-worker quotas sum to the configured max
    assert [c["max-inflight-queries"] for c in cfgs] == [2, 2, 1, 1]
    # host cache budget stays constant
    assert sum(c["results-cache-mb"] for c in cfgs) == \
        pytest.approx(64.0)
    # ONE producer edge per host
    assert cfgs[0]["gateway-port"] == 0
    assert all(c["gateway-port"] is None for c in cfgs[1:])


def test_worker_config_propagates_self_monitor():
    """--self-monitor rides into every worker: each runs its OWN loop
    over its own internal shard (shard number = worker-id, so the
    shared stream/data dirs never collide) and stamps its ordinal as
    the worker label on internal series."""
    base = {"num-shards": 4, "self-monitor": True,
            "self-monitor-interval-s": 2.5, "serving-workers": 2,
            "supervisor-port": 0, "run-dir": "/x"}
    ports = [9001, 9002]
    cfgs = [worker_config(base, i, 2, ports, 8080, 7000)
            for i in range(2)]
    for i, cfg in enumerate(cfgs):
        assert cfg["self-monitor"] is True
        assert cfg["self-monitor-interval-s"] == 2.5
        assert cfg["worker-id"] == i


def test_worker_config_fd_fallback():
    cfg = worker_config({"num-shards": 4}, 1, 2, [9001, 9002], 8080,
                        7000, accept_fd=13)
    assert cfg["accept-fd"] == 13
    assert "accept-port" not in cfg


# -- control-plane bus ------------------------------------------------------

def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def test_bus_fans_out_to_other_workers_not_sender():
    hub = SupervisorBus().start()
    got_a, got_b = [], []
    a = BusClient(hub.port, 0, "node0").on(
        "schema", lambda ev: got_a.append(ev)).start()
    b = BusClient(hub.port, 1, "node1").on(
        "schema", lambda ev: got_b.append(ev)).start()
    try:
        assert wait_connected(a) and wait_connected(b)
        assert _wait(lambda: hub.connected_workers() == [0, 1])
        a.publish({"type": "schema", "reason": "col-added"})
        assert _wait(lambda: len(got_b) == 1)
        assert got_b[0]["reason"] == "col-added"
        assert got_b[0]["origin"] == "node0"
        time.sleep(0.1)
        assert got_a == []          # the sender never hears its own event
        # supervisor broadcast reaches everyone
        hub.broadcast({"type": "schema", "reason": "operator"})
        assert _wait(lambda: len(got_a) == 1 and len(got_b) == 2)
    finally:
        a.stop()
        b.stop()
        hub.stop()


def test_bus_apply_suppresses_republish():
    """The loop breaker: a handler that (like the mapper subscriber)
    publishes in reaction to an event must NOT echo bus-applied events
    back onto the bus."""
    hub = SupervisorBus().start()
    got_b = []
    a = BusClient(hub.port, 0, "node0")

    def react(ev):
        # what the ShardMapper subscriber does on an applied transition
        a.publish({"type": "topology", "shard": 0, "status": "active"})
    a.on("topology", react).start()
    b = BusClient(hub.port, 1, "node1").on(
        "topology", lambda ev: got_b.append(ev)).start()
    try:
        assert wait_connected(a) and wait_connected(b)
        assert _wait(lambda: hub.connected_workers() == [0, 1])
        seen0 = hub.events_seen
        b.publish({"type": "topology", "shard": 0, "status": "active"})
        assert _wait(lambda: a.applied >= 1)
        time.sleep(0.2)
        # exactly ONE event crossed the hub (b's publish); a's reactive
        # publish was suppressed by the applying guard
        assert hub.events_seen - seen0 == 1
        assert got_b == []
    finally:
        a.stop()
        b.stop()
        hub.stop()


def test_bus_client_reconnects_and_counts():
    hub = SupervisorBus().start()
    a = BusClient(hub.port, 0, "node0").start()
    try:
        assert wait_connected(a)
        assert a.metrics_snapshot()["connected"] == 1
        assert a.metrics_snapshot()["reconnects"] == 0
        a.publish({"type": "schema"})
        assert _wait(lambda: a.metrics_snapshot()["published"] == 1)
    finally:
        a.stop()
        hub.stop()


# -- exposition merge -------------------------------------------------------

_W0 = """# HELP filodb_plan_cache_hits_total Plan-cache hits
# TYPE filodb_plan_cache_hits_total counter
filodb_plan_cache_hits_total 7
# HELP filodb_shard_status Shard FSM status
# TYPE filodb_shard_status gauge
filodb_shard_status{shard="0",status="active"} 1
# HELP filodb_query_latency_seconds query latency
# TYPE filodb_query_latency_seconds histogram
filodb_query_latency_seconds_bucket{le="0.001"} 2
filodb_query_latency_seconds_bucket{le="+Inf"} 3
filodb_query_latency_seconds_sum 0.5
filodb_query_latency_seconds_count 3
"""

_W1 = """# HELP filodb_plan_cache_hits_total Plan-cache hits
# TYPE filodb_plan_cache_hits_total counter
filodb_plan_cache_hits_total 5
"""


def test_parse_exposition_families_and_histograms():
    helps = {}
    rows = parse_exposition(_W0, help_sink=helps)
    fams = {fam for fam, *_ in rows}
    assert fams == {"filodb_plan_cache_hits_total",
                    "filodb_shard_status",
                    "filodb_query_latency_seconds"}
    assert helps["filodb_plan_cache_hits_total"] == "Plan-cache hits"
    hist = [(name, labels, v) for fam, _mt, name, labels, v in rows
            if fam == "filodb_query_latency_seconds"]
    assert ("filodb_query_latency_seconds_bucket", {"le": "0.001"},
            "2") in hist
    labeled = [labels for _f, _mt, name, labels, _v in rows
               if name == "filodb_shard_status"]
    assert labeled == [{"shard": "0", "status": "active"}]


def test_merge_expositions_injects_worker_label():
    out = merge_expositions({"0": _W0, "1": _W1})
    lines = out.splitlines()
    assert 'filodb_plan_cache_hits_total{worker="0"} 7' in lines
    assert 'filodb_plan_cache_hits_total{worker="1"} 5' in lines
    # one HELP/TYPE block per family even though both workers carry it
    assert sum(1 for ln in lines
               if ln.startswith("# TYPE filodb_plan_cache_hits_total")
               ) == 1
    # histogram children keep their family grouping + worker label
    assert ('filodb_query_latency_seconds_bucket'
            '{le="0.001",worker="0"} 2') in lines \
        or ('filodb_query_latency_seconds_bucket'
            '{worker="0",le="0.001"} 2') in lines
    # worker HELP text survives the merge
    assert "# HELP filodb_plan_cache_hits_total Plan-cache hits" \
        in lines
    # merged output re-parses cleanly
    assert parse_exposition(out)


def test_merge_expositions_idempotent():
    """merge(merge(x)) == merge(x): re-merging an already-merged
    exposition (a supervisor-of-supervisors scrape, a re-aggregated
    payload) is a no-op — the worker label injected by the first merge
    is KEPT, not clobbered, and HELP/TYPE blocks survive. This is also
    what protects self-monitoring's own ``worker``-labeled internal
    series through the supervisor's aggregate view."""
    merged = merge_expositions({"0": _W0, "1": _W1})
    again = merge_expositions({"sup": merged})
    assert again == merged
    # a sample that already carried a worker label keeps it even when
    # merged under a different worker key
    assert 'worker="sup"' not in again


def test_merge_expositions_idempotent_on_real_worker_payloads():
    """The same property pinned on a REAL worker payload — a live
    FiloServer /metrics body, histograms, escapes, and all — since the
    supervisor's self-monitoring view reads through this path."""
    import urllib.request

    from filodb_tpu.obs.metrics import validate_histogram_families
    from filodb_tpu.standalone.server import FiloServer
    srv = FiloServer({"num-shards": 2, "port": 0}).start()
    try:
        srv.seed_dev_data(n_samples=30, n_instances=2,
                          start_ms=1_600_000_000_000)
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
            f"query_range?query=up&start=1600000300&end=1600000400"
            f"&step=60", timeout=60).read()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=60) as r:
            body = r.read().decode()
    finally:
        srv.stop()
    merged = merge_expositions({"0": body, "1": body})
    again = merge_expositions({"0": merged})
    assert again == merged
    # histogram self-consistency survives the merge (registry-wide
    # validator: cumulative buckets, +Inf == _count, _sum emitted)
    assert validate_histogram_families(merged) == []


def test_merged_exposition_passes_format_validator():
    """The merged text must satisfy the same Prometheus text-format
    invariants the per-worker exposition is tested against."""
    out = merge_expositions({"0": _W0, "1": _W1})
    seen_series = set()
    declared = set()
    for ln in out.splitlines():
        if ln.startswith("# TYPE "):
            fam = ln.split()[2]
            assert fam not in declared, f"duplicate TYPE for {fam}"
            declared.add(fam)
        elif ln and not ln.startswith("#"):
            key = ln.rsplit(" ", 1)[0]
            assert key not in seen_series, f"duplicate series {key}"
            seen_series.add(key)


# -- fan-out cap knob -------------------------------------------------------

def test_fanout_workers_knob_and_auto():
    import os

    from filodb_tpu.http.server import FiloHttpServer
    srv = FiloHttpServer({"ds": []}, peer_fanout_workers=24)
    try:
        assert srv.fanout_workers == 24
        assert 'filodb_peer_fanout_workers 24' \
            in srv._metrics_text().splitlines()
    finally:
        srv.httpd.server_close()
    srv = FiloHttpServer({"ds": []})     # auto: sized from the host
    try:
        assert srv.fanout_workers == min(32, max(2, os.cpu_count() or 2))
    finally:
        srv.httpd.server_close()


# -- deterministic response ordering ---------------------------------------

def test_matrix_encode_order_is_data_dependent_not_scan_dependent():
    import numpy as np

    from filodb_tpu.http import prom_json
    from filodb_tpu.query.model import GridResult
    steps = np.array([10_000, 20_000], dtype=np.int64)
    keys = [{"_metric_": "m", "instance": "i1"},
            {"instance": "i0", "_metric_": "m"}]
    vals = np.array([[1.0, 2.0], [3.0, 4.0]])
    fwd = GridResult(steps, keys, vals)
    rev = GridResult(steps, list(reversed(keys)), vals[::-1].copy())
    out_f = prom_json.matrix(fwd)["data"]["result"]
    out_r = prom_json.matrix(rev)["data"]["result"]
    assert out_f == out_r
    assert [r["metric"]["instance"] for r in out_f] == ["i0", "i1"]
    # the pre-encoded fast path agrees byte-for-byte with the dict path
    body_f = prom_json.matrix_bytes(fwd, {"x": 1}).body
    body_r = prom_json.matrix_bytes(rev, {"x": 1}).body
    assert body_f == body_r
    env = prom_json.matrix(fwd)
    env["stats"] = {"x": 1}
    assert body_f == json.dumps(env, separators=(",", ":")).encode()


def test_supervisor_object_start_stop_without_workers(tmp_path):
    """Supervisor lifecycle without real FiloServer subprocesses: 0
    configured workers is clamped to the core count, so use the
    smallest real fleet (1) against a config that makes the worker
    exit immediately — the monitor must keep respawning with backoff,
    and stop() must terminate cleanly."""
    from filodb_tpu.standalone.supervisor import Supervisor
    sup = Supervisor({"serving-workers": 1, "port": 0,
                      "run-dir": str(tmp_path / "run"),
                      "restart-backoff-s": 30.0,
                      # invalid num-shards (not a power of 2): the
                      # worker process dies during startup
                      "num-shards": 3})
    sup.start()
    try:
        assert _wait(lambda: sup.status()["workers"]["0"]["alive"]
                     in (True, False))
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if not sup.status()["workers"]["0"]["alive"]:
                break
            time.sleep(0.1)
        st = sup.status()
        assert st["workers"]["0"]["alive"] is False
        assert st["status"] == "healthy"
        # aggregate metrics still render with the worker down
        text = sup.metrics_text()
        assert "filodb_supervisor_workers 1" in text.splitlines()
        assert 'filodb_supervisor_worker_alive{worker="0"} 0' \
            in text.splitlines()
    finally:
        sup.stop(graceful=False)


def test_worker_config_splits_qos_budgets():
    """Tenant QoS budgets are HOST bounds like admission: each worker
    gets 1/N of every refill rate / bucket depth, so the fleet charges
    the same aggregate per-tenant budget as one process would."""
    base = {"num-shards": 8, "qos-tenant-rate": 100.0,
            "qos-tenant-burst": 1000.0,
            "qos-tenant-overrides": {"abuser": 40.0,
                                     "vip": [80.0, 400.0]}}
    ports = [9001, 9002, 9003, 9004]
    cfgs = [worker_config(base, i, 4, ports, 8080, 7000)
            for i in range(4)]
    assert sum(c["qos-tenant-rate"] for c in cfgs) == \
        pytest.approx(100.0)
    assert sum(c["qos-tenant-burst"] for c in cfgs) == \
        pytest.approx(1000.0)
    assert sum(c["qos-tenant-overrides"]["abuser"] for c in cfgs) == \
        pytest.approx(40.0)
    assert sum(c["qos-tenant-overrides"]["vip"][0] for c in cfgs) == \
        pytest.approx(80.0)
    assert sum(c["qos-tenant-overrides"]["vip"][1] for c in cfgs) == \
        pytest.approx(400.0)
    # budgets off: no keys are invented for the workers
    cfg_off = worker_config({"num-shards": 8}, 0, 4, ports, 8080, 7000)
    assert "qos-tenant-rate" not in cfg_off


_TENANT_EXPO_W0 = """\
# HELP filodb_tenant_time_series_total Per-tenant series count
# TYPE filodb_tenant_time_series_total gauge
filodb_tenant_time_series_total{_ws_="demo",_ns_="App-0"} 40
# HELP filodb_tenant_budget_remaining Per-tenant token-bucket balance
# TYPE filodb_tenant_budget_remaining gauge
filodb_tenant_budget_remaining{tenant="abuser"} 25.0
# HELP filodb_tenant_throttled_total Budget charges refused
# TYPE filodb_tenant_throttled_total counter
filodb_tenant_throttled_total{tenant="abuser"} 3
"""

_TENANT_EXPO_W1 = """\
# HELP filodb_tenant_time_series_total Per-tenant series count
# TYPE filodb_tenant_time_series_total gauge
filodb_tenant_time_series_total{_ws_="demo",_ns_="App-0"} 24
# HELP filodb_tenant_budget_remaining Per-tenant token-bucket balance
# TYPE filodb_tenant_budget_remaining gauge
filodb_tenant_budget_remaining{tenant="abuser"} -10.0
# HELP filodb_tenant_throttled_total Budget charges refused
# TYPE filodb_tenant_throttled_total counter
filodb_tenant_throttled_total{tenant="abuser"} 5
"""


def test_merge_expositions_carries_tenant_families():
    """The satellite pin: tenant cardinality/budget families flow
    through the supervisor's merged /metrics with the worker label
    injected like every other family."""
    out = merge_expositions({"0": _TENANT_EXPO_W0,
                             "1": _TENANT_EXPO_W1})
    assert ('filodb_tenant_time_series_total'
            '{_ns_="App-0",_ws_="demo",worker="0"} 40') in out
    assert ('filodb_tenant_time_series_total'
            '{_ns_="App-0",_ws_="demo",worker="1"} 24') in out
    assert 'filodb_tenant_budget_remaining{tenant="abuser",worker="0"} 25.0' \
        in out
    assert 'filodb_tenant_throttled_total{tenant="abuser",worker="1"} 5' \
        in out
    # one HELP/TYPE block per family across the fleet
    assert out.count("# TYPE filodb_tenant_time_series_total gauge") == 1


def test_aggregate_tenant_families_host_rollup():
    """filodb_host_tenant_*: per-tenant sums across workers — the
    one-series-per-tenant view a noisy-neighbor alert reads (a
    tenant's shards and its budget split spread ACROSS workers)."""
    from filodb_tpu.standalone.supervisor import aggregate_tenant_families
    out = aggregate_tenant_families({"0": _TENANT_EXPO_W0,
                                     "1": _TENANT_EXPO_W1})
    assert ('filodb_host_tenant_time_series_total'
            '{_ns_="App-0",_ws_="demo"} 64') in out
    assert 'filodb_host_tenant_budget_remaining{tenant="abuser"} 15' \
        in out
    assert 'filodb_host_tenant_throttled_total{tenant="abuser"} 8' in out
    # non-tenant families are not rolled up
    assert "filodb_host_tenant_time_series_total" in out
    assert aggregate_tenant_families({}) == ""


# -- /debug/events fleet merge ------------------------------------------------

def test_debug_events_merged_and_worker_tagged():
    """/debug/events joins the supervisor's merged debug routes: the
    admin port fans the request out to every worker and concatenates
    the event journals, each entry tagged with its worker ordinal —
    one place to read corruption/quarantine/read-only transitions for
    the whole host."""
    from filodb_tpu.standalone.supervisor import Supervisor, _Worker
    sup = Supervisor({"serving-workers": 2, "port": 0})
    sup._workers = {0: _Worker(0, "w0.json", 1), 1: _Worker(1, "w1.json", 2)}
    canned = {
        1: {"status": "success",
            "data": [{"kind": "corruption-detected", "shard": 0}]},
        2: {"status": "success",
            "data": [{"kind": "ingest-read-only", "shard": 3}]},
    }
    sup._worker_get = lambda w, path: (
        canned[w.port] if path.startswith("/debug/events") else None)
    code, body = sup._admin_route("/debug/events?limit=10")
    assert code == 200 and body["status"] == "success"
    assert {(e["kind"], e["worker"]) for e in body["data"]} == {
        ("corruption-detected", 0), ("ingest-read-only", 1)}
    # the ?query passes through to the workers
    seen = []
    sup._worker_get = lambda w, path: (seen.append(path)
                                       or canned[w.port])
    sup._admin_route("/debug/events?kind=quarantine")
    assert seen == ["/debug/events?kind=quarantine"] * 2
