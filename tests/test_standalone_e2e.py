"""Full standalone-node e2e: gateway TCP ingest -> durable shard streams
-> ingestion drivers -> HTTP queries, then SIGKILL + restart replaying
from the checkpoint watermark.

This is the analogue of the reference's dev loop (filodb-dev-start.sh +
dev-gateway.sh) plus the recovery protocol e2e
(coordinator/IngestionActor.scala:174-345): a killed node must come back
with bit-identical query results, rebuilding from the ColumnStore and
replaying the stream tail that never flushed.
"""

import json
import os
import pathlib
import select
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest
import urllib.error
import urllib.parse
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent.parent
T0 = 1_600_000_000
N_SAMPLES = 60          # per series, 10s apart
N_SERIES = 3


def _spawn(cfg_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-m", "filodb_tpu.standalone.server",
         "--config", str(cfg_path)],
        cwd=str(REPO), env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)


def _read_ports(proc, timeout=120.0):
    """First stdout line is machine-readable {"port":..,"gateway_port":..}."""
    deadline = time.monotonic() + timeout
    buf = b""
    while time.monotonic() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 1.0)
        if not r:
            if proc.poll() is not None:
                raise RuntimeError("server died during startup")
            continue
        ch = proc.stdout.read1(4096)
        if not ch:
            raise RuntimeError("server stdout closed before startup line")
        buf += ch
        if b"\n" in buf:
            return json.loads(buf.split(b"\n", 1)[0])
    raise TimeoutError("no startup line")


def _get(port, path, **params):
    qs = urllib.parse.urlencode(params, doseq=True)
    url = f"http://127.0.0.1:{port}{path}"
    if qs:
        url += "?" + qs
    # generous: under a full-suite run the server subprocess competes for
    # CPU with other tests while JIT-compiling its first query
    with urllib.request.urlopen(url, timeout=120) as r:
        return json.loads(r.read())


def _poll(fn, timeout=90.0, interval=0.2):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            ok, last = fn()
            if ok:
                return last
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(interval)
    raise TimeoutError(f"poll timed out; last={last!r}")


def _send_lines(port, lines):
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        s.sendall(("\n".join(lines) + "\n").encode())


def _counter_lines(first_t, last_t):
    """Influx counter lines for N_SERIES series, sample index range
    [first_t, last_t)."""
    out = []
    for t in range(first_t, last_t):
        ts_ns = (T0 + t * 10) * 1_000_000_000
        for s in range(N_SERIES):
            out.append(f"reqs,instance=i{s} counter={(t + 1) * (s + 1)}"
                       f" {ts_ns}")
    return out


def _rate_query(port):
    """rate() over the whole run, keyed by instance (result order is not
    part of the API contract — bootstrap order differs from ingest order)."""
    body = _get(port, "/promql/timeseries/api/v1/query_range",
                query="rate(reqs[5m])",
                start=T0 + 300, end=T0 + (N_SAMPLES - 1) * 10, step=30)
    return {r["metric"]["instance"]: (r["metric"], r["values"])
            for r in body["data"]["result"]}


def test_backend_construction_failure_exits_nonzero(tmp_path):
    """No silent oracle fallback: a node whose device backend cannot be
    built dies with the reason on stderr instead of serving from numpy."""
    cfg_path = tmp_path / "server.json"
    cfg_path.write_text(json.dumps({"num-shards": 1, "port": 0}))
    script = (
        "import sys\n"
        "from filodb_tpu.query import tpu\n"
        "def boom(self, *a, **k):\n"
        "    raise RuntimeError('no device for you')\n"
        "tpu.TpuBackend.__init__ = boom\n"
        "from filodb_tpu.standalone import server\n"
        f"sys.exit(server.main(['--config', {str(cfg_path)!r}]))\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", script], cwd=str(REPO),
                         env=env, capture_output=True, timeout=120)
    assert res.returncode != 0
    assert b"no device for you" in res.stderr
    assert res.stdout.strip() == b""          # no startup line was printed


@pytest.mark.parametrize("want", [None, "", "tpu", "tpu,cpu", "cpu,tpu"])
def test_cpu_is_served_only_when_asked_for_and_for_nothing_else(
        monkeypatch, want):
    """This process runs JAX on the CPU. A node that finds itself there
    exits with the reason unless JAX_PLATFORMS is exactly `cpu`: a list
    that names the CPU beside an accelerator is a fallback order, not a
    request for a CPU node."""
    from filodb_tpu.standalone import server
    if want is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", want)
    with pytest.raises(SystemExit) as e:
        server._device_or_exit()
    assert "came up on the CPU" in str(e.value)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert server._device_or_exit()["platform"] == "cpu"


def test_kill_minus_9_restart_replays_to_identical_results(tmp_path):
    cfg = {
        "num-shards": 2, "groups-per-shard": 2, "port": 0,
        "data-dir": str(tmp_path / "data"),
        "stream-dir": str(tmp_path / "streams"),
        "gateway-port": 0,
        "flush-interval-s": 0.5,
    }
    cfg_path = tmp_path / "server.json"
    cfg_path.write_text(json.dumps(cfg))

    proc = _spawn(cfg_path)
    try:
        ports = _read_ports(proc)
        port, gw_port = ports["port"], ports["gateway_port"]
        assert gw_port is not None
        # the node says what it runs on, as jax reports it
        dev = ports["device"]
        assert dev["platform"] == "cpu" and dev["count"] >= 1
        assert isinstance(dev["kind"], str) and dev["kind"]

        # shards come up ACTIVE (empty streams -> trivial recovery)
        _poll(lambda: ((lambda b: (len(b["data"]) == 2 and all(
            s["status"] == "active" for s in b["data"]), b))(
            _get(port, "/api/v1/cluster/timeseries/status"))))

        # batch 1: ~2/3 of the data; let flush checkpoints land
        _send_lines(gw_port, _counter_lines(0, 40))

        def _all_series_at(t_end):
            body = _get(port, "/promql/timeseries/api/v1/query",
                        query="reqs", time=T0 + (t_end - 1) * 10)
            res = body["data"]["result"]
            vals = {r["metric"]["instance"]: float(r["value"][1])
                    for r in res}
            want = {f"i{s}": float(t_end * (s + 1))
                    for s in range(N_SERIES)}
            return vals == want, vals

        _poll(lambda: _all_series_at(40))
        time.sleep(1.5)          # several flush rotations -> checkpoints

        # batch 2: the tail; kill before the flush interval can persist it
        _send_lines(gw_port, _counter_lines(40, N_SAMPLES))
        _poll(lambda: _all_series_at(N_SAMPLES))
        before = _rate_query(port)
        assert len(before) == N_SERIES

        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    # -- restart on the same dirs: bootstrap + replay ----------------------
    proc2 = _spawn(cfg_path)
    try:
        ports2 = _read_ports(proc2)
        port2 = ports2["port"]
        _poll(lambda: ((lambda b: (len(b["data"]) == 2 and all(
            s["status"] == "active" for s in b["data"]), b))(
            _get(port2, "/api/v1/cluster/timeseries/status"))))
        # every pre-kill sample is back (flushed ones from the ColumnStore,
        # the unflushed tail replayed from the stream logs)
        _poll(lambda: _all_series_at_port(port2, N_SAMPLES))
        after = _rate_query(port2)
        # numerically identical: pre-kill evaluation may route tail steps
        # through the exact write-buffer path while post-replay data sits
        # in chunks on the f32-hybrid fast path (documented 1e-5 rtol)
        assert after.keys() == before.keys()
        for inst in before:
            assert after[inst][0] == before[inst][0]
            bvals, avals = before[inst][1], after[inst][1]
            assert [t for t, _ in avals] == [t for t, _ in bvals]
            np.testing.assert_allclose([float(v) for _, v in avals],
                                       [float(v) for _, v in bvals],
                                       rtol=1e-5)
    finally:
        proc2.send_signal(signal.SIGTERM)
        try:
            proc2.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc2.kill()
            proc2.wait(timeout=30)


def _all_series_at_port(port, t_end):
    body = _get(port, "/promql/timeseries/api/v1/query",
                query="reqs", time=T0 + (t_end - 1) * 10)
    res = body["data"]["result"]
    vals = {r["metric"]["instance"]: float(r["value"][1]) for r in res}
    want = {f"i{s}": float(t_end * (s + 1)) for s in range(N_SERIES)}
    return vals == want, vals
