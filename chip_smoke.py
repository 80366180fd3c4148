#!/usr/bin/env python3
"""chip_smoke.py: the standalone node answers PromQL from the TPU.

Drives the main path once, end to end, through the entry points a user
calls: starts ``python -m filodb_tpu.standalone.server`` as a child with
the environment it was given, loads a seeded store through the gateway's
TCP influx port (gateway -> per-shard WAL stream -> memstore -> flush),
sends ``query_range`` / ``query`` requests over HTTP, compares every
answer with a plain reference computed HERE from this process's own
copy of the samples (pure-Python ``refeval`` on small selections, the
numpy ``rangefn`` oracle for the all-series ``sum by``), and reads
``/metrics`` to prove the device served — a right answer from the host
oracle is a failure.

This parent never imports JAX: a process that has touched JAX holds the
chip, and the node child needs it. One JSON object per phase on stdout;
the LAST line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the node's startup line reports it. Exit code 0 only
if every phase passed AND the node runs on a TPU: under
``JAX_PLATFORMS=cpu`` every phase still runs (a rehearsal) and the run
ends ``"ok": false``, non-zero.

``--chips 4`` runs the path across chips and what it is compared with,
and no other phase: one node with ``"mesh-enabled": true`` drives the
four chips from the resident sharded store.
"""

import argparse
import json
import math
import os
import select
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

from filodb_tpu import native
from filodb_tpu.promql.refeval import RefSeries, ref_eval
from filodb_tpu.query import rangefn

REPO = os.path.dirname(os.path.abspath(__file__))
T0_MS = 1_700_000_000_000          # a whole 10 s tick
DT_MS = 10_000                     # upstream's documented dev scrape cadence
JITTER_MS = 2_000
JOBS = 16
TAIL = 30                          # scrapes sent as live ingest, after the flush
DATASET = "timeseries"
NUM_SHARDS, GROUPS = 4, 2
FLUSH_S = 2.0
# tolerances the parity tests already use: the fused group-sum and the
# aligned counter evaluators end in an f32 epilogue (test_missed_scrapes
# rtol 1e-6, test_groupsum_dispatch 2e-6); everything else is f64 to a
# few ulps
RTOL_F32, RTOL_F64 = 1e-5, 1e-12

_failed = []


def emit(obj):
    print(json.dumps(obj), flush=True)


def check(phase, cond, why):
    if not cond:
        _failed.append(f"{phase}: {why}")
    return bool(cond)


# -- data ---------------------------------------------------------------------

class World:
    """The parent's own copy of every sample it sends."""

    def __init__(self, seed, n_series, n_samples):
        rng = np.random.default_rng(seed)
        self.S, self.N = S, N = n_series, n_samples
        self.Sg = Sg = max(8, S // 8)
        idx = np.arange(S)
        self.job = idx % JOBS
        self.jittered = (idx // JOBS) % 2 == 1
        # (job, cadence, rack) selects <= 64 series for the Python reference
        self.n_racks = max(1, math.ceil(S / (2 * JOBS) / 64))
        self.rack = (idx // (2 * JOBS)) % self.n_racks
        ticks = T0_MS + np.arange(N, dtype=np.int64) * DT_MS
        self.ts = np.broadcast_to(ticks, (S, N)).copy()
        self.ts[self.jittered] += rng.integers(
            -JITTER_MS, JITTER_MS + 1, (int(self.jittered.sum()), N))
        vals = np.cumsum(rng.integers(0, 50, (S, N)), axis=1)
        for r in range(5, S, 37):               # counter resets
            k = int(rng.integers(N // 4, 3 * N // 4))
            vals[r, k:] -= vals[r, k - 1]
        self.vals = vals.astype(np.float64)
        self.g_ts = np.broadcast_to(ticks, (Sg, N))
        self.g_vals = np.round(rng.normal(4.0, 2.0, (Sg, N)), 3)
        self.g_rack = (np.arange(Sg) // JOBS) % max(
            1, math.ceil(Sg / JOBS / 64))
        self.labels = [
            {"job": f"job-{self.job[i]:02d}", "instance": f"i-{i:06d}",
             "cadence": "jitter" if self.jittered[i] else "tick",
             "rack": f"r{self.rack[i]}"} for i in range(S)]
        self.g_labels = [{"job": f"job-{i % JOBS:02d}",
                          "instance": f"g-{i:06d}",
                          "rack": f"r{self.g_rack[i]}"} for i in range(Sg)]
        tags = lambda d: ",".join(f"{k}={v}" for k, v in d.items())
        self._c_prefix = [f"http_requests_total,{tags(l)} counter="
                          for l in self.labels]
        self._g_prefix = [f"node_load1,{tags(l)} gauge="
                          for l in self.g_labels]

    def lines(self, k0, k1, by_series):
        """Influx lines of scrapes [k0, k1), every series: in scrape order
        (what live scrapers send), or each series' samples together (a
        backfill block — the node ingests a run of one series at once)."""
        blocks = []
        for prefix, vals, ts, fmt in (
                (self._c_prefix, self.vals[:, k0:k1].astype(np.int64),
                 self.ts[:, k0:k1], str),
                (self._g_prefix, self.g_vals[:, k0:k1], self.g_ts[:, k0:k1],
                 repr)):
            blocks.append([[f"{p}{fmt(v)} {t}000000" for v, t in zip(vr, tr)]
                           for p, vr, tr in zip(prefix, vals.tolist(),
                                                ts.tolist())])
        rows = blocks[0] + blocks[1]                 # [series][scrape]
        if by_series:
            return [l for row in rows for l in row]
        return [l for col in zip(*rows) for l in col]

    def end_s(self, n):
        """Whole-minute offset (s from T0) of the last step that sees only
        the first ``n`` scrapes."""
        return ((n - 1) * DT_MS // 1000 - 5) // 60 * 60


# -- node ---------------------------------------------------------------------

def start_node(cfg, workdir):
    cfg_path = os.path.join(workdir, "server.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    assert "jax" not in sys.modules, "the parent must never import JAX"
    log = open(os.path.join(workdir, "node.stderr"), "w")
    return subprocess.Popen(
        [sys.executable, "-m", "filodb_tpu.standalone.server",
         "--config", cfg_path],
        cwd=REPO, stdout=subprocess.PIPE, stderr=log)


def read_startup(proc, timeout):
    deadline, buf = time.monotonic() + timeout, b""
    while time.monotonic() < deadline:
        if select.select([proc.stdout], [], [], 1.0)[0]:
            ch = proc.stdout.read1(4096)
            if not ch:
                return None
            buf += ch
            if b"\n" in buf:
                return json.loads(buf.split(b"\n", 1)[0])
        elif proc.poll() is not None:
            return None
    return None


def stop_node(proc):
    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def http_get(port, path, timeout=900, **params):
    url = f"http://127.0.0.1:{port}{path}"
    if params:
        url += "?" + urllib.parse.urlencode(params)
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read()
    except urllib.error.HTTPError as e:
        raise RuntimeError(f"HTTP {e.code} for {url}: "
                           f"{e.read()[:2000].decode(errors='replace')}")


def metrics(port):
    """/metrics -> {family: summed value} (labels folded)."""
    out = {}
    for line in http_get(port, "/metrics", timeout=60).decode().splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.split(" # ", 1)[0].rpartition(" ")
            fam = name.split("{", 1)[0]
            try:
                out[fam] = out.get(fam, 0.0) + float(val)
            except ValueError:
                pass
    return out


DEVICE_FAMILIES = ("filodb_fused_aggs_total", "filodb_mesh_dispatches_total",
                   "filodb_device_execute_seconds_count",
                   "filodb_exec_cache_hits_total",
                   "filodb_exec_cache_misses_total",
                   "filodb_tile_builds_total", "filodb_tile_cache_hits_total")


def served_by(delta):
    """Which device path the counters say served a query."""
    paths = []
    if delta.get("filodb_mesh_dispatches_total"):
        paths.append("mesh-resident sharded store")
    elif delta.get("filodb_fused_aggs_total"):
        paths.append("fused group-sum")
    if delta.get("filodb_device_execute_seconds_count"):
        tiles = (delta.get("filodb_tile_builds_total")
                 or delta.get("filodb_tile_cache_hits_total"))
        paths.append("aligned tiles" if tiles else "packed kernels")
    return paths or ["none: host only"]


def device_delta(before, after):
    return {f: after.get(f, 0) - before.get(f, 0) for f in DEVICE_FAMILIES
            if after.get(f, 0) != before.get(f, 0)}


# -- load ---------------------------------------------------------------------

BLOCK = 60                         # scrapes per backfill block (10 min)


def send_scrapes(gw_port, world, k0, k1, by_series):
    n = 0
    with socket.create_connection(("127.0.0.1", gw_port), timeout=600) as s:
        for b0 in range(k0, k1, BLOCK):
            lines = world.lines(b0, min(b0 + BLOCK, k1), by_series)
            s.sendall(("\n".join(lines) + "\n").encode())
            n += len(lines)
    return n


def wait_for(port, what, pred, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        m = metrics(port)
        if pred(m):
            return m
        time.sleep(0.5)
    check("load", False, f"timed out waiting for {what}")
    return metrics(port)


def ingest(port, gw_port, world, k0, k1, rows_before, timeout,
           by_series):
    """Send scrapes [k0, k1) and wait until the node has ingested them
    all (the gateway's TCP edge has no ack: the shard row counters are
    the acknowledgement)."""
    t0 = time.monotonic()
    n = send_scrapes(gw_port, world, k0, k1, by_series)
    want = rows_before + n
    m = wait_for(port, f"{want} rows ingested",
                 lambda m: m.get("filodb_rows_ingested", 0) >= want, timeout)
    return n, time.monotonic() - t0, m


def wait_flushed(port, m, timeout):
    """Every flush group of every shard has flushed since ``m``: all rows
    ingested by then sit in immutable chunks, which is what the device
    tiles are built from."""
    need = m.get("filodb_flushes_done", 0) + NUM_SHARDS * (GROUPS + 1)
    return wait_for(port, "a flush of every group",
                    lambda m: m.get("filodb_flushes_done", 0) >= need,
                    timeout)


# -- queries and their references ---------------------------------------------

def prom_matrix(body):
    """query_range JSON -> {instance or job: (ts_s list, f64 array)}."""
    doc = json.loads(body)
    assert doc["status"] == "success", doc
    out = {}
    for r in doc["data"]["result"]:
        m = r["metric"]
        key = m.get("instance", m.get("job"))
        vs = r.get("values") or [r["value"]]
        out[key] = ([int(float(t)) for t, _ in vs],
                    np.array([float(v) for _, v in vs]))
    return out


def ref_subset(world, query, sel, n, start_s, step_s, end_s, gauge=False):
    """refeval over the selected series' first n scrapes -> {instance: row};
    start/end are seconds from T0, as everywhere in the query phases."""
    labels = world.g_labels if gauge else world.labels
    ts = world.g_ts if gauge else world.ts
    vals = world.g_vals if gauge else world.vals
    metric = "node_load1" if gauge else "http_requests_total"
    series = [RefSeries({"_metric_": metric, "_ws_": "demo", "_ns_": "App-0",
                         **labels[i]},
                        ts[i, :n].tolist(), vals[i, :n].tolist())
              for i in sel]
    t0_s = T0_MS // 1000
    got = ref_eval(query, series, t0_s + start_s, step_s, t0_s + end_s)
    return {dict(k)["instance"]: np.array(row) for k, row in got.items()}


def counter_subset(world, job, cadence):
    """(PromQL, series indices) of one (job, cadence, rack 0) selection:
    at most 64 series, small enough for the pure-Python reference."""
    sel = [i for i in range(world.S) if world.job[i] == job
           and world.rack[i] == 0
           and world.jittered[i] == (cadence == "jitter")]
    return (f'rate(http_requests_total{{job="job-{job:02d}",'
            f'cadence="{cadence}",rack="r0"}}[5m])'), sel


def ref_sum_by_job(world, n, start_s, step_s, end_s, window_ms):
    """numpy oracle: per-series rate, summed by job (NaN = no sample)."""
    steps = rangefn.step_grid(T0_MS + start_s * 1000, step_s * 1000,
                              T0_MS + end_s * 1000)
    sums = np.zeros((JOBS, steps.size))
    cnts = np.zeros((JOBS, steps.size))
    for i in range(world.S):
        r = rangefn.evaluate("rate", world.ts[i, :n], world.vals[i, :n],
                             int(steps[0]), step_s * 1000, int(steps[-1]),
                             window_ms)
        ok = ~np.isnan(r)
        sums[world.job[i]] += np.where(ok, r, 0.0)
        cnts[world.job[i]] += ok
    return {f"job-{j:02d}": np.where(cnts[j] > 0, sums[j], np.nan)
            for j in range(JOBS)}


def compare(got, want, steps_s):
    """Max relative error over every (series, step); inf on any mismatch
    of series set, step grid or NaN pattern."""
    if set(got) != set(want):
        return math.inf
    worst = 0.0
    for key, row in want.items():
        g_ts, g = got[key]
        ok = ~np.isnan(row)
        if g_ts != [t for t, o in zip(steps_s, ok) if o]:
            return math.inf
        w = row[ok]
        with np.errstate(divide="ignore", invalid="ignore"):
            err = np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
        err = np.where(g == w, 0.0, err)
        if err.size:
            worst = max(worst, float(err.max()))
    return worst


def run_query(port, name, query, want, start_s, end_s, step_s, rtol,
              must_rise, instant=False):
    """Send the query twice (cold, warm; the results cache is bypassed so
    the second run is the warm DEVICE path), compare both answers with
    the reference, and require the named device counters to rise."""
    path = f"/promql/{DATASET}/api/v1/" + ("query" if instant else
                                           "query_range")
    params = ({"query": query, "time": T0_MS // 1000 + end_s} if instant else
              {"query": query, "start": T0_MS // 1000 + start_s,
               "end": T0_MS // 1000 + end_s, "step": step_s})
    steps_s = list(range(T0_MS // 1000 + start_s,
                         T0_MS // 1000 + end_s + 1, step_s))
    if instant:
        steps_s = steps_s[-1:]
    before = metrics(port)
    secs, errs = [], []
    for _ in range(2):
        t0 = time.monotonic()
        body = http_get(port, path, cache="false", **params)
        secs.append(time.monotonic() - t0)
        errs.append(compare(prom_matrix(body), want, steps_s))
    delta = device_delta(before, metrics(port))
    ok = check(name, max(errs) <= rtol,
               f"max relative error {max(errs):.3g} > {rtol:g}")
    ok &= check(name, any(delta.get(f, 0) > 0 for f in must_rise),
                f"none of {must_rise} rose: served off the device ({delta})")
    emit({"phase": "query", "name": name, "query": query,
          "series": len(want), "steps": len(steps_s),
          "cold_s": secs[0], "warm_s": secs[1],
          "max_rel_err": max(errs), "rtol": rtol,
          "served_by": served_by(delta), "device_counters": delta,
          "ok": ok})


EXEC = ("filodb_device_execute_seconds_count", "filodb_exec_cache_hits_total",
        "filodb_exec_cache_misses_total")


def query_phases(port, world, n_prefix, chips):
    """Everything asked while the first n_prefix scrapes are flushed and
    nothing else has arrived: ranges end inside the flushed prefix."""
    end = world.end_s(n_prefix)
    start = 600
    q = "sum(rate(http_requests_total[5m])) by (job)"
    run_query(port, "sum_by_job_in_prefix", q,
              ref_sum_by_job(world, n_prefix, start, 60, end, 300_000),
              start, end, 60, RTOL_F32,
              ["filodb_fused_aggs_total"] if chips == 1 else
              ["filodb_mesh_dispatches_total"])
    # four chips: the cross-chip path and what it is compared with, only
    for cadence in ("tick", "jitter") if chips == 1 else ("tick",):
        q, sel = counter_subset(world, 3, cadence)
        run_query(port, f"rate_{cadence}_series", q,
                  ref_subset(world, q, sel, n_prefix, start, 60, end),
                  start, end, 60, RTOL_F32,
                  EXEC if chips == 1 else ["filodb_mesh_dispatches_total"])
    if chips != 1:
        return
    q = 'max_over_time(node_load1{job="job-05",rack="r0"}[5m])'
    gsel = [i for i in range(world.Sg)
            if i % JOBS == 5 and world.g_rack[i] == 0]
    run_query(port, "max_over_time_gauges", q,
              ref_subset(world, q, gsel, n_prefix, start, 60, end,
                         gauge=True),
              start, end, 60, RTOL_F64, EXEC)
    q, sel = counter_subset(world, 7, "tick")
    run_query(port, "rate_instant", q,
              ref_subset(world, q, sel, n_prefix, end, 60, end),
              end, end, 60, RTOL_F32, EXEC, instant=True)


def now_phases(port, world):
    """The same questions ending at *now*, with the last TAIL scrapes just
    ingested: flushed tiles plus the live write-buffer tail."""
    end = world.end_s(world.N)
    start = max(600, end - 1800)
    q = "sum(rate(http_requests_total[5m])) by (job)"
    run_query(port, "sum_by_job_at_now", q,
              ref_sum_by_job(world, world.N, start, 60, end, 300_000),
              start, end, 60, RTOL_F32,
              EXEC + ("filodb_fused_aggs_total",))
    q, sel = counter_subset(world, 3, "jitter")
    run_query(port, "rate_jitter_series_at_now", q,
              ref_subset(world, q, sel, world.N, start, 60, end),
              start, end, 60, RTOL_F32, EXEC)


def readback(port, world, n):
    """One acknowledged series, read back in full: a bare selector on the
    series' own scrape grid returns every sample that was sent."""
    i = next(i for i in range(world.S // 2, world.S) if not world.jittered[i])
    inst = world.labels[i]["instance"]
    body = http_get(port, f"/promql/{DATASET}/api/v1/query_range",
                    query=f'http_requests_total{{instance="{inst}"}}',
                    start=T0_MS // 1000,
                    end=(T0_MS + (n - 1) * DT_MS) // 1000,
                    step=DT_MS // 1000, cache="false")
    got = prom_matrix(body).get(inst, ([], np.empty(0)))
    want_ts = (world.ts[i, :n] // 1000).tolist()
    return check("load", got[0] == want_ts
                 and np.array_equal(got[1], world.vals[i, :n]),
                 f"series {inst} read back {len(got[0])} samples, "
                 f"sent {n}, or values differ")


# -- main ---------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--series", type=int, default=8192)
    ap.add_argument("--samples", type=int, default=720,
                    help="per series, 10 s apart (720 = 2 h)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    t_all = time.monotonic()

    world = World(args.seed, args.series, args.samples)
    n_prefix = world.N - TAIL
    emit({"phase": "data", "seed": args.seed, "counter_series": world.S,
          "gauge_series": world.Sg, "samples_per_series": world.N,
          "samples": (world.S + world.Sg) * world.N,
          "cadence_s": DT_MS // 1000, "jitter_s": JITTER_MS // 1000,
          "jittered_series": int(world.jittered.sum()), "job_groups": JOBS})

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    cfg = {"dataset": DATASET, "num-shards": NUM_SHARDS,
           "groups-per-shard": GROUPS, "port": 0, "gateway-port": 0,
           "data-dir": os.path.join(workdir, "data"),
           "stream-dir": os.path.join(workdir, "streams"),
           "flush-interval-s": FLUSH_S,
           # a cold node compiles every program on its first queries;
           # the all-series `sum by` scans the whole store by design
           "query-timeout-s": 900.0,
           "query-sample-limit": 0, "query-series-limit": 0,
           "mesh-enabled": args.chips > 1}
    device, proc = None, start_node(cfg, workdir)
    try:
        t0 = time.monotonic()
        line = read_startup(proc, 300)
        if line is None:
            check("node", False, "the node printed no startup line")
            return finish(device)
        device = line.get("device")
        port, gw_port = line["port"], line["gateway_port"]
        emit({"phase": "node", "device": device,
              "startup_s": time.monotonic() - t0})
        check("node", device and device["platform"] == "tpu",
              f"the node runs on {device}, not on a TPU")
        check("node", device and device["count"] == args.chips,
              f"the node sees {device and device['count']} devices, "
              f"asked for {args.chips}")

        t0 = time.monotonic()
        n, t_ing, m = ingest(port, gw_port, world, 0, n_prefix, 0, 900,
                             by_series=True)
        m = wait_flushed(port, m, 120)
        t_res = time.monotonic() - t0
        rb = readback(port, world, n_prefix)
        emit({"phase": "load", "lines": n, "ingest_s": t_ing,
              "lines_per_s": n / t_ing, "resident_s": t_res,
              "rows_ingested": m.get("filodb_rows_ingested"),
              "flushes_done": m.get("filodb_flushes_done"),
              "native_codec": native.load_nibblepack() is not None,
              "readback_ok": rb})

        query_phases(port, world, n_prefix, args.chips)
        if args.chips == 1:
            n2, t_ing, m = ingest(port, gw_port, world, n_prefix, world.N,
                                  n, 300, by_series=False)
            emit({"phase": "live_tail", "lines": n2, "ingest_s": t_ing,
                  "rows_ingested": m.get("filodb_rows_ingested")})
            now_phases(port, world)
        check("node", proc.poll() is None, "the node died during the run")
    except Exception as e:      # noqa: BLE001 — any phase's error fails the run
        traceback.print_exc()
        check("run", False, f"{type(e).__name__}: {e}"[:500])
    finally:
        stop_node(proc)
        if _failed:
            with open(os.path.join(workdir, "node.stderr")) as f:
                sys.stderr.write("---- node stderr (tail) ----\n"
                                 + f.read()[-6000:] + "\n")
        shutil.rmtree(workdir, ignore_errors=True)
    emit({"phase": "done", "seconds": time.monotonic() - t_all,
          "failed": _failed})
    return finish(device)


def finish(device):
    for f in _failed:
        print("FAILED " + f, file=sys.stderr)
    emit({"ok": not _failed, "device": device})
    return 0 if not _failed else 1


if __name__ == "__main__":
    sys.exit(main())
