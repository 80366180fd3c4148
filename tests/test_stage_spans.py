"""Stage spans (filodb_tpu.obs.trace): one ``span()`` at every layer
boundary of the served path, always timed. Self-time arithmetic on
hand-made nesting and across ``capture()``/``use()`` thread hops; which
stages each query path raises with the tracer OFF; the accounting
identity against ``filodb_query_latency_seconds``; the breakdown keys the
slow log and ``&explain`` keep; the exposition of the new families; the
histograms the stage spans feed; and the stages on the profiler's clock.
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.parse
import urllib.request

import pytest

from filodb_tpu.obs import metrics as obm
from filodb_tpu.obs import trace as obt
from filodb_tpu.standalone.server import FiloServer

T0 = 1_600_000_000

FUSED = "sum(rate(http_requests_total[5m])) by (job)"
GAUGE = "max_over_time(heap_usage[5m])"
RATE = "rate(http_requests_total[5m])"

# every stage under the ``query`` root (admission-wait is outside it)
QUERY_PATH = ("query", "parse", "plan", "execute", "encode",
              "resultcache-stitch", "select-series", "select-span",
              "group-keys", "aggregate", "device-eval", "pack",
              "tile-entry", "tile-build", "fused-eligibility", "onehot",
              "kernel-build", "device-dispatch", "device-sync",
              "batcher-queue-wait")


def _ns(name):
    st = obt._STAGE_TABLE[name]
    with st.lock:
        return st.calls, st.self_ns, st.cpu_ns


def _calls():
    return {n: t[0] for n, t in obt.stage_totals().items()}


def _rose(before, after):
    return {n for n in after if after[n] != before[n]}


@pytest.fixture(scope="module")
def srv():
    s = FiloServer({"num-shards": 2, "port": 0,
                    "slow-query-ms": 0.001}).start()
    s.seed_dev_data(n_samples=360, n_instances=8, start_ms=T0 * 1000)
    yield s
    s.stop()


def _get(srv, path, **params):
    url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/{path}?"
           + urllib.parse.urlencode(params))
    return json.loads(urllib.request.urlopen(url, timeout=120).read())


def _range(srv, query, shift=0, **extra):
    return _get(srv, "query_range", query=query, start=T0 + 600 + shift,
                end=T0 + 3000 + shift, step=60, cache="false", **extra)


def _metrics(srv):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
        text = r.read().decode()
    vals = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            name, v = ln.rsplit(" ", 1)
            vals[name] = float(v)
    return text, vals


# -- self-time arithmetic ------------------------------------------------------

def test_self_time_is_duration_less_child_stages():
    b = {n: _ns(n) for n in ("query", "parse", "encode")}
    with obt.span("query") as q:
        with obt.span("parse") as p:
            time.sleep(0.02)
        time.sleep(0.01)
        with obt.span("encode") as e:
            with obt.span("parse") as p2:
                time.sleep(0.005)
    a = {n: _ns(n) for n in b}
    d = {n: tuple(x - y for x, y in zip(a[n], b[n])) for n in b}
    assert d["query"][0] == 1 and d["parse"][0] == 2 and d["encode"][0] == 1
    # exact in integer nanoseconds: every stage's self time is its own
    # duration less its direct children's, so the selfs sum to the root
    assert d["parse"][1] == p.dur_ns + p2.dur_ns
    assert d["encode"][1] == e.dur_ns - p2.dur_ns
    assert d["query"][1] == q.dur_ns - p.dur_ns - e.dur_ns
    assert sum(x[1] for x in d.values()) == q.dur_ns
    assert q.dur_ns >= 35e6 and d["query"][1] >= 10e6
    # a sleeping stage burns no CPU: wall minus CPU is the wait
    assert d["parse"][2] < 0.5 * d["parse"][1]
    assert q.ms == round(q.dur_ns / 1e6, 3)


def test_self_time_across_capture_use_thread_hop():
    """A stage run on another thread under ``use(capture())`` is a child
    of the stage that was open at the capture: its wall time (not its
    CPU) leaves the waiting stage's self time."""
    b = {n: _ns(n) for n in ("batcher-queue-wait", "device-dispatch")}
    spans = {}

    def executor(ctx):
        with obt.use(ctx):
            with obt.span("device-dispatch") as sp:
                time.sleep(0.03)
            spans["dispatch"] = sp

    tr = obt.Trace()
    with obt.activate(tr):
        with obt.span("batcher-queue-wait") as w:
            t = threading.Thread(target=executor, args=(obt.capture(),))
            t.start()
            t.join()
    a = {n: _ns(n) for n in b}
    wait_self = a["batcher-queue-wait"][1] - b["batcher-queue-wait"][1]
    disp_self = a["device-dispatch"][1] - b["device-dispatch"][1]
    assert disp_self == spans["dispatch"].dur_ns >= 30e6
    assert wait_self == w.dur_ns - spans["dispatch"].dur_ns
    by_name = {s.name: s for s in tr.spans}
    assert by_name["device-dispatch"].parent_id \
        == by_name["batcher-queue-wait"].span_id
    # untraced, the frame alone still hops; use(None) stays a no-op
    with obt.span("batcher-queue-wait"):
        ctx = obt.capture()
    assert ctx is not None and ctx[0] is None
    assert obt.capture() is None
    with obt.use(None):
        pass


def _burn(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_cpu_clock_is_read_per_root_and_weighted():
    """Only a thread's root span decides whether its tree reads the
    thread CPU clock (a system call); a tree that does stands for the
    roots skipped since the last one."""
    root, child = obt._STAGE_TABLE["query"], obt._STAGE_TABLE["parse"]

    def request():
        with obt.span("query"):
            with obt.span("parse"):
                _burn(0.004)

    def cpu(st):
        return _ns(st.name)[2]

    with root.lock:
        root.cpu_next_ns, root.cpu_skipped = 0, 0
    c0 = cpu(child)
    request()                           # read, weight 1
    c1 = cpu(child)
    assert 3e6 <= c1 - c0 <= 12e6
    request()                           # within the interval: not read
    request()
    assert cpu(child) == c1 and _ns("parse")[0] >= 3
    with root.lock:
        assert root.cpu_skipped == 2
        root.cpu_next_ns = 0
    request()                           # read again, standing for three
    assert 3 * 3e6 <= cpu(child) - c1 <= 3 * 12e6
    # a root under a thread hop draws for itself, not from the waiter
    disp = obt._STAGE_TABLE["device-dispatch"]
    with disp.lock:
        disp.cpu_next_ns, disp.cpu_skipped = 0, 0
    d0 = cpu(disp)

    def executor(ctx):
        with obt.use(ctx):
            with obt.span("device-dispatch"):
                _burn(0.004)

    with obt.span("query"):             # not read (interval)
        with obt.span("batcher-queue-wait"):
            t = threading.Thread(target=executor, args=(obt.capture(),))
            t.start()
            t.join(timeout=30)
    assert 3e6 <= cpu(disp) - d0 <= 12e6


def test_non_stage_span_is_still_the_shared_noop_when_untraced():
    assert not obt.trace_active()
    assert obt.span("peer-attempt", peer="n1") is obt._NOOP
    assert obt.span("rule-eval") is obt._NOOP
    st = obt.span("parse")
    assert st is not obt._NOOP
    with st as sp:
        sp.tag(plan_cache="hit")        # harmless without a trace
    assert sp.span_id is None and sp.dur_ns >= 0
    # traced: a stage span still lands in the trace as a plain Span
    tr = obt.Trace()
    with obt.activate(tr):
        with obt.span("parse", k="v") as sp:
            with obt.span("peer-attempt"):
                pass
    assert [s.name for s in tr.spans] == ["peer-attempt", "parse"]
    assert tr.spans[1].tags == {"k": "v"}
    assert tr.spans[1].span_id == sp.span_id
    assert tr.spans[0].parent_id == sp.span_id
    assert tr.spans[1].dur_ns == sp.dur_ns


def test_trace_module_never_imports_jax():
    code = ("import sys; import filodb_tpu.obs.trace as t\n"
            "with t.span('query'):\n"
            "    with t.span('parse'): pass\n"
            "assert t.stage_totals()['parse'][0] == 1\n"
            "assert 'jax' not in sys.modules, 'trace.py pulled in jax'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, timeout=120)
    assert p.returncode == 0, p.stderr.decode()[-2000:]


# -- which stages each path raises, tracer OFF ---------------------------------

_COMMON = {"admission-wait", "query", "parse", "execute", "encode",
           "select-series", "device-dispatch", "device-sync"}
PATHS = {
    "fused": (lambda s, i: _range(s, FUSED, 60 * i),
              _COMMON | {"plan", "group-keys", "tile-entry",
                         "fused-eligibility", "onehot", "aggregate"}),
    "packed": (lambda s, i: _range(s, GAUGE, 60 * i),
               _COMMON | {"plan", "device-eval", "pack"}),
    "instant": (lambda s, i: _get(s, "query", query=RATE,
                                  time=T0 + 2000 + 10 * i),
                _COMMON | {"device-eval", "tile-entry"}),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_path_raises_exactly_its_stages_with_the_tracer_off(srv, path):
    run, want = PATHS[path]
    assert not srv.http.tracer.enabled
    assert run(srv, 0)["status"] == "success"   # builds tiles, compiles
    before = _calls()
    assert run(srv, 1)["status"] == "success"
    after = _calls()
    assert _rose(before, after) == want
    assert all(after[n] - before[n] == 1 for n in want - {"device-sync"})
    # ... and the same on /metrics, three families per stage
    _, vals = _metrics(srv)
    for n in want:
        fam = "filodb_stage_" + n.replace("-", "_")
        assert vals[fam + "_calls_total"] >= 1
        assert vals[fam + "_self_seconds_total"] > 0
        assert vals[fam + "_cpu_seconds_total"] >= 0


@pytest.mark.parametrize("mesh", [False, True], ids=["one-chip", "mesh"])
def test_device_execute_seconds_covers_the_fused_dispatch(mesh):
    s = FiloServer({"num-shards": 2, "port": 0,
                    "mesh-enabled": mesh}).start()
    try:
        s.seed_dev_data(n_samples=360, n_instances=8, start_ms=T0 * 1000)
        _range(s, FUSED)
        _, m0 = _metrics(s)
        for i in (1, 2, 3):
            assert _range(s, FUSED, 60 * i)["data"]["result"]
        _, m1 = _metrics(s)
        fused = m1["filodb_fused_aggs_total"] - m0["filodb_fused_aggs_total"]
        assert fused == 3
        assert m1["filodb_device_execute_seconds_count"] \
            - m0["filodb_device_execute_seconds_count"] == 3
        if mesh:
            assert m1["filodb_mesh_dispatches_total"] \
                - m0["filodb_mesh_dispatches_total"] == 3
    finally:
        s.stop()


# -- the accounting identity ---------------------------------------------------

def test_query_path_self_seconds_add_up_to_the_latency_histogram():
    """The stages under ``query`` add up to the latency histogram, and
    what no named stage covers (the self time of ``query`` and
    ``execute``, a fixed cost of about 0.4 ms a request on the CPU) is
    under a tenth of it. The requests read a store of 128 instances, so
    that the named stages carry the work a request does: over the module's
    8 nothing builds or compiles in the window, and a request is little
    more than that fixed cost."""
    s = FiloServer({"num-shards": 2, "port": 0,
                    "slow-query-ms": 0.001}).start()
    try:
        s.seed_dev_data(n_samples=360, n_instances=128, start_ms=T0 * 1000)
        for q in (FUSED, GAUGE):
            _range(s, q)
        b, (_, m0) = obt.stage_totals(), _metrics(s)
        for i in range(1, 11):
            _range(s, FUSED, 60 * i)
            _range(s, GAUGE, 60 * i)
        a, (_, m1) = obt.stage_totals(), _metrics(s)
    finally:
        s.stop()
    assert m1["filodb_query_latency_seconds_count"] \
        - m0["filodb_query_latency_seconds_count"] == 20
    lat = m1["filodb_query_latency_seconds_sum"] \
        - m0["filodb_query_latency_seconds_sum"]
    selfs = {n: a[n][1] - b[n][1] for n in QUERY_PATH}
    assert sum(selfs.values()) == pytest.approx(lat, rel=0.05)
    assert selfs["query"] + selfs["execute"] < 0.10 * lat


# -- the breakdowns keep their keys -------------------------------------------

def test_timings_slowlog_and_explain_keep_their_keys(srv):
    body = _range(srv, RATE, 120)
    tm = body["stats"]["timings"]
    assert set(tm) == {"parseMs", "planMs", "execMs", "plan", "planCache",
                       "resultCache"}
    assert all(tm[k] >= 0 for k in ("parseMs", "planMs", "execMs"))
    slow = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/debug/slow_queries",
        timeout=30).read())
    rec = next(r for r in slow["data"] if r["query"] == RATE
               and r["kind"] == "range")
    assert {"parseMs", "planMs", "execMs", "encodeMs", "planCache",
            "resultCache"} <= set(rec["stages"])
    stage_sum = sum(v for k, v in rec["stages"].items() if k.endswith("Ms"))
    assert 0 < stage_sum <= rec["elapsed_ms"] + 1e-3
    inst = next(r for r in slow["data"] if r["kind"] == "instant")
    assert {"parseMs", "execMs", "encodeMs"} <= set(inst["stages"])
    ex = _range(srv, RATE, 180, explain="analyze")
    assert {"parseMs", "planMs", "execMs", "encodeMs"} \
        <= set(ex["analyze"]["stages"])
    names = {s["name"] for s in ex["trace"]["spans"]}
    assert {"query", "parse", "plan", "execute", "encode",
            "select-series", "device-eval", "tile-entry",
            "device-dispatch", "device-sync"} <= names
    by_id = {s["span_id"]: s for s in ex["trace"]["spans"]}
    sync = next(s for s in ex["trace"]["spans"]
                if s["name"] == "device-sync")
    assert by_id[sync["parent_id"]]["name"] == "device-eval"


# -- exposition ----------------------------------------------------------------

REMOVED = ("filodb_batcher_occupancy_avg", "filodb_batcher_occupancy_max",
           "filodb_batcher_batched_queries_total",
           "filodb_batcher_gather_wait_ms_total")


@pytest.mark.parametrize("suffix,mtype", [
    ("calls_total", "counter"), ("self_seconds_total", "counter"),
    ("cpu_seconds_total", "counter"), ("gc_seconds_total", "counter")])
def test_every_stage_family_has_help_and_type(srv, suffix, mtype):
    text, vals = _metrics(srv)
    for name in obt.STAGES:
        fam = f"filodb_stage_{name.replace('-', '_')}_{suffix}"
        assert f"# HELP {fam} " in text, fam
        assert f"# TYPE {fam} {mtype}" in text, fam
        assert fam in vals                  # unlabelled: one sample each
    assert obm.validate_histogram_families(text) == []
    samples = [ln.rsplit(" ", 1)[0] for ln in text.splitlines()
               if ln and not ln.startswith("#")]
    assert len(samples) == len(set(samples))


def test_the_four_batcher_families_are_gone(srv):
    text, vals = _metrics(srv)
    for fam in REMOVED:
        assert fam not in text
    # what gives the same numbers stays
    assert "filodb_batcher_queries_total" in vals
    assert "filodb_batcher_batches_total" in vals
    assert "# TYPE filodb_batcher_batch_size histogram" in text
    assert "# TYPE filodb_batcher_queue_wait_seconds histogram" in text


@pytest.mark.parametrize("stage,family", sorted(
    (s, h[0]) for s, h in obt.STAGE_HISTOGRAMS.items()))
def test_stage_span_feeds_its_histogram(stage, family):
    """One clock pair: the span's duration is what the histogram sees."""
    name, help_, buckets = obt.STAGE_HISTOGRAMS[stage]
    h = obm.GLOBAL_REGISTRY.histogram(name, help_, buckets)
    before = h.snapshot()
    with obt.span(stage) as sp:
        time.sleep(0.002)
    after = h.snapshot()
    assert after["count"] - before["count"] == 1
    assert after["sum"] - before["sum"] == pytest.approx(sp.dur_ns / 1e9)
    assert h.buckets == tuple(float(b) for b in buckets)


def test_write_path_stages(tmp_path):
    from filodb_tpu.gateway.server import send_lines
    s = FiloServer({"num-shards": 2, "port": 0, "gateway-port": 0,
                    "data-dir": str(tmp_path / "data"),
                    "stream-dir": str(tmp_path / "streams"),
                    "flush-interval-s": 0.2}).start()
    try:
        before = _calls()
        lines = [f"cpu_load,host=h{i},_ws_=demo,_ns_=App-0 value={i} "
                 f"{(T0 + 10 * k) * 1_000_000_000}"
                 for k in range(30) for i in range(4)]
        send_lines("127.0.0.1", s.gateway.port, lines)
        want = {"gateway-parse", "wal-append", "shard-ingest", "flush",
                "flush-encode", "flush-write"}
        deadline = time.time() + 30
        while time.time() < deadline \
                and not want <= _rose(before, _calls()):
            time.sleep(0.1)
        assert want <= _rose(before, _calls())
    finally:
        s.stop()


# -- the profiler's clock ------------------------------------------------------

def test_stages_are_events_on_the_profilers_host_plane(srv, tmp_path):
    import jax
    from jax.profiler import ProfileData
    _range(srv, GAUGE)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _range(srv, GAUGE, 60)
        _range(srv, FUSED, 60)
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                       / "*.xplane.pb"))[0]
    host = next(p for p in ProfileData.from_file(pb).planes
                if p.name == "/host:CPU")
    found = 0
    for line in host.lines:
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for e in line.events if e.name.startswith("filodb:")]
        for name, q0, q1 in evs:
            if name != "filodb:query":
                continue
            inside = {n for n, s0, s1 in evs if q0 <= s0 and s1 <= q1}
            assert {"filodb:select-series", "filodb:encode",
                    "filodb:execute", "filodb:device-dispatch"} <= inside
            found += 1
    assert found == 2


def test_collections_are_events_on_the_profilers_host_plane(srv, tmp_path):
    """Generations 1 and 2, beside the stage they interrupt; generation
    0 is too frequent for a traced run and stays off the plane."""
    import gc

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obt.span("execute"):
            gc.collect(0)
            gc.collect(1)
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                       / "*.xplane.pb"))[0]
    host = next(p for p in ProfileData.from_file(pb).planes
                if p.name == "/host:CPU")
    found = set()
    for line in host.lines:
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for e in line.events if e.name.startswith("filodb:")]
        for name, x0, x1 in evs:
            if name != "filodb:execute":
                continue
            found |= {n for n, s0, s1 in evs
                      if n.startswith("filodb:gc") and x0 <= s0 and s1 <= x1}
    assert found == {"filodb:gc1", "filodb:gc2"}


def test_stage_counters_lose_no_update_under_contention():
    threads, per = 16, 2000
    b = _ns("pack")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for _ in range(per):
                with obt.span("pack"):
                    pass
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    a = _ns("pack")
    assert a[0] - b[0] == threads * per
    assert a[1] > b[1]
