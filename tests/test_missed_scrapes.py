"""The served ``sum by (job)`` of ``rate[5m]`` over a store with missed
scrapes: a scrape that failed stored no sample, so a series has holes on
the cadence grid and its tiles are not dense. The fused gate
(``tilestore.groupsum_counters``) serves the selection with the one
grouped f32-hybrid program it runs over dense tiles too, over the filled
channels: one cached executable, and only the ``[T, G]`` sums and counts
come to the host.

Held here, through ``FiloServer``'s HTTP query path with the TPU backend
against ``promql/refeval.py`` in float64: the answer over each kind of hole,
and the route, as ``/metrics`` tells it. A small fleet of this file's own
(2 apps x 4 jobs x 16 instances, 120 scrape ticks 10 s apart, every second
series +-2 s off the tick, one counter reset), the same for every case but
for the scrapes a case takes out.
"""

import json
import math
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.gateway.producer import (TestTimeseriesProducer,
                                         ingest_builders)
from filodb_tpu.promql.refeval import RefSeries, ref_eval
from filodb_tpu.query import engine as eng
from filodb_tpu.standalone.server import FiloServer

T0 = 1_600_000_000          # s; tick k is at T0 + 10 k
TICKS = 120
APPS, JOBS, INST = 2, 4, 16
START, END, STEP = T0 + 360, T0 + 1080, 60    # 13 steps, windows inside
QUERY = ('{op}(rate(http_requests_total{{_ws_="demo",_ns_="App-0"}}[5m]))'
         ' by (job)')
# the window of the step at T0 + 600 is [T0 + 300, T0 + 600]: ticks 30..60
FIRST_SLOT, LAST_SLOT = 30, 60
# the per-series rates are float32 on the device (the f32-hybrid
# evaluator's epilogue, ~3e-7 relative; the fused kernel's likewise) and
# both fused programs sum a group's 16 of them in float32
RTOL = 1e-6


def _fleet(seed=20261003, poison=None):
    """[(labels, ts ms [TICKS], vals [TICKS])] of App-0 and App-1;
    ``poison`` puts a value the f32 program cannot carry into series 6
    (App-0, job-0): ``"inf"`` one infinite sample, ``"span-1e60"`` every
    value times 1e60."""
    rng = np.random.default_rng(seed)
    out = []
    for a in range(APPS):
        for j in range(JOBS):
            for i in range(INST):
                n = len(out)
                ts = (T0 + 10 * np.arange(TICKS, dtype=np.int64)) * 1000
                if n % 2:
                    ts = ts + rng.integers(-2000, 2001, TICKS)
                vals = np.cumsum(rng.integers(0, 50, TICKS)).astype(float)
                if n == 5:
                    vals[70:] -= vals[69]           # a counter reset
                if n == 6 and poison == "inf":
                    vals[45] = np.inf
                if n == 6 and poison == "span-1e60":
                    vals = vals * 1e60
                out.append(({"_metric_": "http_requests_total",
                             "_ws_": "demo", "_ns_": f"App-{a}",
                             "job": f"job-{j}", "instance": f"i-{n:03d}"},
                            ts, vals))
    return out


def _flaky(n):
    return n % 4 == 3


def _single_misses(n, rng):
    return rng.choice(np.arange(2, TICKS - 2), 4, replace=False) \
        if _flaky(n) else []


def _run_of_four(n, rng):
    k = int(rng.integers(2, TICKS - 6))
    return range(k, k + 4) if _flaky(n) else []


def _under_two(n, rng):
    """Ticks 31..60 out: the window of T0 + 600 keeps one sample. One
    instance of job-0 (its group's count drops there) and every instance
    of job-1 (the group has no point there)."""
    return range(31, 61) if n == 3 or INST <= n < 2 * INST else []


# case -> (series number, rng) -> the ticks whose scrape failed
CASES = {
    "dense": lambda n, rng: [],
    "single-misses": _single_misses,
    "run-of-four": _run_of_four,
    "hole-on-first-slot": lambda n, rng: [FIRST_SLOT] if _flaky(n) else [],
    "hole-on-last-slot": lambda n, rng: [LAST_SLOT] if _flaky(n) else [],
    "window-under-two-samples": _under_two,
    "one-flaky-among-dense": lambda n, rng: [47] if n == 6 else [],
    "every-series-has-a-hole":
        lambda n, rng: rng.choice(np.arange(2, TICKS - 2), 2, replace=False),
}


def _store(case, poison=None):
    """-> (server, [RefSeries]) with the case's scrapes taken out."""
    srv = FiloServer({"num-shards": 2, "port": 0}).start()
    producer = TestTimeseriesProducer(DEFAULT_SCHEMAS, num_shards=2)
    rng = np.random.default_rng(7)
    builders, ref = {}, []
    for n, (labels, ts, vals) in enumerate(_fleet(poison=poison)):
        keep = np.ones(TICKS, bool)
        keep[list(CASES[case](n, rng))] = False
        b = builders.setdefault(producer.shard_for("prom-counter", labels),
                                RecordBuilder(DEFAULT_SCHEMAS))
        for t, v in zip(ts[keep].tolist(), vals[keep].tolist()):
            b.add_sample("prom-counter", labels, t, v)
        ref.append(RefSeries(labels, ts[keep].tolist(), vals[keep].tolist()))
    ingest_builders(srv.store, srv.ref, builders)
    srv.store.flush_all(srv.ref)
    return srv, ref


def _metrics(srv):
    with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/metrics",
                                timeout=30) as r:
        lines = r.read().decode().splitlines()
    return {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
            for ln in lines if ln.startswith("filodb_") and "{" not in ln}


def _served(srv, op, app=0, start=START, end=END, step=STEP):
    """-> {job: {step s: value}} as the node answers over HTTP."""
    url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
           "query_range?" + urllib.parse.urlencode(dict(
               query=QUERY.format(op=op).replace("App-0", f"App-{app}"),
               start=start, end=end, step=step, cache="false")))
    body = json.loads(urllib.request.urlopen(url, timeout=300).read())
    assert body["status"] == "success"
    return {r["metric"]["job"]: {int(t): float(v) for t, v in r["values"]}
            for r in body["data"]["result"]}


def _reference(ref, op, app=0, start=START, end=END, step=STEP):
    rows = ref_eval(QUERY.format(op=op).replace("App-0", f"App-{app}"), ref,
                    start, step, end)
    steps = range(start, end + 1, step)
    return {dict(key)["job"]: {t: v for t, v in zip(steps, row)
                               if not math.isnan(v)}
            for key, row in rows.items()}


def _assert_answer(got, want):
    assert set(got) == set(want)
    for job, row in want.items():
        assert sorted(got[job]) == sorted(row), (job, "steps")
        np.testing.assert_allclose([got[job][t] for t in sorted(row)],
                                   [row[t] for t in sorted(row)], rtol=RTOL,
                                   err_msg=job)


OPS = ("sum", "count", "avg")   # the shapes the fused path owns

T_G_GRIDS = 2 * 13 * JOBS * 4   # bytes of two [T, G] float32 grids

NEW_FAMILIES = (
    "filodb_fused_holes_aggs_total", "filodb_fused_refused_total",
    "filodb_fused_refused_gaps_total",
    "filodb_aligned_fast_evals_total", "filodb_aligned_slide_evals_total",
    "filodb_aligned_exact_evals_total", "filodb_device_to_host_bytes_total")


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_sum_by_job_over_missed_scrapes(case, monkeypatch):
    oracle_calls = []
    real = eng.periodic_samples

    def oracle(*a, **kw):
        oracle_calls.append(1)
        return real(*a, **kw)
    monkeypatch.setattr(eng, "periodic_samples", oracle)
    srv, ref = _store(case)
    n_query = len(OPS)
    try:
        m0 = _metrics(srv)
        assert set(NEW_FAMILIES) <= set(m0)
        for op in OPS:
            got, want = _served(srv, op), _reference(ref, op)
            assert set(want) == {f"job-{j}" for j in range(JOBS)}
            _assert_answer(got, want)
            if case == "window-under-two-samples":
                # the step whose window keeps one sample of the series
                assert T0 + 600 not in got["job-1"]
                if op == "count":
                    assert got["job-0"][T0 + 600] == INST - 1
                    assert got["job-0"][T0 + 540] == INST
        m1 = _metrics(srv)
        d = {f: m1[f] - m0[f] for f in m1 if f in m0}
        assert not oracle_calls
        # both sides of the gate's one choice are fused: one program a
        # query, two [T, G] float32 grids read back and nothing else
        assert d["filodb_fused_aggs_total"] == n_query
        assert d["filodb_fused_holes_aggs_total"] \
            == (0 if case == "dense" else n_query)
        assert d["filodb_fused_refused_total"] == 0
        assert d["filodb_fused_refused_gaps_total"] == 0
        for family in ("fast", "slide", "exact"):
            assert d[f"filodb_aligned_{family}_evals_total"] == 0
        assert d["filodb_device_to_host_bytes_total"] == n_query * T_G_GRIDS
    finally:
        srv.stop()


def test_a_second_request_over_holes_builds_nothing():
    """Another app of the same shape at another grid position runs the
    executable the first request built: no exec-cache miss and no
    ``kernel-build`` stage, one int64 vector and one id vector go up."""
    srv, ref = _store("single-misses")
    try:
        _assert_answer(_served(srv, "sum"), _reference(ref, "sum"))
        m0 = _metrics(srv)
        moved = dict(app=1, start=START + 60, end=END + 60)
        _assert_answer(_served(srv, "sum", **moved),
                       _reference(ref, "sum", **moved))
        _assert_answer(_served(srv, "avg", **moved),
                       _reference(ref, "avg", **moved))
        m1 = _metrics(srv)
        d = {f: m1[f] - m0[f] for f in m1 if f in m0}
        assert d["filodb_fused_holes_aggs_total"] == 2
        assert d["filodb_exec_cache_misses_total"] == 0
        assert d["filodb_exec_cache_hits_total"] == 2
        assert d["filodb_stage_kernel_build_calls_total"] == 0
        assert d["filodb_stage_device_dispatch_calls_total"] == 2
        assert d["filodb_device_execute_seconds_count"] == 2
        assert d["filodb_batcher_queries_total"] == 0
    finally:
        srv.stop()


def test_a_grid_wider_than_int32_ms_over_holes_is_refused_and_counted():
    """The ``("t",)`` family: the exact all-f64 aligned evaluator and
    the host's ``aggregate`` still serve it, and the refusal still
    counts as one for holes."""
    srv, ref = _store("single-misses")
    wide = dict(start=START, end=START + 2 * 2 ** 21, step=2 ** 21)
    try:
        m0 = _metrics(srv)
        got, want = _served(srv, "sum", **wide), _reference(ref, "sum", **wide)
        assert all(list(row) == [START] for row in want.values())
        np.testing.assert_allclose(
            [got[job][START] for job in sorted(want)],
            [want[job][START] for job in sorted(want)], rtol=1e-9)
        assert set(got) == set(want)
        m1 = _metrics(srv)
        d = {f: m1[f] - m0[f] for f in m1 if f in m0}
        assert d["filodb_fused_aggs_total"] == 0
        assert d["filodb_fused_holes_aggs_total"] == 0
        assert d["filodb_fused_refused_total"] == 1
        assert d["filodb_fused_refused_gaps_total"] == 1
        assert d["filodb_aligned_exact_evals_total"] == 1
        assert d["filodb_aligned_fast_evals_total"] == 0
    finally:
        srv.stop()


@pytest.mark.parametrize("poison", ["inf", "span-1e60"])
@pytest.mark.parametrize("case", ["dense", "single-misses"])
def test_a_value_f32_cannot_carry_is_refused_and_served_exactly(case,
                                                                poison):
    """A selection with an infinite value, or with a span whose rates
    summed in f32 would overflow, is refused by the fused gate
    (``AlignedTiles.f32_safe``), over dense tiles and over holes alike,
    and the exact all-f64 aligned family and the host's ``aggregate``
    answer it as the float64 reference does: ``+Inf`` where the reference
    has it, 1e60-sized rates to the digit, never an f32 overflow."""
    srv, ref = _store(case, poison)
    try:
        m0 = _metrics(srv)
        got = _served(srv, "sum")
        m1 = _metrics(srv)
        rows = ref_eval(QUERY.format(op="sum"), ref, START, STEP, END)
        steps = range(START, END + 1, STEP)
        want = {dict(key)["job"]: dict(zip(steps, row))
                for key, row in rows.items()}
        assert set(got) == set(want)
        for job, row in want.items():
            assert sorted(got[job]) == sorted(row), (job, "steps")
            np.testing.assert_allclose([got[job][t] for t in sorted(row)],
                                       [row[t] for t in sorted(row)],
                                       rtol=1e-9, err_msg=job)
        if poison == "inf":
            assert math.isinf(got["job-0"][T0 + 600])
        else:
            assert 1e59 < got["job-0"][T0 + 600] < 1e62
        d = {f: m1[f] - m0[f] for f in m1 if f in m0}
        assert d["filodb_fused_aggs_total"] == 0
        assert d["filodb_fused_refused_total"] == 1
        assert d["filodb_fused_refused_gaps_total"] \
            == (0 if case == "dense" else 1)
        assert d["filodb_aligned_exact_evals_total"] == 1
    finally:
        srv.stop()
