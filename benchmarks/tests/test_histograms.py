"""Histograms in the harness: ``bucket_quantile`` on hand-worked cases, the
native world against its classic expansion through the reference, the
candidate ``histogram-dev.p99-by-job`` end to end at its toy size on the CPU
(its ``must_rise`` is empty: no device path serves histograms yet), its
controls and a fault, and the admitted cells' reference answers and PromQL
pinned to the parent commit's (PR 37) bit for bit.

    python -m pytest benchmarks/tests/test_histograms.py -q
"""

import hashlib
import importlib
import math

import numpy as np
import pytest

import reference
import rehearse
import run
import traffic
from datagen import histogram_latency

CELL = "histogram-dev.p99-by-job"
INF = math.inf
NAN = math.nan


# -- bucket_quantile -----------------------------------------------------------

# (q, les, cumulative counts of one step, monotone, want), worked by hand
CASES = [
    # inside a middle bucket: rank 20 in (1, 2] over counts 10 -> 30
    (0.5, [1, 2, 4, INF], [10, 30, 38, 40], False, 1.0 + 1.0 * (10 / 20)),
    (0.9, [1, 2, 4, INF], [10, 30, 38, 40], False, 2.0 + 2.0 * (6 / 8)),
    # a rank inside the +Inf bucket: the second-highest bound
    (0.99, [1, 2, 4, INF], [10, 30, 38, 40], False, 4.0),
    # the first bucket interpolates from 0
    (0.5, [1, 2, 4, INF], [20, 30, 38, 40], False, 1.0),
    (0.9, [2, 4, INF], [100, 100, 100], False, 1.8),
    # a first bucket whose bound is <= 0 gives that bound
    (0.5, [0, 1, INF], [30, 40, 40], False, 0.0),
    (0.9, [-1, 1, INF], [95, 99, 100], False, -1.0),
    # no +Inf bucket, no +Inf point, fewer than two buckets, no observation
    (0.5, [1, 2, 4], [10, 30, 40], False, NAN),
    (0.5, [1, 2, INF], [10, 30, NAN], False, NAN),
    (0.5, [INF], [40], False, NAN),
    (0.99, [1, 2, INF], [0, 0, 0], False, NAN),
    # a bucket with no point is left out: (1, 4] over counts 10 -> 38
    (0.5, [1, 2, 4, INF], [10, NAN, 38, 40], False, 1.0 + 3.0 * (10 / 28)),
    # classic: the running max over the buckets first (counts 10 -> 10)
    (0.5, [1, 2, 4, INF], [10, 8, 30, 40], True, 2.0 + 2.0 * (10 / 20)),
    (0.99, [1, 2, 4, INF], [10, 8, 30, 40], True, 4.0),
]


@pytest.mark.parametrize("q, les, counts, monotone, want", CASES)
def test_bucket_quantile_by_hand(q, les, counts, monotone, want):
    counts = np.array(counts, dtype=np.float64)
    # three steps and two groups: every cell of [G, B, T] alike
    grid = np.broadcast_to(counts[None, :, None], (2, counts.size, 3))
    got = reference.bucket_quantile(q, les, grid, monotone)
    assert got.shape == (2, 3)
    if math.isnan(want):
        assert np.isnan(got).all()
    else:
        assert (got == want).all(), (got, want)


# -- the native world and its classic expansion --------------------------------

def _world(seed, scale=None):
    spec = run.Spec(CELL, CELL)
    return spec, histogram_latency.make(
        spec.config, seed, scale or rehearse.toy_scale(spec.config))


@pytest.mark.parametrize("seed", [5, 3800000011])
def test_datagen_keeps_its_promises(seed):
    spec, w = _world(seed)
    s, n, b = w.vals.shape
    assert (s, n) == w.ts.shape == w.sums.shape and b == len(w.les) == 12
    assert w.les[-1] == INF and (np.diff(w.les) > 0).all()
    assert (np.diff(w.vals, axis=2) >= 0).all()        # cumulative buckets
    assert (w.vals == np.round(w.vals)).all()
    drop = np.diff(w.vals, axis=1) < 0
    rows = np.flatnonzero(drop.any(axis=(1, 2)))
    assert rows.tolist() == list(range(5, s, spec.config["data"]
                                       ["reset_every"]))
    for r in rows:
        k = int(np.flatnonzero(drop[r].any(axis=1))[0]) + 1
        # a reset: every bucket and the sum are 0 at once, nothing else falls
        assert (w.vals[r, k] == 0).all() and w.sums[r, k] == 0
        assert drop[r].any(axis=1).sum() == 1
        held = w.vals[r, k - 1] > 0
        assert drop[r, k - 1][held].all()
    again = _world(seed)[1]
    assert (again.vals == w.vals).all() and (again.ts == w.ts).all()


@pytest.mark.parametrize("by", [["le", "job"], ["job", "le"], ["le"]])
@pytest.mark.parametrize("control", [None, "stale"])
def test_native_and_classic_give_the_same_quantiles(by, control):
    """(Not under ``bf16``: rounding the cumulative counts can make one
    bucket's increase exceed the next one's, which only the classic form's
    running max puts right.)"""
    spec, native = _world(3800000013)
    classic = histogram_latency.classic(native)
    tmpl = spec.workload["queries"][0]
    rng = np.random.default_rng(17)
    for _ in range(6):
        req = traffic.make_request(native, tmpl, rng, "/q")
        q_native = {**req.query, "by": [b for b in by if b != "le"]}
        q_classic = {**req.query, "metric": req.query["metric"] + "_bucket",
                     "by": by}
        assert traffic.key_labels(q_native) == traffic.key_labels(q_classic)
        args = (req.start_s, req.end_s, req.step_s)
        want, steps = reference.evaluate(native, q_native, *args,
                                         control=control)
        got, steps_c = reference.evaluate(classic, q_classic, *args,
                                          control=control)
        assert steps == steps_c and set(got) == set(want) and want
        for key, pair in want.items():
            for w, g in zip(pair, got[key]):
                assert (np.isnan(w) == np.isnan(g)).all()
                ok = ~np.isnan(w)
                assert ok.any()
                assert np.abs(g[ok] - w[ok]).max() <= 1e-12 * np.abs(
                    w[ok]).max()


def test_render_and_keys():
    q = {"metric": "m", "select": {"_ns_": "App-01"}, "fn": "rate",
         "window_s": 300, "agg": "sum", "by": ["job"], "quantile": 0.99}
    assert traffic.render(q) == ('histogram_quantile(0.99, sum(rate(m{_ns_='
                                 '"App-01"}[300s])) by (job))')
    assert traffic.key_labels(q) == ["job"]
    classic = {**q, "metric": "m_bucket", "by": ["le", "job"]}
    assert traffic.render(classic) == (
        'histogram_quantile(0.99, sum(rate(m_bucket{_ns_="App-01"}[300s])) '
        'by (le,job))')
    assert traffic.key_labels(classic) == ["job"]


def _mix(**query):
    q = {"metric": "m", "fn": "rate", "window_s": 300, "agg": "sum",
         "by": ["job"], "quantile": 0.99, **query}
    return {"queries": [{"name": "t", "query": q}], "scrape": None}


@pytest.mark.parametrize("mix, hist", [
    (_mix(quantile=1.0), True), (_mix(quantile=0), True),
    (_mix(agg="avg"), True), (_mix(fn="max_over_time"), True),
    (_mix(), False),                               # classic without le
    ({**_mix(), "queries": [{"name": "t", "query": {
        "metric": "m", "fn": "rate", "window_s": 300, "agg": "sum",
        "by": ["job"]}}]}, True),                  # histograms, no quantile
    ({**_mix(), "scrape": {"in_flight_scrapes": 1}}, True),
])
def test_check_refuses_at_load(mix, hist):
    _, native = _world(5)
    world = native if hist else histogram_latency.classic(native)
    with pytest.raises(ValueError):
        traffic.check(mix, world)


def test_check_takes_the_candidate_and_every_admitted_cell():
    _, native = _world(5)
    traffic.check(run.Spec(CELL, CELL).workload, native)
    for cell in PINNED:
        spec = run.Spec(cell)
        datagen = importlib.import_module("datagen." + spec.config["datagen"])
        traffic.check(spec.workload, datagen.make(
            spec.config, PINNED_SEED, PINNED_SCALES[cell]))


def test_toy_size_comes_from_the_config():
    spec = run.Spec(CELL, CELL)
    assert rehearse.toy_scale(spec.config) == spec.config["toy"]
    for cell in PINNED:
        config = run.Spec(cell).config
        assert "toy" not in config
        assert rehearse.toy_scale(config) == rehearse.TOY.get(
            config["datagen"], {})
    assert rehearse.TOY["shards128_counters"]


# -- the candidate end to end at its toy size -----------------------------------

def _toy_run(**kw):
    spec = run.Spec(CELL, CELL)
    return run.run_cell(CELL, 3800000019, 3, 0, look_for_chip=False,
                        scale=rehearse.toy_scale(spec.config),
                        candidate=CELL, **kw)


def test_candidate_rehearses_correct():
    code, result = _toy_run()
    assert result["correct"] and code == 0, result["checks"]
    assert result["failed"] == 0
    assert result["checks"]["answers_compared"]["value"] == \
        run.Spec(CELL, CELL).workload["check"]["sample"]


@pytest.mark.parametrize("kw", [{"control": "bf16"}, {"control": "stale"},
                                {"fault": "alter_answer"}])
def test_control_and_fault_are_not_correct(kw):
    code, result = _toy_run(**kw)
    c = result["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert not result["correct"] and code != 0


# -- the admitted cells: bit for bit the parent's reference and PromQL ---------

PINNED_SCALES = {
    "promperf.history-sumby": {"apps": 3, "jobs": 4, "instances": 16,
                               "live_samples": 1500},
    "tsbs-devops.host-dashboards": {"hosts": 64},
    "promperf-missed-scrapes.history-sumby": {"apps": 3, "jobs": 4,
                                              "instances": 16},
    "shards128.mesh-sumby": {"apps": 8, "jobs": 4, "instances": 8},
}
PINNED_SEED = 3800000017
PINNED_REQUESTS = 24
# (answers, PromQL) as the parent commit's (407b89c) reference.py and
# traffic.py give them through reference_digest
PINNED = {
    "promperf.history-sumby": (
        "a78ad8bc2b5915eed6d28f3127e9c48d2fe30e92964c060c1f43c7a55be110e3",
        "f4c3f5fdd46a6d91ba15cd7a30cbe27afa4a66279ded7ee46593b19094aa6214"),
    "tsbs-devops.host-dashboards": (
        "3a9e7e31e5ca5852f697783e5cc97059010fe2f5d3688c43473f28ee92225286",
        "9cee4eb2d5f3b2772ed07e4099b8bfc6ab3d8a8b78c3bf0439ddda1734c09c65"),
    "promperf-missed-scrapes.history-sumby": (
        "25ac92c8c8b241ce9663243af7de7f16a0108a3f84f4529466f9bd2eae6a920e",
        "f4c3f5fdd46a6d91ba15cd7a30cbe27afa4a66279ded7ee46593b19094aa6214"),
    "shards128.mesh-sumby": (
        "270c0f786123b875bee9a7f3dc4e1701041558bb81e99b48927521532c81b222",
        "3a4d98ac3e3b4024a6c91a56e19ec2c2edaaee40ed09d56f0f349995e50c6930"),
}


def reference_digest(cell):
    """-> (sha256 of every answer the reference gives, the program's and
    each control's, to ``PINNED_REQUESTS`` requests of the cell's traffic;
    sha256 of their PromQL and key labels)."""
    spec = run.Spec(cell)
    datagen = importlib.import_module("datagen." + spec.config["datagen"])
    world = datagen.make(spec.config, PINNED_SEED, PINNED_SCALES[cell])
    rng = np.random.default_rng(PINNED_SEED)
    answers, promql = hashlib.sha256(), hashlib.sha256()
    queries = spec.workload["queries"]
    for i in range(PINNED_REQUESTS):
        req = traffic.make_request(world, queries[i % len(queries)], rng, "/q")
        promql.update(traffic.render(req.query).encode() + b"\n")
        promql.update(repr(traffic.key_labels(req.query)).encode())
        for control in (None,) + tuple(reference.CONTROLS):
            rows, steps = reference.evaluate(world, req.query, req.start_s,
                                             req.end_s, req.step_s,
                                             control=control)
            answers.update(repr(steps).encode())
            for key in sorted(rows):
                low, high = rows[key]
                answers.update(repr(key).encode() + low.tobytes()
                               + high.tobytes())
    return answers.hexdigest(), promql.hexdigest()


def test_pinned_cells_are_admitted():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    assert set(PINNED) <= {w["name"] for w in bench["workloads"]}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_admitted_cell_answers_as_the_parent(cell):
    assert reference_digest(cell) == PINNED[cell]
