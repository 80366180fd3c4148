"""The 128-shard deployment's board, at a small size on the CPU's virtual
devices: one node that owns all 128 shards and a mesh answers
``sum(rate(http_requests_total{_ws_="demo"}[5m])) by (job)`` over every
series of the workspace from the mesh-resident sharded store (the
selection's tiles sharded by series over the devices, the group sums a
``psum`` over the shard axis).

Held here, through ``FiloServer``'s HTTP query path: the answer against
``promql/refeval.py`` in float64 and, byte for byte, against the same node
without the mesh; the route as ``/metrics`` tells it (dispatches, refusals by
reason, placements, evictions, the ``mesh-place`` stage); and that a
selection the mesh store turns down is counted and still answered. A fleet of
this file's own: 8 apps x 4 jobs x 8 instances over 128 shards, 120 scrape
ticks 10 s apart, every second series +-2 s off the tick, a counter reset in
every 37th series.
"""

import json
import math
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesShard
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.gateway.producer import (TestTimeseriesProducer,
                                         ingest_builders)
from filodb_tpu.obs import trace as obs_trace
from filodb_tpu.parallel import shardstore
from filodb_tpu.promql.refeval import RefSeries, ref_eval
from filodb_tpu.query import tpu
from filodb_tpu.query.engine import select_memo
from filodb_tpu.standalone.server import FiloServer

T0 = 1_600_000_000          # s; tick k is at T0 + 10 k
TICKS = 120
SHARDS = 128
APPS, JOBS, INST = 8, 4, 8
START, END, STEP = T0 + 360, T0 + 1080, 60    # 13 steps, windows inside
QUERY = '{op}(rate(http_requests_total{{_ws_="demo"}}[5m])) by (job)'
OPS = ("sum", "avg", "count")   # the shapes the fused path owns
# the per-series rates are float32 on the device (the f32-hybrid
# evaluator's epilogue, ~3e-7 relative, on one chip and on the mesh
# alike), so float64 agreement ends there; the group sums are float64
RTOL = 1e-6


def _fleet(seed=20261004):
    """[(labels, ts ms [TICKS], vals [TICKS])] of App-00 .. App-07."""
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
                if n % 37 == 5:
                    k = int(rng.integers(TICKS // 4, 3 * TICKS // 4))
                    vals[k:] -= vals[k - 1]         # a counter reset
                out.append(({"_metric_": "http_requests_total",
                             "_ws_": "demo", "_ns_": f"App-{a:02d}",
                             "job": f"job-{j:02d}",
                             "instance": f"i-{a:02d}-{j:02d}-{i:04d}"},
                            ts, vals))
    return out


def _node(mesh, holes=()):
    """-> (server, [RefSeries]): a node that owns all 128 shards over the
    fleet; ``holes`` are (series number, tick) scrapes that failed."""
    srv = FiloServer({"num-shards": SHARDS, "port": 0,
                      "mesh-enabled": mesh}).start()
    producer = TestTimeseriesProducer(DEFAULT_SCHEMAS, num_shards=SHARDS)
    builders, ref = {}, []
    for n, (labels, ts, vals) in enumerate(_fleet()):
        keep = np.ones(TICKS, bool)
        keep[[k for s, k in holes if s == n]] = False
        b = builders.setdefault(producer.shard_for("prom-counter", labels),
                                RecordBuilder(DEFAULT_SCHEMAS))
        for t, v in zip(ts[keep].tolist(), vals[keep].tolist()):
            b.add_sample("prom-counter", labels, t, v)
        ref.append(RefSeries(labels, ts[keep].tolist(), vals[keep].tolist()))
    assert len(builders) > SHARDS // 16     # the fleet is spread over shards
    ingest_builders(srv.store, srv.ref, builders)
    srv.store.flush_all(srv.ref)
    return srv, ref


@pytest.fixture(scope="module")
def place_calls_at_start():
    """The ``mesh-place`` stage's calls before this file's nodes exist: the
    stage counters are the process's, and another test file on the same
    worker may have placed."""
    return obs_trace.stage_totals()["mesh-place"][0]


@pytest.fixture(scope="module")
def nodes(place_calls_at_start):
    """The same fleet on a node with the mesh and on one without."""
    select_memo.clear()
    meshed, ref = _node(True)
    plain, _ = _node(False)
    assert meshed.backend.mesh_eval is not None
    assert plain.backend.mesh_eval is None
    yield meshed, plain, ref
    meshed.stop()
    plain.stop()


def _metrics(srv):
    """-> {family: value, labels summed}, and the exposition's text."""
    with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    out = {}
    for ln in text.splitlines():
        if ln.startswith("filodb_"):
            name, val = ln.rsplit(" ", 1)
            fam = name.split("{", 1)[0]
            out[fam] = out.get(fam, 0.0) + float(val)
            out[name] = float(val)
    return out, text


def _raw(srv, op="sum", start=START, end=END, step=STEP):
    url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
           "query_range?" + urllib.parse.urlencode(dict(
               query=QUERY.format(op=op), start=start, end=end, step=step,
               cache="false")))
    return urllib.request.urlopen(url, timeout=300).read()


def _served(raw):
    """-> {job: {step s: value}} of one answer."""
    body = json.loads(raw)
    assert body["status"] == "success"
    return {r["metric"]["job"]: {int(t): float(v) for t, v in r["values"]}
            for r in body["data"]["result"]}


def _reference(ref, op, start=START, end=END, step=STEP):
    rows = ref_eval(QUERY.format(op=op), ref, start, step, end)
    steps = range(start, end + 1, step)
    return {dict(key)["job"]: {t: v for t, v in zip(steps, row)
                               if not math.isnan(v)}
            for key, row in rows.items()}


def _assert_answer(got, want):
    assert set(got) == set(want) == {f"job-{j:02d}" for j in range(JOBS)}
    for job, row in want.items():
        assert sorted(got[job]) == sorted(row), (job, "steps")
        np.testing.assert_allclose([got[job][t] for t in sorted(row)],
                                   [row[t] for t in sorted(row)], rtol=RTOL,
                                   err_msg=job)


def _delta(srv, m0, *families):
    m1, _ = _metrics(srv)
    return [m1[f] - m0[f] for f in families]


MESH = "filodb_mesh_dispatches_total"
REFUSED = "filodb_mesh_refused_total"
PLACED = "filodb_mesh_placements_total"
EVICTED = "filodb_mesh_placement_evictions_total"
PLACE_CALLS = "filodb_stage_mesh_place_calls_total"


@pytest.mark.parametrize("op", OPS)
def test_mesh_answer_is_the_float64_reference(nodes, op):
    meshed, _, ref = nodes
    m0, _ = _metrics(meshed)
    _assert_answer(_served(_raw(meshed, op)), _reference(ref, op))
    # the mesh store served it, and turned nothing down
    assert _delta(meshed, m0, MESH, REFUSED, "filodb_fused_aggs_total",
                  "filodb_fused_refused_total") == [1, 0, 1, 0]


@pytest.mark.parametrize("op", OPS)
def test_mesh_answer_is_the_single_chip_answer_byte_for_byte(nodes, op,
                                                             monkeypatch):
    """The mesh program sums float32 rates in float64, which is exact for
    any group a node can hold (24 bits a rate, 53 in the sum), so it gives
    the per-series path's answer whatever the order: the same node without
    the mesh, as a CPU node runs it (the aligned f32-hybrid evaluator, then
    the host's float64 ``aggregate``). The one-chip fused program sums a
    group in float32 and so differs in the last float32 digits."""
    meshed, plain, _ = nodes
    moved = dict(start=START + 60, end=END + 60)
    fused = _raw(plain, op, **moved)            # conftest: the fused flag
    monkeypatch.setattr(tpu, "FUSED_GROUPSUM_INTERPRET", False)
    m0, _ = _metrics(plain)
    a, b = _raw(meshed, op, **moved), _raw(plain, op, **moved)
    assert _delta(plain, m0, "filodb_fused_aggs_total",
                  "filodb_fused_refused_total") == [0, 1]
    assert json.loads(a)["data"]["result"]
    assert json.dumps(json.loads(a)["data"], sort_keys=True) \
        == json.dumps(json.loads(b)["data"], sort_keys=True)
    _assert_answer(_served(a), _served(fused))
    m, _ = _metrics(plain)
    assert m[MESH] == 0 and m[REFUSED] == 0     # no mesh node: not a refusal


def test_the_selection_is_placed_once_and_all_of_it(nodes,
                                                    place_calls_at_start):
    meshed, _, _ = nodes
    _raw(meshed)                                # (placed by now at the latest)
    m0, _ = _metrics(meshed)
    # the node's own counters, and the process's stage since the fixture
    assert m0[PLACED] == m0[PLACE_CALLS] - place_calls_at_start == 1
    assert m0[EVICTED] == 0
    for k in (2, 3):
        _raw(meshed, start=START + 60 * k, end=END + 60 * k)
    assert _delta(meshed, m0, MESH, PLACED, PLACE_CALLS, EVICTED) \
        == [2, 0, 0, 0]
    placed, = (st for _, st in meshed.backend.mesh_eval._placed.values())
    assert placed.S == APPS * JOBS * INST
    n_dev = meshed.backend.mesh_eval.ndev
    assert n_dev > 1 and placed.S_pad % n_dev == 0
    # series on the mesh's first axis: every device holds its slice of
    # each [slots, series] channel
    shard_shapes = {s.data.shape for s in placed._cv.addressable_shards}
    n_shard = placed.mesh.shape[placed.mesh.axis_names[0]]
    assert shard_shapes == {(placed.cap, placed.S_pad // n_shard)}


def _gid_puts(monkeypatch, placed):
    """-> a list that grows by one for every ``jax.device_put`` of an
    ``[S_pad]`` vector (the padded group ids) from here on."""
    puts = []
    real = shardstore.jax.device_put

    def counting(x, *args, **kw):
        if getattr(x, "shape", None) == (placed.S_pad,):
            puts.append(x.dtype)
        return real(x, *args, **kw)
    monkeypatch.setattr(shardstore.jax, "device_put", counting)
    return puts


@pytest.mark.parametrize("then", ["many-requests", "a-new-placement"])
def test_the_group_ids_are_put_on_the_devices_once(nodes, monkeypatch, then):
    """The padded tile-order group ids live on the devices with the
    placement: a request sends its grid scalars and nothing else, whatever
    its op and grid position, and the answer stays the single-chip answer
    byte for byte. A placement built anew puts them anew, once."""
    meshed, plain, _ = nodes
    for _ in range(3):      # (the entry the tile build's read ended, then
        _raw(meshed)        # one that stays: its ids are kept from here)
    ev = meshed.backend.mesh_eval
    placed, = (st for _, st in ev._placed.values())
    kept = dict(placed._gids.kept)
    assert 1 <= len(kept) <= 2
    puts = _gid_puts(monkeypatch, placed)
    m0, _ = _metrics(meshed)
    if then == "a-new-placement":
        with ev._lock:
            ev._placed.clear()
    monkeypatch.setattr(tpu, "FUSED_GROUPSUM_INTERPRET", False)
    asked = 0
    for k, op in enumerate(OPS * 2):
        moved = dict(start=START + 60 * (k % 3), end=END + 60 * (k % 3))
        a, b = _raw(meshed, op, **moved), _raw(plain, op, **moved)
        assert json.dumps(json.loads(a)["data"], sort_keys=True) \
            == json.dumps(json.loads(b)["data"], sort_keys=True)
        asked += 1
    new = then == "a-new-placement"
    assert _delta(meshed, m0, MESH, PLACED, "filodb_fused_refused_total") \
        == [asked, int(new), 0]
    assert puts == [np.int32] * int(new)
    now, = (st for _, st in ev._placed.values())
    assert (now is placed) == (not new)
    if not new:
        assert {k: v[1] for k, v in now._gids.kept.items()} \
            == {k: v[1] for k, v in kept.items()}     # the same arrays
    # every kept copy is the padded vector of its (frozen) tile-order ids
    for gvec, on_dev in now._gids.kept.values():
        assert not gvec.flags.writeable
        host = np.asarray(on_dev)
        assert host.dtype == np.int32 and host.shape == (now.S_pad,)
        assert np.array_equal(host[:now.S], gvec)
        assert (host[now.S:] == -1).all()
        assert len(on_dev.sharding.device_set) == ev.ndev


PLAN_HITS = "filodb_plan_selection_facts_hits_total"
PLAN_WALKS = "filodb_plan_selection_facts_walks_total"


def test_a_repeated_request_plans_without_asking_a_shard(monkeypatch):
    """The cell's template twice over 128 shards nobody writes to: the
    first request's lowering walks every shard's match; once an entry of
    the selection memo stands (the request after the tile build), the
    lowering takes "no histogram" from its facts, and no shard is asked
    for its partitions by the planner or by the engine."""
    select_memo.clear()
    srv, ref = _node(True)
    asked = []
    real = TimeSeriesShard.lookup_partitions

    def spy(shard, *args, **kw):
        asked.append(shard.shard_num)
        return real(shard, *args, **kw)
    monkeypatch.setattr(TimeSeriesShard, "lookup_partitions", spy)
    try:
        m0, _ = _metrics(srv)
        first = _raw(srv)
        # every shard twice: the lowering's walk, then the engine's select
        assert sorted(asked) == sorted(2 * list(range(SHARDS)))
        assert _delta(srv, m0, PLAN_HITS, PLAN_WALKS) == [0, 1]
        _raw(srv)           # (the tile build's read ended the first entry)
        del asked[:]
        m0, _ = _metrics(srv)
        for k in (1, 2):
            assert json.loads(_raw(srv))["data"] == json.loads(first)["data"]
            assert asked == []
            assert _delta(srv, m0, PLAN_HITS, PLAN_WALKS, MESH) == [k, 0, k]
        _assert_answer(_served(first), _reference(ref, "sum"))
    finally:
        srv.stop()
        select_memo.clear()


@pytest.mark.parametrize("family,mtype", [
    (REFUSED, "counter"), (PLACED, "counter"), (EVICTED, "counter"),
    ("filodb_selection_facts_hits_total", "counter"),
    ("filodb_selection_facts_misses_total", "counter"),
    (PLAN_HITS, "counter"), (PLAN_WALKS, "counter"),
    ("filodb_stage_mesh_place_calls_total", "counter"),
    ("filodb_stage_mesh_place_self_seconds_total", "counter"),
    ("filodb_stage_mesh_place_cpu_seconds_total", "counter")])
def test_metrics_exports_the_family_with_help_and_type(nodes, family, mtype):
    for srv in nodes[:2]:       # with a mesh store and without one
        vals, text = _metrics(srv)
        assert family in vals
        assert f"# TYPE {family} {mtype}" in text
        assert f"# HELP {family} " in text
    if family == REFUSED:
        assert {n for n in vals if n.startswith(REFUSED + "{")} == {
            REFUSED + '{reason="%s"}' % r
            for r in ("family", "grid", "tiles")}


def test_the_placement_is_its_own_stage_not_eligibilitys(monkeypatch):
    """A build of the placement is the ``mesh-place`` stage, a child of
    ``fused-eligibility``: its time is out of that stage's self time."""
    real = shardstore.ShardedTiles.__init__

    def slow(self, mesh, tiles):
        time.sleep(0.3)
        real(self, mesh, tiles)
    monkeypatch.setattr(shardstore.ShardedTiles, "__init__", slow)
    srv, ref = _node(True)
    try:
        before = obs_trace.stage_totals()
        _assert_answer(_served(_raw(srv)), _reference(ref, "sum"))
        after = obs_trace.stage_totals()
        calls, self_s = {}, {}
        for name in ("mesh-place", "fused-eligibility"):
            calls[name] = after[name][0] - before[name][0]
            self_s[name] = after[name][1] - before[name][1]
        assert calls == {"mesh-place": 1, "fused-eligibility": 1}
        assert self_s["mesh-place"] >= 0.3
        assert self_s["fused-eligibility"] < 0.15
    finally:
        srv.stop()


def test_one_hole_is_refused_by_the_mesh_counted_and_answered():
    """Tiles with a hole are not the mesh store's: the node's one-chip
    fused program serves the query, and the refusal has a name."""
    srv, ref = _node(True, holes=[(6, 47)])
    try:
        m0, _ = _metrics(srv)
        for op in OPS:
            _assert_answer(_served(_raw(srv, op)), _reference(ref, op))
        m1, _ = _metrics(srv)
        assert m1[REFUSED + '{reason="tiles"}'] \
            - m0[REFUSED + '{reason="tiles"}'] == len(OPS)
        assert _delta(srv, m0, REFUSED, MESH, PLACED,
                      "filodb_fused_holes_aggs_total") \
            == [len(OPS), 0, 0, len(OPS)]
    finally:
        srv.stop()


def test_a_grid_wider_than_int32_ms_is_refused_by_family(nodes):
    """The exact all-f64 family keeps the single-chip path: 26 days of
    hourly steps that end inside the data."""
    meshed, _, ref = nodes
    wide = dict(start=T0 + 600 - 26 * 86400, end=T0 + 600, step=3600)
    m0, _ = _metrics(meshed)
    _assert_answer(_served(_raw(meshed, **wide)),
                   _reference(ref, "sum", **wide))
    m1, _ = _metrics(meshed)
    assert m1[REFUSED + '{reason="family"}'] \
        - m0[REFUSED + '{reason="family"}'] == 1
    assert _delta(meshed, m0, REFUSED, MESH) == [1, 0]


def test_a_placement_pushed_out_is_counted_and_built_again(nodes,
                                                           monkeypatch):
    """``MAX_PLACEMENTS`` placements are kept; the oldest goes without a
    word, so the drop has a counter."""
    meshed, _, _ = nodes
    ev = shardstore.ShardedTileEvaluator(meshed.backend.mesh_eval.mesh)
    monkeypatch.setattr(ev, "MAX_PLACEMENTS", 1)
    entries = list(meshed.backend._tile_cache.values())
    tiles = entries[0].tiles
    assert ev.place(tiles) is ev.place(tiles)       # a hit builds nothing
    assert (ev.placements, ev.evictions) == (1, 0)

    class Other:            # another selection's tiles: same arrays, new id
        def __getattr__(self, name):
            return getattr(tiles, name)
    other = Other()
    assert ev.place(other) is not None
    assert (ev.placements, ev.evictions) == (2, 1)
    assert ev.place(tiles) is not None              # built again
    assert (ev.placements, ev.evictions) == (3, 2)
    assert ev.snapshot()["evictions"] == 2
