"""The fused group-sum as ONE cached executable (tilestore.groupsum_counters
-> ``_jit_lookup`` -> ``_groupsum_program``, plain XLA): a query sends one
int64[3] grid and its group ids (the served path keeps the ids on the
device); nothing is traced or compiled again for a shape the table has.
On the CPU: counts and bits, never a time.

(a) one miss then hits over grid positions and group vectors, the program
traced once, a new static its own entry; (b) an id outside the groups;
(c) four threads' first call at once; (d) through the served engine:
``&explain=analyze`` dispositions and the ``filodb_exec_cache_*``
counters; (e) over dense tiles and tiles with holes alike: the
per-series evaluator's rates grouped in float64 here, and one executable a
shape.
"""

import json
import threading
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.obs import devprof
from filodb_tpu.query import tilestore as tst
from filodb_tpu.standalone.server import FiloServer

BASE = 1_600_000_000_000
DT = 10_000
W = 300_000


def _tiles(S, N=288, jitter=2000, seed=7):
    rng = np.random.default_rng(seed)
    ts = (BASE + np.arange(N)[None, :] * DT
          + rng.uniform(-jitter, jitter, (S, N)))
    vals = 1e12 + np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    vals[5 % S, N // 2:] *= 0.99          # counter reset
    return tst.AlignedTiles([{} for _ in range(S)], BASE, DT,
                            np.ones((S, N), bool), ts, vals)


def _steps(nsteps, shift=0, step=60_000):
    return BASE + 400_000 + shift + np.arange(nsteps, dtype=np.int64) * step


def _groupsum_keys():
    return [k for k in tst._EVAL_T_JIT if k[0] == "groupsum"]


@pytest.fixture
def fresh_table():
    """The dispatch tables are module-global and a cache: drop what
    earlier tests of this worker built of the fused program, so a miss
    below is provably this test's."""
    for k in _groupsum_keys():
        del tst._EVAL_T_JIT[k]


def _delta(before):
    after = tst.executable_cache_stats()
    return {k: after[k] - before[k] for k in after}


@pytest.fixture
def traces(monkeypatch):
    """Calls of the program body, i.e. how often it was traced."""
    calls = []
    real = tst._groupsum_program

    def counted(*a):
        calls.append(1)
        return real(*a)
    counted.__name__ = real.__name__
    monkeypatch.setattr(tst, "_groupsum_program", counted)
    return calls


# -- (a) one executable per static tuple ---------------------------------------

def test_one_miss_then_hits_and_one_trace(fresh_table, traces):
    S, G, T = 96, 4, 20
    tiles = _tiles(S)
    rng = np.random.default_rng(3)
    before = tst.executable_cache_stats()
    outs = []
    for i in range(6):
        gid = rng.integers(0, G, S)
        res = tst.groupsum_counters(tiles, "rate", _steps(T, 60_000 * i),
                                    W, gid, G)
        assert res is not None
        outs.append(np.asarray(res[0]))
    assert _delta(before) == {"hits": 5, "misses": 1, "entries": 1}
    assert len(traces) == 1
    assert len(_groupsum_keys()) == 1
    # six different questions got six different answers
    assert all(not np.array_equal(outs[0], o) for o in outs[1:])


@pytest.mark.parametrize("change", ["nsteps", "G", "holes", "func"])
def test_a_new_static_is_a_second_miss_with_its_own_entry(
        fresh_table, traces, change):
    S, G, T = 96, 4, 20
    tiles = _tiles(S, jitter=500)
    gid = np.arange(S) % G
    assert tst.groupsum_counters(tiles, "rate", _steps(T), W, gid,
                                 G) is not None
    before = tst.executable_cache_stats()
    kw = dict(func="rate", steps=_steps(T), G=G)
    if change == "nsteps":
        kw["steps"] = _steps(T + 3)
    elif change == "G":
        kw["G"] = G + 1
    elif change == "holes":
        # tiles of the same shape with one hole: seven channels, not two
        valid = np.ones(tiles.valid.shape, bool)
        valid[3, 50] = False
        tiles = tst.AlignedTiles(tiles.keys, BASE, DT, valid,
                                 np.asarray(tiles.ts), np.asarray(tiles.vals))
    else:
        kw["func"] = "increase"
    for _ in range(2):
        assert tst.groupsum_counters(tiles, kw["func"], kw["steps"], W, gid,
                                     kw["G"]) is not None
    assert _delta(before) == {"hits": 1, "misses": 1, "entries": 1}
    assert len(traces) == 2
    k0, k1 = _groupsum_keys()
    assert k0 != k1


# -- (b) an id outside the groups ----------------------------------------------

def test_an_id_outside_the_groups_is_in_no_group():
    S, G = 40, 4
    tiles = _tiles(S)
    gid = np.arange(S) % G
    full = tst.groupsum_counters(tiles, "rate", _steps(12), W, gid, G)
    out = gid.copy()
    out[gid == 2] = -1
    part = tst.groupsum_counters(tiles, "rate", _steps(12), W, out, G)
    keep = [0, 1, 3]
    np.testing.assert_array_equal(np.asarray(part[0])[:, keep],
                                  np.asarray(full[0])[:, keep])
    assert not np.asarray(part[1])[:, 2].any()


# -- (c) four first calls at once ----------------------------------------------

def test_four_threads_first_call_at_once(fresh_table, traces):
    S, G, T = 80, 4, 17
    tiles = _tiles(S)
    # everything memoised on the tiles is built before the threads start:
    # the race under test is the executable table's, not the tile's
    gids = [np.random.default_rng(i).integers(0, G, S) for i in range(4)]
    assert tst.groupsum_counters(tiles, "rate", _steps(T + 1), W, gids[0],
                                 G) is not None
    n_traced = len(traces)
    before = tst.executable_cache_stats()
    gate = threading.Barrier(4)
    got, errs = [None] * 4, []

    def run(i):
        try:
            gate.wait(timeout=60)
            r = tst.groupsum_counters(tiles, "rate", _steps(T, 60_000 * i),
                                      W, gids[i], G)
            got[i] = (np.asarray(r[0]), np.asarray(r[1]))
        except Exception as e:      # noqa: BLE001 — reported below
            errs.append(e)
    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not errs, errs
    d = _delta(before)
    assert d["entries"] == 1 and d["hits"] + d["misses"] == 4
    assert 1 <= d["misses"] <= 4
    # those that missed together built once
    assert len(traces) - n_traced == 1
    for i in range(4):
        again = tst.groupsum_counters(tiles, "rate", _steps(T, 60_000 * i),
                                      W, gids[i], G)
        np.testing.assert_array_equal(got[i][0], np.asarray(again[0]))
        np.testing.assert_array_equal(got[i][1], np.asarray(again[1]))
        per = np.asarray(tst.evaluate_counters_t(
            tiles, "rate", _steps(T, 60_000 * i), W))
        want_c = np.stack([(~np.isnan(per))[:, gids[i] == g].sum(axis=1)
                           for g in range(G)], 1)
        np.testing.assert_array_equal(got[i][1], want_c.astype(np.float32))
        want_s = np.stack([np.nan_to_num(per[:, gids[i] == g]).sum(axis=1)
                           for g in range(G)], 1)
        np.testing.assert_allclose(got[i][0], want_s, rtol=1e-5, atol=1e-7)


# -- (d) through the served engine ---------------------------------------------

T0 = 1_600_000_000
FUSED = "sum(rate(http_requests_total[5m])) by (job)"


@pytest.fixture(scope="module")
def srv():
    s = FiloServer({"num-shards": 2, "port": 0}).start()
    s.seed_dev_data(n_samples=360, n_instances=8, start_ms=T0 * 1000)
    yield s
    s.stop()


def _range(srv, shift, **extra):
    url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
           "query_range?" + urllib.parse.urlencode({**dict(
               query=FUSED, start=T0 + 600 + shift, end=T0 + 3000 + shift,
               step=60, cache="false"), **extra}))
    return json.loads(urllib.request.urlopen(url, timeout=300).read())


def _metric(srv, name):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
        for ln in r.read().decode().splitlines():
            if ln.startswith(name + " "):
                return float(ln.rsplit(" ", 1)[1])
    raise AssertionError(f"no {name} on /metrics")


def _fused_execs(body):
    return [e for e in body["analyze"]["device"]["executables"]
            if e["site"] == "groupsum"]


def test_served_fused_query_builds_once_then_runs_the_compiled_object(
        srv, fresh_table):
    devprof.GLOBAL_PROFILER.reset()
    first = _range(srv, 0, explain="analyze")
    assert first["status"] == "success" and first["data"]["result"]
    (e,) = _fused_execs(first)
    assert e["dispositions"] == ["build", "aot"]
    assert e["executable"].startswith("groupsum/rate/")
    assert e["builds"] == 1 and e.get("recompiles", 0) == 0
    names = [s["name"] for s in first["trace"]["spans"]]
    assert "kernel-build" in names
    misses = _metric(srv, "filodb_exec_cache_misses_total")
    hits = _metric(srv, "filodb_exec_cache_hits_total")
    fused = _metric(srv, "filodb_fused_aggs_total")
    second = _range(srv, 120, explain="analyze")
    (e2,) = _fused_execs(second)
    assert e2["dispositions"] == ["aot"]
    assert e2["builds"] == 1 and e2.get("recompiles", 0) == 0
    assert "kernel-build" not in [s["name"]
                                  for s in second["trace"]["spans"]]
    assert _metric(srv, "filodb_exec_cache_misses_total") == misses
    assert _metric(srv, "filodb_exec_cache_hits_total") == hits + 1
    assert _metric(srv, "filodb_fused_aggs_total") == fused + 1
    # the answer itself: the per-series path's rates, summed by job here
    per = _range(srv, 120, query="rate(http_requests_total[5m])")
    want = {}
    for r in per["data"]["result"]:
        tot = want.setdefault(r["metric"]["job"], {})
        for t, v in r["values"]:
            tot[t] = tot.get(t, 0.0) + float(v)
    assert {r["metric"]["job"] for r in second["data"]["result"]} \
        == set(want)
    for r in second["data"]["result"]:
        w = want[r["metric"]["job"]]
        assert [t for t, _ in r["values"]] == sorted(w)
        np.testing.assert_allclose([float(v) for _, v in r["values"]],
                                   [w[t] for t in sorted(w)], rtol=1e-5)


# -- (e) dense tiles and tiles with holes: one program -------------------------

S_H, N_H, G_H = 64, 200, 5


def _holed(seed=7, S=S_H):
    """Tiles whose series miss scrapes: every fourth four single ticks,
    series 1 everything from slot 40 to 110 (empty in the windows that end
    there), series 2 all but one sample of slots 60..95."""
    rng = np.random.default_rng(seed)
    ts = (BASE + np.arange(N_H)[None, :] * DT
          + rng.integers(-2000, 2001, (S, N_H)))
    vals = 1e9 + np.cumsum(rng.uniform(0, 5, (S, N_H)), axis=1)
    vals[5, N_H // 2:] *= 0.99              # counter reset
    valid = np.ones((S, N_H), bool)
    for r in range(3, S, 4):
        valid[r, rng.choice(np.arange(2, N_H - 2), 4, replace=False)] = False
    valid[1, 40:111] = False
    valid[2, 60:96] = False
    valid[2, 77] = True
    return tst.AlignedTiles([{} for _ in range(S)], BASE, DT, valid, ts,
                            vals)


@pytest.fixture(scope="module")
def holed():
    return _holed()


@pytest.fixture(scope="module")
def dense():
    """``_holed``'s samples with every slot filled: its timestamps and
    values, where a hole's slot takes the tick and its neighbours' mean."""
    h = _holed()
    valid = np.asarray(h.valid)
    ticks = BASE + np.arange(N_H)[None, :] * DT + np.zeros((S_H, 1))
    ts = np.where(valid, np.asarray(h.ts), ticks)
    v = np.array(h.vals)
    for r in range(S_H):
        ok = np.flatnonzero(valid[r])
        v[r] = np.interp(np.arange(N_H), ok, v[r, ok])
    return tst.AlignedTiles([{} for _ in range(S_H)], BASE, DT,
                            np.ones((S_H, N_H), bool), ts, v)


# (first window end relative to BASE, step, steps): windows are W = 300 s
GRIDS = {
    "interior": (400_000, 60_000, 20),
    "not-interior": (-120_000, 60_000, 44),     # from before slot 0 to past N
    "window-not-whole-steps": (400_000, 70_000, 18),
    "step-not-whole-slots": (403_000, 45_500, 25),
    "one-step": (900_000, 0, 1),
}


def _grid(name):
    first, step, n = GRIDS[name]
    return BASE + first + np.arange(n, dtype=np.int64) * step


@pytest.mark.parametrize("kind", ["dense", "holes"])
@pytest.mark.parametrize("grid", sorted(GRIDS))
@pytest.mark.parametrize("op", ["sum", "count", "avg"])
@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_grouped_program_over_holes_equals_per_series_grouped_in_float64(
        holed, dense, func, op, grid, kind):
    """The one program over either kind of tiles. Over dense tiles the
    regular interior grids are the ones ``counters_batch_family`` gives
    to the slide evaluator, which the program serves all the same."""
    tiles = holed if kind == "holes" else dense
    steps = _grid(grid)
    if kind == "dense" and grid in ("interior", "window-not-whole-steps"):
        assert tst.counters_batch_family(tiles, func, steps, W)[0] \
            == "slide"
    gid = np.arange(S_H) % (G_H - 1)        # group G_H - 1 has no series
    res = tst.groupsum_counters(tiles, func, steps, W, gid, G_H)
    assert res is not None
    sums, cnts = np.asarray(res[0]), np.asarray(res[1])
    assert sums.shape == cnts.shape == (steps.size, G_H)
    assert sums.dtype == cnts.dtype == np.float32
    per = np.asarray(tst.evaluate_counters_t(tiles, func, steps, W),
                     np.float64)            # [T, S], NaN: under two samples
    assert per.shape == (steps.size, S_H)
    ok = ~np.isnan(per)
    want_c = np.stack([ok[:, gid == g].sum(axis=1) for g in range(G_H)], 1)
    want_s = np.stack([np.where(ok, per, 0.0)[:, gid == g].sum(axis=1)
                       for g in range(G_H)], 1)
    np.testing.assert_array_equal(cnts, want_c)
    if grid == "interior" and kind == "holes":
        # the cases the fleet was built for are inside this grid
        assert not ok[:, 1].all() and not ok[:, 2].all()
        assert ok[:, 1].any() and ok[:, 0].all()
    if grid == "not-interior":
        assert not ok[0].any() and not ok[-1].any() and ok.any()
    if op == "count":
        return
    scale = np.abs(np.where(ok, per, 0.0)).max() or 1.0
    if op == "sum":
        np.testing.assert_allclose(sums, want_s, rtol=2e-6,
                                   atol=2e-6 * scale)
        assert not sums[:, G_H - 1].any() and not cnts[:, G_H - 1].any()
    else:
        # as engine._try_fused_agg takes avg out of (sums, cnts)
        with np.errstate(invalid="ignore", divide="ignore"):
            got = np.where(cnts == 0, np.nan, sums.astype(np.float64) / cnts)
            want = np.where(want_c == 0, np.nan, want_s / want_c)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6 * scale)


def _holes_keys():
    return [k for k in _groupsum_keys() if not k[-1]]


def test_holes_one_miss_then_hits_over_positions_groups_and_tiles(
        fresh_table, monkeypatch):
    """Another grid position, another group vector and ANOTHER selection
    of the same shape (another app's tiles) all run the one executable:
    its key holds shapes, not the tiles."""
    built = []
    real = tst._groupsum_program

    def counted(*a):
        built.append(1)
        return real(*a)
    counted.__name__ = real.__name__
    monkeypatch.setattr(tst, "_groupsum_program", counted)
    T = 20
    a, b = _holed(7), _holed(8)
    for t in (a, b):
        tst._tiles_arrays_fast(t, "rate")
    rng = np.random.default_rng(3)
    before = tst.executable_cache_stats()
    outs = []
    for i in range(6):
        gid = rng.integers(0, G_H, S_H)
        res = tst.groupsum_counters(a if i % 2 else b, "rate",
                                    _steps(T, 60_000 * i), W, gid, G_H)
        outs.append(np.asarray(res[0]))
    assert _delta(before) == {"hits": 5, "misses": 1, "entries": 1}
    assert len(built) == 1                  # traced once
    assert len(_holes_keys()) == 1 and len(_groupsum_keys()) == 1
    assert all(not np.array_equal(outs[0], o) for o in outs[1:])
    # a new static is its own entry, and only that
    for kw in (dict(T=T + 1), dict(G=G_H + 1), dict(func="delta"),
               dict(S=S_H + 8)):
        t = _holed(7, kw.get("S", S_H))
        before = tst.executable_cache_stats()
        for _ in range(2):
            assert tst.groupsum_counters(
                t, kw.get("func", "rate"), _steps(kw.get("T", T)), W,
                np.arange(len(t.keys)) % G_H, kw.get("G", G_H)) is not None
        assert _delta(before) == {"hits": 1, "misses": 1, "entries": 1}, kw


def test_holes_send_one_int64_vector_and_the_ids(holed, monkeypatch):
    """The request's grid is one numpy int64[3]; the tiles' constants are
    their cached device array, and the seven channels the aligned path's
    cached ones. Ids handed over as numpy go as numpy (the backend's tile
    entry hands over device ids)."""
    seen = {}
    real = tst._jit_lookup

    def spy(cache, key, build, site="tilestore", cost_args=None):
        seen.update(key=key, args=cost_args, site=site)
        return real(cache, key, build, site=site, cost_args=cost_args)
    monkeypatch.setattr(tst, "_jit_lookup", spy)
    steps = _steps(12, 3000)
    gid = np.arange(S_H, dtype=np.int64) % G_H
    assert tst.groupsum_counters(holed, "rate", steps, W, gid, G_H,
                                 offset_ms=60_000) is not None
    assert seen["site"] == "groupsum"
    assert seen["key"] == ("groupsum", "rate", 12, G_H, (N_H, S_H), False)
    arrs, consts, grid, ids = seen["args"]
    assert type(grid) is np.ndarray and grid.dtype == np.int64
    w0e = int(steps[0]) - 60_000
    assert grid.tolist() == [w0e - W, w0e, 60_000]
    assert consts is holed.t_consts()
    assert np.asarray(consts).tolist() == [N_H, BASE, DT]
    assert type(ids) is np.ndarray and ids.dtype == np.int32
    assert ids.tolist() == gid.tolist()
    cached = tst._tiles_arrays_fast(holed, "rate")
    assert sorted(arrs) == sorted(cached) and len(arrs) == 7
    assert all(arrs[k] is cached[k] for k in arrs)


def test_holes_an_id_outside_the_groups_is_in_no_group(holed):
    gid = np.arange(S_H) % G_H
    full = tst.groupsum_counters(holed, "rate", _steps(12), W, gid, G_H)
    out = gid.copy()
    out[gid == 2] = -1
    part = tst.groupsum_counters(holed, "rate", _steps(12), W, out, G_H)
    keep = [0, 1, 3, 4]
    np.testing.assert_array_equal(np.asarray(part[0])[:, keep],
                                  np.asarray(full[0])[:, keep])
    assert not np.asarray(part[1])[:, 2].any()
    assert np.asarray(full[1])[:, 2].any()


def test_grid_wider_than_int32_ms_over_holes_is_refused(holed, dense,
                                                        fresh_table):
    """The exact all-f64 family keeps the aligned path, over holes and
    over dense tiles alike."""
    steps = BASE + 400_000 + np.arange(3, dtype=np.int64) * (2 ** 30)
    assert tst.counters_batch_family(holed, "rate", steps, W) == ("t",)
    before = tst.executable_cache_stats()
    for tiles in (holed, dense):
        assert tst.groupsum_counters(tiles, "rate", steps, W,
                                     np.arange(S_H) % G_H, G_H) is None
    assert _delta(before) == {"hits": 0, "misses": 0, "entries": 0}
    assert tst.groupsum_counters(holed, "rate", steps[:0], W,
                                 np.arange(S_H) % G_H, G_H) is None
