"""The mesh planner asks the selection memo before it walks.

Whether a selection holds histogram columns decides its mesh lowering
(``MeshTileExec``, ``MeshAggregateExec`` or the local engine:
query/planner.py ``_hist_selection``). The engine selects the same thing by
the same key a moment later and memoises it with its ``SelectionFacts``, so
where the memo would serve an entry for the range as the versions read now
and its facts say "no histogram", the planner takes that: no
``lookup_partitions`` on any shard, no pass over the partitions
(``_SelectMemo.facts_for``, counted in ``select_counts.plan_hits`` /
``plan_walks``).

Pinned here, on a planner with a mesh-serving backend over four local
shards: the first plans walk and the plan after the entry stands asks no
shard, with the same plan node; whatever ends the entry (an ingest, a
flush, an eviction on ONE shard, ``clear()``, a handle read) or keeps it
from answering (a range it does not hold for) makes the next plan walk; a
histogram and a mixed selection are lowered as the walk lowers them, every
time; the bare windowed shape hits the same way; answers of first and
repeated requests are equal bit for bit; and planners racing an ingest
never get a node the walk would not choose for the versions they saw.
"""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore, TimeSeriesShard
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.gateway.producer import (TestTimeseriesProducer,
                                         ingest_builders)
from filodb_tpu.parallel.mesh import MeshExecutor, make_mesh
from filodb_tpu.parallel.shardstore import ShardedTileEvaluator
from filodb_tpu.promql.parser import TimeStepParams, parse_query_range
from filodb_tpu.query.engine import (QueryEngine, select_memo,
                                     select_raw_series)
from filodb_tpu.query.model import (QueryStats, SelectionFacts,
                                    select_counts)
from filodb_tpu.query.planner import (LocalEngineExec, MeshAggregateExec,
                                      MeshTileExec, QueryPlanner)
from filodb_tpu.query.tpu import TpuBackend

REF = DatasetRef("timeseries")
T0 = 1_600_000_000
SHARDS = 4
ROWS = 360
OPS = ("sum", "avg", "count")
SUMBY = "{op}(rate(http_requests_total[5m])) by (job)"      # the cell's shape
WINDOWED = "rate(http_requests_total[5m])"
HIST = "sum(rate(http_request_latency[5m]))"
MIXED = 'sum(rate({__name__=~"http_requests_total|http_request_latency"}[5m]))'

pytestmark = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs a multi-device mesh")


@pytest.fixture(scope="module")
def mesh_ex():
    return MeshExecutor(make_mesh())


class World:
    """A store of four local shards (six counters, six gauges, two
    histograms, 360 rows each, flushed) under a planner whose backend
    serves the mesh-resident tiles, and a spy on ``lookup_partitions``."""

    def __init__(self, mesh_ex, monkeypatch, histograms=True):
        self.store = TimeSeriesMemStore(DEFAULT_SCHEMAS)
        for sh in range(SHARDS):
            self.store.setup(REF, sh)
        self.producer = TestTimeseriesProducer(DEFAULT_SCHEMAS,
                                               num_shards=SHARDS, spread=1)
        start = T0 * 1000
        self.ingest(self.producer.counters(start, ROWS, 6))
        self.ingest(self.producer.gauges(start, ROWS, 6))
        if histograms:
            self.ingest(self.producer.histograms(start, ROWS))
        self.store.flush_all(REF)
        self.shards = self.store.shards(REF)
        self.mesh_ex = mesh_ex
        self.backend = TpuBackend()
        self.backend.mesh_eval = ShardedTileEvaluator(mesh_ex.mesh)
        self.asked = []         # shard numbers, one per lookup_partitions
        real = TimeSeriesShard.lookup_partitions
        asked = self.asked

        def spy(shard, *args, **kw):
            asked.append(shard.shard_num)
            return real(shard, *args, **kw)
        monkeypatch.setattr(TimeSeriesShard, "lookup_partitions", spy)

    def ingest(self, builders):
        ingest_builders(self.store, REF, builders)

    def more_counter_rows(self, k):
        """Ten more rows of every counter, after all that is in."""
        return self.producer.counters((T0 + 10 * (ROWS + 10 * k)) * 1000,
                                      10, 6)

    def plan(self, query, start=T0 + 600, end=T0 + 3000):
        """-> (exec node, shards asked by the planner, hits, walks)."""
        planner = QueryPlanner(self.shards, backend=self.backend,
                               mesh_executor=self.mesh_ex, spread=1)
        logical = parse_query_range(query, TimeStepParams(start, 60, end))
        del self.asked[:]
        hits, walks = select_counts.plan_hits, select_counts.plan_walks
        node = planner.materialize(logical)
        return (node, sorted(self.asked), select_counts.plan_hits - hits,
                select_counts.plan_walks - walks)

    def settle(self, query, **rng):
        """Plan and execute until a plan hits: over a fresh store the first
        execute builds the tiles, and that read ends its entry; the second
        stores one that stays. -> the answers on the way, in order."""
        out = []
        for _ in range(3):
            node, asked, hits, walks = self.plan(query, **rng)
            assert isinstance(node, MeshTileExec)
            if hits:
                assert asked == [] and walks == 0
                return out
            assert walks == 1 and asked == _every_shard()
            out.append(node.execute())
        raise AssertionError("no entry with facts stands after 3 executes")


@pytest.fixture
def world(mesh_ex, monkeypatch):
    select_memo.clear()
    yield World(mesh_ex, monkeypatch)
    select_memo.clear()


def _every_shard():
    return list(range(SHARDS))


def _raw_plan(query):
    """The raw selector's plan node of ``query`` over the default range."""
    plan = parse_query_range(query, TimeStepParams(T0 + 600, 60, T0 + 3000))
    return plan.inner.raw


def _select(world, raw):
    return select_raw_series(world.shards, raw.filters, raw.start_ms,
                             raw.end_ms, raw.column, QueryStats(), full=True)


# --- (a) the first plans walk, the plan over a standing entry asks nobody ----

@pytest.mark.parametrize("op", OPS)
def test_first_plan_walks_and_the_repeated_plan_asks_no_shard(world, op):
    query = SUMBY.format(op=op)
    node, asked, hits, walks = world.plan(query)
    assert isinstance(node, MeshTileExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)
    node.execute()              # builds the tiles: that read ends the entry
    node, asked, hits, walks = world.plan(query)
    assert isinstance(node, MeshTileExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)
    node.execute()              # an entry that stays, facts made
    dispatches = world.backend.mesh_dispatches
    for _ in range(3):
        node, asked, hits, walks = world.plan(query)
        assert isinstance(node, MeshTileExec)
        assert asked == [] and (hits, walks) == (1, 0)
        del world.asked[:]
        node.execute()
        assert world.asked == []        # the engine's selection hit as well
    assert world.backend.mesh_dispatches == dispatches + 3


# --- (b) a change on ONE shard makes the next plan walk ----------------------

def _ingest_one(world):
    shard, builder = sorted(world.more_counter_rows(0).items())[0]
    world.ingest({shard: builder})
    return shard


def _flush_one(world):
    shard = _ingest_one(world)          # (something to flush)
    world.settle(SUMBY.format(op="sum"))
    assert world.shards[shard].flush_all() > 0
    return shard


def _evict_one(world):
    for shard in world.shards:
        if shard.evict_partitions(cutoff_ts=2**62):
            return shard.shard_num
    raise AssertionError("nothing to evict")


@pytest.mark.parametrize("change", [_ingest_one, _flush_one, _evict_one],
                         ids=["ingest", "flush", "evict"])
def test_a_change_on_one_shard_makes_the_next_plan_walk(world, change):
    query = SUMBY.format(op="sum")
    world.settle(query)
    assert world.plan(query)[1:] == ([], 1, 0)
    versions = [s.version for s in world.shards]
    moved = change(world)
    now = [s.version for s in world.shards]
    assert now[moved] > versions[moved]
    node, asked, hits, walks = world.plan(query)
    assert isinstance(node, MeshTileExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)
    # and what is served afterwards is the store as it is now
    got = node.execute()
    want = QueryEngine(world.shards).execute(parse_query_range(
        query, TimeStepParams(T0 + 600, 60, T0 + 3000)))
    np.testing.assert_allclose(got.values, want.values, rtol=1e-6,
                               equal_nan=True)


# --- (c) a range the entry does not hold for ---------------------------------

def test_a_range_the_entry_does_not_hold_for_walks(world):
    query = SUMBY.format(op="sum")
    world.settle(query)
    entry, = select_memo._entries.values()
    # before the first sample: the index matches nothing there
    early = dict(start=T0 - 7200, end=T0 - 3600)
    assert not entry.holds_for((early["start"] - 300) * 1000,
                               early["end"] * 1000)
    node, asked, hits, walks = world.plan(query, **early)
    assert isinstance(node, MeshTileExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)
    # the entry is still there, and still answers the range it holds for
    assert world.plan(query)[1:] == ([], 1, 0)
    assert world.plan(query, start=T0 + 660, end=T0 + 3060)[1:] == ([], 1, 0)


# --- (d) the entry gone -------------------------------------------------------

def _clear(world):
    select_memo.clear()


def _read_a_handle(world):
    series = _select(world, _raw_plan(SUMBY.format(op="sum")))
    assert series.entry.held is not None
    series[0].ts                # a holder reads: the sharing ends
    assert series.entry.held is None


def _moved_versions_unseen(world):
    """A version that moved and no ``lookup`` has looked since: the stale
    entry still stands in the memo, and is not answered from."""
    world.shards[0]._changed()
    assert len(select_memo) == 1


@pytest.mark.parametrize("gone", [_clear, _read_a_handle,
                                  _moved_versions_unseen],
                         ids=["clear", "handle-read", "stale-entry"])
def test_without_a_served_entry_the_plan_walks(world, gone):
    query = SUMBY.format(op="sum")
    world.settle(query)
    assert world.plan(query)[1:] == ([], 1, 0)
    gone(world)
    node, asked, hits, walks = world.plan(query)
    assert isinstance(node, MeshTileExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)


def test_an_entry_without_facts_yet_walks(world):
    """A selection somebody made and nobody derived anything from: the
    entry is served, its facts slot is empty, the planner walks."""
    query = SUMBY.format(op="sum")
    raw = _raw_plan(query)
    series = _select(world, raw)
    assert series.entry.held is not None and series.entry.facts is None
    assert select_memo.facts_for(world.shards, raw.filters, raw.column,
                                 raw.start_ms, raw.end_ms) is None
    node, asked, hits, walks = world.plan(query)
    assert isinstance(node, MeshTileExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)


def test_the_question_only_reads(world):
    """``facts_for`` counts no selection, moves nothing in the memo, builds
    no ``offsets`` and leaves a stale entry for ``lookup`` to drop."""
    query = SUMBY.format(op="sum")
    world.settle(query)
    raw = _raw_plan(query)
    entry, = select_memo._entries.values()
    ask = (world.shards, raw.filters, raw.column, raw.start_ms, raw.end_ms)
    offsets = entry.offsets
    counts = (select_counts.memo_hits, select_counts.memo_misses,
              select_counts.handles, select_counts.facts_hits,
              select_counts.facts_misses)
    assert select_memo.facts_for(*ask) is entry.facts is not None
    assert entry.offsets is offsets
    assert counts == (select_counts.memo_hits, select_counts.memo_misses,
                      select_counts.handles, select_counts.facts_hits,
                      select_counts.facts_misses)
    world.shards[1]._changed()
    assert select_memo.facts_for(*ask) is None
    assert list(select_memo._entries.values()) == [entry]
    assert entry.held is not None


# --- (e) histogram and mixed selections are lowered as the walk lowers them --

def test_a_histogram_selection_lowers_to_the_aggregate_every_time(world):
    les = None
    for _ in range(4):
        node, asked, hits, walks = world.plan(HIST)
        assert isinstance(node, MeshAggregateExec)
        assert asked == _every_shard() and (hits, walks) == (0, 1)
        assert node.hist_les is not None
        if les is None:
            les = node.hist_les
        np.testing.assert_array_equal(node.hist_les, les)
        got = node.execute()
        assert got.is_hist()
    # and the engine's own selection of it, memoised with its facts,
    # changes nothing: "a histogram somewhere" walks
    logical = parse_query_range(HIST, TimeStepParams(T0 + 600, 60, T0 + 3000))
    for _ in range(2):
        QueryEngine(world.shards, backend=world.backend).execute(logical)
    raw = logical.inner.raw
    facts = select_memo.facts_for(world.shards, raw.filters, raw.column,
                                  raw.start_ms, raw.end_ms)
    assert facts is None or facts.any_hist
    node, asked, hits, walks = world.plan(HIST)
    assert isinstance(node, MeshAggregateExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)
    np.testing.assert_array_equal(node.hist_les, les)


def test_a_mixed_selection_stays_local_every_time(world):
    raw = _raw_plan(MIXED)
    for _ in range(4):
        node, asked, hits, walks = world.plan(MIXED)
        assert isinstance(node, LocalEngineExec)
        assert asked == _every_shard() and (hits, walks) == (0, 1)
        # an entry with facts stands from the second round on, and says
        # "a histogram somewhere": no answer for the planner
        series = _select(world, raw)
        if series.entry.facts is None:
            series.entry.facts = SelectionFacts(series)
        assert series.entry.facts.any_hist


# --- (f) the bare windowed shape ----------------------------------------------

def test_the_bare_windowed_shape_hits_the_same_way(world):
    first = world.settle(WINDOWED)[0]
    for _ in range(2):
        node, asked, hits, walks = world.plan(WINDOWED)
        assert isinstance(node, MeshTileExec)
        assert asked == [] and (hits, walks) == (1, 0)
        again = node.execute()
        assert again.keys == first.keys
        assert again.values.tobytes() == first.values.tobytes()
    # the same selection under the grouped shape: the same entry answers
    assert world.plan(SUMBY.format(op="sum"))[1:] == ([], 1, 0)
    # a histogram selection of this shape stays local, as the walk has it
    node, asked, hits, walks = world.plan("rate(http_request_latency[5m])")
    assert isinstance(node, LocalEngineExec)
    assert asked == _every_shard() and (hits, walks) == (0, 1)


# --- (g) first and repeated answers, bit for bit ------------------------------

@pytest.mark.parametrize("op", OPS)
def test_first_and_repeated_answers_are_equal_bit_for_bit(world, op):
    query = SUMBY.format(op=op)
    answers = world.settle(query)
    for _ in range(3):
        node, asked, hits, walks = world.plan(query)
        assert asked == [] and (hits, walks) == (1, 0)
        answers.append(node.execute())
    first = answers[0]
    assert first.values.size and np.isfinite(first.values).any()
    for got in answers[1:]:
        assert got.keys == first.keys
        assert got.steps.tobytes() == first.steps.tobytes()
        assert got.values.tobytes() == first.values.tobytes()


# --- (h) planners racing an ingest --------------------------------------------

def test_planners_racing_an_ingest_get_the_walks_node(mesh_ex, monkeypatch):
    """Two threads plan the regex selection (counters now, the histograms
    too once they are in) while a third ingests and executes. Counter rows
    move versions and leave the selection scalar; then the histograms land
    on one shard and the selection is mixed. A plan that ended before that
    ingest began must be ``MeshTileExec``; a plan that began after it
    returned (the write acknowledged) must be local, whatever entry the
    memo still holds. Hits happen in between, or the test proves nothing."""
    select_memo.clear()
    world = World(mesh_ex, monkeypatch, histograms=False)
    query = MIXED + " by (job)"
    logical = parse_query_range(query,
                                TimeStepParams(T0 + 600, 60, T0 + 3000))
    hist = world.producer.histograms(T0 * 1000, ROWS, n_instances=1)
    (hist_shard, _), = hist.items()
    watched = world.shards[hist_shard]
    mark = {}               # the watched shard's version around the ingest
    seen = []               # (version before, node type, version after)
    errors = []
    stop = threading.Event()
    hits0 = select_counts.plan_hits

    def planner_loop():
        try:
            while not stop.is_set():
                before = watched.version
                planner = QueryPlanner(world.shards, backend=world.backend,
                                       mesh_executor=mesh_ex, spread=1)
                node = planner.materialize(logical)
                seen.append((before, type(node), watched.version))
        except Exception as e:      # noqa: BLE001 (reported below)
            errors.append(e)

    def wait_for_hits(n, limit_s=20.0):
        base = select_counts.plan_hits
        end = time.monotonic() + limit_s
        while select_counts.plan_hits - base < n and time.monotonic() < end:
            time.sleep(0.001)

    def ingest_loop():
        try:
            for k in range(3):
                world.ingest(world.more_counter_rows(k))
                for _ in range(2):      # (an entry with facts stands)
                    QueryPlanner(world.shards, backend=world.backend,
                                 mesh_executor=mesh_ex,
                                 spread=1).materialize(logical).execute()
                wait_for_hits(4)
            mark["before"] = watched.version
            world.ingest(hist)
            mark["after"] = watched.version
            end = time.monotonic() + 20.0       # (until both planned again)
            while time.monotonic() < end and sum(
                    before >= mark["after"] for before, _, _ in seen) < 2:
                time.sleep(0.001)
        except Exception as e:      # noqa: BLE001
            errors.append(e)
        finally:
            stop.set()

    threads = [threading.Thread(target=planner_loop) for _ in range(2)]
    threads.append(threading.Thread(target=ingest_loop))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)         # hand over between any two checks
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        select_memo.clear()
    assert not [t for t in threads if t.is_alive()]
    assert not errors, errors
    assert mark["after"] > mark["before"]
    assert select_counts.plan_hits - hits0 >= 12
    early = [kind for _, kind, after in seen if after <= mark["before"]]
    late = [kind for before, kind, _ in seen if before >= mark["after"]]
    assert early and set(early) == {MeshTileExec}
    assert late and set(late) == {LocalEngineExec}
    assert {kind for _, kind, _ in seen} == {MeshTileExec, LocalEngineExec}
