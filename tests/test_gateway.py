"""Gateway tests: influx line protocol parsing + producer sharding
(reference: gateway/src/test InfluxProtocolParserSpec shapes,
TestTimeseriesProducer)."""

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetRef
from filodb_tpu.gateway.influx import (InfluxParseError, parse_line,
                                       parse_lines, record_to_builder)
from filodb_tpu.gateway.producer import (TestTimeseriesProducer,
                                         ingest_builders)
from filodb_tpu.core.index import ColumnFilter


def test_parse_basic_gauge_line():
    r = parse_line(
        "heap_usage,host=h0,dc=dc1 gauge=12.5 1600000000000000000")
    assert r.measurement == "heap_usage"
    assert r.tags == {"host": "h0", "dc": "dc1"}
    assert r.fields == {"gauge": 12.5}
    assert r.timestamp_ms == 1_600_000_000_000


def test_parse_escapes_and_int_suffix():
    r = parse_line(
        r"my\ metric,tag\,x=a\ b counter=42i 1600000000000000000")
    assert r.measurement == "my metric"
    assert r.tags == {"tag,x": "a b"}
    assert r.fields == {"counter": 42.0}


def test_parse_missing_timestamp_uses_now():
    r = parse_line("m value=1.0", now_ms=123_000)
    assert r.timestamp_ms == 123_000


@pytest.mark.parametrize("bad", ["justname", "m,badtag value=1 x y z",
                                 "m novalue", "m f=abc"])
def test_parse_errors(bad):
    with pytest.raises(InfluxParseError):
        parse_line(bad)


def test_histogram_mapping_and_query():
    b = RecordBuilder(DEFAULT_SCHEMAS)
    used = record_to_builder(parse_line(
        "lat,host=h0 sum=100.0,count=10,2=1,4=4,8=9,+Inf=10 "
        "1600000000000000000"), b)
    assert used == ["prom-histogram"]


def test_counter_lines_end_to_end_query():
    store = TimeSeriesMemStore(DEFAULT_SCHEMAS)
    ref = DatasetRef("ts")
    store.setup(ref, 0)
    b = RecordBuilder(DEFAULT_SCHEMAS)
    t0 = 1_600_000_000_000
    lines = []
    v = 0
    for i in range(60):
        v += 100
        lines.append(f"reqs,host=h0 counter={v} {(t0 + i * 10_000) * 10**6}")
    n = parse_lines("\n".join(lines), b)
    assert n == 60
    for c in b.containers():
        store.ingest(ref, 0, c)
    store.flush_all(ref)
    parts = store.lookup_partitions(
        ref, 0, [ColumnFilter.eq("_metric_", "reqs")], t0, t0 + 10**9)
    assert len(parts) == 1


def test_producer_shards_consistently():
    p = TestTimeseriesProducer(DEFAULT_SCHEMAS, num_shards=8, spread=2)
    labels = p._labels("heap_usage", 1)
    s1 = p.shard_for("gauge", labels)
    s2 = p.shard_for("gauge", labels)
    assert s1 == s2 and 0 <= s1 < 8
    builders = p.gauges(1_600_000_000_000, 30, n_instances=8)
    assert sum(len(c) for b in builders.values()
               for c in b.containers()) == 240


def test_producer_ingest_roundtrip():
    store = TimeSeriesMemStore(DEFAULT_SCHEMAS)
    ref = DatasetRef("ts")
    for i in range(4):
        store.setup(ref, i)
    p = TestTimeseriesProducer(DEFAULT_SCHEMAS, num_shards=4)
    rows = ingest_builders(store, ref,
                           p.counters(1_600_000_000_000, 100))
    assert rows == 400


# -- the TCP edge's per-series route cache and the parser's split fast path --

_ACCEPTED = [
    "http_requests_total,job=j0,instance=i0 counter=42 1600000000000000000",
    "http_requests_total,job=j0,instance=i0 counter=42i 1600000010000000000",
    "node_load1,host=h0 gauge=0.125 1600000000000000000",
    "node_load1,host=h0 value=-3.5e-3 1600000000000000000",
    "node_load1,host=h0 gauge=nan 1600000000000000000",
    "node_load1,host=h0 gauge=inf 1600000000000000000",
    "node_load1,host=h0 gauge=-inf 1600000000000000000",
    "node_load1,host=h0 gauge=1 -1000000",
    "node_load1,_ws_=other,_ns_=App-9,host=h0 gauge=1 1600000000000000000",
    "cpu,host=h0 user=1.5,system=2.5 1600000000000000000",
    "cpu,host=h0 user=1.5,note=\"x\" 1600000000000000000",
    "lat,host=h0 sum=100.0,count=10,2=1,4=4,8=9,+Inf=10 1600000000000000000",
    "bare gauge=7 1600000000000000000",
    "node_load1,host=h0  gauge=2   1600000000000000000",
    "node_load1,host=h0 gauge=1 1600000000000000000\r",
]
_REJECTED = [
    "justname",
    "m,badtag value=1 1600000000000000000",
    "m,host= value=1 1600000000000000000",
    "m novalue 1600000000000000000",
    "m f=abc 1600000000000000000",
    "m gauge=1 notatime",
    "m gauge=1 1.5",
    "m note=\"only a string\" 1600000000000000000",
    "m,host=h0 gauge=1 1600000000000000000 trailing",
]


def _escape_scan_twin(line):
    """The same line with a backslash before the first character of its
    identity and of its field set: `\\x` unescapes to `x`, so it means the
    same record, but every split takes the character scan instead of the
    str.split shortcut."""
    ident, _, rest = line.strip().partition(" ")
    rest = rest.lstrip(" ")
    return "\\" + ident + " \\" + rest if rest else "\\" + ident


@pytest.mark.parametrize("line", _ACCEPTED)
def test_split_shortcut_parses_like_the_escape_scan(line):
    fast, slow = parse_line(line), parse_line(_escape_scan_twin(line))
    assert fast.measurement == slow.measurement
    assert fast.tags == slow.tags
    assert list(fast.fields) == list(slow.fields)
    np.testing.assert_array_equal(list(fast.fields.values()),
                                  list(slow.fields.values()))
    assert fast.timestamp_ms == slow.timestamp_ms


@pytest.mark.parametrize("line", _REJECTED)
def test_split_shortcut_rejects_like_the_escape_scan(line):
    for form in (line, _escape_scan_twin(line)):
        with pytest.raises(ValueError):
            parse_line(form)


def _gateway(**kw):
    from filodb_tpu.gateway.server import GatewayServer
    return GatewayServer({}, DEFAULT_SCHEMAS, num_shards=4, spread=1, **kw)


def _drain(builders):
    """{(shard, schema): (part keys, timestamps, columns, runs)}"""
    out = {}
    for shard, b in builders.items():
        for c in b.containers():
            out[(shard, c.schema.name)] = (
                list(c.part_keys), list(c.timestamps),
                [list(col) for col in c.columns],
                [(a, z, pk) for a, z, pk in c.runs()])
    return out


def _assert_same_containers(got, want):
    assert got.keys() == want.keys()
    for k in want:
        (gpk, gts, gcols, gruns), (wpk, wts, wcols, wruns) = got[k], want[k]
        assert gpk == wpk and gts == wts and gruns == wruns
        for gcol, wcol in zip(gcols, wcols):
            for g, w in zip(gcol, wcol):
                if isinstance(w, tuple):            # (bucket scheme, counts)
                    assert g[0] == w[0]
                    np.testing.assert_array_equal(g[1], w[1])
                else:
                    np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("line", _ACCEPTED + _REJECTED)
def test_route_cache_changes_no_container_and_no_counter(line):
    """A line routed through the gateway three times (the first resolves
    its route, the others hit the cache) fills the same containers as the
    uncached mapping: parse, map, make the part key, hash to a shard."""
    from filodb_tpu.gateway.influx import input_records
    gw = _gateway()
    try:
        got, want = {}, {}
        accepted = 0
        for _ in range(3):
            accepted += gw._route_line(line.strip(), got)
            try:
                samples = input_records(parse_line(line.strip()),
                                        gw.ws, gw.ns)
            except ValueError:
                continue
            for name, labels, ts, values in samples:
                _, shard = gw._route(name, labels)
                want.setdefault(shard, RecordBuilder(
                    DEFAULT_SCHEMAS)).add_sample(name, labels, ts, *values)
        assert accepted == (3 if line in _ACCEPTED else 0)
        assert gw.lines_ingested == accepted
        assert gw.lines_rejected == 3 - accepted
        assert len(gw._routes) == (1 if accepted else 0)
        _assert_same_containers(_drain(got), _drain(want))
    finally:
        gw._server.server_close()


def test_route_cache_is_cleared_at_its_cap_and_stays_right():
    gw = _gateway()
    gw._ROUTE_CACHE_MAX = 3
    lines = [f"m,instance=i{i} counter={i} 1600000000000000000"
             for i in range(8)]
    try:
        rounds = []
        for _ in range(3):
            b = {}
            for line in lines:
                assert gw._route_line(line, b)
                assert len(gw._routes) <= 3
            rounds.append(_drain(b))
        _assert_same_containers(rounds[1], rounds[0])
        _assert_same_containers(rounds[2], rounds[0])
        assert sum(len(v[0]) for v in rounds[0].values()) == len(lines)
    finally:
        gw._server.server_close()


def test_part_key_interning_survives_the_clear_at_its_cap(monkeypatch):
    """from_bytes interns by content and clears the table wholesale when
    it is full: keys decoded before and after the clear are equal (not
    necessarily identical), and a WAL record still decodes into the same
    rows and the same same-series runs."""
    from filodb_tpu.core import record
    from filodb_tpu.core.record import PartKey
    from filodb_tpu.ingest.stream import decode_container, encode_container
    monkeypatch.setattr(record, "_PK_INTERN", {})
    monkeypatch.setattr(record, "_PK_INTERN_MAX", 3)
    schema = DEFAULT_SCHEMAS.by_name("prom-counter")
    keys = [PartKey.make(schema, {"_metric_": "m", "instance": f"i{i}"})
            for i in range(8)]
    bufs = [k.to_bytes() for k in keys]
    first = [PartKey.from_bytes(b) for b in bufs]       # clears twice
    assert len(record._PK_INTERN) <= 3
    again = [PartKey.from_bytes(b) for b in bufs]
    assert first == keys and again == keys
    assert [k.to_bytes() for k in again] == bufs
    assert PartKey.from_bytes(bufs[-1]) is again[-1]    # interned
    b = RecordBuilder(DEFAULT_SCHEMAS)
    for k, key in enumerate(keys):
        for j in range(2):                              # runs of two
            b.add_keyed("prom-counter", key, 1000 * j, float(k))
    (cont,) = b.containers()
    back, _ = decode_container(encode_container(cont), 0, DEFAULT_SCHEMAS)
    assert back.part_keys == cont.part_keys
    assert back.timestamps == cont.timestamps
    assert back.columns == cont.columns
    assert [tuple(r) for r in back.runs()] == [tuple(r) for r in cont.runs()]
