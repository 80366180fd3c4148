"""Query result model: range vectors as dense grid batches.

Replaces the reference's RangeVector / SerializedRangeVector
(core/src/main/scala/filodb.core/query/RangeVector.scala:124,452) with a
columnar, device-friendly representation: after windowing, every series in a
result shares one step grid, so a whole result is ``[num_series, num_steps]``
matrices + per-series label keys.  No per-row serialization is ever needed
intra-process (the reference's Kryo path exists only because of the JVM actor
boundary)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class RangeParams:
    """start/step/end in **milliseconds** (query/TimeStepParams at the edge is
    seconds; converted at the HTTP layer)."""
    start_ms: int
    step_ms: int
    end_ms: int

    @property
    def steps(self) -> np.ndarray:
        if self.step_ms <= 0:
            return np.array([self.start_ms], dtype=np.int64)
        return np.arange(self.start_ms, self.end_ms + 1, self.step_ms,
                         dtype=np.int64)

    @property
    def num_steps(self) -> int:
        if self.step_ms <= 0:
            return 1
        return (self.end_ms - self.start_ms) // self.step_ms + 1


class RawSeries:
    """One series' raw samples (RawDataRangeVector equivalent).

    ``snapshot_key`` identifies the immutable chunk-backed prefix of this
    series in its store — (dataset, shard, part_id, num_chunks, col). Device
    tile caches key on it: the prefix content is pinned by num_chunks (chunks
    are append-only and immutable), so repeated queries over an unchanged
    store snapshot reuse device tiles with zero rebuilds. ``chunk_len`` is
    the length of that prefix; samples beyond it are the mutable write-buffer
    tail (merged host-side / via the general path at query time).

    What is read when. Built from arrays (the wire decoders, a span or range
    selection, ``clip_series``, tests) a series simply holds them. A
    ``full=True`` selection of a local shard hands out a HANDLE
    (``RawSeries.handle``): ``labels`` (the part key's shared mapping: copy
    it before changing it), ``is_counter``, ``is_hist``, ``bucket_les``,
    ``snapshot_key``, ``chunk_len``, ``tail_first_ts`` and ``last_ts`` are
    facts taken with the selection, and ``ts`` / ``values`` /
    ``hist_drop_rows`` are read from the partition the first time one of
    them is touched, as exactly the rows the selection saw. A consumer that
    answers from the facts (the fused group-sum on a tile hit) never pays
    for the samples. An unread handle may be shared: the selection memo
    (query/engine.py) hands the same handles to every query the store has
    not changed under, until the first read of one ends that; whoever
    holds it then still reads those rows, once."""

    __slots__ = ("labels", "is_counter", "is_hist", "bucket_les",
                 "snapshot_key", "chunk_len", "_ts", "_values", "_drops",
                 "_tail", "_read")

    def __init__(self, labels: Mapping[str, str],
                 ts: np.ndarray,            # int64 ms, sorted
                 values: np.ndarray,        # f64 [n] or [n, num_buckets]
                 is_counter: bool = False,
                 bucket_les: Optional[np.ndarray] = None,   # histogram series
                 snapshot_key: Optional[Tuple] = None,
                 chunk_len: int = -1,   # -1: everything is immutable (no tail)
                 # histogram reset rows from the sectioned drop tables (row i
                 # = reset between rows i-1 and i); None = caller rescans
                 hist_drop_rows: Optional[np.ndarray] = None):
        self.labels = labels
        self.is_counter = is_counter
        self.is_hist = values.ndim == 2
        self.bucket_les = bucket_les
        self.snapshot_key = snapshot_key
        self.chunk_len = chunk_len
        self._ts = ts
        self._values = values
        self._drops = hist_drop_rows
        self._tail = None
        self._read = None

    @classmethod
    def handle(cls, labels, is_counter, is_hist, bucket_les, snapshot_key,
               chunk_len, tail_first_ts, last_ts, read) -> "RawSeries":
        """A series of facts whose samples ``read(series)`` fetches on first
        touch: it calls ``series.fill`` with them."""
        s = cls.__new__(cls)
        s.labels = labels
        s.is_counter = is_counter
        s.is_hist = is_hist
        s.bucket_les = bucket_les
        s.snapshot_key = snapshot_key
        s.chunk_len = chunk_len
        s._tail = (tail_first_ts, last_ts)
        s._read = read
        return s

    def fill(self, ts, values, hist_drop_rows=None) -> None:
        """The samples of a handle; from here on it is a series of arrays
        (the tail facts are read off them, and say the same)."""
        self._ts = ts
        self._values = values
        self._drops = hist_drop_rows
        self._tail = None
        self._read = None

    # A handle of a memoised selection (query/engine.py) has several
    # holders, and another's read may land between any two lines here: each
    # property takes ``_read`` / ``_tail`` once, and ``fill`` sets the
    # arrays before it clears either.
    @property
    def filled(self) -> bool:
        """Does it hold its samples (always, unless a handle not yet
        read)."""
        return self._read is None

    @property
    def ts(self) -> np.ndarray:
        read = self._read
        if read is not None:
            read(self)
        return self._ts

    @property
    def values(self) -> np.ndarray:
        read = self._read
        if read is not None:
            read(self)
        return self._values

    @property
    def hist_drop_rows(self) -> Optional[np.ndarray]:
        read = self._read
        if read is not None:
            read(self)
        return self._drops

    @property
    def tail_first_ts(self) -> Optional[int]:
        """Timestamp of the first row beyond the chunk prefix (None: the
        prefix is everything there is)."""
        tail = self._tail
        if tail is not None:
            return tail[0]
        ts, cl = self._ts, self.chunk_len
        return int(ts[cl]) if 0 <= cl < ts.size else None

    @property
    def last_ts(self) -> Optional[int]:
        tail = self._tail
        if tail is not None:
            return tail[1]
        return int(self._ts[-1]) if self._ts.size else None


class _SelectCounts:
    """``filodb_select_series_total`` / ``_read_total``: handles a
    ``full=True`` selection handed out, and handles whose samples some
    consumer then read; ``filodb_select_memo_{hits,misses}_total``: such
    selections over local shards that the memo answered, and that ran the
    loop; ``filodb_selection_facts_{hits,misses}_total``: requests that
    took their ``SelectionFacts`` from the memo entry, and that made the
    pass over the series; ``filodb_plan_selection_facts_{hits,walks}_total``:
    mesh lowerings (query/planner.py ``_hist_selection``) that learned
    from the memo entry's facts that the selection holds no histogram, and
    that walked the matched partitions to learn it. Plain adds, like the
    backend's counters."""

    __slots__ = ("handles", "reads", "memo_hits", "memo_misses",
                 "facts_hits", "facts_misses", "plan_hits", "plan_walks")

    def __init__(self):
        self.handles = 0
        self.reads = 0
        self.memo_hits = 0
        self.memo_misses = 0
        self.facts_hits = 0
        self.facts_misses = 0
        self.plan_hits = 0
        self.plan_walks = 0


select_counts = _SelectCounts()

# groupings kept with one selection, wherever it lives: the (by, without)
# sets of a memo entry, the tile-order group ids of a tile entry, the
# device-resident ids of a mesh placement
MAX_GROUPINGS = 8


class PerGrouping:
    """What is derived from a group-id array, kept per FROZEN array. The
    selection memo hands every holder of a selection the same read-only ids
    per (by, without), and a keeper of this kind hands out read-only arrays
    in its turn, so an array's identity names its grouping; the reference
    kept beside the id keeps the id from being recycled. At most
    ``MAX_GROUPINGS`` (then all go: each is made again when asked for). An
    array that is not frozen is nobody's: made as it comes, not kept."""

    __slots__ = ("kept",)

    def __init__(self):
        self.kept: Dict[int, Tuple[np.ndarray, object]] = {}

    def get(self, gids, make):
        """``make(gids)``, once per frozen ``gids``."""
        gids = np.asarray(gids)
        if gids.flags.writeable:
            return make(gids)
        got = self.kept.get(id(gids))
        if got is not None and got[0] is gids:
            return got[1]
        made = make(gids)
        if len(self.kept) >= MAX_GROUPINGS:
            self.kept.clear()
        # (two threads' first requests: both take the one that landed)
        return self.kept.setdefault(id(gids), (gids, made))[1]


class TileKey:
    """A selection's key in the device tile cache: its snapshot keys (or
    object ids) as one tuple, hashed once. Two keys made from two selections
    of the same store are equal tuple by tuple (24,576 compares); the
    backend swaps a ``SelectionFacts``' key for the cache's own at the
    first hit, so every later lookup ends at ``is``."""

    __slots__ = ("parts", "_hash")

    def __init__(self, parts: Tuple):
        self.parts = parts
        self._hash = hash(parts)

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        if self is other:
            return True
        if not isinstance(other, TileKey):
            return NotImplemented
        return self._hash == other._hash and self.parts == other.parts


class SelectionFacts:
    """What a request derives from its selection alone, and not from its
    grid, in one place and from one function, so that a selection that is
    shared (the selection memo, query/engine.py) is walked once and not
    once a request:

    ``key``       the tile-cache key: the snapshot keys where every series
                  carries one (``use_snap``: pinned content), else the
                  series' object ids;
    ``ident``     the snapshot keys minus the chunk-count field, stable
                  across flushes for the same partitions and column (None
                  without ``use_snap``);
    ``tail_min``  the earliest timestamp beyond any series' chunk prefix
                  (None: no tail anywhere), from the facts: nothing is read;
    ``any_hist``  is any series a histogram;
    ``les``       the bucket bounds every series shares, as a tuple, where
                  every series is a histogram of one bucket scheme (None
                  else: a selection a bucket-axis tile cannot hold).

    They are as fresh as the handles they were read from: a read may
    rewrite a handle's facts (a partition evicted or paged in under it), so
    whoever reads the samples makes them again afterwards."""

    __slots__ = ("key", "ident", "tail_min", "any_hist", "les")

    def __init__(self, series: Sequence[RawSeries]):
        keys = [s.snapshot_key for s in series]
        if None not in keys:
            self.key = TileKey(tuple(keys))
            self.ident = tuple([k[:3] + k[4:] for k in keys])
        else:
            self.key = TileKey(tuple([id(s) for s in series]))
            self.ident = None
        tails = [t for t in [s.tail_first_ts for s in series]
                 if t is not None]
        self.tail_min = min(tails) if tails else None
        self.any_hist = True in [s.is_hist for s in series]
        self.les = _one_scheme(series) if self.any_hist else None

    @property
    def use_snap(self) -> bool:
        return self.ident is not None

    def tail_bound(self, cov_min_ms: Optional[int]) -> Optional[int]:
        """``tail_min``, or a tile entry's coverage bound if that is
        earlier (None: neither)."""
        tm = self.tail_min
        if tm is None or cov_min_ms is not None and cov_min_ms < tm:
            return cov_min_ms
        return tm


def _one_scheme(series: Sequence[RawSeries]) -> Optional[Tuple[float, ...]]:
    """The bucket bounds of a selection whose every series is a histogram
    of one scheme, or None."""
    first = None
    for s in series:
        les = s.bucket_les
        if not s.is_hist or les is None:
            return None
        if first is None:
            first = les
        elif les is not first and not np.array_equal(les, first):
            return None
    return tuple(np.asarray(first, np.float64).tolist())


def selection_facts(series: Sequence[RawSeries]) -> SelectionFacts:
    """The facts of ``series``, once a request: from the memo entry the
    selection is shared through, if it has one that is still served and
    some holder has made them (a hit: no pass over the series), else made
    here (a miss) and left there for the next holder. A plain list, a
    selection off remote shards or one whose entry was dropped makes them
    the same way.

    Exactly as fresh as the handles: the entry is dropped at the first read
    of one of them (before that read may rewrite a snapshot key), when a
    shard's version moves, and on ``clear()``; a dropped entry's slot is
    never read again. A holder that took the facts before a concurrent drop
    is where a holder that built its key before one always was: the tiles
    under the old key hold the old key's pinned content. No lock: the slot
    is one attribute, read once and written once.

    A selection that is used once (its consumer reads the handles, so its
    entry dies with the request) pays the one pass here and no more than
    before: the tile build makes its own after it read, as the key was
    built before and after a build."""
    entry = getattr(series, "entry", None)
    served = entry is not None and entry.held is not None
    if served:
        facts = entry.facts         # once: a drop empties the slot
        if facts is not None:
            select_counts.facts_hits += 1
            return facts
    facts = SelectionFacts(series)
    select_counts.facts_misses += 1
    if served:
        entry.facts = facts
    return facts


def clip_series(series: Sequence[RawSeries], start_ms: int, end_ms: int
                ) -> List[RawSeries]:
    """Restrict each series to samples in [start_ms, end_ms] (views, no
    copies). Used to hand the oracle / general device path only the span a
    window grid can touch, while tile caches keep the full snapshot."""
    out = []
    for s in series:
        lo = int(np.searchsorted(s.ts, start_ms, side="left"))
        hi = int(np.searchsorted(s.ts, end_ms, side="right"))
        if lo == 0 and hi == s.ts.size:
            out.append(s)
        else:
            dr = s.hist_drop_rows
            if dr is not None:
                dr = dr[(dr >= lo) & (dr < hi)] - lo
            out.append(RawSeries(s.labels, s.ts[lo:hi], s.values[lo:hi],
                                 s.is_counter, s.bucket_les,
                                 hist_drop_rows=dr))
    return out


@dataclass
class GridResult:
    """A periodic (windowed) result: shared step grid + per-series rows.

    ``values`` is [num_series, num_steps] float64 (NaN = no sample — carries
    the reference's NaN/staleness semantics through the pipeline).
    For histogram results, ``hist_values`` is [num_series, num_steps, nb].

    ``partial``/``warnings`` carry degraded-mode provenance (the
    Thanos/M3 partial-response analogue): a result assembled while some
    shard group was unreachable is flagged, and every aggregation /
    concatenation / stitch step propagates the flag upward so the Prom
    JSON edge can surface ``"partial": true`` + per-shard warnings."""
    steps: np.ndarray                       # int64 [num_steps] ms
    keys: List[Dict[str, str]]              # per-series labels
    values: np.ndarray                      # f64 [S, T]
    hist_values: Optional[np.ndarray] = None  # f64 [S, T, NB]
    bucket_les: Optional[np.ndarray] = None
    partial: bool = False                   # some shard group missing
    warnings: List[str] = field(default_factory=list)

    @property
    def num_series(self) -> int:
        return len(self.keys)

    def is_hist(self) -> bool:
        return self.hist_values is not None

    def absorb_degraded(self, *parts: "GridResult") -> "GridResult":
        """Fold children's partial flags/warnings into this result
        (returns self for chaining)."""
        for p in parts:
            if isinstance(p, GridResult):
                self.partial = self.partial or p.partial
                self.warnings.extend(w for w in p.warnings
                                     if w not in self.warnings)
        return self

    @staticmethod
    def empty(steps: np.ndarray) -> "GridResult":
        return GridResult(steps, [], np.zeros((0, steps.size)))


@dataclass
class ScalarResult:
    """scalar(...) / literal results: one value per step."""
    steps: np.ndarray
    values: np.ndarray  # f64 [T]


@dataclass
class QueryStats:
    """(core/query/QueryStats equivalent) threaded through execution."""
    series_scanned: int = 0
    samples_scanned: int = 0
    result_bytes: int = 0
    # partial-result notes surfaced in the Prometheus response's
    # `warnings` array (e.g. a shard still bootstrapping on its adopter)
    warnings: list = field(default_factory=list)
    # True when a shard group was dropped from this result (breaker
    # open / peer exhausted under allow_partial) — drives the response's
    # top-level "partial": true
    partial: bool = False

    def add(self, other: "QueryStats") -> None:
        self.series_scanned += other.series_scanned
        self.samples_scanned += other.samples_scanned
        self.result_bytes += other.result_bytes
        self.warnings.extend(other.warnings)
        self.partial = self.partial or other.partial


class QueryError(Exception):
    pass


class StaleRoutingError(QueryError):
    """A peer was asked for shards it no longer serves: the caller's
    routing table lags a planned shard handoff (topology epoch moved).

    Raised server-side by ``leaf_select``/the pushdown expect-shards
    check; the entry node catches it, applies the responder's ``owners``
    hint to its ShardMapper, invalidates plan/results caches, and
    re-materializes against fresh routing instead of returning the
    stale (silently incomplete) response to the client.

    ``__str__`` renders a machine-parseable sentinel so the error
    round-trips losslessly through BOTH peer planes (the JSON control
    plane's ``error`` string and the gRPC response's error field);
    :meth:`parse` recovers it on the caller."""

    PREFIX = "stale_routing:"

    def __init__(self, owners=None, epoch: int = 0, node: str = "",
                 detail: str = ""):
        # shard -> owning node, per the RESPONDER's mapper (it is the
        # former owner and witnessed the handoff)
        self.owners = {int(k): v for k, v in (owners or {}).items()}
        self.epoch = int(epoch)
        self.node = node
        self.detail = detail
        super().__init__(self._render())

    def _render(self) -> str:
        import json as _json
        return self.PREFIX + _json.dumps(
            {"owners": {str(k): v for k, v in self.owners.items()},
             "epoch": self.epoch, "node": self.node,
             "detail": self.detail}, sort_keys=True)

    def __str__(self) -> str:
        return self._render()

    @classmethod
    def parse(cls, s) -> "Optional[StaleRoutingError]":
        """Recover a StaleRoutingError from an error string carrying
        the sentinel (possibly wrapped, e.g. ``remote node n: ...``);
        None when the string is not one."""
        import json as _json
        if not isinstance(s, str):
            return None
        i = s.find(cls.PREFIX)
        if i < 0:
            return None
        try:
            d = _json.loads(s[i + len(cls.PREFIX):])
        except ValueError:
            return None
        return cls(owners=d.get("owners"), epoch=d.get("epoch", 0),
                   node=d.get("node", ""), detail=d.get("detail", ""))


class QueryLimitError(QueryError):
    """A per-query guardrail tripped (ExecPlan.scala:46 enforceLimits —
    the reference aborts plans exceeding sample/series budgets)."""


@dataclass(frozen=True)
class QueryLimits:
    """Per-query guardrails, enforced at series-selection time
    (core/query/QueryContext PlannerParams enforcedLimits). 0 = off."""
    series_limit: int = 0
    sample_limit: int = 0

    def refuses(self, series_scanned: int, samples_scanned: int) -> bool:
        """Would ``check`` raise at these counts."""
        return bool(
            self.series_limit and series_scanned > self.series_limit
            or self.sample_limit and samples_scanned > self.sample_limit)

    def check(self, stats: "QueryStats") -> None:
        if self.series_limit and stats.series_scanned > self.series_limit:
            raise QueryLimitError(
                f"query matched {stats.series_scanned} series, exceeding "
                f"the limit of {self.series_limit}")
        if self.sample_limit and stats.samples_scanned > self.sample_limit:
            raise QueryLimitError(
                f"query would scan more than {self.sample_limit} samples "
                f"(scanned {stats.samples_scanned} so far)")


@dataclass
class QueryWarnings:
    messages: List[str] = field(default_factory=list)
