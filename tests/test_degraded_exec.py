"""Exec-layer degraded-mode units + advisor-fix regressions:

* ConcatExec: child failure tolerated only under allow_partial (result
  flagged partial, warning names the lost child); mismatched histogram
  bucket schemes raise instead of silently mixing buckets; deadline
  checked between children.
* groupsum dispatcher: oversized [T,G]/scratch/onehot VMEM footprints
  fall back to the general path (None) instead of failing at Mosaic
  compile time.
* fixed-point packer: series whose value span cannot be represented at
  any in-range scale exponent return None (exact f64 fallback) instead
  of silently wrapping int64."""

import numpy as np
import pytest

from filodb_tpu.parallel.resilience import Deadline, DeadlineExceeded
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query.model import GridResult, QueryError, QueryStats
from filodb_tpu.query.planner import ConcatExec

BASE = 1_600_000_000_000
DT = 10_000
STEPS = np.arange(0, 600_000, 60_000, dtype=np.int64)


class _Child:
    def __init__(self, grid=None, exc=None):
        self.grid = grid
        self.exc = exc

    def execute(self):
        if self.exc is not None:
            raise self.exc
        return self.grid

    def plan_tree(self, indent=0):
        return " " * indent + "FakeChild"


def _grid(n=2, les=None, partial=False, warnings=()):
    hv = None
    if les is not None:
        hv = np.zeros((n, STEPS.size, len(les)))
    return GridResult(STEPS, [{"i": str(k)} for k in range(n)],
                      np.zeros((n, STEPS.size)),
                      hist_values=hv,
                      bucket_les=np.asarray(les, float)
                      if les is not None else None,
                      partial=partial, warnings=list(warnings))


# -- ConcatExec degraded mode ----------------------------------------------

def test_concat_failfast_by_default():
    ex = ConcatExec([_Child(_grid()), _Child(exc=QueryError("peer died"))],
                    QueryStats())
    with pytest.raises(QueryError):
        ex.execute()


def test_concat_allow_partial_drops_child_and_flags():
    stats = QueryStats()
    ex = ConcatExec([_Child(_grid(3)),
                     _Child(exc=QueryError("node1 unreachable"))],
                    stats, allow_partial=True)
    out = ex.execute()
    assert out.num_series == 3
    assert out.partial and stats.partial
    assert any("node1 unreachable" in w for w in out.warnings)


def test_concat_all_children_failed_still_errors():
    ex = ConcatExec([_Child(exc=QueryError("a")),
                     _Child(exc=QueryError("b"))],
                    QueryStats(), allow_partial=True)
    with pytest.raises(QueryError, match="all shard groups failed"):
        ex.execute()


def test_concat_propagates_child_partial_flags():
    out = ConcatExec([_Child(_grid(1, partial=True,
                                   warnings=["shard 3 recovering"])),
                      _Child(_grid(1))], QueryStats()).execute()
    assert out.partial
    assert "shard 3 recovering" in out.warnings


def test_concat_deadline_checked_between_children():
    t = [0.0]
    d = Deadline(1.0, clock=lambda: t[0])

    class _Slow(_Child):
        def execute(self):
            t[0] += 2.0                      # burns past the budget
            return _grid()

    ex = ConcatExec([_Slow(), _Child(_grid())], QueryStats(),
                    deadline=d)
    with pytest.raises(DeadlineExceeded):
        ex.execute()


# -- ConcatExec histogram bucket verification (advisor, planner.py) --------

def test_concat_hist_prefix_les_pads_to_max_width():
    out = ConcatExec([_Child(_grid(1, les=[1, 2, 5])),
                      _Child(_grid(1, les=[1, 2, 5, 10]))],
                     QueryStats()).execute()
    assert list(out.bucket_les) == [1, 2, 5, 10]
    assert out.hist_values.shape == (2, STEPS.size, 4)
    # the narrower child's missing bucket is NaN-padded, not zero-filled
    assert np.isnan(out.hist_values[0, :, 3]).all()


def test_concat_hist_mismatched_les_raises():
    ex = ConcatExec([_Child(_grid(1, les=[1, 2, 5])),
                     _Child(_grid(1, les=[1, 3, 5]))], QueryStats())
    with pytest.raises(QueryError, match="bucket schemes"):
        ex.execute()


# -- the fused group-sum gate (tilestore.py) --------------------------------

def _tiles(S=8, N=288, seed=7, span=None):
    rng = np.random.default_rng(seed)
    ts = (BASE + np.arange(N)[None, :] * DT
          + rng.uniform(-2000, 2000, (S, N)))
    if span is None:
        vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    else:
        vals = np.linspace(-span, span, N)[None, :] * np.ones((S, 1))
    return tst.AlignedTiles([{} for _ in range(S)], BASE, DT,
                            np.ones((S, N), bool), ts, vals)


STEPS = np.arange(BASE + 400_000, BASE + 2_400_000, 60_000, dtype=np.int64)


def _gs(tiles, G, func="delta", S=8):
    return tst.groupsum_counters(tiles, func, STEPS, 300_000,
                                 np.arange(S) % G, G)


def _grouped_in_float64(tiles, func, gid, G):
    """The per-series evaluator's rates, summed and counted by group in
    float64 here."""
    per = np.asarray(tst.evaluate_counters_t(tiles, func, STEPS, 300_000),
                     np.float64)
    ok = ~np.isnan(per)
    sums = np.stack([np.where(ok, per, 0.0)[:, gid == g].sum(axis=1)
                     for g in range(G)], 1)
    cnts = np.stack([ok[:, gid == g].sum(axis=1) for g in range(G)], 1)
    return sums, cnts


def test_groupsum_vmem_budget_rejects_wide_group_tables():
    """A group table of 1,500 is served by the one fused program, and
    equals the float64 grouping; most groups hold no series."""
    tiles = _tiles()
    G = 1500
    gid = (np.arange(8) * 211) % G
    res = tst.groupsum_counters(tiles, "delta", STEPS, 300_000, gid, G)
    assert res is not None
    sums, cnts = np.asarray(res[0]), np.asarray(res[1])
    assert sums.shape == (STEPS.size, G)
    want_s, want_c = _grouped_in_float64(tiles, "delta", gid, G)
    np.testing.assert_array_equal(cnts, want_c)
    np.testing.assert_allclose(sums, want_s, rtol=2e-6,
                               atol=2e-6 * np.abs(want_s).max())
    assert cnts.sum() == 8 * STEPS.size
    # the same tiles with a small group table dispatch too
    assert _gs(tiles, 4) is not None


def test_fixed_channels_refuse_unrepresentable_span():
    tiles = _tiles(span=1e60)
    # a rate of a span this wide, summed over the cohort, is past f32:
    # the gate refuses and the caller takes the exact host path
    assert not tiles.f32_safe("v")
    assert _gs(tiles, 4) is None


def test_fixed_channels_normal_span_still_packs():
    tiles = _tiles()
    assert tiles.f32_safe("v") and tiles.f32_safe("cv")
    assert _gs(tiles, 4) is not None
