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


# -- groupsum dispatcher VMEM budget (advisor, tilestore.py) ---------------

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


def _gs(tiles, G, func="delta", S=8):
    steps = np.arange(BASE + 400_000, BASE + 2_400_000, 60_000,
                      dtype=np.int64)
    return tst.groupsum_counters(tiles, func, steps, 300_000,
                                 np.arange(S) % G, G, interpret=True)


def test_groupsum_vmem_budget_rejects_wide_group_tables():
    tiles = _tiles()
    # G=1500 passes the old accumulator-only check (256*1500*8 ~ 3MB
    # < 4MB) but the DMA scratch + onehot block push the total past
    # VMEM: the dispatcher must fall back, not die in Mosaic
    assert _gs(tiles, 1500) is None
    # the same tiles with a small group table still dispatch
    assert _gs(tiles, 4) is not None


# -- fixed-point scale-exponent underflow (advisor, tilestore.py) ----------

def test_fixed_channels_refuse_unrepresentable_span():
    tiles = _tiles(span=1e60)                # needs s < -96: unencodable
    # before the fix the scale exponent was clipped to -96 and the
    # int64 rint silently wrapped; now the packer refuses and the
    # dispatcher takes the non-fused fallback
    assert tiles._fixed_channels("v") is None
    assert _gs(tiles, 4) is None


def test_fixed_channels_normal_span_still_packs():
    tiles = _tiles()
    assert tiles._fixed_channels("v") is not None
