"""Fused Pallas counter group-sum kernel (pallas_kernels.counter_groupsum
via tilestore.groupsum_counters): parity vs the per-series transposed
evaluator + numpy grouping on jittered huge-counter data with resets,
plus dispatcher fallbacks. Runs in interpret mode on the CPU test mesh;
the real-TPU compile is asked of the chip's compiler in
tests/test_tpu_compile.py and run on the chip by chip_smoke.py.

(Reference semantics: rangefn/RateFunctions.scala:23-79 extrapolated
rate; the grouping matches exec/AggrOverRangeVectors sum-by.)"""

import numpy as np
import pytest

from filodb_tpu.query import tilestore as tst

BASE = 1_600_000_000_000
DT = 10_000


def _tiles(S=100, N=288, huge=True, seed=7):
    rng = np.random.default_rng(seed)
    ts = (BASE + np.arange(N)[None, :] * DT
          + rng.uniform(-2000, 2000, (S, N)))
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    if huge:
        vals = 1e15 + vals
    vals[5 % S, N // 2:] *= 0.99          # counter reset
    return tst.AlignedTiles([{} for _ in range(S)], BASE, DT,
                            np.ones((S, N), bool), ts, vals)


@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_groupsum_matches_per_series_eval(func):
    S, G = 100, 5
    tiles = _tiles(S)
    steps = np.arange(BASE + 400_000, BASE + 2_400_000, 60_000,
                      dtype=np.int64)
    gid = np.arange(S) % G
    res = tst.groupsum_counters(tiles, func, steps, 300_000, gid, G,
                                interpret=True)
    assert res is not None
    sums, cnts = np.asarray(res[0]), np.asarray(res[1])
    per = np.asarray(tst.evaluate_counters_t(tiles, func, steps, 300_000))
    ok = ~np.isnan(per)
    want_s = np.stack([np.where(ok[:, gid == g], per[:, gid == g], 0)
                       .sum(axis=1) for g in range(G)], 1)
    want_c = np.stack([ok[:, gid == g].sum(axis=1)
                       for g in range(G)], 1).astype(np.float32)
    np.testing.assert_array_equal(cnts, want_c)
    np.testing.assert_allclose(sums, want_s, rtol=1e-5, atol=1e-7)


def _want(tiles, func, steps, window, gid, G):
    per = np.asarray(tst.evaluate_counters_t(tiles, func, steps, window))
    ok = ~np.isnan(per)
    want_s = np.stack([np.where(ok[:, gid == g], per[:, gid == g], 0)
                       .sum(axis=1) for g in range(G)], 1)
    want_c = np.stack([ok[:, gid == g].sum(axis=1)
                       for g in range(G)], 1).astype(np.float32)
    return want_s, want_c


@pytest.mark.parametrize("phase", [3000, -3000])
def test_groupsum_phase_elided_families(phase):
    """Grid phases that clear the tile's jitter compile the CUR/ALT
    static modes (no fallback-family stream); results must still match
    the per-series evaluator exactly."""
    S, G = 64, 4
    rng = np.random.default_rng(11)
    N = 288
    ts = (BASE + np.arange(N)[None, :] * DT
          + rng.uniform(-500, 500, (S, N)))          # small jitter
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1) + 1e12
    tiles = tst.AlignedTiles([{} for _ in range(S)], BASE, DT,
                             np.ones((S, N), bool), ts, vals)
    assert tiles.jitter_ms() <= 500
    steps = np.arange(BASE + 400_000 + phase, BASE + 2_400_000, 60_000,
                      dtype=np.int64)
    gid = np.arange(S) % G
    res = tst.groupsum_counters(tiles, "rate", steps, 300_000, gid, G,
                                interpret=True)
    assert res is not None
    want_s, want_c = _want(tiles, "rate", steps, 300_000, gid, G)
    np.testing.assert_array_equal(np.asarray(res[1]), want_c)
    np.testing.assert_allclose(np.asarray(res[0]), want_s,
                               rtol=1e-5, atol=1e-7)


def test_groupsum_st1_single_stream():
    """step == dt puts every boundary family inside the one merged
    residue plane (single DMA stream per tile)."""
    S, G = 48, 3
    tiles = _tiles(S, 400)
    steps = np.arange(BASE + 400_000, BASE + 2_000_000, 10_000,
                      dtype=np.int64)
    gid = np.arange(S) % G
    res = tst.groupsum_counters(tiles, "increase", steps, 300_000,
                                gid, G, interpret=True)
    assert res is not None
    want_s, want_c = _want(tiles, "increase", steps, 300_000, gid, G)
    np.testing.assert_array_equal(np.asarray(res[1]), want_c)
    np.testing.assert_allclose(np.asarray(res[0]), want_s,
                               rtol=1e-5, atol=1e-7)


def test_groupsum_dispatcher_fallbacks():
    tiles = _tiles(16, 288)
    gid, G = np.zeros(16, np.int64), 1
    # irregular step (not a slot multiple)
    steps = np.arange(BASE + 400_000, BASE + 1_000_000, 61_000,
                      dtype=np.int64)
    assert tst.groupsum_counters(tiles, "rate", steps, 300_000,
                                 gid, G, interpret=True) is None
    # grid past the tile end
    steps = np.arange(BASE + 400_000, BASE + 288 * DT + 600_000, 60_000,
                      dtype=np.int64)
    assert tst.groupsum_counters(tiles, "rate", steps, 300_000,
                                 gid, G, interpret=True) is None
    # gappy tiles
    rng = np.random.default_rng(3)
    valid = rng.random((16, 288)) > 0.2
    ts = BASE + np.arange(288)[None, :] * DT + np.zeros((16, 1))
    vals = np.cumsum(np.ones((16, 288)), axis=1)
    gappy = tst.AlignedTiles([{} for _ in range(16)], BASE, DT,
                             valid, ts, vals)
    steps = np.arange(BASE + 400_000, BASE + 1_000_000, 60_000,
                      dtype=np.int64)
    # ... are no fallback since PR 33: the gate's second program serves
    # them (tests/test_groupsum_dispatch.py part (e)), unless the grid is
    # wider than int32 ms from the tile base
    sums, cnts = tst.groupsum_counters(gappy, "rate", steps, 300_000,
                                       gid, G, interpret=True)
    per = np.asarray(tst.evaluate_counters_t(gappy, "rate", steps, 300_000))
    np.testing.assert_array_equal(np.asarray(cnts)[:, 0],
                                  (~np.isnan(per)).sum(axis=1))
    np.testing.assert_allclose(np.asarray(sums)[:, 0],
                               np.nansum(per.astype(np.float64), axis=1),
                               rtol=2e-6)
    wide = steps[0] + np.arange(3, dtype=np.int64) * 2 ** 30
    assert tst.groupsum_counters(gappy, "rate", wide, 300_000,
                                 gid, G, interpret=True) is None
    # window not a whole number of steps: merged kc/kl stream contract
    steps = np.arange(BASE + 400_000, BASE + 1_000_000, 60_000,
                      dtype=np.int64)
    assert tst.groupsum_counters(tiles, "rate", steps, 290_000,
                                 gid, G, interpret=True) is None
    # window/step beyond the merged-stream row cap
    steps = np.arange(BASE + 900_000, BASE + 2_000_000, 10_000,
                      dtype=np.int64)
    assert tst.groupsum_counters(tiles, "rate", steps, 600_000,
                                 gid, G, interpret=True) is None
    # non-finite values fall back to the exact f64 path
    bad = _tiles(16, 288)
    bad.vals = bad.vals.at[0, 5].set(np.inf) if hasattr(
        bad.vals, "at") else bad.vals
    import jax.numpy as jnp
    bad.vals = jnp.asarray(np.where(
        np.arange(288)[None, :] == 5, np.inf, np.asarray(bad.vals)))
    bad._channels.clear()
    bad._tch.clear()
    steps = np.arange(BASE + 400_000, BASE + 1_000_000, 60_000,
                      dtype=np.int64)
    assert tst.groupsum_counters(bad, "rate", steps, 300_000,
                                 gid, G, interpret=True) is None


# ---------------------------------------------------------------------------
# tile widening + DMA pipeline depth (PR 14: the deferred counter_groupsum
# DMA pipelining / tile widening)
# ---------------------------------------------------------------------------

def test_gs_pipeline_chooser_frontier():
    from filodb_tpu.query import pallas_kernels as pk

    # long range, single stream: the widened 512-step tile + the
    # triple-buffered DMA pipeline both fit
    tt, nbuf = pk._gs_pipeline(6, 5, pk.GS_CUR, pk.GS_CUR, 460, 16)
    assert tt == pk._GS_TT_WIDE and nbuf == pk._GS_NBUF_MAX
    # three streams: widening would blow the scratch budget — fall to
    # the 256 tile, and the deepest pipeline that still fits
    tt3, nbuf3 = pk._gs_pipeline(6, 5, pk.GS_BOTH, pk.GS_BOTH, 460, 16)
    assert tt3 == pk._GS_TT and nbuf3 >= 2
    # short ranges never widen (nothing to amortize)
    tt1, _ = pk._gs_pipeline(6, 5, pk.GS_CUR, pk.GS_CUR, 100, 16)
    assert tt1 == pk._GS_TT
    # an impossible footprint yields None (dispatcher falls back): a
    # giant group count makes even the smallest config exceed VMEM
    assert pk._gs_pipeline(6, 5, pk.GS_BOTH, pk.GS_BOTH, 30_000,
                           4096) is None


@pytest.mark.parametrize("nsteps", [300, 520])
def test_groupsum_wide_tile_parity(nsteps):
    """Step grids past 256 ride the widened 512-step tile (and the
    deeper DMA pipeline where it fits): parity vs the per-series
    evaluator must hold through the new tiling."""
    from filodb_tpu.query import pallas_kernels as pk

    S, G = 64, 4
    # enough slots that the wide grid stays interior
    tiles = _tiles(S, N=max(512, nsteps * 6 // 1 + 96), huge=False)
    steps = (BASE + 400_000
             + np.arange(nsteps, dtype=np.int64) * 60_000)
    gid = np.arange(S) % G
    assert pk._gs_pipeline(6, 5, pk.GS_BOTH, pk.GS_BOTH, nsteps,
                           G) is not None
    res = tst.groupsum_counters(tiles, "rate", steps, 300_000, gid, G,
                                interpret=True)
    assert res is not None
    sums, cnts = np.asarray(res[0]), np.asarray(res[1])
    assert sums.shape == (nsteps, G)
    want_s, want_c = _want(tiles, "rate", steps, 300_000, gid, G)
    np.testing.assert_array_equal(cnts, want_c)
    np.testing.assert_allclose(sums, want_s, rtol=1e-5, atol=1e-7)


def test_groupsum_widest_config_parity_interpret():
    """The (512-step tile, triple-buffered) config — reachable only in
    the phase-elided single-stream case — must run the full DMA
    pipeline correctly (interpret mode emulates the async copies)."""
    from filodb_tpu.query import pallas_kernels as pk

    S, N, G = 48, 2200, 4
    # ZERO jitter + on-slot grid phase: both fallback families elide
    # (GS_CUR/GS_CUR), leaving the single merged stream
    ts = (BASE + np.arange(N)[None, :] * DT) * np.ones((S, 1))
    vals = np.cumsum(np.random.default_rng(2).uniform(0, 5, (S, N)),
                     axis=1)
    tiles = tst.AlignedTiles([{} for _ in range(S)], BASE, DT,
                             np.ones((S, N), bool), ts, vals)
    assert tiles.jitter_ms() == 0.0
    T = 300
    steps = BASE + 400_000 + np.arange(T, dtype=np.int64) * 60_000
    assert pk._gs_pipeline(6, 5, pk.GS_CUR, pk.GS_CUR, T, G) \
        == (pk._GS_TT_WIDE, pk._GS_NBUF_MAX)
    gid = np.arange(S) % G
    res = tst.groupsum_counters(tiles, "rate", steps, 300_000, gid, G,
                                interpret=True)
    assert res is not None
    sums, cnts = np.asarray(res[0]), np.asarray(res[1])
    want_s, want_c = _want(tiles, "rate", steps, 300_000, gid, G)
    np.testing.assert_array_equal(cnts, want_c)
    np.testing.assert_allclose(sums, want_s, rtol=1e-5, atol=1e-7)
