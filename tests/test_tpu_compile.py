"""Ask the chip's compiler before the chip: the main path's device
programs compile for one *described* TPU v5e (no chip attached), from
shapes only, at the sizes ``chip_smoke.py`` uses (8,192 series x 720
samples, 16 groups).

These are compiles, never chip runs: they say nothing about results or
times. They catch what interpret mode cannot — a kernel Mosaic refuses,
a program that does not fit, a sharding that silently replicates, and
the f64 cumulative sum whose reduce-window form took the TPU compiler
minutes (query/cumsum.py).

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU library, and every xdist worker
imports every test file. Keep all such tests in THIS file.
"""

import functools
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from filodb_tpu.query import pallas_kernels as pk
from filodb_tpu.query import tilestore as tst
from filodb_tpu.query import tpu
from filodb_tpu.query.cumsum import cumsum_f64

S, N, G = 8192, 720, 16            # chip_smoke.py's default store
S_HOST = pk._GS_SS                 # series of the host-side stand-in tiles
BASE, DT = 1_600_000_000_000, 10_000
W, STEP, T = 300_000, 60_000, 100  # 5m windows, 1m steps, ~100 steps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "no compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # can never be read back without the chip: keep it off for this file
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _tiles(jitter_ms):
    """Stand-in tiles built here on the CPU, only to ask the dispatchers
    which arrays they pass: S_HOST series (one kernel lane tile) — the
    tests widen every series dimension to S before compiling."""
    rng = np.random.default_rng(11)
    ts = BASE + np.arange(N, dtype=np.float64)[None, :] * DT
    if jitter_ms:
        ts = ts + rng.integers(-jitter_ms, jitter_ms + 1, (S_HOST, N))
    else:
        ts = np.broadcast_to(ts, (S_HOST, N))
    vals = np.cumsum(rng.integers(0, 50, (S_HOST, N)).astype(np.float64),
                     axis=1)
    return tst.AlignedTiles([{"i": str(i)} for i in range(S_HOST)], BASE,
                            DT, np.ones((S_HOST, N), bool), ts, vals)


@pytest.fixture(scope="module")
def jittered():
    return _tiles(2000)


def _steps():
    return BASE + 600_000 + np.arange(T, dtype=np.int64) * STEP


def _shapes(tree, sharding):
    """Arrays/scalars -> ShapeDtypeStructs on the described device, with
    every S_HOST-sized (series) dimension widened to the real S."""
    def sds(a):
        a = np.asarray(a) if not hasattr(a, "shape") else a
        shape = tuple(S if d == S_HOST else d for d in a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=sharding)
    return jax.tree_util.tree_map(sds, tree)


def _compile(fn, *shapes):
    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, time.monotonic() - t0


# -- (b) the f64 cumulative sum, first of all ---------------------------------

@pytest.mark.parametrize("shape,axis", [((S, N), 1), ((64, S), 0),
                                        ((4096, 4096), 1)])
def test_cumsum_f64_compiles_in_seconds(one_chip, shape, axis):
    x = jax.ShapeDtypeStruct(shape, jnp.float64, sharding=one_chip)
    compiled, secs = _compile(lambda v: cumsum_f64(v, axis), x)
    # ~1 s idle; the reduce-window form took 230 s at [8, 128]
    assert secs < 120, f"f64 cumulative sum took {secs:.0f}s to compile"
    # still f64, and no reduce-window crept back in
    assert jax.eval_shape(lambda v: cumsum_f64(v, axis), x).dtype == jnp.float64
    assert "reduce-window" not in compiled.as_text()


def test_cumsum_f64_is_sequential_f64():
    """Left-to-right f64 adds: bit-for-bit np.cumsum on any backend."""
    x = np.random.default_rng(0).normal(0, 1e9, (37, 721))
    for axis in (0, 1):
        got = np.asarray(jax.jit(functools.partial(cumsum_f64, axis=axis))(x))
        assert got.dtype == np.float64
        np.testing.assert_array_equal(got, np.cumsum(x, axis=axis))


def test_tile_build_corrected_channel_compiles(one_chip):
    v = jax.ShapeDtypeStruct((S, N), jnp.float64, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((S, N), jnp.bool_, sharding=one_chip)
    _, secs = _compile(tst._counter_corrected, v, valid, v)
    assert secs < 120


@pytest.mark.parametrize("func", ["rate", "delta"])
def test_packed_endpoint_rate_compiles(one_chip, func):
    """The irregular-cadence rate family as every backend serves it: the
    packed endpoint evaluator (histogram bounds, counter correction by
    the f64 scan, takes, the f64 extrapolation) as ONE program, at a
    whole packed block with a power-of-two step bucket."""
    s, n, t = 4096, 4096, 512
    sh = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i64 = sh((), jnp.int64)
    lowered = tpu._window_endpoint.lower(
        func, sh((s, n), jnp.int64), sh((s, n), jnp.float64),
        sh((s,), jnp.int32), i64, i64, i64, t, sh((), jnp.float64))
    assert (lowered.out_info.shape, lowered.out_info.dtype) \
        == ((s, t), jnp.float64)
    # no wall-clock assertion: the suite shares its cores
    compiled = lowered.compile()
    # the window bounds' int32 histogram sum may be a reduce-window; an
    # f64 one is the form that took the TPU compiler minutes
    assert not re.search(r"= f64\[[^\n]*reduce-window\(", compiled.as_text())


# -- (a) the fused group-sum program, as the dispatcher jits it ---------------

@pytest.mark.parametrize("jitter_ms,nstreams", [(0, 1), (2000, 3)])
def test_counter_groupsum_compiles(one_chip, monkeypatch, jittered,
                                   jitter_ms, nstreams):
    """What a fused query runs on the chip: the dispatcher's ONE jitted
    program (one-hot from the group ids, the Pallas kernel, the slices),
    built by the dispatcher's own ``build`` with its own statics."""
    tiles = jittered if jitter_ms else _tiles(0)
    seen = {}

    def capture(cache, key, build, site="tilestore", cost_args=None):
        seen.update(key=key, build=build, args=cost_args, site=site)
        return lambda *a: None
    monkeypatch.setattr(tst, "_jit_lookup", capture)
    assert tst.groupsum_counters(tiles, "rate", _steps(), W,
                                 np.arange(S_HOST) % G, G) is None
    monkeypatch.undo()
    assert seen["site"] == "groupsum"
    _, func, st, dspan, hi, lo = seen["key"][:6]
    assert pk._gs_nstreams(st, hi, lo) == nstreams
    v_p, base, params, ids = seen["args"]
    assert (params.dtype, params.shape) == (np.int32, (5,))
    assert (ids.dtype, ids.shape) == (np.int32, (S_HOST,))
    # the kernel's series dimension is its grid: n_s lane tiles of _GS_SS
    n_s = S // pk._GS_SS
    assert v_p.shape[0] == base.shape[0] == 1
    sh = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = seen["build"]().lower(
        sh((n_s,) + v_p.shape[1:], v_p.dtype),
        sh((n_s,) + base.shape[1:], base.dtype),
        sh((5,), jnp.int32), sh((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the five scalars arrive as one vector: no program assembles them
    assert "concatenate" not in text


# -- (c) the per-series aligned evaluators, as tilestore jits them ------------

def _grid_args():
    steps = _steps()
    w0e = int(steps[0])
    return (np.int64(N), np.int64(BASE), np.int64(DT), np.int64(w0e - W),
            np.int64(w0e), np.int64(STEP))


def test_eval_counter_fast_compiles(one_chip, jittered):
    arrs = tst._tiles_arrays_fast(jittered, "rate")
    fn = functools.partial(tst._eval_counter_fast, "rate", T)
    _compile(fn, *_shapes((arrs,) + _grid_args(), one_chip))


@pytest.fixture(scope="module")
def holed():
    """The jittered tiles with every fourth series missing scrapes: not
    dense, so the evaluator takes the filled and prefix-count channels."""
    t = _tiles(2000)
    valid = np.ones((S_HOST, N), bool)
    valid[3::4, 100:104] = False
    valid[3::4, 300] = False
    return tst.AlignedTiles(t.keys, BASE, DT, valid, np.asarray(t.ts),
                            np.asarray(t.vals))


@pytest.mark.parametrize("width", [None, 2, 8])
def test_eval_counter_fast_over_holes_compiles(one_chip, holed, width):
    """What serves a per-series ``rate`` whose selection has a missed
    scrape (and, until PR 33, its ``sum by``): the f32-hybrid evaluator
    over non-dense channels, alone and vmapped over the grid scalars at
    the batcher's two first widths."""
    arrs = tst._tiles_arrays_fast(holed, "rate")
    assert "ps_ones" in arrs
    fn = functools.partial(tst._eval_counter_fast, "rate", T)
    args = (arrs,) + _grid_args()
    if width is not None:
        fn = jax.vmap(fn, in_axes=tst._GRID_AXES)
        args = tuple(np.full(width, a) if ax == 0 else a
                     for a, ax in zip(args, tst._GRID_AXES))
    _compile(fn, *_shapes(args, one_chip))


@pytest.mark.parametrize("func", ["rate", "delta"])
def test_groupsum_over_holes_compiles(one_chip, monkeypatch, func):
    """What serves a ``sum by`` whose selection has a missed scrape: the
    dispatcher's ONE jitted program (the non-dense evaluator, the one-hot
    from the group ids, two f32 matmuls at HIGHEST), built by the
    dispatcher's own ``build``, at the benchmark's third cell: 2,048
    series x 728 slots, 16 groups, 31 steps."""
    s_cell, n_cell, t_cell = 2048, 728, 31
    rng = np.random.default_rng(12)
    ts = (BASE + np.arange(n_cell, dtype=np.float64)[None, :] * DT
          + rng.integers(-2000, 2001, (S_HOST, n_cell)))
    vals = np.cumsum(rng.integers(0, 50, (S_HOST, n_cell)).astype(
        np.float64), axis=1)
    valid = np.ones((S_HOST, n_cell), bool)
    valid[3::4, 100:104] = False
    valid[3::4, 300] = False
    tiles = tst.AlignedTiles([{} for _ in range(S_HOST)], BASE, DT, valid,
                             ts, vals)
    seen = {}

    def capture(cache, key, build, site="tilestore", cost_args=None):
        seen.update(key=key, build=build, args=cost_args, site=site)
        return lambda *a: None
    monkeypatch.setattr(tst, "_jit_lookup", capture)
    steps = BASE + 600_000 + np.arange(t_cell, dtype=np.int64) * STEP
    assert tst.groupsum_counters(tiles, func, steps, W,
                                 np.arange(S_HOST) % G, G) is None
    monkeypatch.undo()
    assert seen["site"] == "groupsum"
    assert seen["key"] == ("groupsum", "holes", func, t_cell, G,
                           (n_cell, S_HOST))
    arrs, consts, grid, ids = seen["args"]
    assert (consts.dtype, consts.shape) == (np.int64, (3,))
    assert type(grid) is np.ndarray
    assert (grid.dtype, grid.shape) == (np.int64, (3,))
    assert (ids.dtype, ids.shape) == (np.int32, (S_HOST,))

    def sds(a):
        shape = tuple(s_cell if d == S_HOST else d for d in a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)
    compiled = seen["build"]().lower(
        *jax.tree_util.tree_map(sds, (arrs, consts, grid, ids))).compile()
    text = compiled.as_text()
    # no Pallas kernel in this program, and what leaves it is [T, G] f32
    assert "tpu_custom_call" not in text
    assert re.search(r"ENTRY[^\n]*->\s*\(f32\[31,16\][^\n]*f32\[31,16\]", text)


@pytest.mark.parametrize("dense", [True, False])
def test_hist_quantile_program_compiles(one_chip, monkeypatch, dense):
    """What serves ``histogram_quantile(q, sum by (job) (rate(h[5m])))``
    over native histograms: the dispatcher's ONE jitted program (the
    evaluator with a bucket axis, masked f64 group sums, the quantile),
    built by the dispatcher's own ``build``, at the histogram cell's size:
    256 series x 728 slots x 12 buckets, 16 groups, 31 steps. No f64
    matmul (the compiler spells one as loops) and only [T, G] f64 leaves
    it."""
    s_cell, n_cell, t_cell, b = 256, 728, 31, 12
    rng = np.random.default_rng(13)
    ts = (BASE + np.arange(n_cell, dtype=np.float64)[None, :] * DT
          + rng.integers(-2000, 2001, (S_HOST, n_cell)))
    vals = np.cumsum(rng.poisson(3.0, (S_HOST, n_cell, b)), axis=1).astype(
        np.float64)
    valid = np.ones((S_HOST, n_cell), bool)
    if not dense:
        valid[3::4, 100:104] = False
    les = tuple(float(2 ** i) for i in range(b - 1)) + (float("inf"),)
    tiles = tst.HistTiles([{} for _ in range(S_HOST)], BASE, DT, valid, ts,
                          vals, np.zeros_like(vals), les)
    seen = {}

    def capture(cache, key, build, site="tilestore", cost_args=None):
        seen.update(key=key, build=build, args=cost_args, site=site)
        return lambda *a: None
    monkeypatch.setattr(tst, "_jit_lookup", capture)
    steps = BASE + 600_000 + np.arange(t_cell, dtype=np.int64) * STEP
    assert tst.hist_quantile_groupsum(tiles, "rate", steps, W,
                                      np.arange(S_HOST) % G, G,
                                      0.99) is None
    monkeypatch.undo()
    assert seen["site"] == "groupsum"
    assert seen["key"] == ("groupsum", "hist", "rate", t_cell, G,
                           (n_cell, b * S_HOST), not dense)

    def sds(a):
        a = np.asarray(a) if not hasattr(a, "shape") else a
        wide = {S_HOST: s_cell, b * S_HOST: b * s_cell}
        shape = tuple(wide.get(d, d) for d in a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)
    compiled = seen["build"]().lower(
        *jax.tree_util.tree_map(sds, seen["args"])).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and " while(" not in text
    # the channels are taken by rows where they lie: no copy of a whole
    # [slots, buckets x series] channel into another layout
    assert not re.search(r"copy\(f64\[%d,%d\]" % (n_cell, b * s_cell), text)
    assert re.search(r"ENTRY[^\n]*->\s*f64\[31,16\]", text)


def test_eval_counter_slide_compiles(one_chip, jittered):
    st = STEP // DT
    arrs = tst._tiles_arrays_slide(jittered, "rate", st)
    fn = functools.partial(tst._eval_counter_slide, "rate", T, st)
    _compile(fn, *_shapes((arrs,) + _grid_args(), one_chip))


def test_eval_core_gauge_family_compiles(one_chip, jittered):
    """avg_over_time: the aligned gauge family (f64 prefix sums)."""
    arrs = tst._tiles_arrays(jittered, "avg_over_time")
    fn = functools.partial(tst._eval_core, "avg_over_time", T)
    _compile(fn, *_shapes((arrs,) + _grid_args(), one_chip))


def test_packed_gather_max_over_time_compiles(one_chip):
    """max_over_time is an order statistic: it rides the packed gather
    kernel, not the aligned tiles — at the smoke's <= 64-series subset."""
    s, n, t_bucket, w_bound = 64, 1024, 128, 32
    sh = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i64 = sh((), jnp.int64)
    tpu._window_gather.lower(
        "max_over_time", w_bound, sh((s, n), jnp.int64),
        sh((s, n), jnp.float64), sh((s,), jnp.int32), i64, i64, i64,
        t_bucket, 0.0).compile()


# -- (d) the path across chips: sharded, with a collective --------------------

def test_grouped_pair_on_four_chips_is_sharded_with_collective(topo):
    from filodb_tpu.parallel.shardstore import _build_grouped_pair_eval
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("shard", "time"))
    cap = 1024                                   # pow2 slot capacity
    col = NamedSharding(mesh, P(None, "shard"))
    row = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    i64x3 = jax.ShapeDtypeStruct((3,), jnp.int64, sharding=rep)
    args = (jax.ShapeDtypeStruct((cap, S), jnp.int32, sharding=col),
            jax.ShapeDtypeStruct((cap, S), jnp.float64, sharding=col),
            jax.ShapeDtypeStruct((S,), jnp.int32, sharding=row),
            i64x3, i64x3)
    compiled = _build_grouped_pair_eval(mesh, "rate", T, G).lower(
        *args).compile()
    # the psum over the shard axis: an all-reduce, which this compiler
    # spells for emulated f64 as an async all-gather + local adds
    text = compiled.as_text()
    assert any(c in text for c in ("all-reduce", "all-gather")), \
        "no cross-chip collective in the grouped program"
    # an f64 dot is spelled as loops over bf16 pieces, four fifths of the
    # device ops a request leaves in a trace (PERF.md section 6, PR 37)
    assert " while(" not in text, "an emulated f64 dot is back"
    whole = cap * S * (4 + 8) + S * 4
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 * whole < per_device < 0.3 * whole, (per_device, whole)
