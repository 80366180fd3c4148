"""Ask the chip's compiler before the chip: the main path's device
programs compile for one *described* TPU v5e (no chip attached), from
shapes only, at the sizes ``chip_smoke.py`` uses (8,192 series x 720
samples, 16 groups).

These are compiles, never chip runs: they say nothing about results or
times. They catch what a CPU run cannot — a program the chip's compiler
refuses, a program that does not fit, a sharding that silently replicates, and
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

from filodb_tpu.query import tilestore as tst
from filodb_tpu.query import tpu
from filodb_tpu.query.cumsum import cumsum_f64
from filodb_tpu.query.model import RawSeries

S, N, G = 8192, 720, 16            # chip_smoke.py's default store
S_HOST = 512                       # series of the host-side stand-in tiles
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


def _tiles():
    """Stand-in tiles built here on the CPU, only to ask the dispatchers
    which arrays they pass: S_HOST series, each +-2 s off the tick — the
    tests widen every series dimension to S before compiling."""
    rng = np.random.default_rng(11)
    ts = (BASE + np.arange(N, dtype=np.float64)[None, :] * DT
          + rng.integers(-2000, 2001, (S_HOST, N)))
    vals = np.cumsum(rng.integers(0, 50, (S_HOST, N)).astype(np.float64),
                     axis=1)
    return tst.AlignedTiles([{"i": str(i)} for i in range(S_HOST)], BASE,
                            DT, np.ones((S_HOST, N), bool), ts, vals)


@pytest.fixture(scope="module")
def jittered():
    return _tiles()


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

@pytest.mark.parametrize("dense", [True, False], ids=["dense", "holes"])
@pytest.mark.parametrize("func", ["rate", "delta"])
def test_groupsum_over_holes_compiles(one_chip, monkeypatch, func, dense):
    """What serves a ``sum by`` of a counter rate: the dispatcher's ONE
    jitted program (the f32-hybrid evaluator, the one-hot from the group
    ids, two f32 matmuls at HIGHEST), built by the dispatcher's own
    ``build``, at the benchmark's first and third cells: 2,048 series x
    728 slots, 16 groups, 31 steps, over dense tiles (two channels) and
    over tiles with missed scrapes (seven)."""
    s_cell, n_cell, t_cell = 2048, 728, 31
    rng = np.random.default_rng(12)
    ts = (BASE + np.arange(n_cell, dtype=np.float64)[None, :] * DT
          + rng.integers(-2000, 2001, (S_HOST, n_cell)))
    vals = np.cumsum(rng.integers(0, 50, (S_HOST, n_cell)).astype(
        np.float64), axis=1)
    valid = np.ones((S_HOST, n_cell), bool)
    if not dense:
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
    assert seen["key"] == ("groupsum", func, t_cell, G, (n_cell, S_HOST),
                           dense)
    arrs, consts, grid, ids = seen["args"]
    assert len(arrs) == (2 if dense else 7)
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
    # no Pallas kernel in this program, and what leaves it is ONE f32
    # [2, T, G]: the sums and the counts stacked, one transfer
    assert "tpu_custom_call" not in text
    assert re.search(r"ENTRY[^\n]*->\s*f32\[2,31,16\]\s*\{", text)


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
    t = _tiles()
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


@pytest.mark.parametrize("s_bucket", [8, 16])
def test_packed_launch_takes_two_arrays(one_chip, s_bucket):
    """The packed launch at ``tsbs-devops.host-dashboards``' shapes: 390
    samples at 10 s padded to N 512, 13 steps to a bucket of 16, the
    window bound of a 300 s window over 10 s samples, one or two members'
    rows. ONE int64 and ONE f64 block go in, ONE f64 grid comes out. The
    blocks are sliced on the device into the kernel's seven arguments:
    61 device ops a launch where the separate-argument batch took 56 (and
    a lone member, with scalar grids, 51); at most the seven slices more."""
    n, t_bucket = 512, 16
    ts = np.arange(390, dtype=np.int64) * 10_000
    w_bound = tpu.TpuBackend._window_sample_bound(
        [RawSeries({}, ts, np.zeros(390))], W, n)
    assert w_bound == 32
    sh = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = tpu._packed_window.lower(
        "max_over_time", w_bound, t_bucket,
        sh((s_bucket, n + 4), jnp.int64),
        sh((s_bucket, n + 1), jnp.float64)).compile().as_text()
    entry = re.search(r"\nENTRY ([^\n]*)", text).group(1)
    assert re.search(rf"\(ib[\w.]*: s64\[{s_bucket},516\], "
                     rf"fb[\w.]*: f64\[{s_bucket},513\]\) -> "
                     rf"f64\[{s_bucket},16\]", entry), entry
    vec = sh((s_bucket,), jnp.int64)
    separate = tpu._window_gather.lower(
        "max_over_time", w_bound, sh((s_bucket, n), jnp.int64),
        sh((s_bucket, n), jnp.float64), sh((s_bucket,), jnp.int32),
        vec, vec, vec, t_bucket, sh((), jnp.float64)).compile().as_text()
    assert _device_ops(text) <= _device_ops(separate) + 7
    assert _device_ops(text) <= 61


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
    # sums and counts stacked before ONE psum: one output, one collective
    # (two all-gathers in this compiler's spelling of an f64 psum, four
    # with a psum each), 55 device ops a launch here where the program
    # with two outputs made 59 (60 and 54 at cell 4's 24,576 series and
    # 31 steps)
    assert re.search(r"ENTRY[^\n]*->\s*f64\[2,100,16\]\s*\{", text)
    assert text.count(" all-gather(") + text.count(" all-reduce(") <= 2
    assert _device_ops(text) <= 55
    whole = cap * S * (4 + 8) + S * 4
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 * whole < per_device < 0.3 * whole, (per_device, whole)


# what a launch's trace records: one event a device op of the entry
# computation, so ``_device_ops`` counts those and skips what runs no op
_NO_OP = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
          "after-all", "partition-id", "replica-id"}
_OP_ALWAYS = {"fusion", "custom-call", "copy", "copy-start", "copy-done",
              "reduce", "reshape", "transpose", "gather", "slice",
              "concatenate", "pad", "broadcast", "dynamic-slice"}


def _device_ops(text: str) -> int:
    """Device ops a launch of the compiled program runs (an op of one
    element that is neither a fusion nor a copy nor a collective is folded
    into the scalar unit's work and left out)."""
    entry = re.search(r"\nENTRY [^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    n = 0
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(",
                     line)
        if m is None or m.group(2) in _NO_OP:
            continue
        dims = re.findall(r"\w+\[([\d,]*)\]", m.group(1))
        elems = max((int(np.prod([int(x) for x in d.split(",") if x]))
                     for d in dims), default=1)
        if m.group(2) in _OP_ALWAYS or m.group(2).startswith("all-") \
                or elems > 1:
            n += 1
    return n


@pytest.mark.parametrize("parts,ops_at_most", [(1, 45), (2, 53), (3, 61)])
def test_hist_quantile_on_four_chips_is_sharded_with_collective(
        topo, parts, ops_at_most):
    """The mesh store's bucket sums over a 128-shard node's histogram
    fleet: 6,144 series x 12 buckets (16 with the pad), 1,024 padded slots,
    16 groups, 31 steps, each chip a quarter of the channels, each channel
    in one f32 part (integer counts below 2**24), two or three; the
    [T, G, B] partials go
    through a collective, no f64 matmul comes back, and only [T, G, B] f64
    leaves it. A traced run records every device op of every launch on
    every chip, and the harness reads the trace in a bounded time: the
    ops a launch are held where they are."""
    from filodb_tpu.parallel.shardstore import _build_hist_quantile_eval
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("shard", "time"))
    s_cell, b, bp, cap, t_cell = 6144, 12, 16, 1024, 31
    sds = jax.ShapeDtypeStruct
    part = sds((cap, bp, s_cell), jnp.float32,
               sharding=NamedSharding(mesh, P(None, None, "shard")))
    args = (sds((cap, s_cell), jnp.int32,
                sharding=NamedSharding(mesh, P(None, "shard"))),
            (part,) * parts, (part,) * parts,
            sds((s_cell,), jnp.int32, sharding=NamedSharding(mesh,
                                                             P("shard"))),
            sds((8 * t_cell + 3,), jnp.int32,
                sharding=NamedSharding(mesh, P("time"))))
    compiled = _build_hist_quantile_eval(mesh, "rate", t_cell, G, b).lower(
        *args).compile()
    text = compiled.as_text()
    assert any(c in text for c in ("all-reduce", "all-gather")), \
        "no cross-chip collective in the histogram program"
    assert "tpu_custom_call" not in text and " while(" not in text
    assert re.search(r"ENTRY[^\n]*->\s*f64\[31,16,12\]", text)
    assert _device_ops(text) <= ops_at_most
    whole = cap * s_cell * (4 + 2 * parts * 4 * bp) + s_cell * 4
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 * whole < per_device < 0.3 * whole, (per_device, whole)
