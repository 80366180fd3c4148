"""Device-resident sharded tile serving: the multi-chip query hot path.

The scatter-gather mesh executor (parallel/mesh.py) re-packs and
re-ships every query's series to the devices — fine for a dry run,
hopeless as a serving path (the pack dominates at production shapes).
This module makes the SHARDED tile store the thing queries dispatch
from: the aligned tile store's slot-major channels
(query/tilestore.py AlignedTiles) are placed ONCE across the
('shard', 'time') mesh — series ride the shard axis, each device holds
its S/n_shard slice of every [N, S] channel resident in HBM — and the
slot-major counter evaluator plus the grid-batched evaluator families
lower through ``shard_map``:

  * per-series windowed evaluation (``_eval_counter_fast`` /
    ``_eval_core`` — the SAME traceable bodies the single-device
    dispatch compiles, so member (t, s) of the sharded output is
    bit-for-bit the single-device value): output step-grid slices ride
    the time axis, series slices the shard axis;
  * grouped aggregation keeps the one-hot [S, G] matmul + ``psum``
    collective of the scatter-gather path (mesh._grouped_reduce) but
    feeds it from the resident tiles; the fused pair sums by group with
    masked f64 sums (an f64 matmul is emulated on the chip);
  * PartitionSpecs name the mesh's axes through ``mesh.axis_names``
    (first axis = series shards, second = output steps):
    ``P(None, s_axis)`` = replicated slots x sharded series,
    ``P(t_axis, s_axis)`` = steps x series — the evaluator code never
    hard-codes an axis name, so it runs unchanged on any user mesh;
  * cross-flush tile refreshes are ZERO-COPY in HBM: the slot channels
    are capacity-padded and a flush appends its new slot columns via a
    ``donate_argnums`` jit (``_append_step``) — the donated buffers are
    reused in place, no re-placement, no second copy of a multi-GB
    store during rebuild;
  * a histogram cohort (query/tilestore.py HistTiles) has a placement
    of its own (``ShardedHistTiles``): the corrected buckets and the
    correction, each device holding every bucket of its own series,
    serve ``histogram_quantile(q, sum by (g) (rate|increase(h[w])))``
    with ONE program (``_build_hist_quantile_eval``): the one-chip
    program's evaluator and partial sums on every device and a ``psum``
    of the [T, G, B] bucket sums and counts; the host then takes the
    quantile of the summed buckets, which is not linear and so comes
    after the psum.

Escape hatches: tiles must be dense (every slot valid) with the tile
span in int32 ms — exactly the fast-family eligibility of the
single-device dispatcher — and a query whose grid leaves the int32
range (or whose tiles never qualified) falls back to the single-device
tilestore path unchanged. A histogram placement has no donated append:
a flush drops it (an eviction) and the next request places the new
tiles.
"""

from __future__ import annotations

import functools
import threading
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from filodb_tpu.lint.caches import cache_registry
from filodb_tpu.lint.capacity import (capacity, drop_resident,
                                      ensure_residency_collector,
                                      record_resident)
from filodb_tpu.lint.contracts import kernel_contract
from filodb_tpu.lint.locks import guarded_by
from filodb_tpu.lint.numerics import order_insensitive, precision
from filodb_tpu.obs import trace as obs_trace
from filodb_tpu.parallel.mesh import _grouped_reduce, make_mesh
from filodb_tpu.query.cumsum import cumsum_f64
from filodb_tpu.query.model import PerGrouping

# cache inventory (graftlint): the sharded-evaluator dispatch table
# memoizes compiled shard_map programs keyed purely on (kernel family,
# func, step shape, mesh shape) — a pure function of the request shape
# and device topology, immune to every world event by construction
__cache_registry__ = {
    "shardstore-executables": {"keyed": ("kernel", "func", "shape-bucket",
                                         "mesh-shape")},
}

_SHARD_EVAL_JIT: Dict[Tuple, object] = {}


def _jit_lookup(key: Tuple, build, cost_args=None):
    """Dispatch-table lookup through the tilestore's profiled builder:
    miss-side builds compile AOT with XLA cost_analysis capture
    (obs/devprof.py), so every sharded executable shows up in
    filodb_executable_* and &explain=analyze keyed by (kernel,
    device-count)."""
    from filodb_tpu.query import tilestore as tst
    return tst._jit_lookup(_SHARD_EVAL_JIT, key, build,
                           site="mesh-tiles", cost_args=cost_args)


# ---------------------------------------------------------------------------
# Donated refresh step
# ---------------------------------------------------------------------------

@precision(
    "append-carry-exact", bits=53, rel_ulps=0,
    reason="the donated append extends the counter-corrected channel "
           "in exact f64: absent counter resets in the appended block "
           "the carry and cumsum terms are all zero, so the refreshed "
           "channel is BITWISE the from-scratch rebuild (certified); "
           "with resets the carry value itself is still exact, only "
           "the add order differs from a rebuild")
@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _append_step(tsr, v, cv, new_tsr, new_v, n_filled):
    """Zero-copy slot append: write a flush's new slot columns into the
    capacity-padded channels IN PLACE (the donated buffers are reused
    by XLA — no second copy of the store in HBM during a refresh).

    The counter-corrected channel extends exactly like a full rebuild:
    the correction carry at the append point is read off the resident
    buffers (``cv[n-1] - v[n-1]``), the previous-sample chain starts at
    the last resident row, and drops accumulate through the appended
    block — so rate/increase over the refreshed store match a
    from-scratch rebuild (bit-for-bit when the appended span carries no
    counter resets; the carry is the same value either way)."""
    prev0 = jax.lax.dynamic_slice_in_dim(v, n_filled - 1, 1, axis=0)
    corr0 = jax.lax.dynamic_slice_in_dim(cv, n_filled - 1, 1, axis=0) - prev0
    prevs = jnp.concatenate([prev0, new_v[:-1]], axis=0)
    drop = new_v < prevs
    new_cv = new_v + cumsum_f64(jnp.where(drop, prevs, 0.0), axis=0) + corr0
    tsr = jax.lax.dynamic_update_slice_in_dim(tsr, new_tsr, n_filled, axis=0)
    v = jax.lax.dynamic_update_slice_in_dim(v, new_v, n_filled, axis=0)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, new_cv, n_filled, axis=0)
    return tsr, v, cv


# ---------------------------------------------------------------------------
# Sharded evaluator programs (compiled per (func, grid shape, mesh shape))
# ---------------------------------------------------------------------------

def _scalars(consts, grid):
    """What every program of the store takes besides its channels, as the
    evaluator's six arguments: the placement's ``consts`` int64[3]
    (n_filled, base_ms, dt_ms; on the devices, replicated, since the
    placement was built or last appended to) and the request's ``grid``,
    the one host array a call hands over: int64[3] (w0s, w0e, step), or
    int64[3, B] for a batch (a row of w0s, one of w0e, the step in every
    column of the third)."""
    n, base, dt = consts[0], consts[1], consts[2]
    if grid.ndim == 2:
        return n, base, dt, grid[0], grid[1], grid[2, 0]
    return n, base, dt, grid[0], grid[1], grid[2]


def _sharded_counter_check():
    """Abstract check under a minimal 1x1 ('shard','time') mesh: the
    shard_map body traces on CPU, nothing executes."""
    from filodb_tpu.query.tilestore import _eval_counter_fast  # noqa: F401
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("shard", "time"))
    fn = _build_counter_eval(mesh, "rate", 16, batch=0)
    out = jax.eval_shape(
        fn, jax.ShapeDtypeStruct((64, 8), jnp.int32),
        jax.ShapeDtypeStruct((64, 8), jnp.float64),
        np.array([64, 0, 10_000], np.int64),
        np.array([100_000, 400_000, 60_000], np.int64))
    if tuple(out.shape) != (16, 8) or str(out.dtype) != "float32":
        return f"sharded counter eval {out.shape}/{out.dtype} != (16,8) f32"
    return None


@kernel_contract(
    "sharded_counter_eval", kind="shard_map",
    check=_sharded_counter_check,
    rel_time_bits=31, span_guard="ShardedTiles.query_fits",
    notes="slot-major counter fast path lowered over the ('shard','time')"
          " mesh from device-resident sharded tiles; PartitionSpecs "
          "name mesh.axis_names, per-device step-grid slices via axis_index; "
          "bit-for-bit the single-device _eval_counter_fast values")
def _build_counter_eval(mesh: Mesh, func: str, nsteps_local: int,
                        batch: int):
    """One jitted sharded program: [N, S] resident channels, the
    placement's constants and the request's grid (``_scalars``) ->
    [T, S] (batch == 0) or [B, T, S] (batch == B) windowed counter
    grids. ``batch`` members vmap over the grid scalars exactly like
    the single-device evaluate_counters_t_batch family."""
    from filodb_tpu.query.tilestore import _eval_counter_fast

    s_axis, t_axis = mesh.axis_names[:2]

    def counter_body(tsr, vv, consts, grid):
        n, base, dt, w0s, w0e, step = _scalars(consts, grid)
        # this device's slice of the output step grid rides the time
        # axis (sequence parallel): offset the window scalars
        t_off = (jax.lax.axis_index(t_axis).astype(jnp.int64)
                 * nsteps_local * step)
        arrs = {"tsr": tsr, "ff_v": vv}
        ev = functools.partial(_eval_counter_fast, func, nsteps_local,
                               arrs, n, base, dt)
        if batch:
            return jax.vmap(lambda a, b: ev(a + t_off, b + t_off,
                                            step))(w0s, w0e)
        return ev(w0s + t_off, w0e + t_off, step)

    @jax.jit
    def run(tsr, vv, consts, grid):
        inner = jax.shard_map(
            counter_body, mesh=mesh,
            in_specs=(P(None, s_axis), P(None, s_axis), P(), P()),
            out_specs=(P(None, t_axis, s_axis) if batch
                       else P(t_axis, s_axis)))
        return inner(tsr, vv, consts, grid)
    return run


def _build_aligned_eval(mesh: Mesh, func: str, nsteps_local: int,
                        batch: int, arr_keys: Tuple[Tuple[str, int], ...]):
    """Sharded program for the non-counter aligned families: the SAME
    _eval_core body as the single-device dispatch, series on the shard
    axis, output steps on the time axis -> [S, T] f64 (or [B, S, T]).
    ``arr_keys`` is the channel-set signature ((name, ndim), ...); the
    scalars come as ``_scalars`` says."""
    from filodb_tpu.query.tilestore import _eval_core

    s_axis, t_axis = mesh.axis_names[:2]
    arr_specs = {k: (P(s_axis) if nd == 1 else P(s_axis, None))
                 for k, nd in arr_keys}

    def aligned_body(arrs, consts, grid):
        n, base, dt, w0s, w0e, step = _scalars(consts, grid)
        t_off = (jax.lax.axis_index(t_axis).astype(jnp.int64)
                 * nsteps_local * step)
        ev = functools.partial(_eval_core, func, nsteps_local, arrs,
                               n, base, dt)
        if batch:
            return jax.vmap(lambda a, b: ev(a + t_off, b + t_off,
                                            step))(w0s, w0e)
        return ev(w0s + t_off, w0e + t_off, step)

    @jax.jit
    def run(arrs, consts, grid):
        inner = jax.shard_map(
            aligned_body, mesh=mesh,
            in_specs=(arr_specs, P(), P()),
            out_specs=(P(None, s_axis, t_axis) if batch
                       else P(s_axis, t_axis)))
        return inner(arrs, consts, grid)
    return run


@order_insensitive(
    "grouped-pair-psum", tolerance=1e-12,
    reason="sums and counts are f64 per-device masked-sum "
           "partials psummed over the shard axis; regrouping across "
           "device counts moves the sums by at most a few f64 ulps "
           "(counts are exact integers in f64) — certified at "
           "1/2/4/8 virtual devices")
def _build_grouped_pair_eval(mesh: Mesh, func: str, nsteps_local: int,
                             num_groups: int):
    """The fused-groupsum contract from resident tiles: per-device
    windowed counter evaluation + masked sum by group, the partials
    stacked and ONE psum over the shard axis -> f64 [2, T, G], the sums
    at [0] (meaningful where the count > 0) and the counts at [1],
    exactly the one-chip fused programs' return shape: one collective a
    launch and one buffer to sync. The program takes the resident
    channels and group ids, the placement's constants and the request's
    grid (``_scalars``): one host array."""
    from filodb_tpu.query.tilestore import _eval_counter_fast

    s_axis, t_axis = mesh.axis_names[:2]

    def grouped_pair_body(tsr, vv, gids, consts, grid):
        n, base, dt, w0s, w0e, step = _scalars(consts, grid)
        t_off = (jax.lax.axis_index(t_axis).astype(jnp.int64)
                 * nsteps_local * step)
        arrs = {"tsr": tsr, "ff_v": vv}
        local = _eval_counter_fast(func, nsteps_local, arrs, n, base,
                                   dt, w0s + t_off, w0e + t_off, step)
        # masked f64 sums, not an f64 dot: the chip has no f64 matmul,
        # and its compiler spells one as nine loops over bf16 pieces,
        # 210 of this program's 268 device ops and half of its time at
        # 16 groups, more at 1,024 (chip, PR 37); f32 rates add in f64
        # without rounding, so the sums came out the same to the bit.
        # A padding row's -1 is no group's id.
        member = gids[None, :] == jnp.arange(num_groups)[:, None]  # [G, S_l]
        ok = ~jnp.isnan(local)[:, None, :] & member[None]    # [T_l, G, S_l]
        sums = jnp.sum(jnp.where(ok, local[:, None, :], 0.0),
                       axis=2, dtype=jnp.float64)
        cnts = jnp.sum(ok, axis=2, dtype=jnp.int32).astype(jnp.float64)
        return jax.lax.psum(jnp.stack([sums, cnts]), s_axis)

    @jax.jit
    def run(tsr, vv, gids, consts, grid):
        inner = jax.shard_map(
            grouped_pair_body, mesh=mesh,
            in_specs=(P(None, s_axis), P(None, s_axis), P(s_axis), P(),
                      P()),
            out_specs=P(None, t_axis, None))
        return inner(tsr, vv, gids, consts, grid)
    return run


def _build_grouped_eval(mesh: Mesh, func: str, nsteps_local: int,
                        num_groups: int, agg: str):
    """Grouped counter aggregation from resident tiles: per-device
    windowed evaluation, then the one-hot [S, G] matmul + psum
    collective (mesh._grouped_reduce — ReduceAggregateExec as a
    collective) -> [G, T]; the scalars come as ``_scalars`` says."""
    from filodb_tpu.query.tilestore import _eval_counter_fast

    s_axis, t_axis = mesh.axis_names[:2]

    @functools.partial(jax.jit, static_argnames=("agg",))
    def run(agg, tsr, vv, gids, consts, grid):
        def grouped_body(tsr, vv, gids, consts, grid):
            n, base, dt, w0s, w0e, step = _scalars(consts, grid)
            t_off = (jax.lax.axis_index(t_axis).astype(jnp.int64)
                     * nsteps_local * step)
            arrs = {"tsr": tsr, "ff_v": vv}
            local = _eval_counter_fast(func, nsteps_local, arrs, n,
                                       base, dt, w0s + t_off,
                                       w0e + t_off, step)
            return _grouped_reduce(local.T.astype(jnp.float64), gids,
                                   num_groups, agg)
        inner = jax.shard_map(
            grouped_body, mesh=mesh,
            in_specs=(P(None, s_axis), P(None, s_axis), P(s_axis), P(),
                      P()),
            out_specs=P(None, t_axis))
        return inner(tsr, vv, gids, consts, grid)
    return run


def _take_rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``a[idx]`` along the first axis for an index array of any shape,
    the indices promised in bounds (the host clipped them): a plain gather,
    without the bounds and negative-index selects ``jnp.take`` adds."""
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(idx.ndim, idx.ndim + a.ndim - 1)),
        collapsed_slice_dims=(0,), start_index_map=(0,))
    return jax.lax.gather(a, idx[..., None], dims, (1,) + a.shape[1:],
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


# a histogram request's plan (``ShardedHistTiles._plan``): eight runs of
# T_l, one value a window each, then the query's first window's two
# candidate slots and its start
(_PL_KC, _PL_KP, _PL_KCL, _PL_KN, _PL_COUNTS, _PL_FLAGS, _PL_WS,
 _PL_WE) = range(8)
_PL_RUNS = 8
# _PL_FLAGS bits: the window's last/first slot is a real slot, no sample
# at or before the window's end, none at or after its start
_FL_HI_OK, _FL_LO_OK, _FL_NONE_HI, _FL_NONE_LO = 1, 2, 4, 8


@order_insensitive(
    "hist-bucket-psum", tolerance=1e-12,
    reason="the [T, G, B] bucket sums are f64 per-device masked-sum "
           "partials psummed over the shard axis (counts are exact "
           "int32); the quantile after the psum divides the sums' "
           "regrouping by a bucket's share of the total, a few hundred "
           "f64 ulps at most — certified at 1/2/4/8 virtual devices")
def _build_hist_quantile_eval(mesh: Mesh, func: str, nsteps_local: int,
                              num_groups: int, num_buckets: int):
    """The device half of ``histogram_quantile(q, sum by (g)
    (rate|increase(h[w])))`` from a resident histogram placement
    (``ShardedHistTiles``) -> [T, G, B] f64, the bucket sums of every
    group over every device's series, NaN where no series of the group
    has a rate (as the host's ``_aggregate_hist_sum``).

    Per device, over its own series: the four boundary rows of every
    window taken in one gather a channel part (the request's plan names
    them), the one-chip evaluator's two-candidate selects and zero point
    (``tilestore._eval_counter_fast``'s histogram branch, the same
    operations), ``tilestore._extrapolated_rate``, then
    ``tilestore.hist_group_partials``; then a ``psum`` of the [T_l, G, B]
    sums and counts over the shard axis. The quantile, which is not linear,
    is the caller's (``ShardedHistTiles.quantile``). What the one-chip
    program computes on the device from the grid, the slot of each
    window's boundaries, the host computes exactly in integers
    (``_plan``), and a channel waits as the f32 parts its values need
    (``ShardedHistTiles``), so that no request splits a whole f64 channel:
    a traced run records every device op of every launch on every chip,
    and this keeps a launch at 45 of them a chip with one part a channel,
    53 with two (``tests/test_tpu_compile.py`` holds the counts).

    The program takes the resident channels (each a tuple of its parts)
    and group ids and the request's plan: one host array."""
    from filodb_tpu.query.tilestore import (_extrapolated_rate,
                                            hist_group_partials)

    s_axis, t_axis = mesh.axis_names[:2]
    # with steps over a time axis a device's first window is not the
    # query's: the zero point is read at the query's own first window
    own_first = int(mesh.shape[t_axis]) > 1

    def rows(parts, idx):
        # the f64 rows of a channel kept as f32 parts: their exact sum
        out = _take_rows(parts[0], idx).astype(jnp.float64)
        for part in parts[1:]:
            out = out + _take_rows(part, idx).astype(jnp.float64)
        return out

    def hist_sums_body(tsr, cv, corr, gids, plan):
        T = nsteps_local

        def run(i):
            return plan[i * T:(i + 1) * T]
        k4 = plan[:4 * T]                                    # [4 T_l]
        ws, we = run(_PL_WS)[:, None], run(_PL_WE)[:, None]
        flags = run(_PL_FLAGS)
        ts_kc, ts_kp, tsb_kcl, tsb_kn = (
            _take_rows(tsr, k4).reshape(4, T, -1))           # [T_l, S_l]
        v_kc, v_kp, v_kcl, v_kn = rows(cv, k4).reshape(
            (4, T) + cv[0].shape[1:])                        # [T_l, BP, S_l]
        k0 = plan[_PL_RUNS * T:_PL_RUNS * T + 2]
        c2 = rows(corr, k0)                                  # [2, BP, S_l]

        def bit(b):
            return ((flags & b) > 0)[:, None]
        over = bit(_FL_HI_OK) & (ts_kc > we)
        under = bit(_FL_LO_OK) & (tsb_kcl < ws)
        counts = run(_PL_COUNTS)[:, None] - over.astype(jnp.int32) \
            - under.astype(jnp.int32)
        ax = lambda a: a[:, None]                           # noqa: E731
        use1 = ts_kc <= we
        t2 = jnp.where(use1, ts_kc, ts_kp)
        v2 = jnp.where(ax(bit(_FL_NONE_HI)), jnp.nan,
                       jnp.where(ax(use1), v_kc, v_kp))
        useb = tsb_kcl >= ws
        t1 = jnp.where(useb, tsb_kcl, tsb_kn)
        v1 = jnp.where(ax(bit(_FL_NONE_LO)), jnp.nan,
                       jnp.where(ax(useb), v_kcl, v_kn))
        first = (_take_rows(tsr, k0[:1])[0] >= plan[_PL_RUNS * T + 2]
                 if own_first else useb[0])
        c0 = jnp.where(first, c2[0], c2[1])                 # [BP, S_l]
        rates = _extrapolated_rate(ax(ws), ax(we), ax(counts), ax(t1),
                                   v1 - c0, ax(t2), v2 - c0, True,
                                   func == "rate")         # [T_l, BP, S_l]
        sums, cnts = hist_group_partials(rates, gids, num_groups)
        sums = jax.lax.psum(sums[..., :num_buckets], s_axis)
        cnts = jax.lax.psum(cnts[..., :num_buckets], s_axis)
        return jnp.where(cnts > 0, sums, jnp.nan)

    @jax.jit
    def run(tsr, cv, corr, gids, plan):
        cv_s, corr_s = (tuple(P(None, None, s_axis) for _ in c)
                        for c in (cv, corr))
        inner = jax.shard_map(
            hist_sums_body, mesh=mesh,
            in_specs=(P(None, s_axis), cv_s, corr_s, P(s_axis), P(t_axis)),
            out_specs=P(t_axis, None, None))
        return inner(tsr, cv, corr, gids, plan)
    return run


# ---------------------------------------------------------------------------
# The resident store
# ---------------------------------------------------------------------------

def _next_pow2(n: int, lo: int = 8) -> int:
    p = lo
    while p < n:
        p <<= 1
    return p


@capacity(
    "shardstore-resident-channels", bytes_per_sample=20.0, sharded=True,
    overhead_bytes=24,
    reason="the resident store keeps three [cap, S_pad] slot-major "
           "channels — int32 relative timestamps (4 B) + raw f64 "
           "values (8 B) + counter-corrected f64 values (8 B) = 20 B "
           "per PADDED slot (pow2 slot capacity, shard-aligned series "
           "pad) — and its programs' constants, int64[3] (24 B); the "
           "non-counter _aligned placements are transient per-family "
           "row sets cleared on every refresh")
class ShardedTiles:
    """One aligned-tile cohort resident across the mesh: capacity-padded
    [cap, S_pad] slot-major channels (int32 relative timestamps, raw
    values, counter-corrected values), series sharded over the first
    mesh axis. Immutable except through :meth:`append_slots` (the
    donated refresh)."""

    # the filodb_device_memory_bytes{family} this placement counts under
    FAMILY = "shardstore-resident-channels"

    def __init__(self, mesh: Mesh, tiles) -> None:
        self.mesh = mesh
        self.base_ms = int(tiles.base_ms)
        self.dt_ms = int(tiles.dt_ms)
        self.keys = list(tiles.keys)
        S = len(self.keys)
        N = int(tiles.num_slots)
        n_shard = int(mesh.shape[mesh.axis_names[0]])
        self.n_time = int(mesh.shape[mesh.axis_names[1]])
        self.S = S
        self.S_pad = -(-S // n_shard) * n_shard
        self.cap = _next_pow2(N, 64)
        self.n_filled = N
        self._col_sharding = NamedSharding(mesh, P(None, mesh.axis_names[0]))
        ts = np.asarray(tiles.ts, dtype=np.float64)             # [S, N]
        self._tsr = self._place_cols(
            (ts - self.base_ms).T.astype(np.int32), self.S_pad)
        self._place_channels(tiles)
        # the padded group ids on the devices, per tile-order vector the
        # backend's tile entry handed out (``_row_gids``)
        self._gids = PerGrouping()
        self._put_consts()
        # runtime residency accounting: live device bytes under the
        # filodb_device_memory_bytes{family,shard} gauge, dropped when
        # the store is collected
        ensure_residency_collector()
        self._res_key = (self.FAMILY, str(n_shard), id(self))
        weakref.finalize(self, drop_resident, *self._res_key)
        self._record_residency()

    def _place_cols(self, host_nx_c: np.ndarray, width: int) -> jnp.ndarray:
        """``host_nx_c`` [N, C] (C <= ``width``) zero-padded to [cap,
        width] and put on the mesh with its columns sharded over the
        first axis."""
        buf = np.zeros((self.cap, width), dtype=host_nx_c.dtype)
        buf[:host_nx_c.shape[0], :host_nx_c.shape[1]] = host_nx_c
        return jax.device_put(buf, self._col_sharding)

    def _place_channels(self, tiles) -> None:
        """The counter channels beside ``_tsr``: raw and corrected f64."""
        v = np.asarray(tiles.channel("v"), dtype=np.float64)
        self._v = self._place_cols(v.T, self.S_pad)
        cv = np.asarray(tiles.channel("cv"), dtype=np.float64)
        self._cv = self._place_cols(cv.T, self.S_pad)
        # non-counter aligned channel placements, per function family
        self._aligned: Dict[Tuple, Dict[str, jnp.ndarray]] = {}

    def _put_consts(self) -> None:
        """n_filled, base_ms and dt_ms on the devices, replicated: the
        programs' ``consts`` (``_scalars``). Put where the placement is
        built and again where ``append_slots`` moves ``n_filled``."""
        self._consts = jax.device_put(
            np.array([self.n_filled, self.base_ms, self.dt_ms], np.int64),
            NamedSharding(self.mesh, P()))

    def _buffers(self):
        """The resident channels, for the residency gauge."""
        yield from (self._tsr, self._v, self._cv)
        for placed in self._aligned.values():
            yield from placed.values()

    def _record_residency(self) -> None:
        record_resident(*self._res_key,
                        sum(int(a.nbytes) for a in self._buffers()))

    # -- eligibility -------------------------------------------------------

    @staticmethod
    def tiles_eligible(tiles) -> bool:
        """Build-time gate, mirroring the single-device fast-family
        guard: dense tiles whose whole span fits int32 ms."""
        from filodb_tpu.query.tilestore import _SENT_HI
        return (tiles is not None and tiles._dense
                and len(tiles.keys) > 0
                and tiles.num_slots * tiles.dt_ms + tiles.dt_ms < _SENT_HI)

    def query_fits(self, steps: np.ndarray, window_ms: int,
                   offset_ms: int) -> bool:
        """Per-query span guard: the grid must sit in int32 ms relative
        to the tile base (the dispatcher's fits_i32 condition) — wider
        grids take the single-device exact-f64 path."""
        from filodb_tpu.query.tilestore import _SENT_HI, _SENT_LO
        if steps.size == 0:
            return False
        w0s = int(steps[0] - offset_ms) - window_ms
        return (_SENT_LO < w0s - self.base_ms
                and int(steps[-1] - offset_ms) - self.base_ms < _SENT_HI)

    def _grid(self, steps: np.ndarray, window_ms: int, offset_ms: int):
        """-> (steps a device of the time axis computes, the request's
        ``grid``: int64[3] w0s, w0e, step)."""
        nsteps = steps.size
        T_pad = -(-nsteps // self.n_time) * self.n_time
        w0e = int(steps[0] - offset_ms)
        step = int(steps[1] - steps[0]) if nsteps > 1 else 1
        return T_pad // self.n_time, np.array([w0e - window_ms, w0e, step],
                                              np.int64)

    def _batch_grid(self, nsteps: int, step: int, w0s_list: Sequence[int],
                    w0e_list: Sequence[int]):
        """-> (steps a device computes, the batch's ``grid``: int64[3,
        B_pad], rows w0s, w0e and the step, B padded to a power of
        two)."""
        from filodb_tpu.query.tilestore import _pad_pow2
        w0s_v = _pad_pow2(list(w0s_list))
        grid = np.stack([w0s_v, _pad_pow2(list(w0e_list)),
                         np.full(w0s_v.shape, step)]).astype(np.int64)
        T_pad = -(-nsteps // self.n_time) * self.n_time
        return T_pad // self.n_time, grid

    def _mesh_key(self) -> Tuple:
        return (int(self.mesh.shape[self.mesh.axis_names[0]]),
                self.n_time, int(self.mesh.devices.size))

    # -- evaluation --------------------------------------------------------

    def eval_counters(self, func: str, steps: np.ndarray, window_ms: int,
                      offset_ms: int = 0) -> jnp.ndarray:
        """rate/increase/delta from the resident store -> device
        [T, S] f32 (callers slice/transpose; values bit-for-bit the
        single-device fast-path's)."""
        t_local, grid = self._grid(steps, window_ms, offset_ms)
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, self._consts, grid)
        key = ("mesh-fast", func, t_local, self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_counter_eval(
            self.mesh, func, t_local, batch=0), cost_args=args)
        return fn(*args)[:steps.size, :self.S]

    def eval_counters_batch(self, func: str, nsteps: int, step: int,
                            w0s_list: Sequence[int],
                            w0e_list: Sequence[int]) -> jnp.ndarray:
        """One sharded dispatch computing B counter grids -> device
        [B_pad, T, S] (callers slice [:len(w0s_list)]) — the
        mesh-shaped micro-batch."""
        t_local, grid = self._batch_grid(nsteps, step, w0s_list, w0e_list)
        b_pad = int(grid.shape[1])
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, self._consts, grid)
        key = ("mesh-fast-b", func, t_local, b_pad, self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_counter_eval(
            self.mesh, func, t_local, batch=b_pad), cost_args=args)
        return fn(*args)[:, :nsteps, :self.S]

    def _aligned_arrs(self, tiles, func: str) -> Dict[str, jnp.ndarray]:
        """Sharded placement of the row-major channel set ``func``
        needs (query/tilestore._tiles_arrays), cached per channel-set
        signature."""
        from filodb_tpu.query.tilestore import _tiles_arrays
        arrs = _tiles_arrays(tiles, func)
        key = tuple(sorted(arrs))
        placed = self._aligned.get(key)
        if placed is None:
            s_axis = self.mesh.axis_names[0]
            row = NamedSharding(self.mesh, P(s_axis))
            row2 = NamedSharding(self.mesh, P(s_axis, None))
            placed = {}
            for k, a in arrs.items():
                h = np.asarray(a)
                pad = self.S_pad - h.shape[0]
                if pad:
                    h = np.concatenate(
                        [h, np.zeros((pad,) + h.shape[1:], h.dtype)])
                placed[k] = jax.device_put(h, row if h.ndim == 1 else row2)
            self._aligned[key] = placed
            self._record_residency()
        return placed

    def eval_aligned(self, tiles, func: str, steps: np.ndarray,
                     window_ms: int, offset_ms: int = 0) -> jnp.ndarray:
        """Non-counter aligned families from sharded channels ->
        device [S, T] f64, bit-for-bit the single-device _eval_core."""
        t_local, grid = self._grid(steps, window_ms, offset_ms)
        arrs = self._aligned_arrs(tiles, func)
        sig = tuple(sorted((k, v.ndim) for k, v in arrs.items()))
        args = (arrs, self._consts, grid)
        key = ("mesh-aligned", func, t_local, sig, self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_aligned_eval(
            self.mesh, func, t_local, 0, sig), cost_args=args)
        return fn(*args)[:self.S, :steps.size]

    def eval_aligned_batch(self, tiles, func: str, nsteps: int, step: int,
                           w0s_list: Sequence[int],
                           w0e_list: Sequence[int]) -> jnp.ndarray:
        t_local, grid = self._batch_grid(nsteps, step, w0s_list, w0e_list)
        b_pad = int(grid.shape[1])
        arrs = self._aligned_arrs(tiles, func)
        sig = tuple(sorted((k, v.ndim) for k, v in arrs.items()))
        args = (arrs, self._consts, grid)
        key = ("mesh-aligned-b", func, t_local, b_pad, sig,
               self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_aligned_eval(
            self.mesh, func, t_local, b_pad, sig), cost_args=args)
        return fn(*args)[:, :self.S, :nsteps]

    def _put_gids(self, gids: np.ndarray) -> jnp.ndarray:
        g = np.full(self.S_pad, -1, dtype=np.int32)     # -1 = padding rows
        g[:self.S] = gids
        row = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        return jax.device_put(g, row)

    def _row_gids(self, gids) -> jnp.ndarray:
        """``gids`` (tile order) padded to ``int32[S_pad]`` and put on the
        devices with the row sharding: once per frozen vector (the
        backend's tile entry hands every request of one grouping the SAME
        read-only one), so that a request sends its grid scalars and
        nothing else. The copies go with the placement, and on
        ``append_slots``. Any other array is put as it comes."""
        return self._gids.get(gids, self._put_gids)

    def eval_grouped(self, func: str, steps: np.ndarray, window_ms: int,
                     gids: np.ndarray, num_groups: int, agg: str = "sum",
                     offset_ms: int = 0) -> np.ndarray:
        """sum/count/avg/min/max by (g) of rate/increase/delta straight
        off the resident store: one-hot matmul + psum over the shard
        axis -> [G, T] numpy."""
        t_local, grid = self._grid(steps, window_ms, offset_ms)
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (agg, self._tsr, vv, self._row_gids(gids), self._consts,
                grid)
        key = ("mesh-grouped", func, agg, t_local, num_groups,
               self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_grouped_eval(
            self.mesh, func, t_local, num_groups, agg), cost_args=args)
        return np.asarray(fn(*args))[:, :steps.size]

    def dispatch_grouped_pair(self, func: str, steps: np.ndarray,
                              window_ms: int, gids: np.ndarray,
                              num_groups: int, offset_ms: int = 0):
        """Enqueue the fused `sum by (g)` program off the resident store
        -> ONE device array f64 [2, T_pad, G], the sums at [0] and the
        counts at [1]; the caller syncs it once and cuts to
        ``steps.size`` rows (``eval_grouped_pair`` does both). The
        program, its key and its arguments' shapes and dtypes
        are the same whether ``gids`` was on the devices already
        (``_row_gids``) or is put now. With the ids on the devices the
        call hands over one host array, the request's grid: the channels
        and the constants (``_scalars``) stay with the placement."""
        t_local, grid = self._grid(steps, window_ms, offset_ms)
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, self._row_gids(gids), self._consts, grid)
        key = ("mesh-grouped-pair", func, t_local, num_groups,
               self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_grouped_pair_eval(
            self.mesh, func, t_local, num_groups), cost_args=args)
        return fn(*args)

    def eval_grouped_pair(self, func: str, steps: np.ndarray,
                          window_ms: int, gids: np.ndarray,
                          num_groups: int, offset_ms: int = 0
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Fused `sum by (g)` contract off the resident store ->
        (sums [T, G], counts [T, G]) numpy, matching the one-chip
        group-sum's return shape (TpuBackend.fused_groupsum)."""
        out = np.asarray(self.dispatch_grouped_pair(
            func, steps, window_ms, gids, num_groups, offset_ms))
        T = steps.size
        return out[0, :T], out[1, :T]

    # -- the donated refresh ----------------------------------------------

    def append_slots(self, tiles_new) -> bool:
        """Cross-flush refresh: when ``tiles_new`` extends this store's
        series set by appended slots (same cohort, same cadence, grown
        prefix), write the new slot columns in place through the
        donated :func:`_append_step` and serve the fresh world with
        ZERO buffer copies. Returns False when incompatible — the
        caller re-places from scratch."""
        if not self.tiles_eligible(tiles_new):
            return False
        if (int(tiles_new.base_ms) != self.base_ms
                or int(tiles_new.dt_ms) != self.dt_ms
                or list(tiles_new.keys) != self.keys):
            return False
        n_new = int(tiles_new.num_slots)
        if n_new <= self.n_filled:
            return n_new == self.n_filled    # nothing to append
        k = n_new - self.n_filled
        # pow2-bucketed append width: repeat-pad the tail row so the
        # compiled append program is reused across flush cadences (the
        # padded rows land beyond n_filled and are never read — the
        # next append overwrites them)
        k_pad = _next_pow2(k, 8)
        if self.n_filled + k_pad > self.cap:
            return False                     # out of capacity: re-place
        ts = np.asarray(tiles_new.ts, dtype=np.float64)[:, self.n_filled:]
        v = np.asarray(tiles_new.channel("v"),
                       dtype=np.float64)[:, self.n_filled:]
        new_tsr = np.zeros((k_pad, self.S_pad), np.int32)
        new_v = np.zeros((k_pad, self.S_pad), np.float64)
        new_tsr[:k, :self.S] = (ts - self.base_ms).T.astype(np.int32)
        new_v[:k, :self.S] = v.T
        new_tsr[k:] = new_tsr[k - 1:k]
        new_v[k:] = new_v[k - 1:k]
        col = self._col_sharding
        self._tsr, self._v, self._cv = _append_step(
            self._tsr, self._v, self._cv,
            jax.device_put(new_tsr, col), jax.device_put(new_v, col),
            np.int64(self.n_filled))
        self.n_filled = n_new
        self._put_consts()
        self._aligned.clear()   # row-major placements are per-snapshot
        # the new tiles come with a tile entry, and so tile-order vectors,
        # of their own: the old ones' copies would never be asked for
        self._gids.kept.clear()
        self._record_residency()
        return True


@capacity(
    "shardstore-resident-hist-channels", bytes_per_sample=32.34,
    sharded=True, overhead_bytes=24,
    reason="a histogram placement prices its bucket axis: a sample is ONE "
           "bucket value of one PADDED slot (pow2 slot capacity, "
           "shard-aligned series pad), kept as at most three f32 parts "
           "in each of the corrected-bucket and correction channels "
           "[cap, BP, S_pad], whose bucket axis pads to the chip's 8-row "
           "tile (BP 16 for 12 buckets): 24 * BP / B B, plus the slot's "
           "int32 relative timestamp (4 B) shared by its B buckets: (24 "
           "* BP + 4) / B, priced at the Prometheus client's 12 default "
           "buckets (32.34 B; integer counts below 2**24 keep one part a "
           "channel, a third of that, below 2**48 two); besides, the "
           "base class's constants int64[3] (24 B). The group ids come "
           "with requests, as for counters")
class ShardedHistTiles(ShardedTiles):
    """A histogram cohort (``tilestore.HistTiles``) resident across the
    mesh, for the fused quantile: ``_tsr`` [cap, S_pad] as for counters,
    and the corrected buckets and the correction [cap, BP, S_pad] with the
    series sharded over the first axis, so each device holds every bucket
    of its own S_l series. The bucket axis pads to BP, a multiple of the
    chip's 8-row tile (the padded buckets are zeros and never summed):
    with a gather along the slots the rows then come out as [.., BP, S_l]
    with no layout change. Each channel is kept as the f32 parts whose
    sum is exactly its f64 values (``f32(x)``, then ``f32`` of what is
    left, as long as something is: integer counts below 2**24 keep one
    part, below 2**48 two, any finite value three), so that no request
    splits a whole channel again; the program adds the parts of the rows
    it takes. The bounds stay on the host, for ``quantile``.

    It serves ``dispatch_hist_quantile`` alone: the counter programs of
    the base class are not for it. No donated append exists for
    histograms yet: ``append_slots`` refuses, so a flush drops the
    placement (``ShardedTileEvaluator.refresh`` counts it as an eviction)
    and the next request over the new tiles places them again."""

    FAMILY = "shardstore-resident-hist-channels"

    def _place_channels(self, tiles) -> None:
        B = int(tiles.num_buckets)
        self.B, self.BP = B, -(-B // 8) * 8
        self.S_l = self.S_pad // int(self.mesh.shape[self.mesh.axis_names[0]])
        self.les = np.asarray(tiles.les, np.float64)
        sharding = NamedSharding(self.mesh,
                                 P(None, None, self.mesh.axis_names[0]))

        def parts(ch):
            # [N, B*S] (column b*S + s) -> [cap, BP, S_pad] f64, then the
            # f32 parts whose sum is exactly it (as few as its values
            # need: one for integers below 2**24, two below 2**48, three
            # for any finite value), each put with the series sharded
            x = np.zeros((self.cap, self.BP, self.S_pad), np.float64)
            x[:self.n_filled, :B, :self.S] = np.asarray(
                ch, np.float64).reshape(self.n_filled, B, self.S)
            kept = [x.astype(np.float32)]
            rest = np.where(np.isfinite(kept[0]), x - kept[0], 0.0)
            while rest.any() and len(kept) < 3:
                kept.append(rest.astype(np.float32))
                rest = rest - kept[-1]
            return tuple(jax.device_put(h, sharding) for h in kept)
        self._cv = parts(tiles.t_cv)
        self._corr = parts(tiles.t_corr)

    def _buffers(self):
        yield self._tsr
        yield from self._cv
        yield from self._corr

    def append_slots(self, tiles_new) -> bool:
        """No donated append for histograms: the caller places anew."""
        return False

    def _plan(self, steps: np.ndarray, window_ms: int, offset_ms: int):
        """-> (steps a device of the time axis computes, the request's
        plan: int32 [n_time * (8 T_l + 3)], the block of each device of
        the time axis in turn). For every window, in ms from the tile base
        and slots of the placement, what ``tilestore._eval_counter_fast``
        computes from the grid on the device, here exactly in integers,
        each a run of T_l: the slots nearest its end (``k_hi = floor((we +
        dt/2) / dt)``) and its start (``k_lo = ceil((ws - dt/2) / dt)``)
        and their neighbours, clipped to the filled slots; the slot count
        between them; the flags ``_FL_*``; the window's start and end.
        Then the query's first window's two candidate slots and its start,
        where the buckets' zero point is read. ``query_fits`` has to hold:
        every start and end is int32."""
        t_local, grid = self._grid(steps, window_ms, offset_ms)
        N, dt = self.n_filled, self.dt_ms
        t = np.arange(t_local * self.n_time, dtype=np.int64)
        ws = int(grid[0]) - self.base_ms + t * int(grid[2])
        we = int(grid[1]) - self.base_ms + t * int(grid[2])
        k_hi = (2 * we + dt) // (2 * dt)
        k_lo = -((dt - 2 * ws) // (2 * dt))
        hi_ok = (k_hi >= 0) & (k_hi <= N - 1)
        lo_ok = (k_lo >= 0) & (k_lo <= N - 1)
        runs = np.empty((_PL_RUNS, t.size), np.int64)
        runs[_PL_KC] = np.clip(k_hi, 0, N - 1)
        runs[_PL_KP] = np.clip(k_hi - 1, 0, N - 1)
        runs[_PL_KCL] = np.clip(k_lo, 0, N - 1)
        runs[_PL_KN] = np.clip(k_lo + 1, 0, N - 1)
        runs[_PL_COUNTS] = np.clip(k_hi, -1, N - 1) + 1 - np.clip(k_lo, 0, N)
        runs[_PL_FLAGS] = (hi_ok * _FL_HI_OK + lo_ok * _FL_LO_OK
                           + (k_hi < 0) * _FL_NONE_HI
                           + (k_lo > N - 1) * _FL_NONE_LO)
        runs[_PL_WS], runs[_PL_WE] = ws, we
        blocks = runs.reshape(_PL_RUNS, self.n_time, t_local).transpose(1, 0, 2)
        first = np.broadcast_to(
            np.array([runs[_PL_KCL, 0], runs[_PL_KN, 0], ws[0]]),
            (self.n_time, 3))
        plan = np.concatenate([blocks.reshape(self.n_time, -1), first], 1)
        return t_local, plan.astype(np.int32).reshape(-1)

    def dispatch_hist_quantile(self, func: str, steps: np.ndarray,
                               window_ms: int, gids: np.ndarray,
                               num_groups: int, offset_ms: int = 0):
        """Enqueue the bucket sums off the resident placement -> device
        f64 [T_pad, G, B]; the caller syncs, cuts to ``steps.size`` rows
        and takes the quantile (``quantile``). ``gids`` in tile order
        (``_row_gids``); the call hands over one host array, the request's
        plan (``query_fits`` has to hold)."""
        t_local, plan = self._plan(steps, window_ms, offset_ms)
        args = (self._tsr, self._cv, self._corr, self._row_gids(gids), plan)
        key = ("mesh-hist-sums", func, t_local, num_groups,
               tuple(self._cv[0].shape), len(self._cv), len(self._corr),
               self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_hist_quantile_eval(
            self.mesh, func, t_local, num_groups, self.B), cost_args=args)
        return fn(*args)

    def quantile(self, h: np.ndarray, q: float) -> np.ndarray:
        """``histogram_quantile(q, ..)`` of the synced bucket sums [T, G, B]
        -> [T, G] f64, on the host: ``tilestore._bucket_quantile`` in
        numpy f64. After the psum this is a few thousand values, and on
        the chip it would be some twenty more ops a launch on every
        device."""
        from filodb_tpu.query.tilestore import _bucket_quantile
        with np.errstate(divide="ignore", invalid="ignore"):
            return _bucket_quantile(q, self.les, h, xp=np)


# ---------------------------------------------------------------------------
# Placement cache (the evaluator the backend holds)
# ---------------------------------------------------------------------------

# cache inventory: placements key on tile-snapshot IDENTITY (an
# AlignedTiles instance is an immutable snapshot; a weakref finalizer
# drops the placement the moment its tiles die, so a recycled id can
# never serve stale channels)
@cache_registry("sharded-tile-placement", keyed=("tiles-identity",))
@guarded_by("_lock", "_placed")
class ShardedTileEvaluator:
    """The serving-path facade TpuBackend holds: lazily places eligible
    aligned-tile cohorts across the mesh, serves the sharded evaluator
    families from them, and rides cross-flush rebuilds through the
    donated append."""

    MAX_PLACEMENTS = 8

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self._lock = threading.Lock()
        # id(tiles) -> (weakref to tiles, ShardedTiles)
        self._placed: Dict[int, Tuple[object, ShardedTiles]] = {}
        self.placements = 0          # observability: builds
        # those that pushed the oldest placement out: it is built again
        # if its tiles are asked for once more
        self.evictions = 0
        self.donated_refreshes = 0   # observability: zero-copy appends

    @property
    def ndev(self) -> int:
        return int(self.mesh.devices.size)

    def place(self, tiles) -> Optional[ShardedTiles]:
        """The resident placement for ``tiles`` (built on first sight): a
        ``ShardedHistTiles`` for a histogram cohort, else a
        ``ShardedTiles``; None when the tiles don't qualify."""
        if tiles is None or not ShardedTiles.tiles_eligible(tiles):
            return None
        key = id(tiles)
        with self._lock:
            got = self._placed.get(key)
            if got is not None:
                return got[1]
        from filodb_tpu.query.tilestore import HistTiles
        kind = (ShardedHistTiles if isinstance(tiles, HistTiles)
                else ShardedTiles)
        with obs_trace.span("mesh-place", series=len(tiles.keys)):
            placed = kind(self.mesh, tiles)

        def _drop(_ref, *, _self=self, _key=key):
            with _self._lock:
                _self._placed.pop(_key, None)

        ref = weakref.ref(tiles, _drop)
        with self._lock:
            while len(self._placed) >= self.MAX_PLACEMENTS:
                self._placed.pop(next(iter(self._placed)))
                self.evictions += 1
            self._placed[key] = (ref, placed)
            self.placements += 1
        return placed

    def refresh(self, old_tiles, new_tiles) -> bool:
        """Cross-flush hand-over: move the old tiles' placement onto
        the freshly-built tiles via the donated append when compatible
        (zero-copy in HBM); otherwise drop it (the next query
        re-places). A histogram placement, which has no donated append,
        is dropped and counted in ``evictions``. Returns True when the
        donated path served."""
        with self._lock:
            got = self._placed.pop(id(old_tiles), None)
            if got is not None and isinstance(got[1], ShardedHistTiles):
                self.evictions += 1
                return False
        if got is None or new_tiles is None:
            return False
        placed = got[1]
        if not placed.append_slots(new_tiles):
            return False

        key = id(new_tiles)

        def _drop(_ref, *, _self=self, _key=key):
            with _self._lock:
                _self._placed.pop(_key, None)

        ref = weakref.ref(new_tiles, _drop)
        with self._lock:
            self._placed[key] = (ref, placed)
            self.donated_refreshes += 1
        return True

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"placements": self.placements,
                    "evictions": self.evictions,
                    "resident": len(self._placed),
                    "donated_refreshes": self.donated_refreshes,
                    "devices": self.ndev}
