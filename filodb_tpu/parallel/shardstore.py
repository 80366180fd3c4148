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
    store during rebuild.

Escape hatches: tiles must be dense (every slot valid) with the tile
span in int32 ms — exactly the fast-family eligibility of the
single-device dispatcher — and a query whose grid leaves the int32
range (or whose tiles never qualified) falls back to the single-device
tilestore path unchanged.
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
        np.int64(64), np.int64(0), np.int64(10_000),
        np.int64(100_000), np.int64(400_000), np.int64(60_000))
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
    """One jitted sharded program: [N, S] resident channels ->
    [T, S] (batch == 0) or [B, T, S] (batch == B) windowed counter
    grids. ``batch`` members vmap over the grid scalars exactly like
    the single-device evaluate_counters_t_batch family."""
    from filodb_tpu.query.tilestore import _eval_counter_fast

    s_axis, t_axis = mesh.axis_names[:2]

    def counter_body(tsr, vv, n, base, dt, w0s, w0e, step):
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

    if batch:
        @jax.jit
        def run_b(tsr, vv, n, base, dt, w0s, w0e, step):
            inner = jax.shard_map(
                counter_body, mesh=mesh,
                in_specs=(P(None, s_axis), P(None, s_axis), P(), P(), P(),
                          P(None), P(None), P()),
                out_specs=P(None, t_axis, s_axis))
            return inner(tsr, vv, n, base, dt, w0s, w0e, step)
        return run_b

    @jax.jit
    def run(tsr, vv, n, base, dt, w0s, w0e, step):
        inner = jax.shard_map(
            counter_body, mesh=mesh,
            in_specs=(P(None, s_axis), P(None, s_axis), P(), P(), P(),
                      P(), P(), P()),
            out_specs=P(t_axis, s_axis))
        return inner(tsr, vv, n, base, dt, w0s, w0e, step)
    return run


def _build_aligned_eval(mesh: Mesh, func: str, nsteps_local: int,
                        batch: int, arr_keys: Tuple[Tuple[str, int], ...]):
    """Sharded program for the non-counter aligned families: the SAME
    _eval_core body as the single-device dispatch, series on the shard
    axis, output steps on the time axis -> [S, T] f64 (or [B, S, T]).
    ``arr_keys`` is the channel-set signature ((name, ndim), ...)."""
    from filodb_tpu.query.tilestore import _eval_core

    s_axis, t_axis = mesh.axis_names[:2]
    arr_specs = {k: (P(s_axis) if nd == 1 else P(s_axis, None))
                 for k, nd in arr_keys}

    def aligned_body(arrs, n, base, dt, w0s, w0e, step):
        t_off = (jax.lax.axis_index(t_axis).astype(jnp.int64)
                 * nsteps_local * step)
        ev = functools.partial(_eval_core, func, nsteps_local, arrs,
                               n, base, dt)
        if batch:
            return jax.vmap(lambda a, b: ev(a + t_off, b + t_off,
                                            step))(w0s, w0e)
        return ev(w0s + t_off, w0e + t_off, step)

    if batch:
        @jax.jit
        def run_b(arrs, n, base, dt, w0s, w0e, step):
            inner = jax.shard_map(
                aligned_body, mesh=mesh,
                in_specs=(arr_specs, P(), P(), P(),
                          P(None), P(None), P()),
                out_specs=P(None, s_axis, t_axis))
            return inner(arrs, n, base, dt, w0s, w0e, step)
        return run_b

    @jax.jit
    def run(arrs, n, base, dt, w0s, w0e, step):
        inner = jax.shard_map(
            aligned_body, mesh=mesh,
            in_specs=(arr_specs, P(), P(), P(), P(), P(), P()),
            out_specs=P(s_axis, t_axis))
        return inner(arrs, n, base, dt, w0s, w0e, step)
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
    windowed counter evaluation + masked sum by group, psum over the shard
    axis -> (sums [T, G], counts [T, G]) f64 — sums meaningful where
    counts > 0, exactly the Pallas group-sum kernel's return shape."""
    from filodb_tpu.query.tilestore import _eval_counter_fast

    s_axis, t_axis = mesh.axis_names[:2]

    def grouped_pair_body(tsr, vv, gids, n, base, dt, w0s, w0e, step):
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
        return (jax.lax.psum(sums, s_axis), jax.lax.psum(cnts, s_axis))

    @jax.jit
    def run(tsr, vv, gids, n, base, dt, w0s, w0e, step):
        inner = jax.shard_map(
            grouped_pair_body, mesh=mesh,
            in_specs=(P(None, s_axis), P(None, s_axis), P(s_axis),
                      P(), P(), P(), P(), P(), P()),
            out_specs=(P(t_axis, None), P(t_axis, None)))
        return inner(tsr, vv, gids, n, base, dt, w0s, w0e, step)
    return run


def _build_grouped_eval(mesh: Mesh, func: str, nsteps_local: int,
                        num_groups: int, agg: str):
    """Grouped counter aggregation from resident tiles: per-device
    windowed evaluation, then the one-hot [S, G] matmul + psum
    collective (mesh._grouped_reduce — ReduceAggregateExec as a
    collective) -> [G, T]."""
    from filodb_tpu.query.tilestore import _eval_counter_fast

    s_axis, t_axis = mesh.axis_names[:2]

    @functools.partial(jax.jit, static_argnames=("agg",))
    def run(agg, tsr, vv, gids, n, base, dt, w0s, w0e, step):
        def grouped_body(tsr, vv, gids, n, base, dt, w0s, w0e, step):
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
            in_specs=(P(None, s_axis), P(None, s_axis), P(s_axis),
                      P(), P(), P(), P(), P(), P()),
            out_specs=P(None, t_axis))
        return inner(tsr, vv, gids, n, base, dt, w0s, w0e, step)
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
    reason="the resident store keeps three [cap, S_pad] slot-major "
           "channels — int32 relative timestamps (4 B) + raw f64 "
           "values (8 B) + counter-corrected f64 values (8 B) = 20 B "
           "per PADDED slot (pow2 slot capacity, shard-aligned series "
           "pad); the non-counter _aligned placements are transient "
           "per-family row sets cleared on every refresh")
class ShardedTiles:
    """One aligned-tile cohort resident across the mesh: capacity-padded
    [cap, S_pad] slot-major channels (int32 relative timestamps, raw
    values, counter-corrected values), series sharded over the first
    mesh axis. Immutable except through :meth:`append_slots` (the
    donated refresh)."""

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
        col = NamedSharding(mesh, P(None, mesh.axis_names[0]))
        self._col_sharding = col

        def place(host_nx_s, dtype):
            buf = np.zeros((self.cap, self.S_pad), dtype=dtype)
            buf[:N, :S] = host_nx_s
            return jax.device_put(buf, col)

        ts = np.asarray(tiles.ts, dtype=np.float64)             # [S, N]
        self._tsr = place((ts - self.base_ms).T.astype(np.int32), np.int32)
        v = np.asarray(tiles.channel("v"), dtype=np.float64)
        self._v = place(v.T, np.float64)
        cv = np.asarray(tiles.channel("cv"), dtype=np.float64)
        self._cv = place(cv.T, np.float64)
        # non-counter aligned channel placements, per function family
        self._aligned: Dict[Tuple, Dict[str, jnp.ndarray]] = {}
        # the padded group ids on the devices, per tile-order vector the
        # backend's tile entry handed out (``_row_gids``)
        self._gids = PerGrouping()
        # runtime residency accounting: live device bytes under the
        # filodb_device_memory_bytes{family,shard} gauge, dropped when
        # the store is collected
        ensure_residency_collector()
        self._res_key = ("shardstore-resident-channels", str(n_shard),
                         id(self))
        weakref.finalize(self, drop_resident, *self._res_key)
        self._record_residency()

    def _record_residency(self) -> None:
        nbytes = int(self._tsr.nbytes + self._v.nbytes + self._cv.nbytes)
        nbytes += sum(int(a.nbytes) for placed in self._aligned.values()
                      for a in placed.values())
        record_resident(*self._res_key, nbytes)

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
        nsteps = steps.size
        T_pad = -(-nsteps // self.n_time) * self.n_time
        w0e = np.int64(steps[0] - offset_ms)
        w0s = np.int64(w0e - window_ms)
        step = np.int64(steps[1] - steps[0]) if nsteps > 1 else np.int64(1)
        return T_pad // self.n_time, w0s, w0e, step

    def _mesh_key(self) -> Tuple:
        return (int(self.mesh.shape[self.mesh.axis_names[0]]),
                self.n_time, int(self.mesh.devices.size))

    # -- evaluation --------------------------------------------------------

    def eval_counters(self, func: str, steps: np.ndarray, window_ms: int,
                      offset_ms: int = 0) -> jnp.ndarray:
        """rate/increase/delta from the resident store -> device
        [T, S] f32 (callers slice/transpose; values bit-for-bit the
        single-device fast-path's)."""
        t_local, w0s, w0e, step = self._grid(steps, window_ms, offset_ms)
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, np.int64(self.n_filled),
                np.int64(self.base_ms), np.int64(self.dt_ms), w0s, w0e,
                step)
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
        from filodb_tpu.query.tilestore import _pad_pow2
        w0s_v = jnp.asarray(_pad_pow2(list(w0s_list)))
        w0e_v = jnp.asarray(_pad_pow2(list(w0e_list)))
        b_pad = int(w0s_v.shape[0])
        T_pad = -(-nsteps // self.n_time) * self.n_time
        t_local = T_pad // self.n_time
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, np.int64(self.n_filled),
                np.int64(self.base_ms), np.int64(self.dt_ms), w0s_v,
                w0e_v, np.int64(step))
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
        t_local, w0s, w0e, step = self._grid(steps, window_ms, offset_ms)
        arrs = self._aligned_arrs(tiles, func)
        sig = tuple(sorted((k, v.ndim) for k, v in arrs.items()))
        args = (arrs, np.int64(self.n_filled), np.int64(self.base_ms),
                np.int64(self.dt_ms), w0s, w0e, step)
        key = ("mesh-aligned", func, t_local, sig, self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_aligned_eval(
            self.mesh, func, t_local, 0, sig), cost_args=args)
        return fn(*args)[:self.S, :steps.size]

    def eval_aligned_batch(self, tiles, func: str, nsteps: int, step: int,
                           w0s_list: Sequence[int],
                           w0e_list: Sequence[int]) -> jnp.ndarray:
        from filodb_tpu.query.tilestore import _pad_pow2
        w0s_v = jnp.asarray(_pad_pow2(list(w0s_list)))
        w0e_v = jnp.asarray(_pad_pow2(list(w0e_list)))
        b_pad = int(w0s_v.shape[0])
        T_pad = -(-nsteps // self.n_time) * self.n_time
        t_local = T_pad // self.n_time
        arrs = self._aligned_arrs(tiles, func)
        sig = tuple(sorted((k, v.ndim) for k, v in arrs.items()))
        args = (arrs, np.int64(self.n_filled), np.int64(self.base_ms),
                np.int64(self.dt_ms), w0s_v, w0e_v, np.int64(step))
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
        t_local, w0s, w0e, step = self._grid(steps, window_ms, offset_ms)
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, self._row_gids(gids),
                np.int64(self.n_filled), np.int64(self.base_ms),
                np.int64(self.dt_ms), w0s, w0e, step)
        args = (agg,) + args
        key = ("mesh-grouped", func, agg, t_local, num_groups,
               self._mesh_key())
        fn = _jit_lookup(key, lambda: _build_grouped_eval(
            self.mesh, func, t_local, num_groups, agg), cost_args=args)
        return np.asarray(fn(*args))[:, :steps.size]

    def dispatch_grouped_pair(self, func: str, steps: np.ndarray,
                              window_ms: int, gids: np.ndarray,
                              num_groups: int, offset_ms: int = 0):
        """Enqueue the fused `sum by (g)` program off the resident store
        -> device (sums [T_pad, G], counts [T_pad, G]); the caller syncs
        and cuts to ``steps.size`` rows (``eval_grouped_pair`` does
        both). The program, its key and its arguments' shapes and dtypes
        are the same whether ``gids`` was on the devices already
        (``_row_gids``) or is put now."""
        t_local, w0s, w0e, step = self._grid(steps, window_ms, offset_ms)
        vv = self._cv if func in ("rate", "increase") else self._v
        args = (self._tsr, vv, self._row_gids(gids),
                np.int64(self.n_filled), np.int64(self.base_ms),
                np.int64(self.dt_ms), w0s, w0e, step)
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
        (sums [T, G], counts [T, G]) numpy, matching the Pallas
        group-sum kernel's return shape (TpuBackend.fused_groupsum)."""
        sums, cnts = self.dispatch_grouped_pair(
            func, steps, window_ms, gids, num_groups, offset_ms)
        T = steps.size
        return np.asarray(sums)[:T], np.asarray(cnts)[:T]

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
        self._aligned.clear()   # row-major placements are per-snapshot
        # the new tiles come with a tile entry, and so tile-order vectors,
        # of their own: the old ones' copies would never be asked for
        self._gids.kept.clear()
        self._record_residency()
        return True


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
        """The resident placement for ``tiles`` (built on first sight),
        or None when the tiles don't qualify."""
        if tiles is None or not ShardedTiles.tiles_eligible(tiles):
            return None
        key = id(tiles)
        with self._lock:
            got = self._placed.get(key)
            if got is not None:
                return got[1]
        with obs_trace.span("mesh-place", series=len(tiles.keys)):
            placed = ShardedTiles(self.mesh, tiles)

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
        re-places). Returns True when the donated path served."""
        with self._lock:
            got = self._placed.pop(id(old_tiles), None)
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
