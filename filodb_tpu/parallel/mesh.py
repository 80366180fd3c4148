"""Mesh scatter-gather execution of windowed range functions + aggregation.

This is the TPU-native replacement for the reference's distributed query tree
(coordinator/queryplanner/SingleClusterPlanner.scala:253 materialize →
per-shard MultiSchemaPartitionsExec leaves dispatched over Akka, gathered by
DistConcatExec / ReduceAggregateExec, AggrOverRangeVectors.scala:98,193
map-reduce):

  * shards ride the mesh **'shard' axis** (horizontal data partitioning —
    one shard's series tile lives on one device slice);
  * output query steps ride the **'time' axis** (sequence/context
    parallelism: each device slice computes a contiguous slice of the
    output step grid — windows only need that device's local series tile,
    which is replicated along 'time');
  * the cross-shard aggregation tree is `psum`/`pmax`/`pmin` over ICI —
    the collective IS ReduceAggregateExec;
  * grouped (`by (...)`) aggregation is a one-hot [S,G] matmul against the
    [S,T] result tile — an MXU op — followed by the same psum.

Wire format between host and device is dense padded tiles from
`pack_sharded` (CSR-ragged series → [shard, S_pad, N_pad]).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from filodb_tpu.lint.caches import cache_registry
from filodb_tpu.lint.contracts import kernel_contract
from filodb_tpu.lint.numerics import order_insensitive
from filodb_tpu.query.model import RangeParams, RawSeries
from filodb_tpu.query.tpu import (_GATHER_FUNCS, _TS_PAD, TpuBackend,
                                  _window_endpoint, _window_gather,
                                  _next_pow2, clean_rows)

# Aggregations executable as mesh collectives (AggrOverRangeVectors
# RowAggregator map/reduce protocol, aggregator/RowAggregator.scala:28).
MESH_AGGS = frozenset({"sum", "count", "avg", "min", "max", "group"})


def make_mesh(n_shard_groups: Optional[int] = None,
              time_parallel: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a ('shard', 'time') mesh over the available devices.

    n_shard_groups × time_parallel must equal the device count; by default
    all devices go on the shard axis (pure scatter-gather, like the
    reference's one-node-per-shard-group layout)."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    n = devs.size
    if n_shard_groups is None:
        n_shard_groups = n // time_parallel
    if n_shard_groups * time_parallel != n:
        raise ValueError(f"{n_shard_groups}x{time_parallel} != {n} devices")
    return Mesh(devs.reshape(n_shard_groups, time_parallel),
                ("shard", "time"))


def pack_sharded(series_by_shard: Sequence[Sequence[RawSeries]],
                 drop_nan: bool = True,
                 s_pad: Optional[int] = None,
                 n_pad: Optional[int] = None,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, List[List[Dict[str, str]]]]:
    """Pack per-shard ragged series into [G, S, N] tiles (G = shard groups).

    Equalizes series-count and sample-count across shards by padding
    (pow2-bucketized so XLA reuses compiled kernels). Padding series have
    len 0 and _TS_PAD timestamps so every kernel treats them as empty."""
    G = len(series_by_shard)
    maxlen, maxs = 1, 1
    cleaned: List[List[Tuple[np.ndarray, np.ndarray]]] = []
    keys: List[List[Dict[str, str]]] = []
    for group in series_by_shard:
        row, ml = clean_rows(group, drop_nan)
        cleaned.append(row)
        keys.append([dict(s.labels) for s in group])
        maxlen = max(maxlen, ml)
        maxs = max(maxs, len(row))
    S = s_pad or _next_pow2(maxs, 1)
    N = n_pad or _next_pow2(maxlen)
    ts_pad = np.full((G, S, N), _TS_PAD, dtype=np.int64)
    vals_pad = np.zeros((G, S, N), dtype=np.float64)
    lens = np.zeros((G, S), dtype=np.int32)
    for g, row in enumerate(cleaned):
        for i, (ts, vals) in enumerate(row):
            n = ts.size
            ts_pad[g, i, :n] = ts
            vals_pad[g, i, :n] = vals
            lens[g, i] = n
    return ts_pad, vals_pad, lens, keys


def _grouped_reduce_check():
    """Abstract check under a minimal 1-device ('shard','time') mesh:
    shard_map traces on CPU, nothing executes."""
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("shard", "time"))
    S, T, G = 8, 16, 4
    f = jax.shard_map(
        lambda loc, g: _grouped_reduce(loc, g, G, "sum"),
        mesh=mesh, in_specs=(P("shard", None), P("shard")),
        out_specs=P(), check_vma=False)
    out = jax.eval_shape(f, jax.ShapeDtypeStruct((S, T), jnp.float64),
                         jax.ShapeDtypeStruct((S,), jnp.int32))
    if tuple(out.shape) != (G, T) or str(out.dtype) != "float64":
        return f"grouped reduce {out.shape}/{out.dtype} != ({G},{T}) f64"
    return None


@order_insensitive(
    "grouped-reduce-psum", tolerance=1e-12,
    reason="the sum/avg family psums f64 per-device partial "
           "aggregates whose grouping follows the shard-axis device "
           "count; each per-device partial is a one-hot matmul of at "
           "most S/n_dev f64 terms, so regrouping moves the result by "
           "at most a few f64 ulps — certified at 1/2/4/8 virtual "
           "devices. min/max ride pmin/pmax (order-free) and counts "
           "are integers in f64 (exact below 2**53)")
@kernel_contract(
    "mesh_grouped_reduce", kind="shard_map",
    check=_grouped_reduce_check,
    notes="per-device one-hot [S,G] matmul / segment min-max, then "
          "psum/pmin/pmax over the 'shard' axis — ReduceAggregateExec "
          "as a collective; requires a ('shard','time') mesh context")
def _grouped_reduce(local: jnp.ndarray, gids: jnp.ndarray, num_groups: int,
                    agg: str) -> jnp.ndarray:
    """[S,T] per-series windowed results + [S] group ids → [G,T] partial
    aggregate for this device, then collective over 'shard'.

    Sum-family runs as a one-hot [S,G] matmul (MXU); min/max as segment
    reductions; NaN (stale/empty) entries contribute nothing. Mean is
    sum/count reduced separately (AvgRowAggregator keeps (mean, count)
    pairs — same math, batched).

    Padding rows carry the sentinel gid -1: their one-hot row is all-zero
    and their entries are masked out, so functions that map empty rows to
    non-NaN values (absent_over_time -> 1.0) cannot contaminate group 0,
    while a REAL series with zero samples still aggregates normally."""
    valid = (gids >= 0)[:, None]                       # [S, 1]
    ok = ~jnp.isnan(local) & valid
    local = jnp.where(valid, local, jnp.nan)
    gids = jnp.where(valid[:, 0], gids, 0)
    onehot = ((gids[:, None] == jnp.arange(num_groups)[None, :])
              & valid).astype(local.dtype)             # [S, G]
    cnt = onehot.T @ ok.astype(local.dtype)            # [G, T]
    cnt = jax.lax.psum(cnt, "shard")
    if agg == "count":
        return jnp.where(cnt > 0, cnt, jnp.nan)
    if agg == "group":
        return jnp.where(cnt > 0, 1.0, jnp.nan)
    if agg in ("sum", "avg"):
        s = jax.lax.psum(onehot.T @ jnp.where(ok, local, 0.0), "shard")
        if agg == "avg":
            s = s / cnt
        return jnp.where(cnt > 0, s, jnp.nan)
    if agg in ("min", "max"):
        big = jnp.inf if agg == "min" else -jnp.inf
        masked = jnp.where(ok, local, big)              # [S, T]
        segf = jax.ops.segment_min if agg == "min" else jax.ops.segment_max
        red = segf(masked, gids, num_segments=num_groups)  # [G, T]
        red = (jax.lax.pmin if agg == "min" else jax.lax.pmax)(red, "shard")
        return jnp.where(cnt > 0, red, jnp.nan)
    raise ValueError(f"unhandled mesh agg {agg}")


# cache inventory: the cached_property executables (_step/_step_topk)
# close over ONE mesh instance and specialize per static kernel shape —
# world-independent by construction; a topology change builds a new
# executor, never mutates this one
@cache_registry("mesh-executable", keyed=("mesh", "kernel-shape"))
class MeshExecutor:
    """Distributed query step executor over a ('shard','time') mesh.

    The single entry point `window_aggregate` fuses the reference's whole
    per-query pipeline below the planner — SelectRawPartitions (already
    packed) → PeriodicSamplesMapper → AggregateMapReduce → ReduceAggregate
    — into one pjit'd program with collectives."""

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        # first-sight keys for compile-event telemetry (one executor is
        # driven by one query engine call at a time; races would only
        # double-count a build event)
        self._exec_seen: set = set()

    @functools.cached_property
    def _step(self):
        mesh = self.mesh

        @functools.partial(
            jax.jit,
            static_argnames=("func", "agg", "num_groups", "nsteps_local",
                             "w_bound"))
        def run(func, agg, num_groups, nsteps_local, w_bound, ts, vals,
                lens, gids, w0s, w0e, step, scalar):
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P("shard", None, None), P("shard", None, None),
                          P("shard", None), P("shard", None),
                          P(), P(), P(), P()),
                out_specs=P(None, "time"))
            def inner(ts, vals, lens, gids, w0s, w0e, step, sc):
                # local tiles arrive [G_local, S, N]; collapse shard groups
                gl, S, N = ts.shape
                ts2, vals2 = ts.reshape(gl * S, N), vals.reshape(gl * S, N)
                lens2, gids2 = lens.reshape(-1), gids.reshape(-1)
                # this device's slice of the step grid (sequence parallel)
                t_off = jax.lax.axis_index("time").astype(
                    jnp.int64) * nsteps_local * step
                if func in _GATHER_FUNCS:
                    local = _window_gather(func, w_bound, ts2, vals2, lens2,
                                           w0s + t_off, w0e + t_off, step,
                                           nsteps_local, sc)   # [S_l, T_l]
                else:
                    local = _window_endpoint(func, ts2, vals2, lens2,
                                             w0s + t_off, w0e + t_off, step,
                                             nsteps_local, sc)
                return _grouped_reduce(local, gids2, num_groups,
                                       agg)                    # [G, T_l]
            return inner(ts, vals, lens, gids,
                         jnp.asarray(w0s, jnp.int64),
                         jnp.asarray(w0e, jnp.int64),
                         jnp.asarray(step, jnp.int64),
                         jnp.asarray(scalar, dtype=jnp.float64))
        return run


    def _prepare_inputs(self, series_by_shard, params, func, window_ms,
                        group_ids_by_shard, offset_ms):
        """Shared packing/padding prologue for the windowed mesh entry
        points: [G,S,N] tiles, padded gid table, step-grid scalars and the
        static per-window sample bound."""
        n_shard = self.mesh.shape["shard"]
        n_time = self.mesh.shape["time"]
        if len(series_by_shard) % n_shard:
            raise ValueError("shard groups must divide mesh shard axis")
        ts, vals, lens, _ = pack_sharded(series_by_shard,
                                         drop_nan=(func != "last_sample"))
        G, S, _ = ts.shape
        gids = np.full((G, S), -1, dtype=np.int32)   # -1 marks padding rows
        for g, row in enumerate(group_ids_by_shard):
            gids[g, :len(row)] = row
        steps = params.steps
        T = steps.size
        T_pad = -(-T // n_time) * n_time
        step = np.int64(params.step_ms if T > 1 else 1)
        w0e = np.int64(steps[0] - offset_ms)
        w0s = np.int64(w0e - window_ms)
        w_bound = 0
        if func in _GATHER_FUNCS:
            all_series = [s for row in series_by_shard for s in row]
            w_bound = TpuBackend._window_sample_bound(
                all_series, window_ms, ts.shape[2])
        return (ts, vals, lens, gids, T, T_pad // n_time, step, w0s, w0e,
                w_bound, S)

    @functools.cached_property
    def _step_topk(self):
        mesh = self.mesh

        @functools.partial(
            jax.jit,
            static_argnames=("func", "num_groups", "k", "bottom",
                            "nsteps_local", "w_bound"))
        def run(func, num_groups, k, bottom, nsteps_local, w_bound, ts,
                vals, lens, gids, w0s, w0e, step, scalar):
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P("shard", None, None), P("shard", None, None),
                          P("shard", None), P("shard", None),
                          P(), P(), P(), P()),
                out_specs=(P(None, "time", None), P(None, "time", None)),
                # outputs ARE shard-replicated (derived from an all_gather
                # over 'shard') but the static checker can't prove it
                check_vma=False)
            def inner(ts, vals, lens, gids, w0s, w0e, step, sc):
                gl, S, N = ts.shape
                ts2, vals2 = ts.reshape(gl * S, N), vals.reshape(gl * S, N)
                lens2, gids2 = lens.reshape(-1), gids.reshape(-1)
                t_off = jax.lax.axis_index("time").astype(
                    jnp.int64) * nsteps_local * step
                if func in _GATHER_FUNCS:
                    local = _window_gather(func, w_bound, ts2, vals2, lens2,
                                           w0s + t_off, w0e + t_off, step,
                                           nsteps_local, sc)
                else:
                    local = _window_endpoint(func, ts2, vals2, lens2,
                                             w0s + t_off, w0e + t_off, step,
                                             nsteps_local, sc)
                # per-group per-step local top-k, then a cross-shard
                # all_gather + re-top-k — the TopBottomK reduce tree as a
                # collective (aggregator TopBottomKRowAggregator)
                sign = -1.0 if bottom else 1.0
                score = jnp.where(jnp.isnan(local), -jnp.inf, sign * local)
                score = jnp.where((gids2 >= 0)[:, None], score, -jnp.inf)
                dev = jax.lax.axis_index("shard").astype(jnp.int32)
                row_ids = dev * (gl * S) + jnp.arange(gl * S,
                                                      dtype=jnp.int32)
                ong = gids2[None, :] == jnp.arange(num_groups)[:, None]
                sc_g = jnp.where(ong[:, :, None], score[None, :, :],
                                 -jnp.inf)              # [G, S_l, T_l]
                sc_t = jnp.transpose(sc_g, (0, 2, 1))   # [G, T_l, S_l]
                kk = min(k, sc_t.shape[-1])
                top_v, top_i = jax.lax.top_k(sc_t, kk)
                top_ids = row_ids[top_i]
                if kk < k:
                    pad = sc_t.shape[:2] + (k - kk,)
                    top_v = jnp.concatenate(
                        [top_v, jnp.full(pad, -jnp.inf)], -1)
                    top_ids = jnp.concatenate(
                        [top_ids, jnp.full(pad, -1, jnp.int32)], -1)
                all_v = jax.lax.all_gather(top_v, "shard")
                all_ids = jax.lax.all_gather(top_ids, "shard")
                n_sh = all_v.shape[0]
                cat_v = jnp.transpose(all_v, (1, 2, 0, 3)).reshape(
                    num_groups, -1, n_sh * k)
                cat_i = jnp.transpose(all_ids, (1, 2, 0, 3)).reshape(
                    num_groups, -1, n_sh * k)
                fin_v, slot = jax.lax.top_k(cat_v, k)   # [G, T_l, k]
                fin_ids = jnp.take_along_axis(cat_i, slot, axis=-1)
                ok = jnp.isfinite(fin_v)
                return (jnp.where(ok, sign * fin_v, jnp.nan),
                        jnp.where(ok, fin_ids, -1))
            return inner(ts, vals, lens, gids,
                         jnp.asarray(w0s, jnp.int64),
                         jnp.asarray(w0e, jnp.int64),
                         jnp.asarray(step, jnp.int64),
                         jnp.asarray(scalar, dtype=jnp.float64))
        return run

    def window_topk(self,
                    series_by_shard: Sequence[Sequence[RawSeries]],
                    params: RangeParams,
                    function: str,
                    window_ms: int,
                    k: int,
                    bottom: bool,
                    group_ids_by_shard: Sequence[Sequence[int]],
                    num_groups: int,
                    func_args: Sequence[float] = (),
                    offset_ms: int = 0):
        """topk/bottomk over the mesh. Returns (values [G, T, k],
        row_ids [G, T, k], S_pad) — row_id // S_pad is the shard group,
        row_id % S_pad the series index within it (-1 = empty slot)."""
        func = function or "last_sample"
        if params.steps.size == 0:
            return (np.empty((num_groups, 0, k)),
                    np.full((num_groups, 0, k), -1, np.int32), 1)
        (ts, vals, lens, gids, T, t_local, step, w0s, w0e, w_bound,
         S) = self._prepare_inputs(series_by_shard, params, func,
                                   window_ms, group_ids_by_shard,
                                   offset_ms)
        sc = float(func_args[0]) if func_args else 0.0
        self._note_exec(
            ("topk", func, int(k), bool(bottom), t_local,
             tuple(ts.shape), self.ndev),
            probe=self._cost_probe(self._step_topk,
                                   (func, num_groups, int(k),
                                    bool(bottom), t_local, w_bound),
                                   (ts, vals, lens, gids),
                                   (w0s, w0e, step, sc)))
        out_v, out_i = self._step_topk(
            func, num_groups, int(k), bool(bottom), t_local,
            w_bound, ts, vals, lens, gids, w0s, w0e, step, sc)
        return np.asarray(out_v)[:, :T], np.asarray(out_i)[:, :T], S

    def window_aggregate(self,
                         series_by_shard: Sequence[Sequence[RawSeries]],
                         params: RangeParams,
                         function: str,
                         window_ms: int,
                         agg: str,
                         group_ids_by_shard: Sequence[Sequence[int]],
                         num_groups: int,
                         func_args: Sequence[float] = (),
                         offset_ms: int = 0) -> np.ndarray:
        """Returns the [num_groups, T] aggregated grid."""
        if agg not in MESH_AGGS:
            raise ValueError(f"agg {agg} not mesh-executable")
        func = function or "last_sample"
        if params.steps.size == 0:
            return np.empty((num_groups, 0), dtype=np.float64)
        (ts, vals, lens, gids, T, t_local, step, w0s, w0e, w_bound,
         _) = self._prepare_inputs(series_by_shard, params, func,
                                   window_ms, group_ids_by_shard,
                                   offset_ms)
        sc = float(func_args[0]) if func_args else 0.0
        self._note_exec(
            ("agg", func, agg, t_local, tuple(ts.shape), self.ndev),
            probe=self._cost_probe(self._step,
                                   (func, agg, num_groups, t_local,
                                    w_bound),
                                   (ts, vals, lens, gids),
                                   (w0s, w0e, step, sc)))
        out = self._step(func, agg, num_groups,
                         t_local, w_bound, ts, vals, lens, gids,
                         w0s, w0e, step, sc)
        return np.asarray(out)[:, :T]

    @property
    def ndev(self) -> int:
        """Device count of the executor's mesh — the per-(kernel,
        device-count) attribution atom every mesh executable key
        carries, so /metrics and &explain=analyze show 1/2/4/8-device
        compiles of the same kernel as distinct executables."""
        return int(self.mesh.devices.size)

    @staticmethod
    def _cost_probe(jitted, statics, arrays, scalars):
        """() -> Compiled lazy cost probe over the abstract signature
        (the tilestore AOT pattern, deferred: the first
        &explain=analyze touching the executable pays the compile,
        serving dispatches never do). Closes over ShapeDtypeStructs,
        never the tiles themselves."""
        abstract = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in (np.asarray(x) for x in arrays))
        consts = tuple(np.asarray(s) for s in scalars)

        def probe():
            return jitted.lower(*statics, *abstract, *consts).compile()
        return probe

    def _note_exec(self, key, probe=None) -> None:
        """Compile/dispatch telemetry for the mesh-executable cache
        (obs/devprof.py): per (kernel, static shape, device count) key
        — first sight is the shard_map trace + pjit compile (and
        registers the lazy cost probe for XLA cost_analysis capture),
        later dispatches reuse the jit cache. Feeds the
        filodb_executable_* families and the &explain=analyze
        executable attribution."""
        from filodb_tpu.obs import devprof
        first = key not in self._exec_seen
        if first:
            self._exec_seen.add(key)
        devprof.note_dispatch("mesh", key, first,
                              probe=probe if first else None)
