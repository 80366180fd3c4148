"""Device-resident aligned tile store: the TPU-native in-memory chunk store.

FiloDB keeps hot chunks in off-heap memory and scans them per query
(core/memstore/TimeSeriesShard.scala, store/ChunkSetInfo.scala:432
WindowedChunkIterator). The TPU equivalent keeps each series as a row in a
**cadence-aligned device tile**: slot ``i`` nominally holds the sample
scraped at time ``i*dt`` (epoch-aligned, like DeltaDeltaVector's const
variant for regular timestamps — memory/format/vectors/DeltaDeltaVector.scala).

Because slots are global, every window boundary maps to the SAME slot
column for all series (+/-1 for scrape jitter), so the windowed hot loop
needs **no per-row gathers** — only shared-column takes, which are ~free
on TPU (vs ~40ns/element for per-row dynamic gathers). Gaps and jitter are
handled exactly:

  * pack time (once per tile publication, amortized over queries):
    validity mask, true timestamps, counter-reset correction, forward/
    backward fills (value+ts at last/first valid slot), inclusive prefix
    sums of any per-sample channel;
  * query time: boundary slots ``K_lo/K_hi`` from closed-form arithmetic,
    2-candidate jitter resolution (a slot's sample can straddle the window
    edge by < dt/2), prefix-difference window sums with edge-slot
    adjustments.

Series whose timestamps don't fit a shared cadence grid (collisions,
irregular scrape) fall back to the general packed path in tpu.py.
"""

from __future__ import annotations

import functools as _functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from filodb_tpu.lint.capacity import capacity
from filodb_tpu.lint.contracts import kernel_contract
from filodb_tpu.lint.numerics import order_insensitive, precision  # noqa: F401
from filodb_tpu.memory import histogram as bh
from filodb_tpu.query import pallas_kernels as pk
from filodb_tpu.query.cumsum import cumsum_f64
from filodb_tpu.query.model import RawSeries

# functions servable from aligned tiles (everything endpoint- or
# prefix-sum-expressible; order statistics fall back to the gather path)
ALIGNED_FUNCS = frozenset({
    "rate", "increase", "delta",
    "sum_over_time", "count_over_time", "avg_over_time",
    "stddev_over_time", "stdvar_over_time", "z_score",
    "changes", "resets", "timestamp",
    "last_sample", "last_over_time", "first_over_time",
    "present_over_time", "absent_over_time",
    "rate_over_delta", "increase_over_delta",
})


def _ffill_idx(valid: jnp.ndarray) -> jnp.ndarray:
    """[S,N] bool -> j_last[s,i] = last valid slot <= i (-1 if none)."""
    idx = jnp.arange(valid.shape[1], dtype=jnp.int32)[None, :]
    return jax.lax.cummax(jnp.where(valid, idx, jnp.int32(-1)), axis=1)


def _counter_corrected(v, valid, ff_v):
    """Counter-reset corrected value channel [S, N]: every sample plus
    the running sum of the values the counter dropped from (``ff_v`` =
    forward-filled previous valid values)."""
    prev = jnp.concatenate([jnp.full_like(ff_v[:, :1], jnp.nan),
                            ff_v[:, :-1]], axis=1)
    drop = valid & (v < prev) & ~jnp.isnan(prev)
    c = v + cumsum_f64(jnp.where(drop, prev, 0.0), axis=1)
    return jnp.where(valid, c, 0.0)


@capacity(
    "tilestore-aligned-tiles", bytes_per_sample=17.0,
    reason="the base device residency of an aligned cohort is three "
           "[S, N] tiles — validity bool (1 B) + true-timestamp f64 "
           "(8 B) + value f64 (8 B) = 17 B per slot; the derived "
           "channels (ones/cv/prefix sums/transposes) are lazy "
           "per-function warm caches over the same slot count, not "
           "part of the cold footprint")
class AlignedTiles:
    """One cohort of series sharing cadence dt, as device tiles."""

    def __init__(self, keys: List[Dict[str, str]], base_ms: int, dt_ms: int,
                 valid: np.ndarray, ts_true: np.ndarray, vals: np.ndarray):
        self.keys = keys
        self.base_ms = int(base_ms)          # time of slot 0
        self.dt_ms = int(dt_ms)
        S, N = vals.shape[:2]
        self.num_slots = N
        self.valid = jnp.asarray(valid)                      # [S,N] bool
        # true timestamps as f64 ms (exact to 2^53); invalid -> NaN so
        # boundary conditions (ts <= wend) are false on gaps
        self.ts = jnp.where(self.valid, jnp.asarray(ts_true, jnp.float64),
                            jnp.nan)
        # [S,N], or [S,N,B] for histograms (HistTiles)
        self.vals = jnp.where(
            self.valid if vals.ndim == 2 else self.valid[..., None],
            jnp.asarray(vals), 0.0)
        self._channels: Dict[str, jnp.ndarray] = {}
        self._ff: Dict[str, jnp.ndarray] = {}
        self._bf: Dict[str, jnp.ndarray] = {}
        self._ps: Dict[str, jnp.ndarray] = {}
        self._tch: Dict[str, jnp.ndarray] = {}
        self._tff: Dict[str, jnp.ndarray] = {}
        self._tbf: Dict[str, jnp.ndarray] = {}
        self._tps: Dict[str, jnp.ndarray] = {}
        self._tperm: Dict[Tuple[str, int], jnp.ndarray] = {}
        self._jitter = None
        self._jl = None
        self._jf = None
        self._dense = bool(np.asarray(valid).all())

    # -- pack-time derived channels (cached) ---------------------------------

    def channel(self, name: str) -> jnp.ndarray:
        """Per-slot f64 channel (0 at invalid slots)."""
        c = self._channels.get(name)
        if c is not None:
            return c
        v, valid = self.vals, self.valid
        if name == "v":
            c = v
        elif name == "ones":
            c = valid.astype(jnp.float64)
        elif name == "vc2":
            # squared deviation from a per-series shift (the series mean):
            # windowed variance from prefix sums of (x-c)^2 avoids the
            # catastrophic cancellation of the E[x^2]-mean^2 form
            d = jnp.where(valid, v - self.vshift[:, None], 0.0)
            c = d * d
        elif name == "ts":
            c = jnp.where(valid, self.ts, 0.0)
        elif name == "cv":                      # counter-reset corrected
            c = _counter_corrected(v, valid, self.ff("v"))
        elif name in ("ev_change", "ev_reset"):
            # event vs previous valid sample, attributed to the later one
            # (AggrOverTimeFunctions ChangesChunkedFunction semantics)
            prev = self.ff("v")[:, :-1]
            prev = jnp.concatenate([jnp.full_like(prev[:, :1], jnp.nan),
                                    prev], axis=1)
            if name == "ev_change":
                ev = valid & (v != prev) & ~jnp.isnan(prev)
            else:
                ev = valid & (v < prev) & ~jnp.isnan(prev)
            c = ev.astype(jnp.float64)
        else:
            raise KeyError(name)
        self._channels[name] = c
        return c

    @property
    def vshift(self) -> jnp.ndarray:
        """Per-series shift for stable variance: mean of valid samples."""
        c = self._channels.get("_vshift")
        if c is None:
            okf = self.valid & jnp.isfinite(self.vals)
            cnt = jnp.maximum(okf.sum(axis=1), 1)
            c = jnp.where(okf, self.vals, 0.0).sum(axis=1) / cnt
            self._channels["_vshift"] = c
        return c

    def ff(self, name: str) -> jnp.ndarray:
        """Forward fill: channel value at last valid slot <= i (NaN none)."""
        if self._dense:
            # fully-valid tiles: the fill is the channel itself (aliased,
            # no extra HBM — the common dense-scrape case)
            return self.ts if name == "ts" else self.channel(name)
        c = self._ff.get(name)
        if c is None:
            if self._jl is None:
                self._jl = _ffill_idx(self.valid)
            src = self.channel(name) if name != "ts" else self.ts
            gathered = jnp.take_along_axis(
                jnp.concatenate([jnp.full_like(src[:, :1], jnp.nan), src],
                                axis=1),
                (self._jl + 1).astype(jnp.int32), axis=1)
            c = gathered
            self._ff[name] = c
        return c

    def bf(self, name: str) -> jnp.ndarray:
        """Backward fill: channel value at first valid slot >= i."""
        if self._dense:
            return self.ts if name == "ts" else self.channel(name)
        c = self._bf.get(name)
        if c is None:
            if self._jf is None:
                rev = jnp.flip(self.valid, axis=1)
                self._jf = (self.valid.shape[1] - 1
                            - jnp.flip(_ffill_idx(rev), axis=1)).astype(
                                jnp.int32)
            src = self.channel(name) if name != "ts" else self.ts
            N = src.shape[1]
            gathered = jnp.take_along_axis(
                jnp.concatenate([src, jnp.full_like(src[:, :1], jnp.nan)],
                                axis=1),
                jnp.clip(self._jf, 0, N), axis=1)
            c = gathered
            self._bf[name] = c
        return c

    def prefix(self, name: str) -> jnp.ndarray:
        """Inclusive prefix sum of a channel, with a leading 0 column:
        ps[:, k+1] = sum of slots 0..k. Shape [S, N+1]."""
        c = self._ps.get(name)
        if c is None:
            cs = cumsum_f64(self.channel(name), axis=1)
            c = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)
            self._ps[name] = c
        return c

    def warm(self, names_ff: Sequence[str] = (), names_bf: Sequence[str] = (),
             names_ps: Sequence[str] = ()) -> None:
        for n in names_ff:
            self.ff(n)
        for n in names_bf:
            self.bf(n)
        for n in names_ps:
            self.prefix(n)

    # -- transposed (slot-major) channels --------------------------------
    # [N, S] layout: one query step's shared slot column is a CONTIGUOUS
    # row, so the per-step gathers of the windowed evaluator read
    # sequential HBM instead of stride-N*8 columns (~4x faster on TPU).
    # Built lazily and cached like the row-major channels.

    def _t(self, cache_name: str, name: str, builder) -> jnp.ndarray:
        cache = getattr(self, cache_name)
        c = cache.get(name)
        if c is None:
            c = jnp.asarray(builder(name).T)
            cache[name] = c
        return c

    def t_ts(self) -> jnp.ndarray:
        return self._t("_tch", "ts_nan", lambda _: self.ts)

    def t_channel(self, name: str) -> jnp.ndarray:
        return self._t("_tch", name, self.channel)

    def t_ff(self, name: str) -> jnp.ndarray:
        if self._dense:     # alias: no second transposed copy
            return self.t_ts() if name == "ts" else self.t_channel(name)
        return self._t("_tff", name, self.ff)

    def t_bf(self, name: str) -> jnp.ndarray:
        if self._dense:
            return self.t_ts() if name == "ts" else self.t_channel(name)
        return self._t("_tbf", name, self.bf)

    def t_prefix(self, name: str) -> jnp.ndarray:
        return self._t("_tps", name, self.prefix)

    # -- int32 relative-time channels for the f32-hybrid fast path -------
    # Timestamps as int32 ms relative to base_ms: exact (guarded to spans
    # < 2^31 ms ≈ 24.8 days by the dispatcher), and boundary compares/
    # subtractions become native int32 ops instead of software-emulated
    # f64 — TPU v5e has no f64 ALU, so the all-f64 evaluator is compute-
    # bound on float-float emulation, not HBM.

    def t_tsr_i32(self) -> jnp.ndarray:
        """[N, S] int32: ts - base_ms (0 at invalid slots)."""
        c = self._tch.get("tsr_i32")
        if c is None:
            rel = jnp.where(self.valid, self.ts - self.base_ms, 0.0)
            c = jnp.asarray(rel.T).astype(jnp.int32)
            self._tch["tsr_i32"] = c
        return c

    def t_ff_tsr_i32(self) -> jnp.ndarray:
        """Forward-filled relative ts; INT32_MIN where no valid slot <= i."""
        if self._dense:
            return self.t_tsr_i32()
        c = self._tch.get("ff_tsr_i32")
        if c is None:
            f = self.ff("ts")
            rel = jnp.where(jnp.isnan(f), float(_SENT_LO),
                            f - self.base_ms)
            c = jnp.asarray(rel.T).astype(jnp.int32)
            self._tch["ff_tsr_i32"] = c
        return c

    def t_bf_tsr_i32(self) -> jnp.ndarray:
        """Backward-filled relative ts; INT32_MAX where no valid slot >= i."""
        if self._dense:
            return self.t_tsr_i32()
        c = self._tch.get("bf_tsr_i32")
        if c is None:
            f = self.bf("ts")
            rel = jnp.where(jnp.isnan(f), float(_SENT_HI),
                            f - self.base_ms)
            c = jnp.asarray(rel.T).astype(jnp.int32)
            self._tch["bf_tsr_i32"] = c
        return c

    def t_consts(self) -> jnp.ndarray:
        """int64[3] on the device: num_slots, base_ms, dt_ms, what the
        fused programs over holes and histograms read of the tiles
        besides their channels; put once, so that a request hands over
        its grid alone."""
        c = self._tch.get("consts")
        if c is None:
            c = jax.device_put(np.array(
                [self.num_slots, self.base_ms, self.dt_ms], np.int64))
            self._tch["consts"] = c
        return c

    def t_ones_i8(self) -> jnp.ndarray:
        c = self._tch.get("ones_i8")
        if c is None:
            c = jnp.asarray(self.valid.T).astype(jnp.int8)
            self._tch["ones_i8"] = c
        return c

    def t_ps_ones_i32(self) -> jnp.ndarray:
        """[N+1, S] int32 inclusive prefix count with leading 0 row."""
        c = self._tch.get("ps_ones_i32")
        if c is None:
            cs = jnp.cumsum(self.valid.astype(jnp.int32), axis=1)
            ps = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)
            c = jnp.asarray(ps.T)
            self._tch["ps_ones_i32"] = c
        return c

    # -- stride-permuted channels for the slide evaluator ----------------
    # Row gathers (jnp.take of T rows) lower to a TPU gather that runs at
    # ~140 GB/s; contiguous/strided slices stream at ~850 GB/s (measured
    # on v5e). For a REGULAR query grid (step % dt == 0, stride st =
    # step//dt) the T boundary rows of each take are k0, k0+st, ... — so
    # storing the [N, S] channel permuted by residue class as [st, G, S]
    # (row k at [k % st, k // st]) turns every take into ONE contiguous
    # dynamic_slice of shape (1, T, S). Cached per (channel, stride);
    # dashboards reuse one stride, so the copy amortizes like the other
    # derived channels.

    def t_perm(self, name: str, st: int, src: jnp.ndarray) -> jnp.ndarray:
        key = (name, st)
        c = self._tperm.get(key)
        if c is None:
            N = src.shape[0]
            G = -(-N // st)
            pad = G * st - N
            if pad:
                fill = jnp.zeros((pad,) + src.shape[1:], src.dtype)
                src = jnp.concatenate([src, fill], axis=0)
            c = jnp.asarray(jnp.swapaxes(
                src.reshape(G, st, *src.shape[1:]), 0, 1))
            self._tperm[key] = c
        return c

    def t_perm_tiled(self, name: str, st: int, src: jnp.ndarray
                     ) -> jnp.ndarray:
        """Stride-permuted AND s-tile-major channel for the Pallas
        group-sum kernel: [n_s, st, G, SS] with SS = kernel lane tile.
        Within one (s-tile, residue) plane, consecutive G rows are
        CONTIGUOUS in HBM, so each kernel DMA is one large linear read
        (the plain [st, G, S] layout would make per-s-tile blocks
        strided 4KB chunks). S is padded to a multiple of SS; G is
        padded past the kernel's tail tile like t_perm."""
        key = (name + "#tiled", st)
        c = self._tperm.get(key)
        if c is None:
            N = src.shape[0]
            S = src.shape[1]
            # pad the permuted G axis past every tail tile: the kernel's
            # merged kc/kl stream reads up to dspan (<= _GS_DSPAN_MAX)
            # + alignment rows past the last window-end row — sized for
            # the WIDEST step tile the pipeline chooser can pick
            G = (-(-N // st) + pk._GS_TT_WIDE + 2 * pk._GS_AL
                 + pk._GS_DSPAN_MAX)
            padn = G * st - N
            if padn:
                src = jnp.concatenate(
                    [src, jnp.zeros((padn, S), src.dtype)], axis=0)
            S_pad = -(-S // pk._GS_SS) * pk._GS_SS
            if S_pad != S:
                src = jnp.concatenate(
                    [src, jnp.zeros((G * st, S_pad - S), src.dtype)],
                    axis=1)
            c = jnp.asarray(
                src.reshape(G, st, S_pad // pk._GS_SS, pk._GS_SS)
                .transpose(2, 1, 0, 3))
            self._tperm[key] = c
        return c

    @precision(
        "fixed-point-split", bits=61, rel_ulps=4,
        reason="exact int32 hi/lo split: |v - mid| * 2**s <= 2**60, so "
               "boundary subtractions in the group-sum kernel are "
               "exact integer ops; only the final f32 recombine "
               "rounds, relative to the delta, with a fixed-point "
               "quantization floor of span * 2**-59 — certified "
               "against the direct f64 delta")
    def _fixed_channels(self, vch: str):
        """Per-series 61-bit fixed-point encoding of a value channel for
        the group-sum kernel: each series is rebased to its in-tile
        midpoint and scaled by a per-series power of two 2^s chosen so
        |v - mid| * 2^s <= 2^60, then split as hi*2^31 + lo with lo in
        [0, 2^31). Integer boundary subtractions in the kernel are then
        EXACT; only the final f32 recombine rounds, relative to the
        delta — the same noise floor as the reference's f64 arithmetic
        (rangefn/RateFunctions.scala:23).

        Returns (hi [N,S] i32, lo [N,S] i32, mid_f32 [S], s [S] i32) or
        None when the channel has non-finite values."""
        key = (vch, "#fixed")
        c = self._tperm.get(key)
        if c is None:
            v = self.t_channel(vch)                      # [N, S] f64
            vmax = jnp.max(v, axis=0)
            vmin = jnp.min(v, axis=0)
            if not bool(jnp.isfinite(vmax).all()
                        & jnp.isfinite(vmin).all()):
                self._tperm[key] = (None,)
                return None
            mid = (vmax + vmin) * 0.5
            # host-side scale selection ([S]-sized; f64 frexp has no TPU
            # lowering): span2 <= 2^e with frexp's m in [0.5, 1)
            span2 = np.maximum(np.asarray(vmax - vmin) * 0.5, 2.0 ** -130)
            _, e = np.frexp(span2)
            if np.any(60 - e < -96):
                # a span this wide (> 2^156) cannot be represented in
                # the 61-bit fixed-point channel at any in-range scale:
                # clipping the exponent would silently WRAP int64 and
                # corrupt results — take the exact f64 fallback instead
                self._tperm[key] = (None,)
                return None
            s_np = np.clip(60 - e, -96, 126).astype(np.int32)
            s = jnp.asarray(s_np)
            scale = jnp.asarray(np.ldexp(1.0, s_np))
            fixed = jnp.rint(
                (v - mid[None, :]) * scale[None, :]
            ).astype(jnp.int64)
            hi64 = fixed >> 31
            lo = (fixed - (hi64 << 31)).astype(jnp.int32)
            c = (hi64.astype(jnp.int32), lo,
                 mid.astype(jnp.float32), s)
            self._tperm[key] = c
        return None if c == (None,) else c

    def t_perm_fixed_tiled(self, vch: str, st: int) -> jnp.ndarray:
        """The Pallas group-sum kernel's packed channel: s-tile-major
        stride-permuted [n_s, st, G, 3*SS] i32 where plane 0 is the
        int32 relative timestamp and planes 1-2 are the per-series
        fixed-point hi/lo split of the value channel (_fixed_channels).
        One kernel DMA per boundary stream fetches timestamps + values
        as a single contiguous read (see t_perm_tiled)."""
        key = (vch + "#fixed_tiled", st)
        c = self._tperm.get(key)
        if c is None:
            fx = self._fixed_channels(vch)
            assert fx is not None, "dispatcher must gate on finiteness"
            hi, lo = fx[0], fx[1]
            parts = [self.t_perm_tiled(f"{vch}#fx{i}", st, ch)
                     for i, ch in enumerate(
                         (self.t_tsr_i32(), hi, lo))]
            c = jnp.asarray(jnp.concatenate(parts, axis=3))
            for i in range(3):
                self._tperm.pop((f"{vch}#fx{i}" + "#tiled", st), None)
            self._tperm[key] = c
        return c

    def t_fixed_base(self, vch: str) -> jnp.ndarray:
        """[n_s, 8, SS] f32 companion of t_perm_fixed_tiled: row 0 =
        per-series rebase midpoint (f32, used only by the counter-zero
        extrapolation limiter), row 1 = 2^(31-s), row 2 = 2^-s."""
        key = (vch + "#fixed_base", 0)
        c = self._tperm.get(key)
        if c is None:
            fx = self._fixed_channels(vch)
            assert fx is not None
            mid, s = fx[2], fx[3]
            c1 = jnp.ldexp(jnp.float32(1.0), 31 - s)
            c2 = jnp.ldexp(jnp.float32(1.0), -s)
            S = mid.shape[0]
            S_pad = -(-S // pk._GS_SS) * pk._GS_SS
            rows = jnp.zeros((3, S_pad), jnp.float32)
            rows = rows.at[0, :S].set(mid).at[1, :S].set(c1)
            rows = rows.at[2, :S].set(c2)
            rows = jnp.pad(rows, ((0, 5), (0, 0)))
            c = jnp.asarray(
                rows.reshape(8, S_pad // pk._GS_SS, pk._GS_SS)
                .transpose(1, 0, 2))
            self._tperm[key] = c
        return c

    def jitter_ms(self) -> float:
        """Max |ts - nominal slot tick| over valid slots: the bound the
        group-sum dispatcher uses to elide jitter-fallback families
        when the query grid phase statically clears it."""
        if self._jitter is None:
            ticks = (self.base_ms
                     + jnp.arange(self.num_slots, dtype=jnp.float64)
                     * self.dt_ms)
            d = jnp.where(self.valid,
                          jnp.abs(self.ts - ticks[None, :]), 0.0)
            self._jitter = float(jnp.max(d))
        return self._jitter


@capacity(
    "tilestore-hist-tiles", bytes_per_sample=24.75,
    reason="a histogram cohort prices its bucket axis: a sample is ONE "
           "bucket value of one slot, 8 B in each of the three f64 tiles "
           "(raw values [S, N, B], the corrected channel and the "
           "correction slot-major [N, B*S]), and the slot's valid bool + "
           "ts f64 (9 B) shared by its B buckets: 24 + 9/B B a bucket "
           "value, priced at the Prometheus client's default scheme of 12 "
           "buckets (24.75 B); the int32 timestamps and a holed cohort's "
           "fills are lazy warm caches, as for counters")
class HistTiles(AlignedTiles):
    """A cohort of native histogram series of one bucket scheme (``les``,
    ``+Inf`` last) sharing cadence dt: ``valid`` and ``ts`` [S, N] as for
    counters, ``vals`` [S, N, B], and the two channels the fused quantile
    program reads, slot-major and bucket-major, [N, B*S] (column ``b*S +
    s`` is bucket b of series s: a slot's row is B runs of the S series,
    as lane-wide as a counter tile's, so no layout change is asked of a
    program that takes rows): the counter-corrected buckets ``t_cv`` and
    the correction itself ``t_corr``. Both follow FiloDB's histogram rule
    (memory/histogram.py ``hist_counter_correction``): a row where ANY
    bucket fell against the row before adds back the WHOLE previous
    histogram, cumulatively; a chunk's drop table is taken where it has
    one. ``t_corr`` holds, at a slot with no sample, the correction of
    the next sample (a backward fill), so the first sample at or after
    any instant reads it where the value channel's backward fill reads
    the value."""

    def __init__(self, keys, base_ms, dt_ms, valid, ts_true, vals, corr,
                 les):
        super().__init__(keys, base_ms, dt_ms, valid, ts_true, vals)
        self.les = tuple(les)
        self.num_buckets = len(self.les)
        cv = np.where(valid[..., None], vals + corr, 0.0)
        self.t_cv = jnp.asarray(_slot_major(cv))
        self.t_corr = jnp.asarray(_slot_major(_bfill_rows(corr, valid)))
        self._tq: Dict[float, jnp.ndarray] = {}

    def t_les(self) -> jnp.ndarray:
        """f64 [B] on the device: the bounds as the fused quantile program
        reads them, put once (a lazy cache, like ``t_consts``)."""
        c = self._tch.get("les")
        if c is None:
            c = jax.device_put(np.asarray(self.les, np.float64))
            self._tch["les"] = c
        return c

    def t_q(self, q: float) -> jnp.ndarray:
        """``q`` as an f64 on the device, put once a value (at most
        ``_Q_KEPT`` values a cohort, then all go): the quantile is a
        runtime argument of the one executable, and a board asks for a
        few. Not bits in the request's int64 grid: the chip's emulated
        f64 takes 0.99 back from its bits to another value than it takes
        0.99 over from the host."""
        c = self._tq.get(q)
        if c is None:
            if len(self._tq) >= _Q_KEPT:
                self._tq.clear()
            c = self._tq.setdefault(q, jax.device_put(np.float64(q)))
        return c

    def t_fill(self, kind: str) -> jnp.ndarray:
        """[N, B*S] forward ("ff") or backward ("bf") fill of ``t_cv``
        over the slots with no sample (NaN where none is there); dense
        tiles alias the channel."""
        if self._dense:
            return self.t_cv
        c = self._tch.get("hist_" + kind)
        if c is None:
            if kind == "ff":
                if self._jl is None:
                    self._jl = _ffill_idx(self.valid)
                idx = self._jl.T + 1                    # [N, S], 0 = none
                src = jnp.concatenate(
                    [jnp.full_like(self.t_cv[:1], jnp.nan), self.t_cv])
            else:
                if self._jf is None:
                    rev = jnp.flip(self.valid, axis=1)
                    self._jf = (self.valid.shape[1] - 1
                                - jnp.flip(_ffill_idx(rev), axis=1)).astype(
                                    jnp.int32)
                idx = jnp.clip(self._jf, 0, self.num_slots).T
                src = jnp.concatenate(
                    [self.t_cv, jnp.full_like(self.t_cv[:1], jnp.nan)])
            c = jnp.take_along_axis(
                src, jnp.tile(idx.astype(jnp.int32), (1, self.num_buckets)),
                axis=0)
            self._tch["hist_" + kind] = c
        return c


# quantile values a histogram cohort keeps on the device (HistTiles.t_q)
_Q_KEPT = 8


def _slot_major(a: np.ndarray) -> np.ndarray:
    """[S, N, B] -> [N, B*S], column ``b*S + s``."""
    S, N, B = a.shape
    return np.ascontiguousarray(a.transpose(1, 2, 0).reshape(N, B * S))


def _bfill_rows(corr: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """[S, N, B] with every slot that holds no sample given the value of
    the next slot that does (the last sample's past the end)."""
    out = np.empty_like(corr)
    for r in range(valid.shape[0]):
        pos = np.flatnonzero(valid[r])
        if not pos.size:
            out[r] = 0.0
            continue
        nxt = np.minimum(np.searchsorted(pos, np.arange(valid.shape[1])),
                         pos.size - 1)
        out[r] = corr[r, pos[nxt]]
    return out


_SENT_LO = -(2 ** 31)           # "no sample at or before this slot"
_SENT_HI = 2 ** 31 - 1          # "no sample at or after this slot"


def _estimate_dt_candidates(series: Sequence[RawSeries]) -> List[int]:
    """Scrape-cadence estimate robust to gaps and jitter: iteratively
    refine the pooled diff median by dividing each diff by its rounded
    multiple (a k-sample gap contributes diff/k), then offer round-number
    snaps (real scrape intervals are round) ordered most-likely first."""
    diffs = []
    for s in series:
        if s.ts.size >= 2:
            d = np.diff(s.ts).astype(np.float64)
            diffs.append(d[d > 0])
    if not diffs:
        return []
    d = np.concatenate(diffs)
    if d.size == 0:
        return []
    dt = float(np.median(d))
    for _ in range(3):
        k = np.maximum(np.round(d / dt), 1.0)
        dt = float(np.median(d / k))
    if dt <= 0:
        return []
    cands: List[int] = []
    for q in (60_000, 10_000, 5_000, 1_000, 500, 100, 1):
        c = int(round(dt / q) * q)
        if c > 0 and abs(c - dt) <= dt * 0.25 and c not in cands:
            cands.append(c)
    return cands


def _align_rows(series: Sequence[RawSeries], dt: int):
    rows, aligned_idx = [], []
    lo = hi = None
    for i, s in enumerate(series):
        if s.values.ndim == 2:
            # a histogram row is a sample: slots from the timestamps alone
            ts, vals = s.ts, s.values
        else:
            m = ~np.isnan(s.values)
            ts, vals = s.ts[m], s.values[m]
        if ts.size == 0:
            continue
        slots = np.round(ts / dt).astype(np.int64)
        if np.unique(slots).size != slots.size:
            continue                      # slot collision -> irregular
        if np.abs(ts - slots * dt).max() >= dt / 2:
            continue
        rows.append((i, slots, ts, vals))
        aligned_idx.append(i)
        lo = slots[0] if lo is None else min(lo, slots[0])
        hi = slots[-1] if hi is None else max(hi, slots[-1])
    return rows, aligned_idx, lo, hi


def build_aligned_tiles(series: Sequence[RawSeries],
                        ) -> Tuple[Optional[AlignedTiles], List[int]]:
    """Try to align series onto a shared cadence grid.

    Returns (tiles, aligned_indices). Series that don't fit (slot
    collisions after NaN-drop, or no shared dt) are excluded; the caller
    routes them through the general path. Returns (None, []) if fewer than
    half the series align or cadence can't be established."""
    if not series:
        return None, []
    les = None
    if series[0].is_hist:
        # a bucket-axis cohort: every series a histogram of one scheme
        les = series[0].bucket_les
        if les is None or any(
                not s.is_hist or s.bucket_les is None
                or not np.array_equal(s.bucket_les, les) for s in series):
            return None, []
    dt_cands = _estimate_dt_candidates(series)
    if not dt_cands:
        return None, []
    best = None
    for dt in dt_cands:
        attempt = _align_rows(series, dt)
        if best is None or len(attempt[0]) > len(best[0][0]):
            best = (attempt, dt)
        if len(attempt[0]) == len(series):
            break
    (rows, aligned_idx, lo, hi), dt = best
    if not rows or len(rows) * 2 < len(series):
        return None, []
    base = int(lo * dt)
    N = int(hi - lo + 1)
    S = len(rows)
    if les is not None:
        return _hist_tiles(series, rows, les, lo, N, base, dt), aligned_idx
    valid = np.zeros((S, N), dtype=bool)
    ts_true = np.zeros((S, N), dtype=np.float64)
    vals_g = np.zeros((S, N), dtype=np.float64)
    keys = []
    for r, (i, slots, ts, vals) in enumerate(rows):
        pos = slots - lo
        valid[r, pos] = True
        ts_true[r, pos] = ts
        vals_g[r, pos] = vals
        keys.append(dict(series[i].labels))
    return AlignedTiles(keys, base, dt, valid, ts_true, vals_g), aligned_idx


def _hist_tiles(series, rows, les, lo, N, base, dt) -> HistTiles:
    """``build_aligned_tiles``' bucket-axis cohort; each series' reset
    correction is taken over its own rows, as the host path takes it."""
    S, B = len(rows), len(les)
    valid = np.zeros((S, N), dtype=bool)
    ts_true = np.zeros((S, N), dtype=np.float64)
    vals_g = np.zeros((S, N, B), dtype=np.float64)
    corr_g = np.zeros((S, N, B), dtype=np.float64)
    keys = []
    for r, (i, slots, ts, vals) in enumerate(rows):
        s = series[i]
        pos = slots - lo
        valid[r, pos] = True
        ts_true[r, pos] = ts
        vals_g[r, pos] = vals
        if s.is_counter:
            corr_g[r, pos] = bh.hist_counter_correction(
                vals, drop_rows=s.hist_drop_rows)
        keys.append(dict(s.labels))
    return HistTiles(keys, base, dt, valid, ts_true, vals_g, corr_g, les)


# ---------------------------------------------------------------------------
# Query-time evaluation (shared-column takes only)
# ---------------------------------------------------------------------------

# The whole per-query computation compiles to ONE XLA program (every
# dispatch has a fixed cost, and XLA fuses the take/select/epilogue chain).
# Tile arrays enter as a dict pytree argument; (func, grid shape, tile
# identity) key the jit cache.

def _take(arr: jnp.ndarray, cols: jnp.ndarray) -> jnp.ndarray:
    """[S, N] x [T] shared columns -> [S, T]."""
    return jnp.take(arr, cols, axis=1)


@jax.named_scope("select_last")
def _select_last(arrs, names, num_slots, k_hi, wend):
    """Channel values at the LAST sample with ts <= wend_t, per series:
    2-candidate select between slot K_hi's forward fill and K_hi-1's."""
    N = num_slots
    kc = jnp.clip(k_hi, 0, N - 1).astype(jnp.int32)
    kp = jnp.clip(k_hi - 1, 0, N - 1).astype(jnp.int32)
    none = (k_hi < 0)[None, :]
    ts1 = _take(arrs["ff_ts"], kc)
    use1 = ts1 <= wend.astype(jnp.float64)[None, :]      # NaN -> False
    out = []
    for n in names:
        a = arrs["ff_" + n]
        v = jnp.where(use1, _take(a, kc), _take(a, kp))
        out.append(jnp.where(none, jnp.nan, v))
    return out


@jax.named_scope("select_first")
def _select_first(arrs, names, num_slots, k_lo, wstart):
    """Channel values at the FIRST sample with ts >= wstart_t."""
    N = num_slots
    kc = jnp.clip(k_lo, 0, N - 1).astype(jnp.int32)
    kn = jnp.clip(k_lo + 1, 0, N - 1).astype(jnp.int32)
    none = (k_lo > N - 1)[None, :]
    ts1 = _take(arrs["bf_ts"], kc)
    use1 = ts1 >= wstart.astype(jnp.float64)[None, :]
    out = []
    for n in names:
        a = arrs["bf_" + n]
        v = jnp.where(use1, _take(a, kc), _take(a, kn))
        out.append(jnp.where(none, jnp.nan, v))
    return out


@jax.named_scope("window_sum")
def _window_sum(arrs, name, num_slots, k_lo, k_hi, wstart, wend):
    """Exact sum of a channel over samples with ts in [wstart_t, wend_t]:
    prefix difference over slots [K_lo, K_hi] minus edge-slot samples that
    jitter outside the window."""
    N = num_slots
    ps = arrs["ps_" + name]
    ch = arrs["ch_" + name]
    hi_i = (jnp.clip(k_hi, -1, N - 1) + 1).astype(jnp.int32)
    lo_i = jnp.clip(k_lo, 0, N).astype(jnp.int32)
    s = _take(ps, hi_i) - _take(ps, lo_i)
    wend_d = wend.astype(jnp.float64)[None, :]
    wstart_d = wstart.astype(jnp.float64)[None, :]
    khx = jnp.clip(k_hi, 0, N - 1).astype(jnp.int32)
    k_hi_ok = ((k_hi >= 0) & (k_hi <= N - 1))[None, :]
    over = k_hi_ok & (_take(arrs["ts"], khx) > wend_d)
    s = s - jnp.where(over, _take(ch, khx), 0.0)
    klx = jnp.clip(k_lo, 0, N - 1).astype(jnp.int32)
    k_lo_ok = ((k_lo >= 0) & (k_lo <= N - 1))[None, :]
    under = k_lo_ok & (_take(arrs["ts"], klx) < wstart_d)
    s = s - jnp.where(under, _take(ch, klx), 0.0)
    return s


# channels each function needs: (ff/bf endpoint channels, prefix channels)
_ENDPOINT_CH = {
    "rate": ["ts", "cv"], "increase": ["ts", "cv"], "delta": ["ts", "v"],
    "last_sample": ["v"], "last_over_time": ["v"],
    "first_over_time": ["v"], "timestamp": ["ts"],
    "changes": ["ev_change"], "resets": ["ev_reset"], "z_score": ["v"],
}
_PREFIX_CH = {
    "sum_over_time": ["v"], "avg_over_time": ["v"],
    "rate_over_delta": ["v"], "increase_over_delta": ["v"],
    "stddev_over_time": ["v", "vc2"], "stdvar_over_time": ["v", "vc2"],
    "z_score": ["v", "vc2"], "changes": ["ev_change"],
    "resets": ["ev_reset"],
}


def _tiles_arrays(tiles: AlignedTiles, func: str) -> Dict[str, jnp.ndarray]:
    """Collect (and lazily pack) the device arrays `func` needs."""
    arrs: Dict[str, jnp.ndarray] = {
        "ts": tiles.ts,
        "ps_ones": tiles.prefix("ones"),
        "ch_ones": tiles.channel("ones"),
    }
    ep = _ENDPOINT_CH.get(func, ())
    if ep:
        arrs["ff_ts"] = tiles.ff("ts")
        arrs["bf_ts"] = tiles.bf("ts")
    for n in ep:
        if func in ("rate", "increase", "delta"):
            arrs["ff_" + n] = tiles.ff(n)
            arrs["bf_" + n] = tiles.bf(n)
        elif func in ("changes", "resets"):
            arrs["bf_" + n] = tiles.bf(n)
        elif func == "first_over_time":
            arrs["bf_" + n] = tiles.bf(n)
        else:
            arrs["ff_" + n] = tiles.ff(n)
    for n in _PREFIX_CH.get(func, ()):
        arrs["ps_" + n] = tiles.prefix(n)
        arrs["ch_" + n] = tiles.channel(n)
    if "vc2" in _PREFIX_CH.get(func, ()):
        arrs["vshift"] = tiles.vshift
    return arrs


def _eval_core(func: str, nsteps: int, arrs: Dict[str, jnp.ndarray],
               num_slots, base, dt, w0s, w0e, step) -> jnp.ndarray:
    """Traceable evaluation body (jitted via _EVAL_JIT). Everything except
    (func, nsteps) is traced, so one compiled program serves every store
    snapshot of the same shape."""
    t = jnp.arange(nsteps, dtype=jnp.int64)
    wend = w0e + t * step
    wstart = w0s + t * step
    # highest slot that could hold a sample <= wend / lowest that could
    # hold one >= wstart (scrape jitter < dt/2 each side)
    k_hi = jnp.floor((wend - base + dt / 2.0) / dt).astype(jnp.int64)
    k_lo = jnp.ceil((wstart - base - dt / 2.0) / dt).astype(jnp.int64)
    counts = _window_sum(arrs, "ones", num_slots, k_lo, k_hi, wstart, wend)
    has = counts >= 0.5
    nan = jnp.nan
    N = num_slots

    if func in ("rate", "increase", "delta"):
        is_counter = func != "delta"
        vch = "cv" if is_counter else "v"
        t2, v2 = _select_last(arrs, ["ts", vch], N, k_hi, wend)
        t1, v1 = _select_first(arrs, ["ts", vch], N, k_lo, wstart)
        out = _extrapolated_rate(wstart[None, :], wend[None, :], counts,
                                 t1, v1, t2, v2,
                                 is_counter, func == "rate")
        return jnp.where(has, out, nan)

    if func in ("last_sample", "last_over_time"):
        (v2,) = _select_last(arrs, ["v"], N, k_hi, wend)
        return jnp.where(has, v2, nan)
    if func == "first_over_time":
        (v1,) = _select_first(arrs, ["v"], N, k_lo, wstart)
        return jnp.where(has, v1, nan)
    if func == "timestamp":
        (t2,) = _select_last(arrs, ["ts"], N, k_hi, wend)
        return jnp.where(has, t2 / 1000.0, nan)
    if func == "present_over_time":
        return jnp.where(has, 1.0, nan)
    if func == "absent_over_time":
        return jnp.where(has, nan, 1.0)

    if func in ("changes", "resets"):
        ch = "ev_change" if func == "changes" else "ev_reset"
        total = _window_sum(arrs, ch, N, k_lo, k_hi, wstart, wend)
        (ev_first,) = _select_first(arrs, [ch], N, k_lo, wstart)
        out = total - jnp.where(jnp.isnan(ev_first), 0.0, ev_first)
        return jnp.where(has, out, nan)

    if func == "count_over_time":
        return jnp.where(has, counts, nan)
    s = _window_sum(arrs, "v", N, k_lo, k_hi, wstart, wend)
    if func in ("sum_over_time", "increase_over_delta"):
        out = s
    elif func == "rate_over_delta":
        out = s / (wend - wstart)[None, :].astype(jnp.float64) * 1000.0
    elif func == "avg_over_time":
        out = s / counts
    else:
        s2 = _window_sum(arrs, "vc2", N, k_lo, k_hi, wstart, wend)
        mean = s / counts
        dmean = mean - arrs["vshift"][:, None]
        var = jnp.maximum(s2 / counts - dmean * dmean, 0.0)
        if func == "stdvar_over_time":
            out = var
        elif func == "stddev_over_time":
            out = jnp.sqrt(var)
        elif func == "z_score":
            (v2,) = _select_last(arrs, ["v"], N, k_hi, wend)
            out = (v2 - mean) / jnp.sqrt(var)
        else:
            raise ValueError(f"aligned path cannot evaluate {func}")
    return jnp.where(has, out, nan)


# ---------------------------------------------------------------------------
# Transposed (slot-major) evaluator for the counter family — the north-star
# hot path. Identical numerics to _eval_core; arrays are [N, S] so each
# step's slot reads are contiguous rows (≈4x the gather bandwidth of
# column reads on TPU). Output is [T, S].
# ---------------------------------------------------------------------------

def _tiles_arrays_t(tiles: AlignedTiles, func: str) -> Dict[str, jnp.ndarray]:
    vch = "cv" if func in ("rate", "increase") else "v"
    if tiles._dense:
        # fully-valid tiles: fills alias the channels and sample counts
        # are slot arithmetic — only (ts, value) tiles live in HBM
        return {"ts": tiles.t_ts(), "ff_v": tiles.t_channel(vch)}
    return {
        "ts": tiles.t_ts(),
        "ps_ones": tiles.t_prefix("ones"),
        "ch_ones": tiles.t_channel("ones"),
        "ff_ts": tiles.t_ff("ts"),
        "bf_ts": tiles.t_bf("ts"),
        "ff_v": tiles.t_ff(vch),
        "bf_v": tiles.t_bf(vch),
    }


@precision(
    "counter-exact-slot-index", bits=31, rel_ulps=4,
    reason="the i64->i32 casts narrow SLOT indices, each clipped to "
           "[0, num_slots] first (num_slots < 2**31 by construction); "
           "the value math stays f64 end to end — certified against "
           "the pure-Python per-window reference evaluator "
           "(promql/refeval) to a few f64 ulps")
def _eval_counter_t(func: str, nsteps: int, arrs: Dict[str, jnp.ndarray],
                    num_slots, base, dt, w0s, w0e, step) -> jnp.ndarray:
    """rate/increase/delta over transposed tiles → [T, S] f64.

    With dense tiles (no "ps_ones"/"ff_ts" in ``arrs``) the fills alias
    the base channels and counts come from slot arithmetic — the hot
    query reads only (ts, value) rows."""
    N = num_slots
    dense = "ps_ones" not in arrs
    t = jnp.arange(nsteps, dtype=jnp.int64)
    wend = w0e + t * step
    wstart = w0s + t * step
    k_hi = jnp.floor((wend - base + dt / 2.0) / dt).astype(jnp.int64)
    k_lo = jnp.ceil((wstart - base - dt / 2.0) / dt).astype(jnp.int64)
    TK = jax.named_scope("window_take")(
        lambda a, k: jnp.take(a, k, axis=0))            # [T, S] rows
    wend_d = wend.astype(jnp.float64)[:, None]
    wstart_d = wstart.astype(jnp.float64)[:, None]
    # counts: prefix diff + edge-slot jitter corrections
    hi_i = (jnp.clip(k_hi, -1, N - 1) + 1).astype(jnp.int32)
    lo_i = jnp.clip(k_lo, 0, N).astype(jnp.int32)
    if dense:
        counts = (hi_i - lo_i).astype(jnp.float64)[:, None]
        one = 1.0
    else:
        counts = TK(arrs["ps_ones"], hi_i) - TK(arrs["ps_ones"], lo_i)
    khx = jnp.clip(k_hi, 0, N - 1).astype(jnp.int32)
    k_hi_ok = ((k_hi >= 0) & (k_hi <= N - 1))[:, None]
    over = k_hi_ok & (TK(arrs["ts"], khx) > wend_d)
    counts = counts - jnp.where(
        over, one if dense else TK(arrs["ch_ones"], khx), 0.0)
    klx = jnp.clip(k_lo, 0, N - 1).astype(jnp.int32)
    k_lo_ok = ((k_lo >= 0) & (k_lo <= N - 1))[:, None]
    under = k_lo_ok & (TK(arrs["ts"], klx) < wstart_d)
    counts = counts - jnp.where(
        under, one if dense else TK(arrs["ch_ones"], klx), 0.0)
    has = counts >= 0.5
    ff_ts = arrs["ts"] if dense else arrs["ff_ts"]
    bf_ts = arrs["ts"] if dense else arrs["bf_ts"]
    bf_v = arrs["ff_v"] if dense else arrs["bf_v"]
    # last sample <= wend (2-candidate select, as _select_last)
    kc = jnp.clip(k_hi, 0, N - 1).astype(jnp.int32)
    kp = jnp.clip(k_hi - 1, 0, N - 1).astype(jnp.int32)
    none_hi = (k_hi < 0)[:, None]
    ts1 = TK(ff_ts, kc)
    use1 = ts1 <= wend_d
    t2 = jnp.where(none_hi, jnp.nan,
                   jnp.where(use1, ts1, TK(ff_ts, kp)))
    v2 = jnp.where(none_hi, jnp.nan,
                   jnp.where(use1, TK(arrs["ff_v"], kc),
                             TK(arrs["ff_v"], kp)))
    # first sample >= wstart
    kcl = jnp.clip(k_lo, 0, N - 1).astype(jnp.int32)
    kn = jnp.clip(k_lo + 1, 0, N - 1).astype(jnp.int32)
    none_lo = (k_lo > N - 1)[:, None]
    tsb = TK(bf_ts, kcl)
    useb = tsb >= wstart_d
    t1 = jnp.where(none_lo, jnp.nan,
                   jnp.where(useb, tsb, TK(bf_ts, kn)))
    v1 = jnp.where(none_lo, jnp.nan,
                   jnp.where(useb, TK(bf_v, kcl),
                             TK(bf_v, kn)))
    is_counter = func != "delta"
    out = _extrapolated_rate(wstart_d, wend_d, counts,
                             t1, v1, t2, v2, is_counter, func == "rate")
    return jnp.where(has, out, jnp.nan)


def _tiles_arrays_fast(tiles: AlignedTiles, func: str
                       ) -> Dict[str, jnp.ndarray]:
    """Channels for the f32-hybrid counter evaluator: int32 relative
    timestamps + the exact f64 value tile. Dense tiles need only the two
    (tsr, value) tiles — 12 bytes/sample in HBM."""
    vch = "cv" if func in ("rate", "increase") else "v"
    if tiles._dense:
        return {"tsr": tiles.t_tsr_i32(), "ff_v": tiles.t_channel(vch)}
    return {
        "tsr": tiles.t_tsr_i32(),
        "ones": tiles.t_ones_i8(),
        "ps_ones": tiles.t_ps_ones_i32(),
        "ff_tsr": tiles.t_ff_tsr_i32(),
        "bf_tsr": tiles.t_bf_tsr_i32(),
        "ff_v": tiles.t_ff(vch),
        "bf_v": tiles.t_bf(vch),
    }


@precision(
    "counter-fast-hybrid", bits=31, rel_ulps=16,
    reason="the int31 span-guard idiom: the dispatcher "
           "(_slide_eligible / ShardedTiles.query_fits) proves the "
           "whole query grid fits int32 ms relative to the tile base "
           "before the i64->i32 timestamp narrowing; boundary deltas "
           "stay exact f64 and only the extrapolation epilogue runs "
           "f32 — certified against the exact-f64 evaluator "
           "(_eval_counter_t) within 16 f32 ulps")
def _eval_counter_fast(func: str, nsteps: int, arrs: Dict[str, jnp.ndarray],
                       num_slots, base, dt, w0s, w0e, step) -> jnp.ndarray:
    """rate/increase/delta over transposed tiles → [T, S] **f32**.

    The f32-hybrid path (rangefn/RateFunctions.scala:37 semantics):
      * timestamps are int32 ms relative to the tile base — exact, and
        every boundary compare/subtract is a native int32 op;
      * the boundary value delta (v2 - v1) is computed in f64 from the
        f64 value tile, so large counters (1e15 + small increments) keep
        exact deltas — the catastrophic-cancellation failure a pure-f32
        value channel would hit;
      * the extrapolation epilogue (durations, averages, divisions) runs
        in f32 — native TPU rate vs software-emulated f64.

    Results match the exact-f64 evaluator to ~1e-6 relative (a few f32
    ulps from the extrapolation factor). The dispatcher guards that the
    query grid fits int32 ms relative to base; wider grids take the
    exact path.

    Histogram channels (``HistTiles``: values [N, B*S]) carry the bucket
    axis through every take and give [T, B, S] **f64**: the timestamps,
    counts and selects are the series' own, shared by its buckets, and
    the epilogue is the f64 formula (``_extrapolated_rate``), since a
    quantile divides the rates' error by a bucket's share of the total.
    With them comes ``corr``, and the buckets count from the query's
    first window's start, as FiloDB reads them: the correction the tile
    holds at the first sample at or after ``w0s`` is taken off both
    boundary values (the zero point moves, the delta does not)."""
    N = num_slots
    dense = "ps_ones" not in arrs
    hist = "corr" in arrs
    TK = jax.named_scope("window_take")(
        lambda a, k: jnp.take(a, k, axis=0))                # [T, S] rows
    TV, ax = TK, (lambda a: a)
    if hist:
        # value rows [T, B*S] as [T, B, S]: masks and times of a series
        # broadcast over its buckets
        S = arrs["tsr"].shape[1]
        B = arrs["ff_v"].shape[1] // S
        TV = lambda a, k: TK(a, k).reshape(-1, B, S)        # noqa: E731
        ax = lambda a: a[:, None]                           # noqa: E731
    t = jnp.arange(nsteps, dtype=jnp.int64)
    wend = w0e + t * step
    wstart = w0s + t * step
    k_hi = jnp.floor((wend - base + dt / 2.0) / dt).astype(jnp.int64)
    k_lo = jnp.ceil((wstart - base - dt / 2.0) / dt).astype(jnp.int64)
    wend_r = (wend - base).astype(jnp.int32)[:, None]       # guarded i32
    wstart_r = (wstart - base).astype(jnp.int32)[:, None]

    kc = jnp.clip(k_hi, 0, N - 1).astype(jnp.int32)         # == khx
    kp = jnp.clip(k_hi - 1, 0, N - 1).astype(jnp.int32)
    kcl = jnp.clip(k_lo, 0, N - 1).astype(jnp.int32)        # == klx
    kn = jnp.clip(k_lo + 1, 0, N - 1).astype(jnp.int32)

    # the 8 unique row-takes (4 of int32 ts, 4 of f64 values); every
    # boundary select and jitter correction below reuses these
    ts_kc, ts_kp = TK(arrs["tsr"] if dense else arrs["ff_tsr"], kc), None
    if dense:
        ts_kp = TK(arrs["tsr"], kp)
        tsb_kcl = TK(arrs["tsr"], kcl)
        tsb_kn = TK(arrs["tsr"], kn)
        raw_kc, raw_kcl = ts_kc, tsb_kcl
    else:
        ts_kp = TK(arrs["ff_tsr"], kp)
        tsb_kcl = TK(arrs["bf_tsr"], kcl)
        tsb_kn = TK(arrs["bf_tsr"], kn)
        raw_kc = TK(arrs["tsr"], kc)
        raw_kcl = TK(arrs["tsr"], kcl)
    v_kc = TV(arrs["ff_v"], kc)
    v_kp = TV(arrs["ff_v"], kp)
    bf_v = arrs["ff_v"] if dense else arrs["bf_v"]
    v_kcl = TV(bf_v, kcl)
    v_kn = TV(bf_v, kn)

    # counts: slot arithmetic (dense) / prefix diff, minus edge-slot
    # samples that jitter outside the window
    hi_i = (jnp.clip(k_hi, -1, N - 1) + 1).astype(jnp.int32)
    lo_i = jnp.clip(k_lo, 0, N).astype(jnp.int32)
    k_hi_ok = ((k_hi >= 0) & (k_hi <= N - 1))[:, None]
    k_lo_ok = ((k_lo >= 0) & (k_lo <= N - 1))[:, None]
    if dense:
        counts = (hi_i - lo_i)[:, None]
        over = k_hi_ok & (raw_kc > wend_r)
        under = k_lo_ok & (raw_kcl < wstart_r)
    else:
        counts = TK(arrs["ps_ones"], hi_i) - TK(arrs["ps_ones"], lo_i)
        ones_kc = TK(arrs["ones"], kc) > 0
        ones_kcl = TK(arrs["ones"], kcl) > 0
        over = k_hi_ok & ones_kc & (raw_kc > wend_r)
        under = k_lo_ok & ones_kcl & (raw_kcl < wstart_r)
    counts = counts - over.astype(jnp.int32) - under.astype(jnp.int32)

    # last sample <= wend (2-candidate select; sentinel/NaN-filled
    # boundaries propagate through the f64 value channel)
    none_hi = (k_hi < 0)[:, None]
    use1 = ts_kc <= wend_r
    t2 = jnp.where(use1, ts_kc, ts_kp)
    v2 = jnp.where(ax(none_hi), jnp.nan,
                   jnp.where(ax(use1), v_kc, v_kp))
    # first sample >= wstart
    none_lo = (k_lo > N - 1)[:, None]
    useb = tsb_kcl >= wstart_r
    t1 = jnp.where(useb, tsb_kcl, tsb_kn)
    v1 = jnp.where(ax(none_lo), jnp.nan,
                   jnp.where(ax(useb), v_kcl, v_kn))
    if hist:
        # the first window's first sample: the same 2-candidate select
        c0 = jnp.where(useb[0], arrs["corr"][kcl[0]].reshape(B, S),
                       arrs["corr"][kn[0]].reshape(B, S))   # [B, S]
        return _extrapolated_rate(
            ax(wstart_r), ax(wend_r), ax(counts), ax(t1), v1 - c0, ax(t2),
            v2 - c0, True, func == "rate")

    return _f32_epilogue(func, counts, t1, v1, t2, v2, wstart_r, wend_r,
                         (w0e - w0s).astype(jnp.float32) / 1000.0)


def _tiles_arrays_slide(tiles: AlignedTiles, func: str, st: int
                        ) -> Dict[str, jnp.ndarray]:
    """Stride-permuted channels for the slide evaluator (dense tiles
    only): int32 relative timestamps + the exact f64 value channel,
    each as [st, G, S]."""
    vch = "cv" if func in ("rate", "increase") else "v"
    return {
        "tsr_p": tiles.t_perm("tsr_i32", st, tiles.t_tsr_i32()),
        "ff_v_p": tiles.t_perm(vch, st, tiles.t_channel(vch)),
    }


@precision(
    "counter-slide-hybrid", bits=31, rel_ulps=16,
    reason="same hybrid numerics as counter-fast-hybrid (int32 "
           "relative timestamps under the _slide_eligible span guard, "
           "exact f64 boundary deltas, f32 epilogue); the stride-"
           "permuted dynamic_slice changes only the memory access "
           "pattern — certified against the exact-f64 evaluator "
           "within the same 16 f32 ulps")
def _eval_counter_slide(func: str, nsteps: int, st: int,
                        arrs: Dict[str, jnp.ndarray],
                        num_slots, base, dt, w0s, w0e, step) -> jnp.ndarray:
    """rate/increase/delta on a REGULAR grid over dense tiles → [T, S] f32.

    Same numerics as ``_eval_counter_fast`` (int32 relative timestamps,
    exact f64 boundary deltas, f32 extrapolation epilogue —
    rangefn/RateFunctions.scala:23-79 semantics), but every boundary
    row-take is ONE contiguous dynamic_slice of the stride-permuted
    [st, G, S] channel: rows k0, k0+st, ... live at [k0 % st,
    k0//st : k0//st + T]. ~6x the HBM efficiency of the gather path on
    v5e. The dispatcher guarantees every index is in bounds, so the
    clip/sentinel masks of the gather path vanish."""
    T = nsteps
    G, S = arrs["tsr_p"].shape[1], arrs["tsr_p"].shape[2]
    sti = jnp.int32(st)
    k_c0 = jnp.floor((w0e - base + dt / 2.0) / dt).astype(jnp.int32)
    k_l0 = jnp.ceil((w0s - base - dt / 2.0) / dt).astype(jnp.int32)

    @jax.named_scope("window_rows")
    def rows(perm, k0):
        r = jnp.mod(k0, sti)
        g = jnp.floor_divide(k0, sti)
        sl = jax.lax.dynamic_slice(perm, (r, g, jnp.int32(0)), (1, T, S))
        return sl.reshape(T, S)

    ts_kc = rows(arrs["tsr_p"], k_c0)
    ts_kp = rows(arrs["tsr_p"], k_c0 - 1)
    tsb_kcl = rows(arrs["tsr_p"], k_l0)
    tsb_kn = rows(arrs["tsr_p"], k_l0 + 1)
    v_kc = rows(arrs["ff_v_p"], k_c0)
    v_kp = rows(arrs["ff_v_p"], k_c0 - 1)
    v_kcl = rows(arrs["ff_v_p"], k_l0)
    v_kn = rows(arrs["ff_v_p"], k_l0 + 1)

    t = jnp.arange(T, dtype=jnp.int64)
    wend_r = (w0e - base + t * step).astype(jnp.int32)[:, None]
    wstart_r = (w0s - base + t * step).astype(jnp.int32)[:, None]
    counts = (k_c0 + 1 - k_l0).astype(jnp.int32)        # same for every t
    over = ts_kc > wend_r
    under = tsb_kcl < wstart_r
    counts = counts - over.astype(jnp.int32) - under.astype(jnp.int32)
    use1 = ts_kc <= wend_r
    t2 = jnp.where(use1, ts_kc, ts_kp)
    v2 = jnp.where(use1, v_kc, v_kp)
    useb = tsb_kcl >= wstart_r
    t1 = jnp.where(useb, tsb_kcl, tsb_kn)
    v1 = jnp.where(useb, v_kcl, v_kn)
    return _f32_epilogue(func, counts, t1, v1, t2, v2, wstart_r, wend_r,
                         (w0e - w0s).astype(jnp.float32) / 1000.0)


@precision(
    "extrapolated-rate-f64", bits=53, rel_ulps=4,
    reason="the shared f64 extrapolation formula every exact counter "
           "path funnels through; certified within a few f64 ulps of "
           "the pure-Python reference (promql/refeval._extrapolated) "
           "— the two arms of the differential rail agree at the "
           "formula level, not just end to end")
@jax.named_scope("rate_epilogue")
def _extrapolated_rate(wstart, wend, counts, t1, v1, t2, v2, is_counter,
                       is_rate):
    """(rangefn/RateFunctions.scala:37 extrapolatedRate, on device.)
    Shape-agnostic: callers broadcast wstart/wend against their tile
    orientation ([S, T] row-major or [T, S] slot-major)."""
    counts = counts.astype(jnp.float64)
    dstart = (t1 - wstart).astype(jnp.float64) / 1000.0
    dend = (wend - t2).astype(jnp.float64) / 1000.0
    sampled = (t2 - t1).astype(jnp.float64) / 1000.0
    avg_dur = sampled / (counts - 1.0)
    delta = v2 - v1
    if is_counter:
        dzero = jnp.where((delta > 0) & (v1 >= 0),
                          sampled * (v1 / jnp.where(delta == 0, jnp.nan,
                                                    delta)),
                          jnp.inf)
        dstart = jnp.minimum(dstart, dzero)
    thresh = avg_dur * 1.1
    extrap = sampled \
        + jnp.where(dstart < thresh, dstart, avg_dur / 2.0) \
        + jnp.where(dend < thresh, dend, avg_dur / 2.0)
    scaled = delta * (extrap / sampled)
    if is_rate:
        scaled = scaled / (wend - wstart) * 1000.0
    return jnp.where(counts >= 2, scaled, jnp.nan)


@precision(
    "counter-epilogue-f32", bits=24, rel_ulps=4,
    reason="the extrapolation epilogue narrows the exact f64 boundary "
           "delta and exact i32 time differences to f32 for the "
           "division chain (native TPU rate vs software-emulated "
           "f64); certified within 4 f32 ulps of the f64-reference "
           "formula — XLA lowers the chain per-program, so two "
           "programs (mesh-on vs mesh-off instant queries) may differ "
           "by at most twice that budget (rel_bound(cross_program))")
@jax.named_scope("rate_epilogue_f32")
def _f32_epilogue(func, counts, t1, v1, t2, v2, wstart_r, wend_r, wdur_s):
    """Shared f32 extrapolation epilogue: exact f64 delta, f32 factor."""
    f32 = jnp.float32
    delta = (v2 - v1).astype(f32)                   # exact f64 difference
    sampled = (t2 - t1).astype(f32) / 1000.0        # exact i32 difference
    dstart = (t1 - wstart_r).astype(f32) / 1000.0
    dend = (wend_r - t2).astype(f32) / 1000.0
    counts_f = counts.astype(f32)
    avg_dur = sampled / (counts_f - 1.0)
    if func != "delta":                             # counter zero-clamp
        v1f = v1.astype(f32)
        dzero = jnp.where((delta > 0) & (v1f >= 0),
                          sampled * (v1f / jnp.where(delta == 0, jnp.nan,
                                                     delta)),
                          jnp.inf)
        dstart = jnp.minimum(dstart, dzero)
    thresh = avg_dur * 1.1
    extrap = sampled \
        + jnp.where(dstart < thresh, dstart, avg_dur * 0.5) \
        + jnp.where(dend < thresh, dend, avg_dur * 0.5)
    factor = extrap / sampled
    if func == "rate":
        factor = factor / wdur_s
    out = delta * factor
    return jnp.where(counts >= 2, out, jnp.nan)


_EVAL_T_JIT: Dict[Tuple, object] = {}

# cache inventory (graftlint): the four module-level dispatch tables
# (_EVAL_T_JIT/_EVAL_JIT and their vmapped twins) memoize compiled
# executables keyed purely on (kernel family, func, pow2 shape bucket)
# — a pure function of the request shape, immune to every world event
# by construction, which is exactly what the declaration records.
__cache_registry__ = {
    "tilestore-executables": {"keyed": ("kernel", "func",
                                        "shape-bucket")},
}

# executable-reuse observability: every dispatch-table lookup counts a
# hit (compiled program reused) or a miss (new trace+compile). Shared
# by the scalar and vmapped (micro-batched) dispatch families and
# surfaced in /metrics as the executable-cache counters.
import threading as _threading

_JIT_STATS = {"hits": 0, "misses": 0}
_JIT_STATS_LOCK = _threading.Lock()
_BUILD_LOCKS: Dict[Tuple, object] = {}      # (table id, key) -> build lock
__guarded_by__ = {"_JIT_STATS": "_JIT_STATS_LOCK",
                  "_BUILD_LOCKS": "_JIT_STATS_LOCK"}


@capacity(
    "tilestore-executable-constants", bytes_per_sample=8.0,
    reason="dispatch-table entries retain the device constants their "
           "closures capture (weight/shape tables lowered into the "
           "compiled program), priced at one f64 (8 B) per packed "
           "slot of the largest captured constant; the executables "
           "themselves are host code, not HBM")
def _bind(fn, *static):
    """``fn`` with its leading static arguments bound, under ``fn``'s own
    name: ``jax.jit`` of a ``functools.partial`` compiles as
    ``jit__unknown``, of this as ``jit_<fn>`` — the name the profiler's
    ``XLA Modules`` line and every op's metadata carry."""
    @_functools.wraps(fn)
    def bound(*args):
        return fn(*static, *args)
    return bound


def _jit_lookup(cache: Dict[Tuple, object], key: Tuple, build,
                site: str = "tilestore", cost_args=None) -> object:
    """Dispatch-table lookup with hit/miss accounting; ``build()`` makes
    the jitted callable on a miss. Miss-side builds observe
    ``filodb_kernel_build_seconds`` — a retrace storm (shape-bucket
    churn, cache invalidation) shows up as histogram mass instead of
    unexplained tail latency.

    Compile/cost profiling (obs/devprof.py): with ``cost_args`` (the
    first call's argument tuple) the miss path lowers + compiles the
    executable AOT — the one compile this miss was paying anyway —
    captures XLA ``cost_analysis()`` FLOPs/bytes per executable, and
    caches a :class:`~filodb_tpu.obs.devprof.ProfiledExecutable` whose
    per-call accounting feeds the recompile counters and the
    ``&explain=analyze`` executable attribution."""
    fn = cache.get(key)
    with _JIT_STATS_LOCK:
        _JIT_STATS["hits" if fn is not None else "misses"] += 1
        lock = None if fn is not None else _BUILD_LOCKS.setdefault(
            (id(cache), key), _threading.Lock())
    if fn is None:
        from filodb_tpu.obs import devprof
        from filodb_tpu.obs import trace as obs_trace
        # request threads that miss one key together (a warm-up's first
        # round) build it once: the others wait here and take the entry
        with lock:
            fn = cache.get(key)
            if fn is None:
                with obs_trace.span("kernel-build", site=site):
                    fn = devprof.build_profiled(site, key, build,
                                                cost_args=cost_args)
                cache[key] = fn
    return fn


def executable_cache_stats() -> Dict[str, int]:
    """Snapshot of compiled-executable reuse across the tilestore
    dispatch tables (scalar + vmapped families)."""
    with _JIT_STATS_LOCK:
        out = dict(_JIT_STATS)
    out["entries"] = (len(_EVAL_JIT) + len(_EVAL_T_JIT)
                      + len(_EVAL_T_VMAP) + len(_EVAL_VMAP))
    return out


def _slide_eligible(tiles: AlignedTiles, nsteps: int, w0s: int, w0e: int,
                    last_ms: int, step: int):
    """Shared dispatch guard for the slide evaluator AND the Pallas
    group-sum kernel: a REGULAR grid (step % dt == 0) over dense tiles,
    entirely interior (no index clipping: kp = kc-1 >= 0 ... kn =
    kcl+1 <= N-1), with every relative time in int32 ms. Returns
    (st, k_c0, k_l0) or None. Both consumers MUST dispatch off this one
    predicate so they agree on the in-bounds proof."""
    N, dt = tiles.num_slots, tiles.dt_ms
    if nsteps < 2 or not tiles._dense or step % dt != 0:
        return None
    lo_rel = w0s - tiles.base_ms
    hi_rel = last_ms - tiles.base_ms
    if not (_SENT_LO < lo_rel and hi_rel < _SENT_HI
            and N * dt + dt < _SENT_HI):
        return None
    st = step // dt
    k_c0 = int(np.floor((w0e - tiles.base_ms + dt / 2.0) / dt))
    k_l0 = int(np.ceil((w0s - tiles.base_ms - dt / 2.0) / dt))
    span = (nsteps - 1) * st
    if not (st >= 1 and k_c0 >= 1 and k_l0 >= 0
            and k_c0 + span <= N - 1 and k_l0 + 1 + span <= N - 1):
        return None
    return st, k_c0, k_l0


@kernel_contract(
    "counters_t_dispatch", kind="dispatch",
    rel_time_bits=31, span_guard="_slide_eligible",
    notes="transposed counter fast path: slide evaluator when "
          "_slide_eligible proves the regular interior grid, f32-hybrid "
          "when the span fits int31 ms, exact all-f64 otherwise")
def evaluate_counters_t(tiles: AlignedTiles, func: str, steps: np.ndarray,
                        window_ms: int, offset_ms: int = 0) -> jnp.ndarray:
    """rate/increase/delta on the transposed fast path → [T, S].

    Dispatch: the f32-hybrid evaluator (f32 output) when the query grid
    and tile span fit int32 ms relative to the tile base (~24.8 days);
    the exact all-f64 evaluator (f64 output) otherwise."""
    assert func in ("rate", "increase", "delta")
    nsteps = steps.size
    w0e = np.int64(steps[0] - offset_ms)
    w0s = np.int64(w0e - window_ms)
    step = np.int64(steps[1] - steps[0]) if nsteps > 1 else np.int64(1)
    lo_rel = int(w0s) - tiles.base_ms
    hi_rel = int(steps[-1] - offset_ms) - tiles.base_ms
    fits_i32 = (_SENT_LO < lo_rel and hi_rel < _SENT_HI
                and tiles.num_slots * tiles.dt_ms + tiles.dt_ms < _SENT_HI)
    el = _slide_eligible(tiles, nsteps, int(w0s), int(w0e),
                         int(steps[-1] - offset_ms), int(step))
    if el is not None:
        st, _, _ = el
        arrs = _tiles_arrays_slide(tiles, func, st)
        key = ("slide", func, nsteps, st)
        args = (arrs, np.int64(tiles.num_slots),
                np.int64(tiles.base_ms), np.int64(tiles.dt_ms),
                np.int64(w0s), np.int64(w0e), np.int64(step))
        fn = _jit_lookup(_EVAL_T_JIT, key, lambda: jax.jit(
            _bind(_eval_counter_slide, func, nsteps, st)),
            cost_args=args)
        return fn(*args)
    if fits_i32:
        arrs = _tiles_arrays_fast(tiles, func)
        key = ("fast", func, nsteps)
        build = lambda: jax.jit(_bind(
            _eval_counter_fast, func, nsteps))
    else:
        arrs = _tiles_arrays_t(tiles, func)
        key = ("t", func, nsteps)
        build = lambda: jax.jit(_bind(
            _eval_counter_t, func, nsteps))
    args = (arrs, np.int64(tiles.num_slots),
            np.int64(tiles.base_ms), np.int64(tiles.dt_ms),
            np.int64(w0s), np.int64(w0e), np.int64(step))
    fn = _jit_lookup(_EVAL_T_JIT, key, build, cost_args=args)
    return fn(*args)


def _group_onehot(ids, G: int):
    """int32 group ids [S] -> f32 one-hot [S, G], built on the device;
    an id outside [0, G) (the padding's -1) gives an all-zero row."""
    return (ids[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]
            ).astype(jnp.float32)


def _ids_arg(gids, n: int):
    """Tile-order group ids as a fused program takes them: int32[n], -1
    (no group) past the series. A device array is taken as it is:
    ``fused_group_ids`` made it so."""
    if isinstance(gids, jax.Array):
        return gids
    ids = np.full(n, -1, np.int32)
    ids[:len(gids)] = gids
    return ids


def fused_group_ids(tiles: AlignedTiles, gvec) -> jax.Array:
    """``gvec`` (tile order) padded for the tiles' fused program (to the
    Pallas kernel's lane tile over dense counter tiles, not at all over
    holes and histograms) and put on the device: the backend's tile entry
    keeps it per grouping, so that a request of the grouping sends no
    ids."""
    S = len(tiles.keys)
    n = (-(-S // pk._GS_SS) * pk._GS_SS
         if tiles._dense and not isinstance(tiles, HistTiles) else S)
    return jax.device_put(_ids_arg(gvec, n))


def _groupsum_program(func: str, st: int, dspan: int, hi_mode: int,
                      lo_mode: int, exact_branch: bool, nsteps: int,
                      G: int, interpret: bool, v_p, base, params, ids):
    """The fused group-sum as ONE traceable program (jitted once per
    static tuple by groupsum_counters): the [S_pad, G] f32 one-hot from
    the int32 group ids (an id that names no group, the padding's -1,
    gives an all-zero row), the Pallas kernel and its [:nsteps] slices.
    Every input is explicitly typed, so the program is the same under
    x64 on and off."""
    return pk.groupsum_call(
        func, st, dspan, hi_mode, lo_mode, exact_branch, nsteps,
        v_p, base, _group_onehot(ids, G), params, interpret=interpret)


def _groupsum_holes_program(func: str, nsteps: int, G: int, arrs, consts,
                            grid, ids):
    """The fused group-sum over tiles with holes as ONE traceable program
    (jitted once per static tuple by groupsum_counters): the non-dense
    f32-hybrid evaluator's [T, S] rates, NaN where a window holds fewer
    than two samples, then the Pallas kernel's own epilogue — the masked
    one-hot matmul to [T, G] sums and counts, f32 at HIGHEST precision
    (the MXU's default bf16 input truncation is another answer), so a
    selection sums the same way with holes and without. ``consts`` is
    the tiles' int64[3] on the device (``t_consts``: num_slots, base_ms,
    dt_ms); ``grid`` the request's int64[3]: w0s, w0e, step; ``ids``
    int32 [S] in tile order, an id outside [0, G) names no group (on the
    device where the backend's tile entry keeps them)."""
    num_slots, base, dt = consts[0], consts[1], consts[2]
    w0s, w0e, step = grid[0], grid[1], grid[2]
    out = _eval_counter_fast(func, nsteps, arrs, num_slots, base, dt,
                             w0s, w0e, step)
    ok = ~jnp.isnan(out)
    onehot = _group_onehot(ids, G)
    dot = _functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    return (dot(jnp.where(ok, out, jnp.float32(0.0)), onehot),
            dot(ok.astype(jnp.float32), onehot))


@kernel_contract(
    "groupsum_dispatch", kind="dispatch",
    vmem_budget=14 << 20,
    rel_time_bits=31, span_guard="_slide_eligible",
    notes="host-side gate and dispatcher of the fused group-sum, two "
          "programs behind it. Dense tiles, the Pallas kernel: regular "
          "interior grid via _slide_eligible, merged-stream window/step "
          "divisibility, dspan cap, full VMEM re-budget (accumulators + "
          "DMA scratch + onehot + base) all decide BEFORE the executable "
          "table is asked; then one cached executable (site groupsum) "
          "per static tuple takes the query's five scalars as one "
          "int32[5] host vector, and its group ids, which the backend's "
          "tile entry keeps on the device. Tiles with holes: "
          "_groupsum_holes")
def groupsum_counters(tiles: AlignedTiles, func: str, steps: np.ndarray,
                      window_ms: int, gids, G: int, offset_ms: int = 0,
                      interpret: bool = False):
    """`sum by (g) (rate/increase/delta(sel[w]))` fused on device ->
    (sums f32 [T, G], counts f32 [T, G]), or None when the preconditions
    don't hold (caller falls back to evaluate_counters_t + host/XLA
    grouping). One gate, two programs, chosen from ``tiles._dense``:
    the Pallas group-sum kernel over dense tiles, the grouped non-dense
    f32-hybrid evaluator (``_groupsum_holes``) over tiles with holes.
    Both are boundary samples -> f32 extrapolation -> masked group
    matmul, over two layouts of the same samples.

    ``gids``: the group id in [0, G) of every series of the tiles, in
    tile order, or ``fused_group_ids`` of them (on the device, padded:
    what the backend's tile entry hands over). One cached executable per
    (func, grid statics, tile shapes, G) serves every query of that
    shape: a query sends one small integer vector, nothing is traced or
    compiled again (``_jit_lookup``: exec-cache hits/misses,
    ``kernel-build``).

    Preconditions of the kernel: dense tiles; regular grid with
    step % dt == 0 fully interior to the tile; a whole number of steps
    a window; finite values; a pipeline within VMEM; span fits int32 ms
    relative to the tile base. The ids are padded to the kernel's lane
    tile with -1, which names no group, so any S works. Over holes only
    the last of them is asked (``_groupsum_holes``)."""
    assert func in ("rate", "increase", "delta")
    if not tiles._dense:
        return _groupsum_holes(tiles, func, steps, window_ms, gids, G,
                               offset_ms)
    nsteps = steps.size
    if nsteps < 2:
        return None
    w0e = int(steps[0] - offset_ms)
    w0s = w0e - window_ms
    step = int(steps[1] - steps[0])
    el = _slide_eligible(tiles, nsteps, w0s, w0e,
                         int(steps[-1] - offset_ms), step)
    if el is None:
        return None
    st, k_c0, k_l0 = el
    # merged-stream contract: the window must span a whole number of
    # steps so the kc/kl families share a stride-residue plane
    d = k_c0 - k_l0
    if d % st != 0 or not (0 <= d // st <= pk._GS_DSPAN_MAX):
        return None
    dspan = d // st
    if st == 1 and k_l0 < 1:
        return None              # the merged block reads one lead row
    S = len(tiles.keys)
    vch = "cv" if func in ("rate", "increase") else "v"
    if tiles._fixed_channels(vch) is None:
        return None              # non-finite values: exact f64 fallback
    # static jitter-phase elision: when the grid phase clears the
    # tile's max |ts - tick|, the boundary-sample choice is the same
    # for every series and step, and the fallback family is never read
    dt = tiles.dt_ms
    J = tiles.jitter_ms()
    phase_e = (w0e - tiles.base_ms) - k_c0 * dt
    phase_s = k_l0 * dt - (w0s - tiles.base_ms)
    hi_mode = (pk.GS_CUR if phase_e >= J else
               pk.GS_ALT if phase_e < -J else pk.GS_BOTH)
    lo_mode = (pk.GS_CUR if phase_s >= J else
               pk.GS_ALT if phase_s < -J else pk.GS_BOTH)
    # full VMEM budget, not just the accumulators: the pipeline chooser
    # (pk._gs_pipeline) walks the (step-tile width, DMA pipeline depth)
    # frontier — accumulators + nbuf x nstreams x mlen scratch + the
    # onehot/base input blocks — and an oversized query must fall back
    # to the general path HERE, not explode at Mosaic compile time
    if pk._gs_pipeline(st, dspan, hi_mode, lo_mode, nsteps, G) is None:
        return None              # no admissible (tt, nbuf) within VMEM
    S_pad = -(-S // pk._GS_SS) * pk._GS_SS
    v_p = tiles.t_perm_fixed_tiled(vch, st)
    base = tiles.t_fixed_base(vch)
    ids = _ids_arg(gids, S_pad)
    params = np.array([k_l0, w0e - tiles.base_ms, window_ms, step, nsteps],
                      np.int32)
    exact_branch = pk.groupsum_exact_branch(window_ms, st, dspan)
    static = (func, st, dspan, hi_mode, lo_mode, exact_branch, nsteps, G,
              interpret)
    key = ("groupsum",) + static + (tuple(v_p.shape), tuple(base.shape))
    args = (v_p, base, params, ids)
    # a kernel the chip's compiler refuses fails the query with the
    # compiler's message: the decided-in-advance route to the general
    # path is the _gs_pipeline budget check above, never an except
    fn = _jit_lookup(_EVAL_T_JIT, key, lambda: jax.jit(
        _bind(_groupsum_program, *static)), site="groupsum",
        cost_args=args)
    return fn(*args)


@kernel_contract(
    "groupsum_holes_dispatch", kind="dispatch",
    rel_time_bits=31, span_guard="counters_batch_family",
    notes="the fused gate's branch over tiles with holes: the grouped "
          "non-dense f32-hybrid evaluator where counters_batch_family "
          "says ('fast',), i.e. the whole grid fits int32 ms relative "
          "to the tile base; _eval_counter_fast clips its own indices, "
          "so none of the kernel's shape conditions is asked. One "
          "cached executable (site groupsum) per (func, nsteps, G, "
          "channel shape) takes the query's int64[3] grid as its one "
          "host array: the tiles' constants and the group ids are on "
          "the device")
def _groupsum_holes(tiles: AlignedTiles, func: str, steps: np.ndarray,
                    window_ms: int, gids, G: int, offset_ms: int):
    """``groupsum_counters`` over tiles that are not dense -> (sums f32
    [T, G], counts f32 [T, G]) from ``_groupsum_holes_program`` over the
    seven cached channels of ``_tiles_arrays_fast`` (what the aligned
    path holds resident already), or None for the exact all-f64
    ``("t",)`` family: a grid wider than int32 ms."""
    nsteps = steps.size
    if nsteps < 1 or counters_batch_family(
            tiles, func, steps, window_ms, offset_ms) != ("fast",):
        return None
    w0e = int(steps[0] - offset_ms)
    step = int(steps[1] - steps[0]) if nsteps > 1 else 1
    arrs = _tiles_arrays_fast(tiles, func)
    grid = np.array([w0e - window_ms, w0e, step], np.int64)
    args = (arrs, tiles.t_consts(), grid, _ids_arg(gids, len(tiles.keys)))
    key = ("groupsum", "holes", func, nsteps, G,
           tuple(arrs["tsr"].shape))
    fn = _jit_lookup(_EVAL_T_JIT, key, lambda: jax.jit(
        _bind(_groupsum_holes_program, func, nsteps, G)),
        site="groupsum", cost_args=args)
    return fn(*args)


def _tiles_arrays_hist(tiles: HistTiles) -> Dict[str, jnp.ndarray]:
    """Channels of the fused quantile program: ``_tiles_arrays_fast``'s,
    the value channels with the bucket axis, and the correction."""
    if tiles._dense:
        return {"tsr": tiles.t_tsr_i32(), "ff_v": tiles.t_cv,
                "corr": tiles.t_corr}
    return {
        "tsr": tiles.t_tsr_i32(),
        "ones": tiles.t_ones_i8(),
        "ps_ones": tiles.t_ps_ones_i32(),
        "ff_tsr": tiles.t_ff_tsr_i32(),
        "bf_tsr": tiles.t_bf_tsr_i32(),
        "ff_v": tiles.t_fill("ff"),
        "bf_v": tiles.t_fill("bf"),
        "corr": tiles.t_corr,
    }


def _bucket_quantile(q, les, h):
    """``histogram_quantile`` (memory/histogram.py ``quantile``, which is
    Prometheus's ``bucketQuantile``) at every (step, group) at once: ``h``
    [T, G, B] cumulative, NaN where the group has no point; ``les`` [B],
    ``+Inf`` last. The bucket is the first whose count reaches
    ``rank = q * total`` (the last one's count is ``total``); in the
    ``+Inf`` bucket the second-highest bound, in a first bucket bounded
    at or below 0 that bound, in an empty bucket its upper bound, else
    linear inside it, from 0 for the first. NaN where ``total`` is 0 or
    NaN; a ``q`` outside [0, 1] gives -Inf or +Inf."""
    B = h.shape[-1]
    total = h[..., -1]
    rank = q * total
    reach = jnp.concatenate(
        [h[..., :-1] >= rank[..., None],
         jnp.ones(h.shape[:-1] + (1,), bool)], axis=-1)
    b = jnp.argmax(reach, axis=-1)                          # [T, G]
    at = jnp.arange(B)

    def pick(x, i):
        # x[.., i] by a masked sum over the buckets: no gather
        return jnp.sum(jnp.where(at == i[..., None], x, 0.0), axis=-1)
    c_end, le_end = pick(h, b), pick(les, b)
    c_start, le_start = pick(h, b - 1), pick(les, b - 1)   # 0 where b = 0
    inside = le_start + (le_end - le_start) * (rank - c_start) \
        / (c_end - c_start)
    out = jnp.where(c_end == c_start, le_end, inside)
    out = jnp.where((b == 0) & (les[0] <= 0), les[0], out)
    out = jnp.where(b == B - 1, les[B - 2], out)
    out = jnp.where(total == 0, jnp.nan, out)
    out = jnp.where((q >= 0) & (q <= 1), out,
                    jnp.where(q > 1, jnp.inf, -jnp.inf))
    return jnp.where(jnp.isnan(total), jnp.nan, out)


@precision(
    "hist-quantile", bits=31, rel_ulps=256, compensated=True,
    reason="int32 relative timestamps under counters_batch_family's "
           "span guard (exact); everything after them is f64: the "
           "boundary deltas, the extrapolation formula, the masked group "
           "sums (an f64 accumulator) and the quantile, whose interpolation divides the rank's "
           "rounding by the bucket's share of the total — certified "
           "against the host path (periodic_samples, _aggregate_hist_sum, "
           "histogram_quantile in numpy f64) over every bucket a p99, a "
           "median and a tenth percentile land in")
def _hist_quantile_program(func: str, nsteps: int, G: int, arrs, consts,
                           les, q, grid, ids):
    """``histogram_quantile(q, sum by (g) (rate|increase(h[w])))`` over a
    histogram cohort as ONE traceable program (jitted once per static
    tuple by ``hist_quantile_groupsum``): the evaluator's [T, B, S] rates
    (``_eval_counter_fast``'s takes, the f64 formula: NaN where a window
    holds fewer than two samples), masked f64 sums by group to [T, G, B]
    (not an f64 dot: the chip has no f64 matmul; a bucket of a group no
    series has a rate in is NaN, as the host's ``_aggregate_hist_sum``),
    then ``_bucket_quantile`` to [T, G] f64. ``consts`` and ``ids`` as
    ``_groupsum_holes_program``'s, ``les`` f64 [B] and ``q`` f64 the
    tiles' (``t_les``, ``t_q``), all four on the device; ``grid`` the
    request's int64[3]: w0s, w0e, step. ``les`` and ``q`` are runtime
    values, so one executable serves every quantile and every scheme of B
    buckets."""
    num_slots, base, dt = consts[0], consts[1], consts[2]
    w0s, w0e, step = grid[0], grid[1], grid[2]
    rates = _eval_counter_fast(func, nsteps, arrs, num_slots, base, dt,
                               w0s, w0e, step)              # [T, B, S]
    member = ids[None, :] == jnp.arange(G, dtype=jnp.int32)[:, None]
    ok = ~jnp.isnan(rates)[:, None] & member[None, :, None, :]
    sums = jnp.sum(jnp.where(ok, rates[:, None], 0.0), axis=3,
                   dtype=jnp.float64)                       # [T, G, B]
    cnts = jnp.sum(ok, axis=3, dtype=jnp.int32)
    return _bucket_quantile(q, les, jnp.where(cnts > 0, sums, jnp.nan))


@kernel_contract(
    "hist_quantile_dispatch", kind="dispatch",
    rel_time_bits=31, span_guard="counters_batch_family",
    notes="the fused histogram quantile: the f32-hybrid evaluator's "
          "takes with a bucket axis and the f64 formula, masked group "
          "sums and the quantile in one program where "
          "counters_batch_family says the grid fits int32 ms relative to "
          "the tile base; _eval_counter_fast clips its own "
          "indices. One cached executable (site groupsum) per (func, "
          "nsteps, G, channel shape) takes the query's int64[3] grid "
          "as its one host array: the tiles' constants, les, q (kept "
          "a value) and the group ids are on the device")
def hist_quantile_groupsum(tiles: HistTiles, func: str, steps: np.ndarray,
                           window_ms: int, gids, G: int, q: float,
                           offset_ms: int = 0):
    """``histogram_quantile(q, sum by (g) (rate|increase(h[w])))`` fused
    on device -> f64 [T, G] (a device array), or None for a grid wider
    than int32 ms from the tile base (the caller serves it on the host).
    ``gids``: the group id in [0, G) of every series of the tiles, in tile
    order, or ``fused_group_ids`` of them."""
    assert func in ("rate", "increase")
    nsteps = steps.size
    if nsteps < 1 or counters_batch_family(
            tiles, func, steps, window_ms, offset_ms) == ("t",):
        return None
    w0e = int(steps[0] - offset_ms)
    step = int(steps[1] - steps[0]) if nsteps > 1 else 1
    arrs = _tiles_arrays_hist(tiles)
    grid = np.array([w0e - window_ms, w0e, step], np.int64)
    args = (arrs, tiles.t_consts(), tiles.t_les(), tiles.t_q(q), grid,
            _ids_arg(gids, len(tiles.keys)))
    key = ("groupsum", "hist", func, nsteps, G,
           tuple(arrs["ff_v"].shape), "ps_ones" in arrs)
    fn = _jit_lookup(_EVAL_T_JIT, key, lambda: jax.jit(
        _bind(_hist_quantile_program, func, nsteps, G)),
        site="groupsum", cost_args=args)
    return fn(*args)


_EVAL_JIT: Dict[Tuple, object] = {}


def evaluate_aligned(tiles: AlignedTiles, func: str, steps: np.ndarray,
                     window_ms: int, offset_ms: int = 0,
                     func_args: Sequence[float] = ()) -> jnp.ndarray:
    """Evaluate one windowed range function over aligned tiles: [S, T] f64,
    as a single compiled XLA program. Numerics match the oracle (rangefn)
    modulo prefix-sum rounding — the same summation scheme the general
    device path uses."""
    nsteps = steps.size
    w0e = np.int64(steps[0] - offset_ms)
    w0s = np.int64(w0e - window_ms)
    step = np.int64(steps[1] - steps[0]) if nsteps > 1 else np.int64(1)
    arrs = _tiles_arrays(tiles, func)
    args = (arrs, np.int64(tiles.num_slots),
            np.int64(tiles.base_ms), np.int64(tiles.dt_ms),
            np.int64(w0s), np.int64(w0e), np.int64(step))
    fn = _jit_lookup(_EVAL_JIT, (func, nsteps), lambda: jax.jit(
        _bind(_eval_core, func, nsteps)), cost_args=args)
    return fn(*args)


# ---------------------------------------------------------------------------
# Micro-batched (multi-grid) dispatch: vmapped evaluator families
# ---------------------------------------------------------------------------
#
# The query micro-batcher (query/batcher.py) stacks concurrent queries
# that share (tiles, func, nsteps, step, window) but differ in grid
# position (w0s/w0e) — the dashboard-refresh / concurrent-client shape.
# Each family below is the SAME traceable body as its scalar dispatch,
# vmapped over the (w0s, w0e) scalars only, so member i of a batch is
# bit-for-bit the scalar path's output (pinned by test_batcher's parity
# tests): the batch axis adds a leading dim, every op stays row-local.

_EVAL_T_VMAP: Dict[Tuple, object] = {}
_EVAL_VMAP: Dict[Tuple, object] = {}

_GRID_AXES = (None, None, None, None, 0, 0, None)


def _pad_pow2(vals: Sequence[int]) -> np.ndarray:
    """Pad a member-scalar list to a coarse batch-width bucket (2, 8,
    32, 128, ...) by repeating the last member. Coarse x4 buckets keep
    the number of compiled batch widths tiny — an XLA compile costs
    ~100ms while computing a few redundant pad grids costs microseconds,
    so trading pad work for compile-cache hits is the right side of the
    bargain on the serving path."""
    b = 2
    while b < len(vals):
        b <<= 2
    out = list(vals) + [vals[-1]] * (b - len(vals))
    return np.asarray(out, np.int64)


def counters_batch_family(tiles: AlignedTiles, func: str,
                          steps: np.ndarray, window_ms: int,
                          offset_ms: int = 0) -> Optional[Tuple]:
    """Hashable dispatch-family key for one counter query — two queries
    may share a batched dispatch only when their families match (the
    family fixes which compiled evaluator the scalar path would pick,
    so batching never changes the kernel choice)."""
    nsteps = steps.size
    w0e = int(steps[0] - offset_ms)
    w0s = w0e - window_ms
    step = int(steps[1] - steps[0]) if nsteps > 1 else 1
    el = _slide_eligible(tiles, nsteps, w0s, w0e,
                         int(steps[-1] - offset_ms), step)
    if el is not None:
        return ("slide", el[0])
    lo_rel = w0s - tiles.base_ms
    hi_rel = int(steps[-1] - offset_ms) - tiles.base_ms
    fits_i32 = (_SENT_LO < lo_rel and hi_rel < _SENT_HI
                and tiles.num_slots * tiles.dt_ms + tiles.dt_ms < _SENT_HI)
    return ("fast",) if fits_i32 else ("t",)


def evaluate_counters_t_batch(tiles: AlignedTiles, func: str,
                              family: Tuple, nsteps: int, step: int,
                              w0s_list: Sequence[int],
                              w0e_list: Sequence[int]) -> jnp.ndarray:
    """One vmapped dispatch computing B counter grids over shared tiles
    -> device [B_pad, T, S] (callers slice [:len(w0s_list)]). All
    members must share ``family`` (see counters_batch_family)."""
    assert func in ("rate", "increase", "delta")
    w0s_v = jnp.asarray(_pad_pow2(list(w0s_list)))
    w0e_v = jnp.asarray(_pad_pow2(list(w0e_list)))
    b_pad = int(w0s_v.shape[0])
    kind = family[0]
    if kind == "slide":
        st = family[1]
        arrs = _tiles_arrays_slide(tiles, func, st)
        key = ("slide", func, nsteps, st, b_pad)
        build = lambda: jax.jit(jax.vmap(
            _bind(_eval_counter_slide, func, nsteps, st),
            in_axes=_GRID_AXES))
    elif kind == "fast":
        arrs = _tiles_arrays_fast(tiles, func)
        key = ("fast", func, nsteps, b_pad)
        build = lambda: jax.jit(jax.vmap(
            _bind(_eval_counter_fast, func, nsteps),
            in_axes=_GRID_AXES))
    else:
        arrs = _tiles_arrays_t(tiles, func)
        key = ("t", func, nsteps, b_pad)
        build = lambda: jax.jit(jax.vmap(
            _bind(_eval_counter_t, func, nsteps),
            in_axes=_GRID_AXES))
    args = (arrs, np.int64(tiles.num_slots),
            np.int64(tiles.base_ms), np.int64(tiles.dt_ms),
            w0s_v, w0e_v, np.int64(step))
    fn = _jit_lookup(_EVAL_T_VMAP, key, build,
                     site="tilestore-batch", cost_args=args)
    return fn(*args)


def evaluate_aligned_batch(tiles: AlignedTiles, func: str, nsteps: int,
                           step: int, w0s_list: Sequence[int],
                           w0e_list: Sequence[int]) -> jnp.ndarray:
    """One vmapped dispatch computing B aligned grids (non-counter
    families) over shared tiles -> device [B_pad, S, T]."""
    w0s_v = jnp.asarray(_pad_pow2(list(w0s_list)))
    w0e_v = jnp.asarray(_pad_pow2(list(w0e_list)))
    b_pad = int(w0s_v.shape[0])
    arrs = _tiles_arrays(tiles, func)
    args = (arrs, np.int64(tiles.num_slots),
            np.int64(tiles.base_ms), np.int64(tiles.dt_ms),
            w0s_v, w0e_v, np.int64(step))
    fn = _jit_lookup(_EVAL_VMAP, (func, nsteps, b_pad),
                     lambda: jax.jit(jax.vmap(
                         _bind(_eval_core, func, nsteps),
                         in_axes=_GRID_AXES)),
                     site="tilestore-batch", cost_args=args)
    return fn(*args)
