"""The Pallas TPU kernel of the served path: the fused counter group-sum.

`sum by (g) (rate(c[w]))`, and its `increase`/`delta` forms, as ONE pass
over the stride-permuted aligned tiles (tilestore.AlignedTiles). The
reference pays this as per-shard AggrOverRangeVectors map-reduce over
row iterators (exec/aggregator/*.scala over rangefn/RangeFunction.scala
windows). XLA's best arrangement of the same computation (slices ->
epilogue -> one-hot matmul) pays ~2.5x the HBM traffic materializing the
[T, S] rate intermediate and re-reading it on the MXU; here the boundary
row-blocks per step-tile are DMA'd HBM->VMEM (double-buffered,
prefetched across the sequential program grid), the f32 extrapolation
epilogue (rangefn/RateFunctions.scala:23-79 semantics) runs in VMEM,
and only the [T, G] group sums + counts ever leave the chip.

Values ride a per-series 2xint32 FIXED-POINT channel: at pack time each
series is rebased to its in-tile midpoint and scaled by a per-series
power of two so the full in-tile value range spans 61 bits split as
hi*2^31 + lo. Boundary deltas are computed as exact int32 subtractions
(dh, dl) and only the final f32 recombine dh*2^(31-s) + dl*2^-s rounds
— relative to the DELTA, not the absolute counter value — so the error
is 2^-23|delta| + span*2^-53: the same noise floor as the reference's
f64 path (RateFunctions.scala computes v2-v1 in f64), at 8 bytes per
value instead of 16 and with native i32 VPU ops instead of f64
emulation. Timestamps enter as int32 ms relative to the tile base: the
dispatcher (tilestore._slide_eligible) guards that the whole query span
fits in int31 (~24.8 days).

Traffic shape: the dispatcher only takes grids where the window is a
whole number of steps ((kc0-kl0) % st == 0), which puts the
window-end family (kc0) and window-start family (kl0) in the SAME
stride-residue plane, dspan = (kc0-kl0)/st rows apart — one merged DMA
of TT+dspan rows serves both, and all views are STATIC slices of one
rolled block. The jitter fallback families (kc0-1 / kl0+1) are elided
entirely (hi_mode/lo_mode) when the query grid's phase relative to the
scrape ticks clears the tile's max jitter: then "is the boundary
sample inside the window" has the same answer for every series and
every step, statically.

This module imports nothing of the query package: the dispatcher
(tilestore.groupsum_counters) runs the kernel inside one cached
executable per static tuple, and tpu.TpuBackend.fused_groupsum calls
that.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from filodb_tpu.lint.contracts import ANY, SEM, SMEM, Block, kernel_contract
from filodb_tpu.lint.numerics import precision

_GS_TT = 256           # query steps per tile (sublane dim of compute):
#                        256 halves the sequential-grid iteration count
#                        vs 128 — the loop is scalar-core/DMA-issue
#                        bound, so fewer, larger tiles win
_GS_TT_WIDE = 512      # widened step tile: picked per query by
#                        _gs_pipeline when the [T, G] accumulators +
#                        DMA scratch still fit the VMEM budget — halves
#                        the sequential grid again for long ranges
_GS_NBUF_MAX = 3       # deepest DMA pipeline: triple-buffered scratch
#                        keeps the DMA engine (nbuf-1) tiles ahead, so
#                        the HBM read of tile g+2 overlaps tile g's
#                        compute ACROSS sequential-program boundaries

_GS_SS = 512           # series per tile (lane dim)
_GS_AL = 8             # sublane alignment Mosaic requires of HBM slices

# boundary-family modes (static per compiled kernel)
GS_BOTH = 0            # jitter straddles the grid phase: select per element
GS_CUR = 1             # the nominal slot is always inside the window
GS_ALT = 2             # the nominal slot is always outside: use kc0-1/kl0+1

_GS_DSPAN_MAX = 48     # dispatcher cap on window/step (merged-stream rows)


def _gs_mlen(st: int, dspan: int, tt: int = _GS_TT) -> int:
    lead = 1 if st == 1 else 0
    return tt + _GS_AL + (-(-(dspan + lead) // _GS_AL)) * _GS_AL


def _gs_nstreams(st: int, hi_mode: int, lo_mode: int) -> int:
    return 1 + (1 if hi_mode != GS_CUR and st != 1 else 0) \
        + (1 if lo_mode != GS_CUR and st != 1 else 0)


def _gs_pipeline(st: int, dspan: int, hi_mode: int, lo_mode: int,
                 nsteps: int, G: int,
                 vmem_budget: int = 14 << 20) -> Optional[Tuple[int, int]]:
    """(tt, nbuf) for one kernel build, or None when no configuration
    fits the VMEM budget: prefer the WIDER step tile (fewer sequential
    grid iterations — the loop is scalar-core/DMA-issue bound), then
    the DEEPER DMA pipeline (prefetch distance nbuf-1 overlaps HBM
    reads with compute across program boundaries). The budget covers
    accumulators + scratch + onehot/base input blocks — the full
    on-chip footprint, so an inadmissible query falls back on the host
    instead of exploding at Mosaic compile time."""
    nstreams = _gs_nstreams(st, hi_mode, lo_mode)
    fixed = _GS_SS * G * 4 + 8 * _GS_SS * 4          # onehot + base
    for tt in (_GS_TT_WIDE, _GS_TT):
        if tt != _GS_TT and nsteps <= _GS_TT:
            continue                                 # nothing to widen
        t_pad = -(-nsteps // tt) * tt
        accum = 2 * t_pad * G * 4
        mlen = _gs_mlen(st, dspan, tt)
        for nbuf in range(_GS_NBUF_MAX, 1, -1):
            scratch = nbuf * nstreams * mlen * 3 * _GS_SS * 4
            if accum + scratch + fixed <= vmem_budget:
                return tt, nbuf
    return None


def _groupsum_kernel(func: str, st: int, dspan: int, hi_mode: int,
                     lo_mode: int, exact_branch: bool, n_ttiles: int,
                     mlen: int, tt: int, nbuf: int,
                     params_ref, v_ref, base_ref, oh_ref,
                     out_ref, v_scr, sems):
    """Grid: (n_s,) sequential. params (SMEM, i32):
    [kl0, w0e_rel, window, step, T]."""
    si = pl.program_id(0)
    n_s = pl.num_programs(0)
    kl0 = params_ref[0]
    w0e_rel = params_ref[1]
    window = params_ref[2]
    step = params_ref[3]
    T = params_ref[4]
    kc0 = kl0 + dspan * st
    lead = 1 if st == 1 else 0
    # st == 1 puts every slot in the single residue plane, so the
    # fallback families live INSIDE the merged block (lead covers kc0-1
    # when dspan == 0); otherwise they are their own streams.
    need1 = hi_mode != GS_CUR and st != 1
    need3 = lo_mode != GS_CUR and st != 1
    idx1 = 1
    idx3 = 1 + (1 if need1 else 0)
    i_kl = lead
    i_kc = lead + dspan
    i_f1 = dspan + lead - 1          # st == 1 only (kc0 - 1)
    i_f3 = lead + 1                  # st == 1 only (kl0 + 1)

    def dmas(si_, slot, ti):
        out = []
        g_m = jax.lax.div(kl0, jnp.int32(st)) + ti * tt - lead
        g8m = pl.multiple_of((g_m // _GS_AL) * _GS_AL, _GS_AL)
        # the permuted G axis is padded past every tail tile
        # (t_perm_tiled), so blocks stay in bounds; dead rows are masked
        # out via `live`. ONE copy per stream: ts + hi + lo planes ride
        # a single contiguous HBM read (consecutive G rows of a
        # (s-tile, residue) plane are adjacent in memory).
        out.append(pltpu.make_async_copy(
            v_ref.at[si_, jax.lax.rem(kl0, jnp.int32(st)),
                     pl.ds(g8m, mlen), :],
            v_scr.at[slot, 0], sems.at[slot, 0]))
        for need, idx, kf in ((need1, idx1, kc0 - 1),
                              (need3, idx3, kl0 + 1)):
            if not need:
                continue
            g = jax.lax.div(kf, jnp.int32(st)) + ti * tt
            g8 = pl.multiple_of((g // _GS_AL) * _GS_AL, _GS_AL)
            out.append(pltpu.make_async_copy(
                v_ref.at[si_, jax.lax.rem(kf, jnp.int32(st)),
                         pl.ds(g8, tt + _GS_AL), :],
                v_scr.at[slot, idx, pl.ds(0, tt + _GS_AL)],
                sems.at[slot, idx]))
        return out

    @pl.when(si == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        # pipeline warm-up: fill nbuf-1 scratch slots ahead (global
        # tiles 0..nbuf-2, crossing program boundaries for tiny grids)
        for g in range(nbuf - 1):

            @pl.when(jnp.int32(g) < n_s * n_ttiles)
            def _(g=g):
                for d in dmas(jnp.int32(g // n_ttiles), g % nbuf,
                              jnp.int32(g % n_ttiles)):
                    d.start()

    def t_loop(ti, _):
        gti = si * n_ttiles + ti
        slot = jax.lax.rem(gti, nbuf)

        # keep the DMA engine nbuf-1 tiles AHEAD — prefetching across
        # sequential-program boundaries, so the HBM read of tile
        # g+nbuf-1 overlaps tile g's compute and the engine never
        # idles between grid programs
        gn = gti + (nbuf - 1)

        @pl.when(gn < n_s * n_ttiles)
        def _():
            for d in dmas(jax.lax.div(gn, jnp.int32(n_ttiles)),
                          jax.lax.rem(gn, jnp.int32(nbuf)),
                          jax.lax.rem(gn, jnp.int32(n_ttiles))):
                d.start()
        for d in dmas(si, slot, ti):
            d.wait()

        gt = ti * tt + jax.lax.broadcasted_iota(
            jnp.int32, (tt, 1), 0)                         # [TT, 1]
        live = gt < T
        wend_r = w0e_rel + gt * step
        wstart_r = wend_r - window

        g_m = jax.lax.div(kl0, jnp.int32(st)) + ti * tt - lead
        g8m = pl.multiple_of((g_m // _GS_AL) * _GS_AL, _GS_AL)
        offm = g_m - g8m
        # ONE dynamic roll; every family view is a STATIC slice of it
        # (plain dynamic_slice on vectors has no Mosaic lowering, and
        # NEGATIVE dynamic roll shifts mis-lower — rotate left by
        # `len - off` instead). Row i of R is permuted-G row g_m + i.
        R = pltpu.roll(v_scr[slot, 0], shift=mlen - offm, axis=0)

        def view(row0):
            return R[row0:row0 + tt]

        def fam_view(idx, kf):
            full = v_scr[slot, idx, :tt + _GS_AL]
            g = jax.lax.div(kf, jnp.int32(st)) + ti * tt
            off = g - pl.multiple_of((g // _GS_AL) * _GS_AL, _GS_AL)
            return pltpu.roll(full, shift=(tt + _GS_AL) - off,
                              axis=0)[:tt]

        def planes(v):
            return (v[:, :_GS_SS], v[:, _GS_SS:2 * _GS_SS],
                    v[:, 2 * _GS_SS:3 * _GS_SS])

        ts_kc, hi_kc, lo_kc = planes(view(i_kc))
        ts_kl, hi_kl, lo_kl = planes(view(i_kl))
        if hi_mode != GS_CUR:
            ts_kp, hi_kp, lo_kp = planes(
                view(i_f1) if st == 1 else fam_view(idx1, kc0 - 1))
        if lo_mode != GS_CUR:
            ts_kn, hi_kn, lo_kn = planes(
                view(i_f3) if st == 1 else fam_view(idx3, kl0 + 1))

        if hi_mode == GS_BOTH:
            over = ts_kc > wend_r
            overc = over.astype(jnp.int32)
            t2 = jnp.where(over, ts_kp, ts_kc)
            h2 = jnp.where(over, hi_kp, hi_kc)
            l2 = jnp.where(over, lo_kp, lo_kc)
        elif hi_mode == GS_CUR:
            overc = jnp.int32(0)
            t2, h2, l2 = ts_kc, hi_kc, lo_kc
        else:
            overc = jnp.int32(1)
            t2, h2, l2 = ts_kp, hi_kp, lo_kp
        if lo_mode == GS_BOTH:
            under = ts_kl < wstart_r
            underc = under.astype(jnp.int32)
            t1 = jnp.where(under, ts_kn, ts_kl)
            h1 = jnp.where(under, hi_kn, hi_kl)
            l1 = jnp.where(under, lo_kn, lo_kl)
        elif lo_mode == GS_CUR:
            underc = jnp.int32(0)
            t1, h1, l1 = ts_kl, hi_kl, lo_kl
        else:
            underc = jnp.int32(1)
            t1, h1, l1 = ts_kn, hi_kn, lo_kn

        counts = (dspan * st + 1) - overc - underc
        # exact integer boundary deltas; the f32 recombine rounds
        # relative to the delta (see module comment)
        dh = (h2 - h1).astype(jnp.float32)
        dl = (l2 - l1).astype(jnp.float32)
        c1 = base_ref[1:2, :]                              # 2^(31-s)
        c2 = base_ref[2:3, :]                              # 2^-s
        delta = dh * c1 + dl * c2
        sampled_i = t2 - t1
        dstart_i = t1 - wstart_r
        dend_i = wend_r - t2
        sampled = sampled_i.astype(jnp.float32) * 1e-3
        dstart = dstart_i.astype(jnp.float32) * 1e-3
        dend = dend_i.astype(jnp.float32) * 1e-3
        counts_f = counts.astype(jnp.float32)
        avg = sampled / (counts_f - 1.0)
        th = avg * 1.1
        # the "gap < 1.1 * avg interval" extrapolation branches: every
        # input is integer ms, so when 10*counts*window can't overflow
        # i32 the branch is decided EXACTLY as 10*(cnt-1)*gap <=
        # 11*sampled (<=, not <: f64 rounds 1.1 upward, so the
        # reference's f64 compare takes the extrapolate side on exact
        # ties — knife-edge windows otherwise flip between the f32
        # kernel and the f64 oracle)
        if exact_branch:
            cm1 = counts - 1
            s11 = 11 * sampled_i
            use_ds = (10 * cm1) * dstart_i <= s11
            use_de = (10 * cm1) * dend_i <= s11
        else:
            use_ds = dstart < th
            use_de = dend < th
        if func != "delta":
            v1f = (h1.astype(jnp.float32) * c1
                   + l1.astype(jnp.float32) * c2) + base_ref[0:1, :]
            dzero = jnp.where(
                (delta > 0) & (v1f >= 0),
                sampled * (v1f / jnp.where(delta == 0, jnp.nan, delta)),
                jnp.inf)
            zlt = dzero < dstart
            dstart = jnp.where(zlt, dzero, dstart)
            # boolean select via mask algebra (Mosaic has no i1 select)
            use_ds = (zlt & (dzero < th)) | (~zlt & use_ds)
        extrap = sampled \
            + jnp.where(use_ds, dstart, avg * 0.5) \
            + jnp.where(use_de, dend, avg * 0.5)
        factor = extrap / sampled
        if func == "rate":
            factor = factor / (window.astype(jnp.float32) * 1e-3)
        out = delta * factor
        ok = live & (counts >= 2) & ~jnp.isnan(out)
        local = jnp.where(ok, out, jnp.float32(0.0))
        okf = jnp.where(ok, jnp.float32(1.0), jnp.float32(0.0))
        oh = oh_ref[:]
        sl = pl.ds(ti * tt, tt)
        # HIGHEST: the MXU's default bf16 input truncation would round
        # every rate to 8 mantissa bits (bf16(0.1) = 0.10009765625)
        prec = jax.lax.Precision.HIGHEST
        out_ref[0, sl, :] += jnp.dot(local, oh,
                                     preferred_element_type=jnp.float32,
                                     precision=prec)
        out_ref[1, sl, :] += jnp.dot(okf, oh,
                                     preferred_element_type=jnp.float32,
                                     precision=prec)

    jax.lax.fori_loop(0, n_ttiles, t_loop, None)


def _groupsum_example():
    """Abstract inputs for jax.eval_shape: st=1 / dspan=0 / both modes
    GS_CUR is the single-stream configuration (mlen = 272)."""
    g_perm = 512
    args = ("rate", 1, 0, GS_CUR, GS_CUR, True, 256,
            jax.ShapeDtypeStruct((1, 1, g_perm, 3 * _GS_SS), jnp.int32),
            jax.ShapeDtypeStruct((1, 8, _GS_SS), jnp.float32),
            jax.ShapeDtypeStruct((_GS_SS, 16), jnp.float32),
            jax.ShapeDtypeStruct((5,), jnp.int32))
    return args, {}


def _groupsum_expect(out):
    want = ((2, 256, 16), jnp.float32)
    if tuple(out.shape) != want[0] or out.dtype != want[1]:
        return f"output {out.shape}/{out.dtype} != {want}"
    return None


def counter_groupsum(func: str, st: int, dspan: int, hi_mode: int,
                     lo_mode: int, v_p, base, onehot,
                     kl0, w0e_rel, window: int, step: int, nsteps: int,
                     interpret: bool = False,
                     exact_branch: Optional[bool] = None):
    """sum by(group) of rate/increase/delta over stride-permuted dense
    tiles -> f32 [2, T, G]: the sums at [0], the counts at [1] (a sum is
    only meaningful where its count > 0), the kernel's one output.

    v_p: the packed kernel channel [n_s, st, G_perm, 3*_GS_SS] i32 —
    plane 0 = int32 relative timestamps, planes 1-2 = the per-series
    fixed-point hi/lo split of the (counter-corrected) value channel
    (AlignedTiles.t_perm_fixed_tiled). base: [n_s, 8, _GS_SS] f32 — row
    0 = per-series rebase midpoint (f32), row 1 = 2^(31-s), row 2 =
    2^-s (AlignedTiles.t_fixed_base). onehot: [n_s * _GS_SS, G] f32
    group membership (pad series with all-zero one-hot rows).

    Static dispatch contract (the tilestore dispatcher checks it):
    regular grid with step == st*dt entirely interior to the tile,
    dense tiles, span fits int32 ms, kc0 - kl0 == dspan * st with
    kc0/kl0 the per-query boundary slots, and hi_mode/lo_mode sound for
    the tile's jitter bound (GS_CUR/GS_ALT only when the grid phase
    clears the max |ts - tick|)."""
    if exact_branch is None:
        exact_branch = groupsum_exact_branch(window, st, dspan)
    params = jnp.stack([jnp.asarray(v, jnp.int32) for v in (
        kl0, w0e_rel, window, step, nsteps)])
    return groupsum_call(func, st, dspan, hi_mode, lo_mode,
                         bool(exact_branch), nsteps, v_p, base, onehot,
                         params, interpret=interpret)


def groupsum_exact_branch(window: int, st: int, dspan: int) -> bool:
    """Whether the integer extrapolation-branch products fit i32 (a
    host decision from the query's window: static per compiled kernel)."""
    return 11 * int(window) * (dspan * st + 1) < 2 ** 31


# Worst-case on-chip footprint the tilestore dispatcher may admit (its
# own cap is 14 MB): three DMA streams at the _GS_DSPAN_MAX merged
# length, modest group count. The dispatcher trades streams against
# [T, G] accumulator size; this declaration pins the largest shape on
# the stream-heavy side of that frontier.
@precision(
    "groupsum-recombine-f32", bits=61, rel_ulps=4,
    reason="boundary deltas are exact int32 subtractions of the "
           "fixed-point hi/lo planes; the f32 recombine "
           "dh*2^(31-s) + dl*2^-s rounds relative to the delta (wide "
           "deltas also round dl itself into f32), bounded by a few "
           "f32 ulps plus the span*2^-59 quantization floor — "
           "certified against the direct f64 delta over full-span "
           "boundary pairs; branch decisions stay in integer space "
           "(exact_branch), which mixed-dtype-comparison polices")
@kernel_contract(
    "counter_groupsum", kind="pallas",
    grid=(8,),
    blocks=(
        Block("params", (5,), "int32", space=SMEM, tiled=False),
        Block("v_p", (8, 2, 4096, 3 * _GS_SS), "int32", space=ANY),
        Block("base", (1, 8, _GS_SS), "float32",
              array_shape=(8, 8, _GS_SS),
              index_map=lambda si: (si, 0, 0)),
        Block("onehot", (_GS_SS, 256), "float32",
              array_shape=(8 * _GS_SS, 256),
              index_map=lambda si: (si, 0)),
    ),
    scratch=(
        # worst-case ADMISSIBLE DMA scratch on the (step-tile width,
        # pipeline depth) frontier _gs_pipeline walks: 2 slots x 3
        # streams x mlen(st=2, dspan=48, tt=256)=312 rows x 3 planes
        # (wider tiles / deeper pipelines are only chosen in cheaper
        # stream configurations — the chooser keeps the total <= 14MB)
        Block("v_scr", (2, 3, 312, 3 * _GS_SS), "int32"),
        Block("sems", (2, 3), "int32", space=SEM),
    ),
    outputs=(
        Block("out", (2, 256, 256), "float32",
              array_shape=(2, 256, 256), index_map=lambda si: (0, 0, 0)),
    ),
    vmem_budget=14 << 20,
    rel_time_bits=31,
    span_guard="filodb_tpu.query.tilestore:_slide_eligible",
    example=_groupsum_example, expect=_groupsum_expect,
    notes="dispatched only via tilestore.groupsum_counters, which "
          "re-derives this footprint per query, falls back to the "
          "general path above 14 MB, and runs the kernel inside one "
          "cached executable per static tuple (the one-hot block is "
          "made there, on the device, from the query's group ids)")
def groupsum_call(func: str, st: int, dspan: int, hi_mode: int,
                  lo_mode: int, exact_branch: bool, nsteps: int,
                  v_p, base, onehot, params, interpret: bool = False):
    """:func:`counter_groupsum` with the per-query scalars as ONE int32[5]
    vector ``params`` = (kl0, w0e_rel, window, step, nsteps), which is
    what the kernel reads from SMEM: the form the jitted dispatcher
    (tilestore.groupsum_counters) traces, so that a query's scalars
    reach the chip as one small copy and no program assembles them.
    Everything before ``v_p`` is static."""
    n_s = v_p.shape[0]
    G = onehot.shape[1]
    assert onehot.shape[0] == n_s * _GS_SS, (onehot.shape, n_s)
    # step-tile width + DMA pipeline depth for this query shape: widen
    # to _GS_TT_WIDE / deepen to triple-buffering whenever the on-chip
    # footprint allows (callers pre-check _gs_pipeline; this assert is
    # the contract)
    pipe = _gs_pipeline(st, dspan, hi_mode, lo_mode, nsteps, G)
    assert pipe is not None, "caller must gate on _gs_pipeline"
    tt, nbuf = pipe
    T_pad = -(-nsteps // tt) * tt
    n_ttiles = T_pad // tt
    mlen = _gs_mlen(st, dspan, tt)
    nstreams = _gs_nstreams(st, hi_mode, lo_mode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_s,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 8, _GS_SS), lambda si, p: (si, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_GS_SS, G), lambda si, p: (si, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((2, T_pad, G), lambda si, p: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((nbuf, nstreams, mlen, 3 * _GS_SS), jnp.int32),
            pltpu.SemaphoreType.DMA((nbuf, nstreams)),
        ],
    )

    def body(params, v_p, base, onehot, *, _k=functools.partial(
            _groupsum_kernel, func, st, dspan, hi_mode, lo_mode,
            bool(exact_branch), n_ttiles, mlen, tt, nbuf)):
        def kern(params_ref, v_ref, base_ref, oh_ref, out_ref, v_scr,
                 sems):
            _k(params_ref, v_ref, base_ref[0], oh_ref, out_ref, v_scr,
               sems)
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((2, T_pad, G), jnp.float32),
            interpret=interpret,
            name="counter_groupsum",
        )(params, v_p, base, onehot)

    with jax.enable_x64(False):
        out = body(params, v_p, base, onehot)
    return out[:, :nsteps]
