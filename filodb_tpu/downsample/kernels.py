"""Device downsample kernels: per-period aggregates over [S, N] tiles.

The reference computes one value per chunk per period with per-row iterator
``ChunkDownsampler``s (core/downsample/ChunkDownsampler.scala:38-353 —
SumDownsampler, CountDownsampler, MinDownsampler, MaxDownsampler,
AvgDownsampler, LastValueDDownsampler, TimeDownsampler) driven by
``DownsamplePeriodMarker`` row ranges (time-aligned, plus counter-correction
boundaries for counters).

Here the whole batch is one fused XLA program: period assignment is integer
arithmetic per sample, aggregation is scatter-add/min/max onto a dense
[S, P] period grid (same trick as the query engine's window bounds — the
scatter rides the VPU, results stay on device until the host encodes
chunks). Counter period boundaries (resets) come out as an emit mask, since
counter downsampling persists boundary samples rather than aggregates.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from filodb_tpu.lint.contracts import kernel_contract


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ds_example(extra_statics, S=8, N=64):
    args = (_sds((S, N), jnp.int64), _sds((S, N), jnp.float64),
            _sds((S,), jnp.int32), _sds((), jnp.int64),
            _sds((), jnp.int64))
    return args, dict(extra_statics)


def _six_expect(S, P):
    """The (sum, count, min, max, last_v, last_ts) output family."""
    def expect(out):
        shapes = [tuple(o.shape) for o in out]
        if shapes != [(S, P)] * 6:
            return f"outputs {shapes} != 6x({S}, {P})"
        if str(out[-1].dtype) != "int64" \
                or any(str(o.dtype) != "float64" for o in out[:5]):
            return "dtypes != (5x f64, i64)"
        return None
    return expect


@kernel_contract(
    "downsample_gauge", kind="jit",
    example=lambda: _ds_example({"nperiods": 16, "w_bound": 8}),
    expect=_six_expect(8, 16),
    notes="general gather path: [S, P, W] bounded gather for order "
          "statistics, prefix sums for sum/count; W static")
@functools.partial(jax.jit, static_argnames=("nperiods", "w_bound"))
def downsample_gauge_tiles(ts, vals, lens, base, res, nperiods: int,
                           w_bound: int = 64):
    """Per-period (sum, count, min, max, last_v, last_ts) for gauge tiles.

    Period p = (ts - base) // res; samples outside [0, nperiods) and row
    padding are dropped; empty periods are NaN. (dSum/dCount/dMin/dMax/
    dAvg/tTime of the gauge schema in one pass; avg = sum/count is
    computed by the caller.)

    Timestamps are sorted per row, so periods are CONTIGUOUS index ranges:
    this is the query engine's uniform window machinery with
    window == step == res — int32 scatter-histogram bounds + f64 prefix
    sums + a [S, P, W] bounded gather for the order statistics. (A direct
    f64 scatter-add/min/max onto [S, P] lowers to a serialized TPU scatter
    and ran ~500x slower.) ``w_bound`` is a static cap on samples per
    period for the min/max gather."""
    from filodb_tpu.query.tpu import _bounds, _prefix, _take

    S, N = ts.shape
    idx = jnp.arange(N)[None, :]
    valid = idx < lens[:, None]
    ts = jnp.where(valid, ts, jnp.int64(1) << 60)   # pad -> no period
    lo, hi = _bounds(ts, base, base + res - 1, res, nperiods)   # [S, P]
    counts = (hi - lo + 1).astype(jnp.float64)
    has = counts >= 1
    nan = jnp.nan
    v = jnp.where(valid, vals, 0.0)
    cs = _prefix(v)
    sums = _take(cs, jnp.clip(hi + 1, 0, N)) - _take(cs, jnp.clip(lo, 0, N))
    hi_c = jnp.clip(hi, 0, N - 1)
    last_v = _take(vals, hi_c)
    last_ts = _take(ts, hi_c)
    # order statistics: bounded gather over each period's index range
    offs = jnp.arange(w_bound)
    gidx = lo[:, :, None] + offs[None, None, :]          # [S, P, W]
    in_p = (gidx <= hi[:, :, None]) & (gidx < lens[:, None, None])
    gidx_c = jnp.clip(gidx, 0, N - 1)
    g = jnp.take_along_axis(vals, gidx_c.reshape(S, -1), axis=1).reshape(
        gidx.shape)
    mins = jnp.min(jnp.where(in_p, g, jnp.inf), axis=2)
    maxs = jnp.max(jnp.where(in_p, g, -jnp.inf), axis=2)
    return (jnp.where(has, sums, nan), jnp.where(has, counts, 0.0),
            jnp.where(has, mins, nan), jnp.where(has, maxs, nan),
            jnp.where(has, last_v, nan),
            jnp.where(has, last_ts, jnp.int64(0)))


def cascade_gauge(prev, base, res, nperiods: int, w_bound: int):
    """Downsample one resolution level from the previous level's outputs
    (sum of sums, count of counts, min of mins, max of maxes, last of
    lasts) — the multi-resolution cascade: only the finest level reads raw
    samples. ``prev`` is the previous level's 6-tuple."""
    p_sums, p_cnts, p_mins, p_maxs, p_last_v, p_last_ts = prev
    S, P = p_sums.shape
    has = p_cnts > 0
    pts = jnp.where(has, p_last_ts, jnp.int64(1) << 60)  # empty -> dropped
    lens = jnp.full((S,), P, dtype=jnp.int32)

    def run(chan):
        return downsample_gauge_tiles(pts, jnp.where(has, chan, 0.0), lens,
                                      base, res, nperiods, w_bound)

    s_out = run(p_sums)
    c_out = run(p_cnts)
    m_out = run(p_mins)
    x_out = run(p_maxs)
    l_out = run(p_last_v)
    counts = jnp.where(jnp.isnan(c_out[0]), 0.0, c_out[0])
    return (s_out[0], counts, m_out[2], x_out[3], l_out[4], s_out[5])


@kernel_contract(
    "counter_emit_mask", kind="jit",
    example=lambda: _ds_example({"nperiods": 16}),
    expect=lambda out: None if tuple(out.shape) == (8, 64)
    and str(out.dtype) == "bool" else f"mask {out.shape}/{out.dtype}",
    notes="pure lane arithmetic (no scatter): last-of-period + both "
          "sides of every counter reset")
@functools.partial(jax.jit, static_argnames=("nperiods",))
def counter_emit_mask(ts, vals, lens, base, res, nperiods: int):
    """Emit mask for counter downsampling: keep the LAST sample of every
    period plus BOTH sides of every reset — the peak right before it and
    the reset sample itself (DownsamplePeriodMarker counter boundaries,
    DownsamplePeriodMarker.scala; dLast of prom-counter).

    Emitting both sides makes every drop visible to query-time counter
    correction even when the counter climbs back above the old peak before
    the period ends, so sum-of-increases over the emitted rows equals the
    raw correction's from any emitted baseline onward."""
    S, N = ts.shape
    idx = jnp.arange(N)[None, :]
    valid = idx < lens[:, None]
    p = ((ts - base) // jnp.maximum(res, 1)).astype(jnp.int32)
    p_ok = valid & (p >= 0) & (p < nperiods)
    # rows are time-sorted: a sample is last-in-period iff its successor is
    # invalid or falls in a different period (pure lane arithmetic — no
    # scatter, which TPU would serialize)
    nxt_p = jnp.concatenate([p[:, 1:],
                             jnp.full((S, 1), -1, p.dtype)], axis=1)
    nxt_valid = jnp.concatenate([valid[:, 1:],
                                 jnp.zeros((S, 1), bool)], axis=1)
    is_last = ~nxt_valid | (nxt_p != p)
    nxt = jnp.concatenate([vals[:, 1:], vals[:, -1:]], axis=1)
    peak = (nxt < vals) & nxt_valid                       # next is a reset
    prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
    is_reset = (vals < prev) & (idx > 0) & valid          # first after drop
    return (is_last | peak | is_reset) & p_ok


# ---------------------------------------------------------------------------
# Regular-cadence fast path: reshape instead of gather
# ---------------------------------------------------------------------------
# For a batch whose rows share one scrape cadence (nominal ticks
# t0 + i*dt, |jitter| < dt/2 — the realistic downsampler input) every
# period's samples form a CONSTANT-length run of R = res//dt sample
# indices, with at most ONE boundary slot per period whose jitter can
# push it into a neighbouring period — and the grid phase decides
# STATICALLY which direction that is. So the whole per-period
# aggregation is reshape + reduce (HBM-bound, compiles in seconds); the
# general [S, P, W] gather kernel above stays as the fallback for
# ragged/irregular batches (its XLA program takes minutes to compile at
# batch shapes and gathers at ~1/6 of streaming bandwidth).


@kernel_contract(
    "downsample_regular", kind="jit",
    example=lambda: (
        (_sds((8, 64), jnp.int64), _sds((8, 64), jnp.float64),
         _sds((), jnp.int64), _sds((), jnp.int64)),
        {"R": 4, "nperiods": 8, "c0": 2, "down": False}),
    expect=_six_expect(8, 8),
    notes="regular-cadence reshape fast path; dispatch gated by "
          "regular_cadence (jitter strictly under dt/2, res % dt == 0)")
@functools.partial(jax.jit,
                   static_argnames=("R", "nperiods", "c0", "down"))
def _ds_regular(ts, vals, base, res, R: int, nperiods: int, c0: int,
                down: bool):
    S, N = ts.shape
    P = nperiods
    SENT = jnp.int64(1) << 60
    if c0 < 0:
        ts = jnp.concatenate(
            [jnp.full((S, -c0), SENT, ts.dtype), ts], axis=1)
        vals = jnp.concatenate(
            [jnp.zeros((S, -c0), vals.dtype), vals], axis=1)
        N -= c0
        c0 = 0
    need = c0 + P * R
    if need > N:
        ts = jnp.concatenate(
            [ts, jnp.full((S, need - N), SENT, ts.dtype)], axis=1)
        vals = jnp.concatenate(
            [vals, jnp.zeros((S, need - N), vals.dtype)], axis=1)
    tw = ts[:, c0:c0 + P * R].reshape(S, P, R)
    vw = vals[:, c0:c0 + P * R].reshape(S, P, R)
    valid = tw < (jnp.int64(1) << 59)
    pb = base + jnp.arange(P, dtype=jnp.int64) * res      # period starts
    # the tick just OUTSIDE the reshape slice can jitter into a covered
    # edge period: in up-mode tick c0-1 into period 0, in down-mode tick
    # c0 + P*R into period P-1 (out-of-range indices read the sentinel
    # padding and fall out via the validity check)
    SENT_LO = jnp.int64(1) << 59
    if down:
        e_ts = ts[:, c0 + P * R] if ts.shape[1] > c0 + P * R \
            else jnp.full((S,), SENT, ts.dtype)
        e_v = vals[:, c0 + P * R] if ts.shape[1] > c0 + P * R \
            else jnp.zeros((S,), vals.dtype)
        e_ok = (e_ts < SENT_LO) & (e_ts < base + P * res) \
            & (e_ts >= base + (P - 1) * res)
        e_period = P - 1
    else:
        e_ts = ts[:, c0 - 1] if c0 >= 1 \
            else jnp.full((S,), SENT, ts.dtype)
        e_v = vals[:, c0 - 1] if c0 >= 1 else jnp.zeros((S,), vals.dtype)
        e_ok = (e_ts < SENT_LO) & (e_ts >= base) & (e_ts < base + res)
        e_period = 0
    if down:
        # only the FIRST slot of a period can cross (into the previous)
        bpos = 0
        b_ts, b_v, b_ok = tw[:, :, 0], vw[:, :, 0], valid[:, :, 0]
        crossed = b_ts < pb[None, :]
    else:
        # only the LAST slot can cross (into the next)
        bpos = R - 1
        b_ts, b_v, b_ok = tw[:, :, -1], vw[:, :, -1], valid[:, :, -1]
        crossed = b_ts >= (pb + res)[None, :]

    own_ok = b_ok & ~crossed
    mv_ok = b_ok & crossed
    # full member mask of window p's OWN samples: every valid slot,
    # with the boundary slot gated on not-crossed
    pos = jnp.arange(R)
    member_ok = jnp.where(pos[None, None, :] == bpos,
                          own_ok[:, :, None], valid)

    def nb(arr, fill):
        """The neighbour period's view of the moved boundary sample."""
        if down:        # b_{p+1} moves INTO p
            return jnp.concatenate(
                [arr[:, 1:], jnp.full_like(arr[:, :1], fill)], axis=1)
        return jnp.concatenate(                     # b_{p-1} moves INTO p
            [jnp.full_like(arr[:, :1], fill), arr[:, :-1]], axis=1)

    mv_ok_n = nb(mv_ok, False)
    mv_v_n = nb(jnp.where(mv_ok, b_v, 0.0), 0.0)
    cnt = (member_ok.sum(axis=2) + mv_ok_n).astype(jnp.float64)
    sums = jnp.where(member_ok, vw, 0.0).sum(axis=2) + mv_v_n
    inf = jnp.inf
    mins = jnp.minimum(jnp.where(member_ok, vw, inf).min(axis=2),
                       nb(jnp.where(mv_ok, b_v, inf), inf))
    maxs = jnp.maximum(jnp.where(member_ok, vw, -inf).max(axis=2),
                       nb(jnp.where(mv_ok, b_v, -inf), -inf))
    # latest own sample: masked ts-max (windows at the batch tail end in
    # padding, so a fixed slot index would miss it), then the value at
    # that (unique, strictly-increasing) timestamp
    IMIN = jnp.int64(-1) << 62
    own_last_ts = jnp.where(member_ok, tw, IMIN).max(axis=2)
    own_last_v = jnp.where(member_ok & (tw == own_last_ts[:, :, None]),
                           vw, 0.0).sum(axis=2)
    own_has = member_ok.any(axis=2)
    if down:
        # an incoming crossed boundary (index (p+1)R + c0) postdates
        # every own sample
        mv_ts_n = nb(jnp.where(mv_ok, b_ts, jnp.int64(0)), jnp.int64(0))
        last_ts = jnp.where(mv_ok_n, mv_ts_n,
                            jnp.where(own_has, own_last_ts, 0))
        last_v = jnp.where(mv_ok_n, mv_v_n,
                           jnp.where(own_has, own_last_v, jnp.nan))
    else:
        # an incoming crossed boundary (index pR + c0 - 1) PREdates
        # every own sample — it is the latest only for windows with no
        # own members
        mv_ts_n = nb(jnp.where(mv_ok, b_ts, jnp.int64(0)), jnp.int64(0))
        last_ts = jnp.where(own_has, own_last_ts,
                            jnp.where(mv_ok_n, mv_ts_n, 0))
        last_v = jnp.where(own_has, own_last_v,
                           jnp.where(mv_ok_n, mv_v_n, jnp.nan))
    # fold the out-of-slice edge tick into its edge period
    ecol = jnp.zeros((P,), bool).at[e_period].set(True)[None, :]
    e_in = e_ok[:, None] & ecol
    cnt = cnt + e_in
    sums = sums + jnp.where(e_in, e_v[:, None], 0.0)
    mins = jnp.minimum(mins, jnp.where(e_in, e_v[:, None], jnp.inf))
    maxs = jnp.maximum(maxs, jnp.where(e_in, e_v[:, None], -jnp.inf))
    if down:
        # the edge tick postdates every covered sample of period P-1
        last_ts = jnp.where(e_in, e_ts[:, None], last_ts)
        last_v = jnp.where(e_in, e_v[:, None], last_v)
    else:
        # the edge tick (c0-1) PREdates period 0's own samples: it is
        # the latest only when the period had none
        e_only = e_in & (last_ts == 0)
        last_ts = jnp.where(e_only, e_ts[:, None], last_ts)
        last_v = jnp.where(e_only, e_v[:, None], last_v)
    has = cnt > 0
    nan = jnp.nan
    return (jnp.where(has, sums, nan), cnt,
            jnp.where(has & jnp.isfinite(mins), mins, nan),
            jnp.where(has & jnp.isfinite(maxs), maxs, nan),
            jnp.where(has, last_v, nan),
            jnp.where(has, last_ts, jnp.int64(0)))


def regular_cadence(ts_pad: np.ndarray, lens: np.ndarray, res: int
                    ) -> Optional[Tuple[int, int]]:
    """Host-side gate for the reshape fast path: dense rows sharing one
    nominal tick grid t0 + i*dt with max |jitter| strictly under dt/2,
    and res a whole number of ticks. Returns (t0, dt) or None."""
    S, N = ts_pad.shape
    if S == 0 or N < 2 or not bool((lens == N).all()):
        return None
    ts = np.asarray(ts_pad)
    dt_raw = float(ts[0, -1] - ts[0, 0]) / (N - 1)
    # jitter makes the raw estimate off by a few ms: snap to round
    # cadences and let the jitter bound (the actual correctness gate)
    # pick the first that fits
    cands = []
    for m in (60_000, 30_000, 15_000, 10_000, 5_000, 1_000, 500, 100,
              10, 1):
        c = int(round(dt_raw / m)) * m
        if c > 0 and c not in cands:
            cands.append(c)
    idx = np.arange(N, dtype=np.int64)
    for dt in cands:
        if res % dt != 0:
            continue
        t0 = int(np.round((ts - idx[None, :] * dt).mean()))
        j = np.abs(ts - (t0 + idx[None, :] * dt)).max()
        if j < dt / 2:
            return t0, dt
    return None


def downsample_gauge_fast(ts_pad, vals_pad, lens, base, res,
                          nperiods: int, cadence=None):
    """Dispatch the reshape fast path when the batch qualifies
    (regular_cadence); None -> caller falls back to the gather kernel.
    ``cadence=(t0, dt)`` skips the host gate for callers that know the
    grid by construction (device-resident benches: the gate would pull
    the whole ts tile back to the host)."""
    rc = cadence if cadence is not None \
        else regular_cadence(ts_pad, lens, int(res))
    if rc is None:
        return None
    t0, dt = rc
    if int(res) % dt != 0:
        return None
    R = int(res) // dt
    if R < 2:
        return None
    o0 = t0 - int(base)
    c0 = -(-(-o0) // dt)                 # ceil(-o0 / dt)
    d1 = o0 + c0 * dt                    # grid phase within the period
    down = d1 < dt / 2
    return _ds_regular(jnp.asarray(ts_pad), jnp.asarray(vals_pad),
                       jnp.int64(base), jnp.int64(res), R, nperiods,
                       c0, down)


@kernel_contract(
    "cascade_aligned", kind="jit",
    example=lambda: (
        (tuple(_sds((8, 16), jnp.float64) for _ in range(5))
         + (_sds((8, 16), jnp.int64),), 4, 1),
        {}),
    expect=_six_expect(8, 5),       # Q = ceil((16 + 1) / 4)
    notes="nested-resolution cascade: reshape + NaN-aware reduce over "
          "ratio consecutive fine periods")
@functools.partial(jax.jit, static_argnames=("ratio", "lead"))
def cascade_gauge_aligned(prev, ratio: int, lead: int):
    """Coarse level from a fine level when the resolutions nest
    (res_coarse % res_fine == 0): each coarse period is `ratio`
    consecutive fine periods (offset by `lead` fine periods for the
    base alignment) — pure reshape + NaN-aware reduce, no kernel."""
    p_sums, p_cnts, p_mins, p_maxs, p_last_v, p_last_ts = prev
    S, P = p_sums.shape
    Q = -(-(P + lead) // ratio)
    padR = Q * ratio - P - lead

    def grp(a, fill):
        a = jnp.concatenate(
            [jnp.full((S, lead), fill, a.dtype), a,
             jnp.full((S, padR), fill, a.dtype)], axis=1)
        return a.reshape(S, Q, ratio)

    has = grp(p_cnts, 0.0) > 0
    cnt = jnp.where(has, grp(p_cnts, 0.0), 0.0).sum(axis=2)
    sums = jnp.where(has, grp(jnp.nan_to_num(p_sums), 0.0), 0.0).sum(axis=2)
    mins = jnp.where(has, grp(jnp.nan_to_num(p_mins, nan=jnp.inf),
                              jnp.inf), jnp.inf).min(axis=2)
    maxs = jnp.where(has, grp(jnp.nan_to_num(p_maxs, nan=-jnp.inf),
                              -jnp.inf), -jnp.inf).max(axis=2)
    lts = jnp.where(has, grp(p_last_ts, jnp.int64(0)), 0)
    lv = grp(jnp.nan_to_num(p_last_v), 0.0)
    # latest non-empty fine period wins (fine last_ts increase with index)
    pick = jnp.argmax(
        jnp.where(has, jnp.arange(ratio, dtype=jnp.int32)[None, None, :],
                  -1), axis=2)
    last_ts = jnp.take_along_axis(lts, pick[:, :, None], axis=2)[:, :, 0]
    last_v = jnp.take_along_axis(lv, pick[:, :, None], axis=2)[:, :, 0]
    okp = cnt > 0
    nan = jnp.nan
    return (jnp.where(okp, sums, nan), cnt,
            jnp.where(okp & jnp.isfinite(mins), mins, nan),
            jnp.where(okp & jnp.isfinite(maxs), maxs, nan),
            jnp.where(okp, last_v, nan),
            jnp.where(okp, last_ts, jnp.int64(0)))


# ---------------------------------------------------------------------------
# numpy oracle (parity model for the kernels)
# ---------------------------------------------------------------------------

def downsample_gauge_oracle(ts: np.ndarray, vals: np.ndarray, base: int,
                            res: int, nperiods: int
                            ) -> Tuple[np.ndarray, ...]:
    """Reference semantics, one series, plain numpy loops."""
    sums = np.full(nperiods, np.nan)
    cnts = np.zeros(nperiods)
    mins = np.full(nperiods, np.nan)
    maxs = np.full(nperiods, np.nan)
    last_v = np.full(nperiods, np.nan)
    last_ts = np.zeros(nperiods, dtype=np.int64)
    for t, v in zip(ts, vals):
        p = (int(t) - base) // res
        if not (0 <= p < nperiods):
            continue
        if cnts[p] == 0:
            sums[p] = v
            mins[p] = v
            maxs[p] = v
        else:
            sums[p] += v
            mins[p] = min(mins[p], v)
            maxs[p] = max(maxs[p], v)
        cnts[p] += 1
        last_v[p] = v
        last_ts[p] = t
    return sums, cnts, mins, maxs, last_v, last_ts
