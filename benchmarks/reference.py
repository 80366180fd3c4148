"""The plain reference: PromQL range queries over the parent's own samples.

Independent of the program: imports nothing of ``filodb_tpu`` and takes
nothing the node has made. Plain numpy in float64 over the ``World`` arrays
the parent generated from ``--seed`` (``ts`` int64 ms ``[S, N]`` sorted per
row, ``vals`` float64 ``[S, N]``).

A query is the structured form a workload file gives (the PromQL string the
node is sent is rendered from the same structure by ``traffic.render``):

    {"metric": ..., "select": {label: value | [values]}, "fn": "rate",
     "window_s": 300, "agg": "sum" | None, "by": ["job"]}

Semantics (Prometheus's, as FiloDB serves them): step ``t`` sees the samples
with ``t - window <= ts <= t``; ``rate`` is the extrapolated rate with
counter-reset correction; an aggregation skips series with no value at a step
and a group with none has no point there.

``control`` computes the same answers the way a tempted later PR would, for
the control of "How correct is decided": ``"bf16"`` keeps sample values in
bfloat16 (the precision below the float32 epilogue the program's counter
kernels end in, and far below the float64 its gauges are served in),
``"stale"`` answers without each window's newest sample (a stale answer,
which the configurations' guarantees forbid).
"""

import json
import math

import numpy as np

CONTROLS = ("bf16", "stale")
# Prometheus extrapolates a rate to the window's edge only where the gap to it
# is under 1.1 x the mean sample interval. With whole-millisecond timestamps
# the gap can EQUAL that threshold (or miss it by parts in 1e7), and then
# rounding decides the branch: float64's own choice is as arbitrary as the
# float32 one of the program's kernels. Within this relative distance both
# branches are right, and an answer has to lie between them.
TIE = 1e-6
RATE_FNS = ("rate", "increase")
OVER_TIME = {"max_over_time": np.fmax.reduce, "min_over_time": np.fmin.reduce}


def to_bf16(x):
    """float64 -> nearest bfloat16 (round to nearest even), as float64."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def step_grid(start_s, end_s, step_s):
    return np.arange(start_s, end_s + 1, step_s, dtype=np.int64) * 1000


def select(world, metric, sel):
    """Indices of the series whose labels match ``sel`` (value or list)."""
    ok = world.label_column("_metric_") == metric
    for label, want in sel.items():
        col = world.label_column(label)
        ok &= np.isin(col, want if isinstance(want, list) else [want])
    return np.flatnonzero(ok)


def _window_index(ts, steps, window_ms, drop_newest):
    """Per (series, step): lo = first sample >= t - window, hi = last
    sample <= t (inclusive indices into the row)."""
    lo = (ts[:, :, None] < (steps - window_ms)[None, None, :]).sum(1)
    hi = (ts[:, :, None] <= steps[None, None, :]).sum(1) - 1
    if drop_newest:
        hi = hi - 1
    return lo, hi


def _rate(ts, vals, steps, window_ms, lo, hi, is_rate):
    drop = np.diff(vals, axis=1, prepend=vals[:, :1])
    corr = np.cumsum(np.where(drop < 0, vals - drop, 0.0), axis=1)
    vals = vals + corr
    n = ts.shape[1]
    counts = hi - lo + 1
    loc, hic = np.clip(lo, 0, n - 1), np.clip(hi, 0, n - 1)
    t_first = np.take_along_axis(ts, loc, 1).astype(np.float64)
    t_last = np.take_along_axis(ts, hic, 1).astype(np.float64)
    v_first = np.take_along_axis(vals, loc, 1)
    v_last = np.take_along_axis(vals, hic, 1)
    wend = steps[None, :].astype(np.float64)
    wstart = wend - window_ms
    with np.errstate(divide="ignore", invalid="ignore"):
        to_start = (t_first - wstart) / 1000.0
        to_end = (wend - t_last) / 1000.0
        sampled = (t_last - t_first) / 1000.0
        avg = sampled / (counts - 1.0)
        delta = v_last - v_first
        to_zero = np.where((delta > 0) & (v_first >= 0),
                           sampled * (v_first / delta), np.inf)
        to_start = np.minimum(to_start, to_zero)
        thr = avg * 1.1
        outs = []
        for tie_is_less in (False, True):
            def below(x):
                tie = np.abs(x - thr) <= TIE * thr
                return np.where(tie, tie_is_less, x < thr)
            extrap = (sampled + np.where(below(to_start), to_start, avg / 2.0)
                      + np.where(below(to_end), to_end, avg / 2.0))
            out = delta * (extrap / sampled)
            if is_rate:
                out = out / (window_ms / 1000.0)
            outs.append(np.where(counts >= 2, out, np.nan))
    return np.minimum(*outs), np.maximum(*outs)


def _over_time(vals, lo, hi, reduce):
    n = vals.shape[1]
    col = np.arange(n)[None, :, None]
    inside = (col >= lo[:, None, :]) & (col <= hi[:, None, :])
    out = reduce(np.where(inside, vals[:, :, None], np.nan), axis=1)
    return np.where(hi >= lo, out, np.nan)


def evaluate(world, q, start_s, end_s, step_s, control=None):
    """-> ({key tuple: (lower row, upper row)}, steps in s): float64 rows
    with NaN where there is no point; the two differ only where a rate's
    extrapolation sits on its threshold (``TIE``).

    ``key`` is the tuple of the ``by`` labels' values for an aggregation and
    the series' ``key_label`` value otherwise."""
    idx = select(world, q["metric"], q.get("select", {}))
    steps = step_grid(start_s, end_s, step_s)
    window_ms = int(q["window_s"]) * 1000
    n = world.ts.shape[1]
    # only the columns any window can reach (rows are within +-slack of the
    # nominal tick grid)
    c0 = max(0, int((steps[0] - window_ms - world.slack_ms - world.t0_ms)
                    // world.dt_ms) - 1)
    c1 = min(n, int((steps[-1] + world.slack_ms - world.t0_ms)
                    // world.dt_ms) + 2)
    ts = world.ts[idx, c0:c1]
    vals = world.vals[idx, c0:c1]
    if control == "bf16":
        vals = to_bf16(vals)
    lo, hi = _window_index(ts, steps, window_ms, control == "stale")
    fn = q["fn"]
    if fn in RATE_FNS:
        bounds = _rate(ts, vals, steps, window_ms, lo, hi, fn == "rate")
    elif fn in OVER_TIME:
        bounds = (_over_time(vals, lo, hi, OVER_TIME[fn]),) * 2
    else:
        raise ValueError(f"reference has no function {fn!r}")
    agg = q.get("agg")
    out = {}
    if not agg:
        keys = world.label_column(q.get("key_label", "instance"))[idx]
        for i, k in enumerate(keys.tolist()):
            if not np.isnan(bounds[0][i]).all():
                out[(k,)] = (bounds[0][i], bounds[1][i])
        return out, (steps // 1000).tolist()
    if agg not in AGGS:
        raise ValueError(f"reference has no aggregation {agg!r}")
    cols = [world.label_column(b)[idx] for b in q.get("by", [])]
    groups = {}
    for i in range(idx.size):
        groups.setdefault(tuple(c[i] for c in cols), []).append(i)
    for key, members in groups.items():
        # every aggregation here is monotone in each series, so the bounds
        # of the group are the aggregates of the series' bounds
        pair = tuple(AGGS[agg](rows[members]) for rows in bounds)
        if not np.isnan(pair[0]).all():
            out[key] = pair
    return out, (steps // 1000).tolist()


def _agg_sum(sub):
    present = ~np.isnan(sub)
    return np.where(present.any(0), np.where(present, sub, 0.0).sum(0),
                    np.nan)


def _agg_avg(sub):
    with np.errstate(invalid="ignore"):
        return _agg_sum(sub) / (~np.isnan(sub)).sum(0)


AGGS = {"sum": _agg_sum, "avg": _agg_avg,
        "max": lambda sub: np.fmax.reduce(sub, axis=0),
        "min": lambda sub: np.fmin.reduce(sub, axis=0)}


def parse_matrix(body, key_labels):
    """query_range JSON -> {key tuple: (ts_s list, float64 array)}."""
    doc = json.loads(body)
    if doc.get("status") != "success":
        raise ValueError(f"status {doc.get('status')!r}: {str(doc)[:300]}")
    out = {}
    for r in doc["data"]["result"]:
        key = tuple(r["metric"].get(k, "") for k in key_labels)
        vs = r["values"]
        out[key] = ([int(float(t)) for t, _ in vs],
                    np.array([float(v) for _, v in vs]))
    return out


def max_rel_err(got, want, steps_s):
    """-> (widest relative gap by which an answer lies outside the reference's
    bounds, over every (series, step); where it is: [key, step in s, got,
    want]); inf where the set of series, the step grid or the pattern of
    missing points differs."""
    if set(got) != set(want):
        odd = sorted(set(got) ^ set(want))[:3]
        return math.inf, ["series differ", odd]
    worst, where = 0.0, None
    for key, (low, high) in want.items():
        g_ts, g = got[key]
        ok = ~np.isnan(low)
        w_ts = [t for t, o in zip(steps_s, ok) if o]
        if g_ts != w_ts:
            return math.inf, ["steps differ", list(key), len(g_ts), len(w_ts)]
        w = low[ok]
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.maximum(np.maximum(w - g, g - high[ok]), 0.0)
            err = out / np.maximum(np.abs(w), 1e-300)
        err = np.where(out == 0.0, 0.0, err)
        if np.isnan(err).any():
            return math.inf, ["not a number", list(key)]
        if err.size and float(err.max()) > worst:
            i = int(err.argmax())
            worst = float(err[i])
            where = [list(key), w_ts[i], float(g[i]), float(w[i])]
    return worst, where


def render_matrix(rows, steps_s, key_labels):
    """The reference's answer in the node's own JSON shape: what stands in
    the program's place when a control is run."""
    result = []
    for key, (row, _) in rows.items():
        result.append({"metric": dict(zip(key_labels, key)),
                       "values": [[t, repr(float(v))]
                                  for t, v in zip(steps_s, row)
                                  if not math.isnan(v)]})
    return json.dumps({"status": "success",
                       "data": {"resultType": "matrix", "result": result}})
