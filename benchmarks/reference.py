"""The plain reference: PromQL range queries over the parent's own samples.

Independent of the program: imports nothing of ``filodb_tpu`` and takes
nothing the node has made. Plain numpy in float64 over the ``World`` arrays
the parent generated from ``--seed`` (``ts`` int64 ms ``[S, N]`` sorted per
row, ``vals`` float64 ``[S, N]``, or ``[S, N, B]`` bucket counts).

A query is the structured form a workload file gives (the PromQL string the
node is sent is rendered from the same structure by ``traffic.render``):

    {"metric": ..., "select": {label: value | [values]}, "fn": "rate",
     "window_s": 300, "agg": "sum" | None, "by": ["job"], "quantile": q}

Semantics (Prometheus's, as FiloDB serves them): step ``t`` sees the samples
with ``t - window <= ts <= t``; ``rate`` is the extrapolated rate with
counter-reset correction; an aggregation skips series with no value at a step
and a group with none has no point there.

Histograms (``"quantile": q`` in the query, ``histogram_quantile(q, sum(
rate(..)) by (..))``) come in two forms, and both end in ``bucket_quantile``:

- classic: one counter series a bucket, with an ``le`` label; ``by`` holds
  ``le``. The sums by ``by`` are grouped by their key without ``le``, the
  ``le`` values parsed (``+Inf`` included) and sorted.
- native: a world with ``les`` (FiloDB's ``prom-histogram`` rows: one series
  carries every bucket, ``vals`` is ``[S, N, B]``). ``_rate`` is applied
  bucket by bucket over the bucket axis, then summed by ``by`` as any other
  aggregation. The counter semantics assumed: each bucket is a cumulative
  counter, and a reset is corrected bucket by bucket (a bucket that falls
  has its previous value added back). FiloDB instead finds a reset as a row
  in which ANY bucket falls and adds back the whole previous histogram; the
  two agree where every bucket that holds a count falls at once, which the
  datagen guarantees (a reset row is all zeros).

A histogram query carries a counter's resets from its first window's start,
as FiloDB does (it reads a query's samples from there and corrects the
resets it sees among them): a reset is seen only between two samples at or
after that start. This sets the zero point a rate extrapolates to (a window
whose first sample comes after a reset the query saw extrapolates from the
corrected value, one after a reset it did not see from the value stored).
The counter cells' reference carries resets from the slice's first column,
one before the first window, and is kept as it was bit for bit.

Bounds of a quantile: the quantile of the lower rows and of the upper rows,
the smaller and the larger of the two. A target's buckets share its
timestamps, so where its rate sits on the extrapolation threshold (``TIE``)
both branches scale all its buckets by one factor (where no bucket's zero
point cuts its extrapolation short), and a quantile does not change under a
common scale. The sum over a group's targets can still take different
branches for different targets, and a mix of branches is neither the lower
nor the upper histogram, so its quantile may lie outside the two. ``TIE``
still bounds that: a target sits on the threshold only where its
whole-millisecond timestamps meet it to within ``TIE``, and a mix needs two
targets of one group on it at one step, taken in opposite ways by the
program; any other target enters both sums alike. Where that happens anyway
the check reads a gap: it can fail a sound run, never pass a wrong one.

``control`` computes the same answers the way a tempted later PR would, for
the control of "How correct is decided": ``"bf16"`` keeps sample values (a
histogram's bucket counts) in bfloat16 (the precision below the float32
epilogue the program's counter kernels end in, and far below the float64 its
gauges are served in), ``"stale"`` answers without each window's newest
sample (a stale answer, which the configurations' guarantees forbid).
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


def _rate(ts, vals, steps, window_ms, lo, hi, is_rate, from_ms=None):
    """``from_ms``: a reset counts only between two samples at or after it
    (FiloDB reads a query's samples from its first window's start and
    carries the resets it sees from there); None: from the slice's first
    column, one before the first window. The two differ only in the zero
    point of a window whose first sample comes after a reset that the one
    sees and the other does not (PERF.md section 7)."""
    drop = np.diff(vals, axis=1, prepend=vals[:, :1])
    seen = drop < 0
    if from_ms is not None:
        seen &= np.concatenate([ts[:, :1], ts[:, :-1]], axis=1) >= from_ms
    corr = np.cumsum(np.where(seen, vals - drop, 0.0), axis=1)
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
    # a histogram query carries its resets from its first window's start
    from_ms = steps[0] - window_ms if "quantile" in q else None
    if fn in RATE_FNS and vals.ndim == 3:   # native histograms: [S, B, T]
        per = [_rate(ts, vals[:, :, b], steps, window_ms, lo, hi,
                     fn == "rate", from_ms) for b in range(vals.shape[2])]
        bounds = tuple(np.stack([p[i] for p in per], axis=1) for i in (0, 1))
    elif fn in RATE_FNS:
        bounds = _rate(ts, vals, steps, window_ms, lo, hi, fn == "rate",
                       from_ms)
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
    if "quantile" in q:
        out = _quantiles(q["quantile"], q.get("by", []), world.les, out)
    return out, (steps // 1000).tolist()


def _quantiles(q, by, les, sums):
    """The sums by ``by`` -> ``{key without le: (lower, upper)}`` of their
    ``q``-quantile: native where the world has ``les`` (each key's rows
    ``[B, T]``), classic otherwise (one row a key, ``le`` among ``by``)."""
    # key -> (bucket bounds, lower [B, T], upper [B, T])
    if les is not None:
        hists = {key: (les, *pair) for key, pair in sums.items()}
    else:
        hists = {}
        at = by.index("le")
        groups = {}
        for key, pair in sums.items():
            groups.setdefault(key[:at] + key[at + 1:], []).append(
                (float(key[at]), pair))
        for key, buckets in groups.items():
            buckets.sort(key=lambda b: b[0])
            hists[key] = (np.array([b[0] for b in buckets]),
                          *(np.stack([b[1][i] for b in buckets])
                            for i in (0, 1)))
    out = {}
    for key, (bounds, low, high) in hists.items():
        qs = [bucket_quantile(q, bounds, rows[None], monotone=les is None)[0]
              for rows in (low, high)]
        if not np.isnan(qs[0]).all():
            out[key] = (np.minimum(*qs), np.maximum(*qs))
    return out


def bucket_quantile(q, les, counts, monotone=False):
    """Prometheus's ``bucketQuantile`` (promql/quantile.go) at every
    (group, step): ``les`` float64 ``[B]`` ascending, ``counts`` cumulative
    ``[groups, B, steps]`` with NaN for a bucket that has no point there.
    -> float64 ``[groups, steps]``, NaN where there is no point.

    Per step, over the buckets that have a point: none where the ``+Inf``
    bucket is missing, fewer than two buckets are left, or no observation
    was made; ``monotone`` (classic series only) first takes the running
    max over the buckets; a rank inside the ``+Inf`` bucket gives the
    second-highest bound, inside a first bucket whose bound is <= 0 that
    bound; otherwise linear inside the bucket, from 0 for the first."""
    les = np.asarray(les, dtype=np.float64)
    out = np.full((counts.shape[0], counts.shape[2]), np.nan)
    for g in range(counts.shape[0]):
        for t in range(counts.shape[2]):
            col = counts[g, :, t]
            have = ~np.isnan(col)
            out[g, t] = _one_quantile(q, les[have], col[have], monotone)
    return out


def _one_quantile(q, les, counts, monotone):
    if les.size < 2 or les[-1] != math.inf:
        return math.nan
    if monotone:
        counts = np.maximum.accumulate(counts)
    observations = counts[-1]
    if observations == 0:
        return math.nan
    rank = q * observations
    b = next((i for i in range(les.size - 1) if counts[i] >= rank),
             les.size - 1)
    if b == les.size - 1:
        return float(les[-2])
    if b == 0 and les[0] <= 0:
        return float(les[0])
    start, count = 0.0, float(counts[b])
    if b > 0:
        start = float(les[b - 1])
        count -= float(counts[b - 1])
        rank -= float(counts[b - 1])
    return start + (float(les[b]) - start) * (rank / count)


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
