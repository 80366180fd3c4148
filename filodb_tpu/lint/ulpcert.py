"""ULP-certification rail (graftlint v4): every numeric annotation in
the tree is dynamically certified, engine-as-assertion style.

:mod:`filodb_tpu.lint.rules_numerics` makes ``@precision`` /
``@order_insensitive`` annotations mandatory at every hybrid site; this
module makes them HONEST. For each registered claim a harness evaluates
the annotated site on seeded inputs shaped by its static bound:

  * **precision claims** run the production path against an f64
    reference (the exact-f64 twin evaluator, the pure-Python refeval
    window loop, or a straight f64 formula) and measure the worst
    error in output-dtype ulps. ``rel_ulps=0`` claims are certified
    BITWISE.
  * **order claims** run the site at 1, 2, 4, and 8 virtual devices
    and measure the worst relative deviation across device counts.
    ``tolerance=0.0`` claims are certified bitwise at every count —
    the dynamic half of the mesh-on/off byte-identity cross-check.

A claim whose measurement exceeds its declared tolerance, or that has
no registered harness at all, is an error-severity ``ulp-certification``
finding in the tier-1 gate: an annotation the rail cannot certify is a
lie, and lies about precision do not ship. Results are memoized per
process (the claims are fixed at import time), so repeated ``run_lint``
calls — the fixture tests — pay the compile cost once.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from filodb_tpu.lint import Finding, register_rule
from filodb_tpu.lint import numerics as nmod

register_rule("ulp-certification", "numerics",
              "a @precision/@order_insensitive annotation failed "
              "dynamic certification (or has no harness) — the "
              "declared tolerance is a lie")

DEVICE_COUNTS = (1, 2, 4, 8)

# claim name -> (kind, harness); precision harnesses return
# (prod, ref, floor), order harnesses are called per device count
HARNESSES: Dict[str, Tuple[str, Callable]] = {}


def precision_harness(name: str) -> Callable:
    def deco(fn):
        HARNESSES[name] = ("precision", fn)
        return fn
    return deco


def order_harness(name: str) -> Callable:
    def deco(fn):
        HARNESSES[name] = ("order", fn)
        return fn
    return deco


@dataclass
class CertResult:
    name: str
    kind: str                   # precision | order
    ok: bool
    measured: float             # worst ulps / rel deviation observed
    claimed: float
    detail: str = ""
    device_counts: Tuple[int, ...] = ()


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _measure_precision(claim: nmod.PrecisionClaim, prod, ref,
                       floor=0.0) -> CertResult:
    import numpy as np
    prod = np.asarray(prod)
    ref = np.asarray(ref)
    if prod.shape != ref.shape:
        return CertResult(claim.name, "precision", False, math.inf,
                          claim.rel_ulps,
                          f"shape mismatch {prod.shape} vs {ref.shape}")
    if np.issubdtype(prod.dtype, np.floating):
        nan_p, nan_r = np.isnan(prod), np.isnan(ref)
        if not np.array_equal(nan_p, nan_r):
            return CertResult(claim.name, "precision", False, math.inf,
                              claim.rel_ulps, "NaN structure differs "
                              "between production and reference")
        m = ~nan_p
        if claim.rel_ulps == 0:
            same = np.array_equal(prod[m], ref[m].astype(prod.dtype))
            return CertResult(
                claim.name, "precision", same, 0.0 if same else math.inf,
                0.0, "bitwise" if same else "exact claim but values "
                "differ from the reference")
        pf = prod.astype(np.float64)[m]
        rf = np.asarray(ref, np.float64)[m]
        err = np.maximum(np.abs(pf - rf) - np.asarray(floor), 0.0)
        # one ulp of the reference in the PRODUCTION dtype
        sp = np.spacing(np.abs(rf).astype(prod.dtype)).astype(np.float64)
        sp = np.maximum(sp, float(np.finfo(prod.dtype).tiny))
        ulps = float(np.max(err / sp)) if err.size else 0.0
        return CertResult(
            claim.name, "precision", ulps <= claim.rel_ulps, ulps,
            claim.rel_ulps,
            f"max {ulps:.3g} ulps over {int(m.sum())} values")
    same = np.array_equal(prod, ref)
    return CertResult(claim.name, "precision", same,
                      0.0 if same else math.inf, claim.rel_ulps,
                      "bitwise" if same else "integer outputs differ")


def _rel_dev(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    nan_a, nan_b = np.isnan(a), np.isnan(b)
    if not np.array_equal(nan_a, nan_b):
        return math.inf
    m = ~nan_a
    if not m.any():
        return 0.0
    diff = np.abs(a[m] - b[m])
    scale = np.maximum(np.maximum(np.abs(a[m]), np.abs(b[m])), 1e-300)
    return float(np.max(diff / scale))


def _measure_order(claim: nmod.OrderClaim, harness,
                   counts: Sequence[int]) -> CertResult:
    import numpy as np
    results = {}
    for n in counts:
        out = harness(n)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        results[n] = [np.asarray(o) for o in out]
    base = results[counts[0]]
    worst = 0.0
    for n in counts[1:]:
        for a, b in zip(base, results[n]):
            if claim.tolerance == 0.0:
                pa, pb = np.asarray(a), np.asarray(b)
                eq = np.array_equal(pa, pb) or (
                    np.issubdtype(pa.dtype, np.floating)
                    and np.array_equal(np.isnan(pa), np.isnan(pb))
                    and np.array_equal(pa[~np.isnan(pa)],
                                       pb[~np.isnan(pb)]))
                if not eq:
                    return CertResult(
                        claim.name, "order", False, math.inf, 0.0,
                        f"byte-identity claim but {counts[0]} vs {n} "
                        f"devices differ", tuple(counts))
            else:
                worst = max(worst, _rel_dev(a, b))
    ok = worst <= claim.tolerance
    return CertResult(claim.name, "order", ok, worst, claim.tolerance,
                      f"max rel deviation {worst:.3g} across device "
                      f"counts {tuple(counts)}", tuple(counts))


# ---------------------------------------------------------------------------
# certify
# ---------------------------------------------------------------------------

_MEMO: Optional[List[CertResult]] = None


def ensure_virtual_devices() -> None:
    """Ask XLA for 8 virtual CPU devices if the backend is not up yet
    (harmless once initialized; tier-1's conftest does the same)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def certify_all(force: bool = False) -> List[CertResult]:
    """Certify every registered claim. Memoized per process."""
    global _MEMO
    if _MEMO is not None and not force:
        return _MEMO
    ensure_virtual_devices()
    nmod.import_annotated_modules()
    import jax
    avail = len(jax.devices())
    counts = [d for d in DEVICE_COUNTS if d <= avail]
    out: List[CertResult] = []
    for name, claim in sorted(nmod.PRECISION.items()):
        entry = HARNESSES.get(name)
        if entry is None or entry[0] != "precision":
            out.append(CertResult(
                name, "precision", False, math.inf, claim.rel_ulps,
                "no certification harness registered — an annotation "
                "the rail cannot evaluate cannot ship"))
            continue
        try:
            prod, ref, floor = entry[1]()
            out.append(_measure_precision(claim, prod, ref, floor))
        except Exception as e:  # noqa: BLE001 — a gate must not crash
            out.append(CertResult(name, "precision", False, math.inf,
                                  claim.rel_ulps, f"harness crashed: "
                                  f"{type(e).__name__}: {e}"))
    for name, claim in sorted(nmod.ORDER.items()):
        entry = HARNESSES.get(name)
        if entry is None or entry[0] != "order":
            out.append(CertResult(
                name, "order", False, math.inf, claim.tolerance,
                "no certification harness registered"))
            continue
        if len(counts) < 2:
            out.append(CertResult(
                name, "order", False, math.inf, claim.tolerance,
                f"only {avail} device(s) available — an order claim "
                f"needs at least two device counts to certify"))
            continue
        try:
            out.append(_measure_order(claim, entry[1], counts))
        except Exception as e:  # noqa: BLE001
            out.append(CertResult(name, "order", False, math.inf,
                                  claim.tolerance, f"harness crashed: "
                                  f"{type(e).__name__}: {e}"))
    _MEMO = out
    return out


def _claim_anchor(claim, mods) -> Tuple[Optional[str], int]:
    relpath = claim.module.replace(".", "/") + ".py"
    for mod in mods or ():
        if mod.relpath == relpath:
            needle = claim.name
            for i, line in enumerate(mod.lines, start=1):
                if needle in line:
                    return relpath, i
            return relpath, 1
    return relpath, 1


def check_certifications(mods=None
                         ) -> List[Tuple[Optional[str], Finding]]:
    """Lint-facing entry: one finding per failed certification."""
    out: List[Tuple[Optional[str], Finding]] = []
    for res in certify_all():
        if res.ok:
            continue
        claim = nmod.PRECISION.get(res.name) or nmod.ORDER.get(res.name)
        relpath, line = _claim_anchor(claim, mods)
        out.append((relpath, Finding(
            rule="ulp-certification", path=relpath or "?", line=line,
            message=(f"annotation {res.name!r} ({res.kind}) failed "
                     f"certification: measured {res.measured:.3g} vs "
                     f"claimed {res.claimed:.3g} — {res.detail}"),
            context=f"ulpcert:{res.name}")))
    return out


# ---------------------------------------------------------------------------
# in-tree harnesses
# ---------------------------------------------------------------------------
#
# Each harness builds SEEDED inputs shaped by the claim's static bound
# (dense tiles, monotone counters, windows with >= 2 samples, branch
# conditions away from knife edges) so certification is deterministic.

_SEED = 0x0DD5


def _counter_world(jitter: bool = True):
    """Shared synthetic world: [N, S] transposed dense counter tiles
    with large-magnitude values (the catastrophic-cancellation regime
    the f64 value channel exists for)."""
    import numpy as np
    rng = np.random.default_rng(_SEED)
    N, S = 128, 8
    dt = 10_000
    base = 1_700_000_000_000
    jit_ms = rng.integers(-2000, 2001, (N, S)) if jitter \
        else np.zeros((N, S), dtype=np.int64)
    ts = base + np.arange(N, dtype=np.int64)[:, None] * dt + jit_ms
    # counters starting at ~1e12 with ~O(10) increments: deltas are
    # exact in f64, catastrophically cancelled in a pure-f32 channel
    v = (1e12 + rng.uniform(0, 1e3, S)[None, :]
         + np.cumsum(rng.uniform(1.0, 20.0, (N, S)), axis=0))
    grid = dict(num_slots=N, base=base, dt=dt,
                w0s=base + 20 * dt + 1_500, w0e=base + 26 * dt + 1_500,
                step=2 * dt, nsteps=16)
    return ts, v, grid


def _jit_eval(fn, *static):
    """An evaluator as its dispatcher runs it: ONE jitted program with the
    function/shape statics bound — not op by op (an eager call compiles
    every primitive separately, which is neither what serves nor
    affordable inside the lint's latency budget)."""
    import functools

    import jax
    return jax.jit(functools.partial(fn, *static))


def _exact_counter_rate(jitter: bool = True):
    """_eval_counter_t over the shared world -> [T, S] f64 numpy: the
    exact-f64 evaluator the hybrid families are certified against
    (memoized: three harnesses share it)."""
    got = _EXACT_MEMO.get(jitter)
    if got is None:
        import numpy as np

        import jax.numpy as jnp

        from filodb_tpu.query.tilestore import _eval_counter_t
        ts, v, g = _counter_world(jitter)
        arrs = {"ts": jnp.asarray(ts, jnp.float64), "ff_v": jnp.asarray(v)}
        got = _EXACT_MEMO[jitter] = np.asarray(
            _jit_eval(_eval_counter_t, "rate", g["nsteps"])(
                arrs, g["num_slots"], g["base"], g["dt"], g["w0s"],
                g["w0e"], g["step"]))
    return got


_EXACT_MEMO: Dict[bool, object] = {}


def _ref_windows(ts, v, grid, func="rate"):
    """Pure-Python per-window reference (promql/refeval semantics) →
    [T, S] f64."""
    import numpy as np

    from filodb_tpu.promql.refeval import eval_range_fn
    T, S = grid["nsteps"], ts.shape[1]
    out = np.full((T, S), np.nan)
    for s in range(S):
        ts_l = [int(x) for x in ts[:, s]]
        v_l = [float(x) for x in v[:, s]]
        for t in range(T):
            we = grid["w0e"] + t * grid["step"]
            ws = grid["w0s"] + t * grid["step"]
            out[t, s] = eval_range_fn(func, ts_l, v_l, ws, we)
    return out


@precision_harness("counter-exact-slot-index")
def _h_counter_exact():
    ts, v, g = _counter_world()
    return _exact_counter_rate(), _ref_windows(ts, v, g), 0.0


@precision_harness("counter-fast-hybrid")
def _h_counter_fast():
    import numpy as np

    from filodb_tpu.query.tilestore import _eval_counter_fast
    ts, v, g = _counter_world()
    import jax.numpy as jnp
    tsr = (ts - g["base"]).astype(np.int32)
    prod = np.asarray(_jit_eval(_eval_counter_fast, "rate", g["nsteps"])(
        {"tsr": jnp.asarray(tsr), "ff_v": jnp.asarray(v)},
        g["num_slots"], np.int64(g["base"]), g["dt"],
        np.int64(g["w0s"]), np.int64(g["w0e"]), np.int64(g["step"])))
    return prod, _exact_counter_rate(), 0.0


@precision_harness("counter-slide-hybrid")
def _h_counter_slide():
    import numpy as np

    from filodb_tpu.query.tilestore import _eval_counter_slide
    ts, v, g = _counter_world(jitter=False)     # regular grid: st = 2
    import jax.numpy as jnp
    st = g["step"] // g["dt"]
    N, S = ts.shape

    def perm(a, dtype):
        G = -(-N // st) + g["nsteps"] + 4
        pad = G * st - N
        ap = np.concatenate([a, np.zeros((pad, S), a.dtype)], axis=0)
        return jnp.asarray(
            ap.reshape(G, st, S).swapaxes(0, 1).astype(dtype))

    tsr = (ts - g["base"]).astype(np.int32)
    arrs = {"tsr_p": perm(tsr, np.int32), "ff_v_p": perm(v, np.float64)}
    prod = np.asarray(
        _jit_eval(_eval_counter_slide, "rate", g["nsteps"], st)(
            arrs, g["num_slots"], np.int64(g["base"]), g["dt"],
            np.int64(g["w0s"]), np.int64(g["w0e"]), np.int64(g["step"])))
    return prod, _exact_counter_rate(jitter=False), 0.0


@precision_harness("counter-epilogue-f32")
def _h_epilogue():
    """_f32_epilogue vs the f64 reference formula. Inputs keep the
    extrapolation branches away from knife edges (dstart/dend well
    under threshold, dzero far above) so production and reference take
    the SAME branch and only rounding differs."""
    import numpy as np

    import jax.numpy as jnp

    from filodb_tpu.query.tilestore import _f32_epilogue
    rng = np.random.default_rng(_SEED + 1)
    T, S = 48, 8
    counts = rng.integers(5, 50, (T, S)).astype(np.int32)
    wstart = (np.arange(T, dtype=np.int64)[:, None] * 60_000)
    wdur = 300_000
    wend = wstart + wdur
    t1 = (wstart + rng.integers(100, 400, (T, S))).astype(np.int64)
    t2 = (wend - rng.integers(100, 400, (T, S))).astype(np.int64)
    v1 = 1e6 + rng.uniform(0, 1e3, (T, S))
    v2 = v1 + rng.uniform(5.0, 500.0, (T, S))
    prod = np.asarray(_jit_eval(_f32_epilogue, "rate")(
        jnp.asarray(counts), jnp.asarray(t1, jnp.int32),
        jnp.asarray(v1), jnp.asarray(t2, jnp.int32), jnp.asarray(v2),
        jnp.asarray(wstart, jnp.int32), jnp.asarray(wend, jnp.int32),
        jnp.float32(wdur / 1000.0)))
    # f64 reference, same formula
    delta = v2 - v1
    sampled = (t2 - t1) / 1000.0
    dstart = (t1 - wstart) / 1000.0
    dend = (wend - t2) / 1000.0
    avg_dur = sampled / (counts - 1.0)
    dzero = np.where((delta > 0) & (v1 >= 0),
                     sampled * (v1 / np.where(delta == 0, np.nan,
                                              delta)), np.inf)
    dstart = np.minimum(dstart, dzero)
    thresh = avg_dur * 1.1
    extrap = sampled \
        + np.where(dstart < thresh, dstart, avg_dur * 0.5) \
        + np.where(dend < thresh, dend, avg_dur * 0.5)
    ref = delta * (extrap / sampled) / (wdur / 1000.0)
    ref = np.where(counts >= 2, ref, np.nan)
    return prod, ref, 0.0


@precision_harness("fixed-point-split")
def _h_fixed_split():
    """The 61-bit hi/lo split + the kernel's f32 recombine
    (dh*2^(31-s) + dl*2^-s) vs the direct f64 boundary delta, with the
    declared span*2^-59 quantization floor."""
    import numpy as np

    from filodb_tpu.query.tilestore import AlignedTiles
    rng = np.random.default_rng(_SEED + 2)
    N, S = 64, 8
    dt = 10_000
    base = 0
    ts = (np.arange(N, dtype=np.int64)[:, None] * dt
          + np.zeros((1, S), np.int64)).T * 1.0      # [S, N] exact grid
    # mixed magnitudes: huge counters, small gauges, negatives
    scales = np.array([1e12, 1e6, 1.0, 1e-3, 5e8, 42.0, 1e10, 7.0])
    vals = (scales[:, None]
            * (1.0 + np.cumsum(rng.uniform(0, 1e-4, (S, N)), axis=1)))
    vals[2] = rng.uniform(-50, 50, N)                # sign-mixed gauge
    valid = np.ones((S, N), dtype=bool)
    tiles = AlignedTiles([{"i": str(i)} for i in range(S)], base, dt,
                         valid, ts, vals)
    fx = tiles._fixed_channels("v")
    assert fx is not None
    hi, lo, _mid, s = (np.asarray(x) for x in fx)    # [N, S], [S]
    c1 = np.ldexp(np.float32(1.0), 31 - s).astype(np.float32)
    c2 = np.ldexp(np.float32(1.0), -s).astype(np.float32)
    i, j = 10, 50                                    # boundary pair
    dh = (hi[j] - hi[i]).astype(np.float32)
    dl = (lo[j] - lo[i]).astype(np.float32)
    prod = dh * c1 + dl * c2                         # [S] f32
    ref = (vals[:, j] - vals[:, i])                  # [S] f64
    span = vals.max(axis=1) - vals.min(axis=1)
    floor = span * 2.0 ** -59
    return prod, ref, floor


@precision_harness("groupsum-recombine-f32")
def _h_groupsum_recombine():
    """The group-sum kernel's recombine (pallas_kernels._groupsum_kernel
    lines around `delta = dh * c1 + dl * c2`): exact int32 hi/lo deltas
    over FULL-SPAN boundary pairs (dl wide enough to round in f32),
    recombined in f32, vs the direct f64 delta."""
    import numpy as np

    from filodb_tpu.query.tilestore import AlignedTiles
    rng = np.random.default_rng(_SEED + 6)
    N, S = 64, 8
    dt = 10_000
    ts = (np.arange(N, dtype=np.int64)[None, :] * dt
          + np.zeros((S, 1), np.int64)) * 1.0
    scales = np.array([1e12, 1e6, 1.0, 1e-3, 5e8, 42.0, 1e10, 7.0])
    vals = (scales[:, None]
            * (1.0 + np.cumsum(rng.uniform(0, 0.2, (S, N)), axis=1)))
    valid = np.ones((S, N), dtype=bool)
    tiles = AlignedTiles([{"i": str(i)} for i in range(S)], 0, dt,
                         valid, ts, vals)
    fx = tiles._fixed_channels("v")
    assert fx is not None
    hi, lo, _mid, s = (np.asarray(x) for x in fx)
    c1 = np.ldexp(np.float32(1.0), 31 - s).astype(np.float32)
    c2 = np.ldexp(np.float32(1.0), -s).astype(np.float32)
    i, j = 0, N - 1                 # widest boundary pair in the tile
    dh = (hi[j] - hi[i]).astype(np.float32)
    dl = (lo[j] - lo[i]).astype(np.float32)
    prod = dh * c1 + dl * c2
    ref = vals[:, j] - vals[:, i]
    span = vals.max(axis=1) - vals.min(axis=1)
    return prod, ref, span * 2.0 ** -59


@precision_harness("extrapolated-rate-f64")
def _h_extrapolated_rate():
    """tilestore._extrapolated_rate (the shared f64 formula) vs the
    pure-Python reference loop (promql/refeval._extrapolated) on the
    same boundary tuples."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from filodb_tpu.promql.refeval import _extrapolated
    from filodb_tpu.query.tilestore import _extrapolated_rate
    rng = np.random.default_rng(_SEED + 7)
    T, S = 32, 8
    wstart = np.arange(T, dtype=np.int64)[:, None] * 60_000
    wend = wstart + 300_000
    counts = rng.integers(2, 40, (T, S))
    t1 = wstart + rng.integers(50, 2_000, (T, S))
    t2 = wend - rng.integers(50, 2_000, (T, S))
    v1 = 1e9 + rng.uniform(0, 1e3, (T, S))
    v2 = v1 + rng.uniform(0.0, 800.0, (T, S))
    prod = np.asarray(jax.jit(
        lambda *a: _extrapolated_rate(*a, True, True))(
        jnp.asarray(wstart, jnp.float64), jnp.asarray(wend, jnp.float64),
        jnp.asarray(counts), jnp.asarray(t1, jnp.float64),
        jnp.asarray(v1), jnp.asarray(t2, jnp.float64), jnp.asarray(v2)))
    ref = np.full((T, S), np.nan)
    for t in range(T):
        for si in range(S):
            n = int(counts[t, si])
            sts = [int(t1[t, si])] + [int(t1[t, si])] * max(n - 2, 0) \
                + [int(t2[t, si])]
            svs = [float(v1[t, si])] * max(n - 1, 1) \
                + [float(v2[t, si])]
            ref[t, si] = _extrapolated(
                int(wstart[t, 0]), int(wend[t, 0]), sts[:n], svs[:n],
                is_counter=True, is_rate=True) if n >= 2 else np.nan
    return prod, ref, 0.0


def _hist_world():
    """Native latency histograms for the quantile harness: 24 series in 4
    groups, the Prometheus client's 12 default bounds, 180 samples 10 s
    apart (every second series +-2 s off the tick), Poisson(50)
    observations a scrape under a log-normal, a reset of every bucket in
    series 2 at sample 90 and one bucket falling in series 5 at sample
    120. -> (series, tile-order group ids, groups, first step ms)."""
    import numpy as np

    from filodb_tpu.query.model import RawSeries
    rng = np.random.default_rng(_SEED + 11)
    les = np.array([.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10,
                    np.inf])
    S, N, G, t0 = 24, 180, 4, 1_700_000_000_000
    series = []
    for s in range(S):
        obs = rng.lognormal(np.log(0.02 * 2.0 ** (s % G)), 0.8,
                            (N, 50))
        per = (obs[..., None] <= les).sum(axis=1).astype(np.float64)
        counts = np.cumsum(per, axis=0)
        if s == 2:
            counts[90:] -= counts[89]
        if s == 5:
            counts[120:, 3] -= 1.0
        ts = t0 + np.arange(N, dtype=np.int64) * 10_000
        if s % 2:
            ts = ts + rng.integers(-2000, 2001, N)
        series.append(RawSeries({"g": str(s % G), "i": str(s)}, ts, counts,
                                is_counter=True, bucket_les=les))
    return series, np.arange(S) % G, G, t0 + 1_200_000


@precision_harness("hist-quantile")
def _h_hist_quantile():
    """The fused quantile program over bucket-axis tiles vs the host path
    (``periodic_samples`` -> ``_aggregate_hist_sum`` ->
    ``histogram_quantile``, numpy f64) at q 0.1, 0.5 and 0.99 of rate
    and increase; the first window starts at series 2's reset."""
    import numpy as np

    from filodb_tpu.query import engine as eng
    from filodb_tpu.query import tilestore as tst
    from filodb_tpu.query.model import RangeParams, clip_series
    series, gids, G, first = _hist_world()
    tiles, idx = tst.build_aligned_tiles(series)
    window, step, nsteps = 300_000, 30_000, 16
    steps = RangeParams(first, step, first + (nsteps - 1) * step).steps
    arrs = tst._tiles_arrays_hist(tiles)
    prod, ref = [], []
    for func in ("rate", "increase"):
        fn = _jit_eval(tst._hist_quantile_program, func, nsteps, G)
        host = eng._aggregate_hist_sum(eng.periodic_samples(
            clip_series(series, first - window, int(steps[-1])),
            RangeParams(first, step, int(steps[-1])), func, window),
            ("g",), ())
        for q in (0.1, 0.5, 0.99):
            grid = np.array([first - window, first, step], np.int64)
            prod.append(np.asarray(fn(arrs, tiles.t_consts(), tiles.t_les(),
                                      tiles.t_q(q), grid,
                                      np.asarray(gids[idx], np.int32))))
            ref.append(eng.histogram_quantile(host, q).values.T)
    return np.stack(prod), np.stack(ref), 0.0


@precision_harness("append-carry-exact")
def _h_append_carry():
    """Donated append vs from-scratch rebuild, reset-free block:
    bitwise (the annotation's exact claim)."""
    import numpy as np

    import jax.numpy as jnp

    from filodb_tpu.parallel.shardstore import _append_step
    rng = np.random.default_rng(_SEED + 3)
    C, S, n, K = 64, 8, 40, 12
    v_full = np.cumsum(rng.uniform(0.5, 10.0, (n + K, S)), axis=0) + 1e9
    tsr = np.zeros((C, S), np.int32)
    v = np.zeros((C, S))
    cv = np.zeros((C, S))
    v[:n] = v_full[:n]
    cv[:n] = v_full[:n]            # no resets: corrected == raw
    new_tsr = np.arange(K, dtype=np.int32)[:, None] + np.zeros(
        (1, S), np.int32)
    out_tsr, out_v, out_cv = _append_step(
        jnp.asarray(tsr), jnp.asarray(v), jnp.asarray(cv),
        jnp.asarray(new_tsr), jnp.asarray(v_full[n:]), n)
    prod = np.asarray(out_cv)[n:n + K]
    ref = v_full[n:]               # rebuild: no resets -> cv == v
    return prod, ref, 0.0


def _shard_mesh(ndev: int):
    import numpy as np

    import jax
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:ndev]).reshape(ndev, 1)
    return Mesh(devs, ("shard", "time"))


@order_harness("grouped-reduce-psum")
def _h_grouped_reduce(ndev: int):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from filodb_tpu.parallel.mesh import _grouped_reduce
    rng = np.random.default_rng(_SEED + 4)
    S, T, G = 16, 12, 4
    local = rng.normal(0, 1e3, (S, T))
    local[rng.random((S, T)) < 0.1] = np.nan         # stale entries
    gids = rng.integers(0, G, S).astype(np.int32)
    gids[-2:] = -1                                    # padding rows
    mesh = _shard_mesh(ndev)

    def body(loc, g):
        return tuple(_grouped_reduce(loc, g, G, agg)
                     for agg in ("sum", "avg"))
    # jitted: an eager shard_map compiles op by op (hundreds of tiny
    # programs) and alone overran the lint's latency budget
    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("shard", None), P("shard")),
        out_specs=P(), check_vma=False))
    return tuple(np.asarray(o)
                 for o in f(jnp.asarray(local), jnp.asarray(gids)))


@order_harness("grouped-pair-psum")
def _h_grouped_pair(ndev: int):
    import numpy as np

    import jax.numpy as jnp

    from filodb_tpu.parallel.shardstore import _build_grouped_pair_eval
    ts, v, g = _counter_world()
    S = ts.shape[1]
    rng = np.random.default_rng(_SEED + 5)
    gids = rng.integers(0, 3, S).astype(np.int32)
    tsr = (ts - g["base"]).astype(np.int32)
    run = _build_grouped_pair_eval(_shard_mesh(ndev), "rate",
                                   g["nsteps"], 3)
    sums, cnts = run(jnp.asarray(tsr), jnp.asarray(v),
                     jnp.asarray(gids),
                     np.array([g["num_slots"], g["base"], g["dt"]],
                              np.int64),
                     np.array([g["w0s"], g["w0e"], g["step"]], np.int64))
    return np.asarray(sums), np.asarray(cnts)
