"""Capacity-certification rail (graftlint v5): every ``@capacity``
residency claim in the tree is dynamically certified, engine-as-
assertion style — the memory twin of :mod:`filodb_tpu.lint.ulpcert`.

:mod:`filodb_tpu.lint.rules_capacity` makes ``@capacity`` annotations
mandatory wherever a device allocation escapes into a long-lived
store; this module makes them HONEST. For each registered claim a
harness builds the annotated structure at seeded sizes and the rail
measures the REAL device bytes it retains (a live-buffer walk over the
store's object graph, deduplicated per buffer), then checks the claim
two-sided:

  * ``measured > claimed`` — the store is bigger than declared: the
    capacity planning the ledger feeds (resident series per 16 GB
    chip) would overcommit HBM;
  * ``claimed > 1.25 x measured`` — the claim pads more than 25% over
    reality: a slack claim hides regressions exactly the way a slack
    ULP tolerance does.

Sharded claims (``sharded=True``) certify at 1/2/4/8 virtual devices —
shard-alignment padding must be priced at every mesh width, not just
the friendly one. A claim with no harness, or whose harness crashes,
fails: an annotation the rail cannot evaluate cannot ship. Failures
surface as error-severity ``capacity-certification`` findings in the
tier-1 gate. Results are memoized per process (claims are fixed at
import time) so repeated ``run_lint`` calls pay the build cost once.

:func:`capacity_ledger` is the certified inventory: per family, the
certified bytes budget and the projected resident series per 16 GB chip
— the baseline number the compressed-chunks work must move. The
``CAPACITY.json`` at the repository root is a copy of it that a
kernel-timing script, since removed, last wrote at commit ``1e6f2ee``;
nothing writes or reads that file now.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from filodb_tpu.lint import Finding, register_rule
from filodb_tpu.lint import capacity as cmod
from filodb_tpu.lint.ulpcert import ensure_virtual_devices

register_rule("capacity-certification", "capacity",
              "a @capacity residency claim failed dynamic "
              "certification (measured device bytes above the claim, "
              "claim >1.25x over measured, or no harness) — the "
              "declared bytes budget is a lie")

DEVICE_COUNTS = (1, 2, 4, 8)

# a claim may pad at most 25% over the measured footprint
OVERCLAIM_RATIO = 1.25

# claim name -> harness. Sharded harnesses take (ndev) and run per
# device count; others take no argument. Both return
# (store, n_samples, n_series): ``store`` is walked for live device
# bytes (or is already a byte count), ``n_samples``/``n_series`` are
# the PADDED logical sizes the claim is evaluated at.
HARNESSES: Dict[str, Callable] = {}


def capacity_harness(name: str) -> Callable:
    def deco(fn):
        HARNESSES[name] = fn
        return fn
    return deco


@dataclass
class CapResult:
    name: str
    ok: bool
    measured: float             # worst-case live device bytes observed
    claimed: float              # claim total at the harness sizes
    n_samples: int = 0
    n_series: int = 0
    detail: str = ""
    device_counts: Tuple[int, ...] = ()


# ---------------------------------------------------------------------------
# live-buffer walk
# ---------------------------------------------------------------------------


def device_bytes(obj, max_depth: int = 10) -> int:
    """Sum the bytes of every distinct device array reachable from
    ``obj``: dicts, sequences, object attributes (``__dict__`` and
    ``__slots__``), and function closures, deduplicated per buffer so
    aliased references count once. Host numpy arrays do NOT count —
    residency is device memory."""
    import jax
    seen_objs: set = set()
    bufs: Dict[int, int] = {}
    stack: List[Tuple[object, int]] = [(obj, 0)]
    while stack:
        cur, depth = stack.pop()
        if cur is None or depth > max_depth:
            continue
        oid = id(cur)
        if oid in seen_objs:
            continue
        seen_objs.add(oid)
        if isinstance(cur, jax.Array):
            bufs[oid] = int(cur.nbytes)
            continue
        if isinstance(cur, (str, bytes, int, float, bool, complex)):
            continue
        if isinstance(cur, dict):
            stack.extend((v, depth + 1) for v in cur.values())
            continue
        if isinstance(cur, (list, tuple, set, frozenset)):
            stack.extend((v, depth + 1) for v in cur)
            continue
        d = getattr(cur, "__dict__", None)
        if isinstance(d, dict):
            stack.extend((v, depth + 1) for v in d.values())
        for klass in type(cur).__mro__:
            for slot in getattr(klass, "__slots__", ()):
                try:
                    stack.append((getattr(cur, slot), depth + 1))
                except AttributeError:
                    pass
        cells = getattr(cur, "__closure__", None)
        if cells:
            for cell in cells:
                try:
                    stack.append((cell.cell_contents, depth + 1))
                except ValueError:      # empty cell
                    pass
    return sum(bufs.values())


def _as_measurement(store, n_samples: int, n_series: int
                    ) -> Tuple[float, int, int]:
    if isinstance(store, (int, float)):
        return float(store), int(n_samples), int(n_series)
    return float(device_bytes(store)), int(n_samples), int(n_series)


# ---------------------------------------------------------------------------
# certify
# ---------------------------------------------------------------------------

_MEMO: Optional[List[CapResult]] = None


def _check(claim: cmod.CapacityClaim, measured: float, n_samples: int,
           n_series: int, counts: Tuple[int, ...]) -> CapResult:
    claimed = claim.claimed_total(n_samples, n_series)
    if measured > claimed:
        return CapResult(
            claim.name, False, measured, claimed, n_samples, n_series,
            f"store holds {measured:.0f} device bytes, claim covers "
            f"{claimed:.0f} at {n_samples} samples x {n_series} series "
            f"— residency above budget", counts)
    if claimed > OVERCLAIM_RATIO * max(measured, 1.0):
        return CapResult(
            claim.name, False, measured, claimed, n_samples, n_series,
            f"claim {claimed:.0f} is {claimed / max(measured, 1.0):.2f}x "
            f"the measured {measured:.0f} bytes — slack claims hide "
            f"regressions", counts)
    return CapResult(claim.name, True, measured, claimed, n_samples,
                     n_series, f"{measured:.0f} bytes measured vs "
                     f"{claimed:.0f} claimed", counts)


def certify_all(force: bool = False) -> List[CapResult]:
    """Certify every registered @capacity claim. Memoized per process."""
    global _MEMO
    if _MEMO is not None and not force:
        return _MEMO
    ensure_virtual_devices()
    cmod.import_annotated_modules()
    import jax
    avail = len(jax.devices())
    counts = tuple(d for d in DEVICE_COUNTS if d <= avail)
    out: List[CapResult] = []
    for name, claim in sorted(cmod.CAPACITY.items()):
        harness = HARNESSES.get(name)
        if harness is None:
            out.append(CapResult(
                name, False, math.inf, 0.0,
                detail="no certification harness registered — an "
                       "annotation the rail cannot evaluate cannot "
                       "ship"))
            continue
        try:
            if claim.sharded:
                worst: Optional[CapResult] = None
                for n in counts:
                    measured, ns, nr = _as_measurement(*harness(n))
                    r = _check(claim, measured, ns, nr, counts)
                    if worst is None or (not r.ok) or \
                            (worst.ok and r.measured > worst.measured):
                        worst = r
                    if not r.ok:
                        worst.detail += f" (at {n} device(s))"
                        break
                out.append(worst)
            else:
                measured, ns, nr = _as_measurement(*harness())
                out.append(_check(claim, measured, ns, nr, ()))
        except Exception as e:  # noqa: BLE001 — a gate must not crash
            out.append(CapResult(name, False, math.inf, 0.0,
                                 detail=f"harness crashed: "
                                        f"{type(e).__name__}: {e}"))
    _MEMO = out
    return out


def _claim_anchor(claim, mods) -> Tuple[Optional[str], int]:
    relpath = claim.module.replace(".", "/") + ".py"
    for mod in mods or ():
        if mod.relpath == relpath:
            for i, line in enumerate(mod.lines, start=1):
                if claim.name in line:
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
        claim = cmod.CAPACITY.get(res.name)
        if claim is None:
            continue
        relpath, line = _claim_anchor(claim, mods)
        out.append((relpath, Finding(
            rule="capacity-certification", path=relpath or "?",
            line=line,
            message=(f"capacity claim {res.name!r} failed "
                     f"certification: measured {res.measured:.4g} vs "
                     f"claimed {res.claimed:.4g} bytes — {res.detail}"),
            context=f"memcert:{res.name}")))
    return out


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


def capacity_ledger(samples_per_series: int = 2880
                    ) -> List[Dict[str, object]]:
    """Certified inventory for CAPACITY.json: per family the claimed
    budget, the measured bytes at the harness sizes, and the projected
    resident series per 16 GB chip at ``samples_per_series`` retained
    samples (the bench grid's 8h @ 10s default)."""
    rows: List[Dict[str, object]] = []
    results = {r.name: r for r in certify_all()}
    for name, claim in sorted(cmod.CAPACITY.items()):
        r = results.get(name)
        measured_bps = (r.measured / r.n_samples
                        if r and r.n_samples else None)
        rows.append({
            "family": name,
            "module": claim.module,
            "qualname": claim.qualname,
            "sharded": claim.sharded,
            "certified": bool(r and r.ok),
            "claimed_bytes_per_sample": claim.bytes_per_sample,
            "claimed_bytes_per_series": claim.bytes_per_series,
            "claimed_overhead_bytes": claim.overhead_bytes,
            "measured_bytes": (None if r is None or
                               not math.isfinite(r.measured)
                               else r.measured),
            "harness_n_samples": r.n_samples if r else 0,
            "harness_n_series": r.n_series if r else 0,
            "measured_bytes_per_sample": measured_bps,
            "device_counts": list(r.device_counts) if r else [],
            "projected_series_per_chip_16gb":
                claim.projected_series_per_chip(samples_per_series),
            "reason": claim.reason,
        })
    return rows


# ---------------------------------------------------------------------------
# in-tree harnesses
# ---------------------------------------------------------------------------
#
# Each harness builds the annotated store at SEEDED sizes chosen so
# the padded layout is exercised (pow2 slot capacity above the logical
# slot count, series counts divisible by every certified shard width)
# and measurement is deterministic.

_SEED = 0x0DD5


def _seed_tiles(S: int = 16, N: int = 56):
    """Dense counter tiles: S series x N slots (N NOT a power of two,
    so the pow2 capacity pad is live in the measurement)."""
    import numpy as np

    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(_SEED)
    base, dt = 1_000_000_000_000, 10_000
    ts = (base + np.arange(N, dtype=np.float64)[None, :] * dt
          + rng.integers(-2000, 2001, (S, N)))
    vals = np.cumsum(rng.uniform(0, 5, (S, N)), axis=1)
    return tst.AlignedTiles([{"i": str(i)} for i in range(S)], base, dt,
                            np.ones((S, N), bool), ts, vals)


def _shard_mesh(ndev: int):
    import jax

    from filodb_tpu.parallel.mesh import make_mesh
    return make_mesh(n_shard_groups=ndev, time_parallel=1,
                     devices=jax.devices()[:ndev])


@capacity_harness("shardstore-resident-channels")
def _h_shardstore(ndev: int):
    """The resident store itself: [cap, S_pad] int32 rel-ts + raw f64
    + corrected f64 = 20 B per padded slot, at every mesh width."""
    from filodb_tpu.parallel.shardstore import ShardedTiles
    tiles = _seed_tiles(S=16, N=56)     # cap pads 56 -> 64
    st = ShardedTiles(_shard_mesh(ndev), tiles)
    return st, st.cap * st.S_pad, st.S_pad


@capacity_harness("shardstore-resident-hist-channels")
def _h_shardstore_hist(ndev: int):
    """A histogram placement of 12 buckets: [cap, S_pad] int32 rel-ts and
    two [cap, BP, S_pad] channels of three f32 parts each (counts with a
    tenth in them, the most a placement holds), (24 BP + 4) / B B a bucket
    value of a padded slot, and its constants, at every mesh width."""
    from filodb_tpu.parallel.shardstore import ShardedHistTiles
    tiles = _seed_hist_tiles(S=10, N=56, frac=0.1)  # cap 64, S pads 4, 8
    st = ShardedHistTiles(_shard_mesh(ndev), tiles)
    return st, st.cap * st.S_pad * st.B, st.S_pad


@capacity_harness("tilestore-aligned-tiles")
def _h_aligned_tiles():
    """Single-device aligned tiles: valid bool + ts f64 + vals f64 =
    17 B per slot (lazy channel caches empty at build)."""
    tiles = _seed_tiles(S=8, N=64)
    return tiles, 8 * 64, 8


def _seed_hist_tiles(S: int = 8, N: int = 64, B: int = 12,
                     frac: float = 0.0):
    """Dense histogram tiles: S series x N slots x B buckets; ``frac`` is
    added to every count and is every correction."""
    import numpy as np

    from filodb_tpu.query import tilestore as tst
    rng = np.random.default_rng(_SEED)
    base, dt = 1_000_000_000_000, 10_000
    ts = base + np.arange(N, dtype=np.float64)[None, :] * dt \
        + rng.integers(-2000, 2001, (S, N))
    vals = np.cumsum(np.cumsum(rng.poisson(5.0, (S, N, B)), axis=2),
                     axis=1).astype(np.float64) + frac
    les = tuple(float(2 ** i) for i in range(B - 1)) + (float("inf"),)
    return tst.HistTiles([{"i": str(i)} for i in range(S)], base, dt,
                         np.ones((S, N), bool), ts, vals,
                         np.full_like(vals, frac), les)


@capacity_harness("tilestore-hist-tiles")
def _h_hist_tiles():
    """A histogram cohort of 12 buckets: valid bool + ts f64 a slot, and
    raw, corrected and correction f64 a bucket value; a sample is one
    bucket value of one slot."""
    return _seed_hist_tiles(S=8, N=64, B=12), 8 * 64 * 12, 8


@capacity_harness("tilestore-executable-constants")
def _h_exec_constants():
    """Packed-executable cache entries retain the device constants
    their closures capture; the claim prices them per packed slot."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from filodb_tpu.query import tilestore as tst
    const = jnp.asarray(
        np.arange(64 * 8, dtype=np.float64).reshape(64, 8))
    cache: Dict = {}

    def build():
        jit_f = jax.jit(lambda x: (x * const).sum(axis=0))

        def entry(x):
            return jit_f(x)
        # the closure-retained constant inventory the walk measures
        entry.__memcert_consts__ = (const,)
        return entry

    fn = tst._jit_lookup(cache, ("memcert", "exec-const"), build,
                         site="memcert")
    np.asarray(fn(jnp.ones((64, 8), jnp.float64)))
    return cache, 64 * 8, 8


@capacity_harness("device-tile-cache")
def _h_tile_cache():
    """The backend tile cache retains whole AlignedTiles cohorts per
    selection snapshot (FIFO-capped at _TILE_CACHE_MAX)."""
    import numpy as np

    from filodb_tpu.query import tpu as tpumod
    be = tpumod.TpuBackend(batcher=None)
    tiles = _seed_tiles(S=8, N=64)
    entry = tpumod._TileEntry(tiles, np.arange(8), False, [], None)
    be._insert_tile_entry(("memcert", "tile-cache"), None, entry)
    return be._tile_cache, 8 * 64, 8


@capacity_harness("downsample-pack-buffers")
def _h_downsample_pack():
    """The downsampler's padded staging block as the batch eval places
    it on device: int64 ts + f64 vals = 16 B per padded slot."""
    import numpy as np

    import jax

    from filodb_tpu.downsample.job import DownsamplerJob
    rng = np.random.default_rng(_SEED)
    job = DownsamplerJob(None)
    batch = []
    for i in range(4):
        ts = (1_000_000_000_000
              + np.arange(48, dtype=np.int64) * 10_000 + i)
        batch.append((f"pk{i}", None, ts, rng.uniform(0, 1, 48)))
    ts_pad, vals_pad, lens, t_lo, t_hi = job._pack(batch)
    placed = (jax.device_put(ts_pad), jax.device_put(vals_pad))
    return placed, ts_pad.size, len(batch)
