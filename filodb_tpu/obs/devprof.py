"""Device compile/cost profiling: executable-level telemetry for every
kernel build site.

The device layer was a black box: the dispatch tables counted hits and
misses, but nothing recorded WHAT was compiled, how long each build
took per executable, how often shape churn forced retraces, or what
the lowered program actually costs (FLOPs / bytes accessed from XLA's
``cost_analysis``). This module is the registry behind three surfaces:

  * **/metrics families** (via a global-registry collector, therefore
    also self-ingested and PromQL-queryable once ``--self-monitor`` is
    on — "recompiles in the last 5m" becomes a query):

      filodb_executable_builds_total{site,bucket}      compile events
      filodb_executable_recompiles_total{site,bucket}  shape-churn
                                                       retraces past the
                                                       first build
      filodb_executable_flops{site,executable}         cost_analysis
      filodb_executable_bytes_accessed{site,executable}
      filodb_executables                               live entries

  * **``&explain=analyze``** — per-query device stats: which
    executables the query's dispatches ran (identity + disposition
    from trace events the profiled call sites emit), each with its
    cost-analysis numbers.

  * **:class:`ProfiledExecutable`** — the wrapper the tilestore
    dispatch tables cache. On a table miss the builder lowers +
    compiles the jitted callable AOT (``fn.lower(*args).compile()``)
    — that IS the first call's compile, not an extra one — captures
    ``cost_analysis()`` from the compiled program, and keeps the
    compiled executable as the primary dispatch for the build shape.
    Calls with a different shape signature fall back to the jitted
    callable (whose own cache handles them) and count as recompiles
    per new signature.

Packed/mesh kernels (module-level ``jax.jit`` with static argnames)
register *lazy* cost probes instead: the call site records the abstract
signature (ShapeDtypeStructs + statics) on first sight, and
:meth:`DeviceProfiler.ensure_cost` lowers + compiles it on demand —
the first ``&explain=analyze`` touching that executable pays the probe
compile; serving dispatches never do.

Everything here is allocation-free on the hot path when untraced:
per-dispatch accounting is one small critical section (the same cost
class as the existing dispatch-table hit counters).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from filodb_tpu.lint.locks import guarded_by
from filodb_tpu.obs import trace as obs_trace

# trace event name the profiled call sites emit per dispatch; the
# analyze payload collects these to attribute executables to a query
EXEC_EVENT = "executable"

# cache inventory (graftlint): the profiler's entry table (and the AOT
# Compiled each ProfiledExecutable holds) key purely on (site,
# dispatch-table key) — a pure function of executable identity, immune
# to every world event by construction (the underlying dispatch tables
# declare their own registries at their owning modules)
__cache_registry__ = {
    "devprof-executable-profiles": {"keyed": ("site", "executable-key")},
}

_KEY_MAX = 96


def key_str(key: Tuple) -> str:
    """Compact, bounded label form of a dispatch-table key."""
    s = "/".join(str(x) for x in key)
    return s if len(s) <= _KEY_MAX else s[:_KEY_MAX - 1] + "~"


def shape_bucket(key: Tuple) -> str:
    """The shape-bucket label for recompile counters: the key minus its
    leading family/func atoms collapses to the numeric bucket tuple
    (pow2-padded dims), which is what churns under load."""
    nums = [str(x) for x in key if isinstance(x, (int, float))]
    return "x".join(nums) if nums else key_str(key)


def arg_sig(args) -> Tuple:
    """Recursive (shape, dtype) signature of a call's dynamic args —
    the identity under which one compiled executable is reusable."""
    out = []
    for a in args:
        if isinstance(a, (tuple, list)):
            out.append(arg_sig(a))
        else:
            shape = getattr(a, "shape", None)
            dtype = getattr(a, "dtype", None)
            if shape is not None:
                out.append((tuple(shape), str(dtype)))
            else:
                out.append(type(a).__name__)
    return tuple(out)


def cost_from_compiled(compiled) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed from a ``Compiled``'s cost_analysis
    (None when the backend doesn't provide one)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 — cost is best-effort telemetry
        return None
    if not isinstance(ca, dict):
        return None
    out: Dict[str, float] = {}
    if ca.get("flops") is not None:
        out["flops"] = float(ca["flops"])
    if ca.get("bytes accessed") is not None:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out or None


class _Entry:
    """One cached executable's running profile (mutation under the
    profiler's lock)."""

    __slots__ = ("site", "key", "key_s", "bucket", "builds", "hits",
                 "recompiles", "build_s_total", "last_build_s", "cost",
                 "sigs", "lazy_probe", "created_s")

    def __init__(self, site: str, key: Tuple):
        self.site = site
        self.key = key
        self.key_s = key_str(key)
        self.bucket = shape_bucket(key)
        self.builds = 0
        self.hits = 0
        self.recompiles = 0
        self.build_s_total = 0.0
        self.last_build_s = 0.0
        self.cost: Optional[Dict[str, float]] = None
        self.sigs: set = set()
        # () -> Compiled; set by sites that defer cost capture
        self.lazy_probe: Optional[Callable] = None
        self.created_s = time.monotonic()

    def to_json(self) -> Dict[str, object]:
        d = {"site": self.site, "executable": self.key_s,
             "bucket": self.bucket, "builds": self.builds,
             "hits": self.hits, "recompiles": self.recompiles,
             "build_s_total": round(self.build_s_total, 6),
             "last_build_s": round(self.last_build_s, 6)}
        if self.cost is not None:
            d.update(self.cost)
        return d


@guarded_by("_lock", "_entries")
class DeviceProfiler:
    """Process-global registry of executable profiles (one per cached
    executable across the tilestore dispatch tables, the packed kernel
    family, and the mesh executors)."""

    # safety valve: label cardinality on the cost gauges is bounded by
    # the pow2 shape bucketing, but a pathological workload could still
    # churn keys — cap the table (oldest entries beyond it are dropped
    # from the PROFILE only; the underlying executables live in their
    # own caches)
    MAX_ENTRIES = 512

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, Tuple], _Entry] = {}

    def _entry_locked(self, site: str, key: Tuple) -> _Entry:
        e = self._entries.get((site, key))
        if e is None:
            if len(self._entries) >= self.MAX_ENTRIES:
                oldest = min(self._entries,
                             key=lambda k: self._entries[k].created_s)
                del self._entries[oldest]
            e = _Entry(site, key)
            self._entries[(site, key)] = e
        return e

    def note_build(self, site: str, key: Tuple, seconds: float,
                   cost: Optional[Dict[str, float]] = None,
                   sig: Optional[Tuple] = None,
                   lazy_probe: Optional[Callable] = None) -> bool:
        """Record one compile event; returns True when this was a
        RECOMPILE (the site+bucket family already had a build — shape
        churn, cache invalidation)."""
        with self._lock:
            e = self._entry_locked(site, key)
            recompile = e.builds > 0
            e.builds += 1
            e.build_s_total += float(seconds)
            e.last_build_s = float(seconds)
            if cost is not None:
                e.cost = cost
            if sig is not None:
                e.sigs.add(sig)
            if lazy_probe is not None and e.lazy_probe is None \
                    and e.cost is None:
                e.lazy_probe = lazy_probe
            if recompile:
                e.recompiles += 1
        return recompile

    def note_call(self, site: str, key: Tuple,
                  sig: Optional[Tuple] = None) -> bool:
        """Record one dispatch through an already-built executable;
        returns True when ``sig`` is NEW for the entry (the call fell
        back to a jit retrace — counted as a recompile)."""
        with self._lock:
            e = self._entry_locked(site, key)
            e.hits += 1
            if sig is not None and sig not in e.sigs:
                e.sigs.add(sig)
                e.recompiles += 1
                return True
        return False

    def set_cost(self, site: str, key: Tuple,
                 cost: Optional[Dict[str, float]]) -> None:
        if cost is None:
            return
        with self._lock:
            self._entry_locked(site, key).cost = cost

    def ensure_cost(self, site: str, key: Tuple
                    ) -> Optional[Dict[str, float]]:
        """Cost-analysis numbers for one executable, computing them via
        the entry's lazy probe on first demand (an ``&explain=analyze``
        request pays this probe compile once per executable; steady
        serving never does)."""
        with self._lock:
            e = self._entries.get((site, key))
            if e is None:
                return None
            if e.cost is not None or e.lazy_probe is None:
                return e.cost
            probe = e.lazy_probe
        # compile OUTSIDE the lock (XLA compiles take ~100ms)
        try:
            compiled = probe()
            cost = cost_from_compiled(compiled)
        except Exception:   # noqa: BLE001 — a probe must never fail a query
            cost = None
        with self._lock:
            e = self._entries.get((site, key))
            if e is not None:
                e.lazy_probe = None     # one attempt; don't re-pay failures
                if cost is not None and e.cost is None:
                    e.cost = cost
            return cost

    def lookup(self, site: str, key_s: str) -> Optional[Dict]:
        """Entry JSON by (site, rendered key) — the analyze path's view
        (trace events carry the rendered key, not the tuple)."""
        with self._lock:
            for (s, _k), e in self._entries.items():
                if s == site and e.key_s == key_s:
                    ensure = (e.site, e.key)
                    break
            else:
                return None
        self.ensure_cost(*ensure)
        with self._lock:
            for (s, _k), e in self._entries.items():
                if s == site and e.key_s == key_s:
                    return e.to_json()
        return None

    def snapshot(self) -> List[Dict[str, object]]:
        with self._lock:
            entries = list(self._entries.values())
        return [e.to_json() for e in sorted(
            entries, key=lambda e: (e.site, e.key_s))]

    def reset(self) -> None:
        """Test hook."""
        with self._lock:
            self._entries.clear()

    # -- /metrics collector ------------------------------------------------
    def collect(self, builder) -> None:
        """Registry collector: executable-level families into the
        exposition (and therefore into the self-monitoring ingest)."""
        snap = self.snapshot()
        builder.sample("filodb_executables", {}, len(snap),
                       help="Cached device executables with a profile "
                            "entry")
        builds: Dict[Tuple[str, str], int] = {}
        recompiles: Dict[Tuple[str, str], int] = {}
        for e in snap:
            k = (e["site"], e["bucket"])
            builds[k] = builds.get(k, 0) + int(e["builds"])
            recompiles[k] = recompiles.get(k, 0) + int(e["recompiles"])
        for (site, bucket), n in sorted(builds.items()):
            builder.sample("filodb_executable_builds_total",
                           {"site": site, "bucket": bucket}, n,
                           mtype="counter",
                           help="Executable compile events (trace + "
                                "XLA build) by build site and shape "
                                "bucket")
        for (site, bucket), n in sorted(recompiles.items()):
            if n:
                builder.sample("filodb_executable_recompiles_total",
                               {"site": site, "bucket": bucket}, n,
                               mtype="counter",
                               help="Retraces past an executable's "
                                    "first build (shape churn; a "
                                    "storm here is a recompile storm)")
        for e in snap:
            if "flops" not in e and "bytes_accessed" not in e:
                continue
            lbl = {"site": e["site"], "executable": e["executable"]}
            if "flops" in e:
                builder.sample("filodb_executable_flops", lbl,
                               e["flops"],
                               help="XLA cost_analysis FLOPs of the "
                                    "lowered executable")
            if "bytes_accessed" in e:
                builder.sample("filodb_executable_bytes_accessed", lbl,
                               e["bytes_accessed"],
                               help="XLA cost_analysis bytes accessed "
                                    "of the lowered executable")


GLOBAL_PROFILER = DeviceProfiler()


def _register_collector() -> None:
    from filodb_tpu.obs import metrics as obs_metrics
    obs_metrics.GLOBAL_REGISTRY.register_collector(GLOBAL_PROFILER.collect)


_register_collector()


class _PutCounts:
    """``filodb_host_to_device_puts_total``: device buffers that calls of
    cached executables made from host values (a numpy array or scalar, a
    Python number), one per device the executable runs on, so that an
    argument replicated over four devices counts four. Plain adds, like
    ``filodb_device_to_host_bytes_total``."""

    __slots__ = ("h2d_puts",)

    def __init__(self):
        self.h2d_puts = 0


put_counts = _PutCounts()


def host_args(args) -> int:
    """How many of a call's arguments (tuples, lists and dicts looked
    into) are host values that the call puts on the device: numpy arrays
    and scalars and Python numbers; a device array is already there and
    a string is a static argument."""
    n = 0
    for a in args:
        if isinstance(a, (tuple, list)):
            n += host_args(a)
        elif isinstance(a, dict):
            n += host_args(a.values())
        elif isinstance(a, (np.ndarray, np.generic, int, float)):
            n += 1
    return n


def _device_count(compiled) -> int:
    """Devices a compiled executable runs on (1 where it cannot say)."""
    try:
        shardings = compiled.input_shardings[0]
    except (AttributeError, IndexError, TypeError):
        return 1
    return max((len(s.device_set) for s in shardings
                if hasattr(s, "device_set")), default=1)


class ProfiledExecutable:
    """The object the tilestore dispatch tables cache: AOT-compiled
    primary dispatch for the build shape + jit fallback for churned
    shapes, with per-call profiling and an ``executable`` trace event
    (no-op when untraced) carrying identity + disposition. Every call adds
    the buffers it makes from host values to ``put_counts``."""

    __slots__ = ("fn", "site", "key", "key_s", "_compiled", "_sig",
                 "_ndev")

    def __init__(self, fn, site: str, key: Tuple,
                 compiled=None, sig: Optional[Tuple] = None):
        self.fn = fn
        self.site = site
        self.key = key
        self.key_s = key_str(key)
        self._compiled = compiled
        self._sig = sig
        self._ndev = 1 if compiled is None else _device_count(compiled)

    def __call__(self, *args):
        put_counts.h2d_puts += host_args(args) * self._ndev
        sig = arg_sig(args)
        if self._compiled is not None and sig == self._sig:
            try:
                out = self._compiled(*args)
                GLOBAL_PROFILER.note_call(self.site, self.key, sig)
                obs_trace.event(EXEC_EVENT, site=self.site,
                                key=self.key_s, disposition="aot")
                return out
            except (TypeError, ValueError):
                # aval/weak-type mismatch the signature missed: the jit
                # path below retraces and its own cache takes over
                pass
        retraced = GLOBAL_PROFILER.note_call(self.site, self.key, sig)
        obs_trace.event(EXEC_EVENT, site=self.site, key=self.key_s,
                        disposition="jit-retrace" if retraced else "jit")
        return self.fn(*args)


def build_profiled(site: str, key: Tuple, build: Callable,
                   cost_args: Optional[Sequence] = None
                   ) -> ProfiledExecutable:
    """Build one dispatch-table entry with full compile telemetry.
    ``build()`` returns the jitted callable; with ``cost_args`` (the
    first call's argument tuple) the executable is lowered + compiled
    AOT right here — the one compile the miss was going to pay anyway —
    and cost_analysis is captured from the compiled program."""
    t0 = time.perf_counter()
    fn = build()
    compiled = None
    cost = None
    sig = None
    if cost_args is not None:
        try:
            compiled = fn.lower(*cost_args).compile()
            cost = cost_from_compiled(compiled)
            sig = arg_sig(cost_args)
        except Exception:   # noqa: BLE001 — profiling must not fail builds
            compiled = None
            sig = None
    build_s = time.perf_counter() - t0
    GLOBAL_PROFILER.note_build(site, key, build_s, cost=cost, sig=sig)
    obs_trace.event(EXEC_EVENT, site=site, key=key_str(key),
                    disposition="build")
    return ProfiledExecutable(fn, site, key, compiled=compiled, sig=sig)


def note_dispatch(site: str, key: Tuple, first_seen: bool,
                  probe: Optional[Callable] = None) -> None:
    """Per-dispatch accounting for lazily-profiled sites (the packed
    path's ``_count_exec`` hook, the mesh executors): first sight is
    the compile event (``probe``, when given, is the () -> Compiled
    lazy cost probe), later dispatches count as cache hits. Emits the
    identity trace event either way."""
    if first_seen:
        GLOBAL_PROFILER.note_build(site, key, 0.0, lazy_probe=probe)
    else:
        GLOBAL_PROFILER.note_call(site, key)
    obs_trace.event(EXEC_EVENT, site=site, key=key_str(key),
                    disposition="build" if first_seen else "jit")


# ---------------------------------------------------------------------------
# &explain=analyze payload
# ---------------------------------------------------------------------------

def analyze_payload(spans: List[Dict], stages: Dict,
                    batcher_stats: Optional[Dict] = None,
                    qos_info: Optional[Dict] = None,
                    residency: Optional[Dict] = None) -> Dict:
    """The ``&explain=analyze`` envelope: per-stage timings (the spans
    PR 4's ``&explain=trace`` already records), the executables this
    query's dispatches actually ran — identity, compile disposition,
    cost-analysis FLOPs/bytes (computed on demand) — batcher occupancy
    at dispatch, cache dispositions, and the shed/degrade decision."""
    execs: Dict[Tuple[str, str], Dict] = {}
    dispatches: List[Dict] = []
    for sp in spans:
        tags = sp.get("tags") or {}
        name = sp.get("name")
        if name == EXEC_EVENT:
            k = (str(tags.get("site", "")), str(tags.get("key", "")))
            e = execs.setdefault(k, {"site": k[0], "executable": k[1],
                                     "dispatches": 0,
                                     "dispositions": []})
            e["dispatches"] += 1
            disp = str(tags.get("disposition", ""))
            if disp and disp not in e["dispositions"]:
                e["dispositions"].append(disp)
        elif name in ("device-dispatch", "device-eval", "kernel-build",
                      "batcher-dispatch", "device-sync",
                      "batcher-queue-wait"):
            d = {"span": name, "dur_us": sp.get("dur_us")}
            d.update(tags)
            dispatches.append(d)
    for (site, key_s), e in execs.items():
        entry = GLOBAL_PROFILER.lookup(site, key_s)
        if entry is not None:
            for f in ("builds", "recompiles", "build_s_total",
                      "last_build_s", "flops", "bytes_accessed",
                      "bucket"):
                if f in entry:
                    e[f] = entry[f]
    out: Dict[str, object] = {
        "stages": dict(stages),
        "device": {
            "executables": sorted(execs.values(),
                                  key=lambda e: (e["site"],
                                                 e["executable"])),
            "dispatches": dispatches,
        },
    }
    if batcher_stats is not None:
        out["batcher"] = batcher_stats
    if qos_info is not None:
        out["qos"] = qos_info
    if residency:
        out["residency"] = {
            family: {"shards": dict(shards),
                     "total_bytes": sum(shards.values())}
            for family, shards in residency.items()
        }
    return out
