"""Host/process-level collector for the global metrics registry.

The reference gets process metrics for free from the JVM's Kamon
system-metrics module; a CPython process has to read /proc itself.
Registered as a registry collector (``register_process_collector``), so
every exposition build — the /metrics scrape AND the self-monitoring
registry walk — carries host-level series from day one:

  filodb_process_resident_memory_bytes   RSS from /proc/self/statm
  filodb_process_virtual_memory_bytes    VSZ from /proc/self/statm
  filodb_process_open_fds                open descriptors (/proc/self/fd)
  filodb_process_threads                 live interpreter threads
  filodb_process_uptime_seconds          seconds since process start
  filodb_build_info                      constant 1 with version labels

Everything degrades gracefully off Linux (missing /proc reads emit
nothing rather than failing the scrape).

It is also the one home of what the interpreter itself costs a request,
both measured where it happens and always on (``acquire_instruments``,
called by ``FiloServer.start()``; each exists ONCE a process however
many servers it holds):

  filodb_process_gc_collections_total{generation}  collections, from
                                         ``gc.get_stats()`` at scrape
  filodb_gc_pause_seconds_total{generation}  what they took: a
                                         ``gc.callbacks`` entry
                                         (:class:`GcTimer`) reads the
                                         clock at ``start`` and ``stop``
  filodb_gc_stalls_total                 collections of at least
  filodb_gc_stall_seconds_total          ``GC_STALL_NS`` and their
                                         seconds (unlabelled: readers
                                         that sum label sets need that)
  filodb_stage_<S>_gc_seconds_total      the same pauses by the stage
                                         they interrupted (exposed by
                                         ``obs/trace.py``; their sum is
                                         at most the generations' sum,
                                         the rest ran outside any stage)
  filodb_interpreter_wait_seconds        histogram: how late a thread
                                         that slept 5-15 ms got the
                                         interpreter back
                                         (:class:`InterpreterProbe`):
                                         the price of one hand-back at
                                         the present load
  filodb_interpreter_probes_waited_total  probes later than
                                         ``PROBE_WAITED_S`` (2.5 ms, past
                                         a sleeper's wake-up latency);
                                         over the histogram's ``_count``
                                         it is the share of time in which
                                         a thread that asks for the
                                         interpreter waits that long for
                                         it (an idle node reads under 1%,
                                         not 0)

Under a profiler session a generation-1 or -2 collection is also an
event ``filodb:gc<n>`` on the host plane, beside the ``filodb:<S>``
stage events."""

from __future__ import annotations

import gc
import os
import random
import sys
import threading
import time
from typing import Callable, Optional

from filodb_tpu.lint.threads import thread_root
from filodb_tpu.obs import metrics as obs_metrics
from filodb_tpu.obs import trace as obs_trace

# process start approximated at first import of the obs layer — the
# server imports it during startup, so the error is milliseconds
_START_MONOTONIC = time.monotonic()

_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096

# bumped per release line; surfaced as filodb_build_info{version=...}
BUILD_VERSION = "0.11.0"


def _statm():
    try:
        with open("/proc/self/statm") as f:
            parts = f.read().split()
        return int(parts[0]) * _PAGE, int(parts[1]) * _PAGE  # vsz, rss
    except (OSError, ValueError, IndexError):
        return None, None


def _open_fds():
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return None


# a collection at least this long is a stall: every request thread of
# the node waits behind it (the longest young collections read well
# under a millisecond)
GC_STALL_NS = 20_000_000


class GcTimer:
    """The ``gc.callbacks`` entry that times collections where they run.

    The collector calls it on the collecting thread with the interpreter
    held, and never starts a collection while one runs, so the fields
    have one writer at a time and need no lock; it allocates no
    container. Each pause goes to the generation's total, to the stall
    pair when it is long, to the stage it interrupted
    (``obs_trace.charge_collector``) and, for generations 1 and 2, onto
    the profiler's clock. ``clock`` is injected for tests."""

    __slots__ = ("_clock", "_t0", "_anno", "pause_ns", "stalls",
                 "stall_ns")

    def __init__(self, clock: Callable[[], int] = time.perf_counter_ns):
        self._clock = clock
        self._t0 = 0
        self._anno = None
        self.pause_ns = [0, 0, 0]
        self.stalls = 0
        self.stall_ns = 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            gen = info["generation"]
            if gen:                 # generation 0: too frequent to trace
                self._anno = obs_trace.collector_annotation(gen)
            self._t0 = self._clock()
            return
        ns = self._clock() - self._t0
        if self._anno is not None:
            self._anno.__exit__(None, None, None)
            self._anno = None
        self.pause_ns[info["generation"]] += ns
        if ns >= GC_STALL_NS:
            self.stalls += 1
            self.stall_ns += ns
        obs_trace.charge_collector(ns)


WAIT_FAMILY = "filodb_interpreter_wait_seconds"
WAIT_HELP = ("How late a probe thread that slept 5-15 ms got the "
             "interpreter back: what one hand-back costs at this load "
             "(the kernel's wake-up latency included)")
WAIT_BUCKETS_S = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                  0.01, 0.025, 0.05, 0.1, 0.25, 1.0)
WAITED_FAMILY = "filodb_interpreter_probes_waited_total"
WAITED_HELP = ("Probes that got the interpreter back more than 2.5 ms "
               "late: over filodb_interpreter_wait_seconds_count, the "
               "share of time in which a thread that asks for it waits "
               "that long (an idle node reads under 1%: the host's own "
               "late wake-ups)")
# later than this counts as having waited. It has to lie above what a
# sleeper's wake-up costs with nobody holding the interpreter, which an
# idle node's probes read: 0.6 ms at the mean on the TPU host's VM, with
# 99.2-99.6% of idle probes under 2.5 ms (at 250 us an idle node read
# 78-82% waited: PERF.md section 6). A bucket edge of the histogram, so
# the counter and the buckets say the same
PROBE_WAITED_S = 0.0025
# drawn uniformly, so that the probe's arrivals are random in time and
# cannot lock step with the interpreter's 5 ms switch interval
PROBE_SLEEP_S = (0.005, 0.015)


def _waited() -> obs_metrics.CounterFamily:
    # looked up a wake, not kept: the registry's reset() drops families
    return obs_metrics.GLOBAL_REGISTRY.counter(WAITED_FAMILY, WAITED_HELP)


class InterpreterProbe:
    """A daemon thread that samples the interpreter's queue: it sleeps
    for a time drawn from ``PROBE_SLEEP_S`` and, on waking, takes how
    late it was. That lateness is the time a thread that wants the
    interpreter waits for it, which is what every hand-back of a request
    thread pays. About 100 probes a second, a few microseconds each.
    ``sleep``, ``clock`` (ns) and ``uniform`` are injected for tests."""

    def __init__(self, sleep: Callable[[float], None] = time.sleep,
                 clock: Callable[[], int] = time.perf_counter_ns,
                 uniform: Callable[[float, float], float] = random.uniform):
        self._sleep = sleep
        self._clock = clock
        self._uniform = uniform
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def probe(self) -> float:
        """One sleep and its lateness in seconds, observed."""
        asked = self._uniform(*PROBE_SLEEP_S)
        t0 = self._clock()
        self._sleep(asked)
        late = max(0.0, (self._clock() - t0) / 1e9 - asked)
        obs_metrics.observe(WAIT_FAMILY, WAIT_HELP, late, WAIT_BUCKETS_S)
        if late > PROBE_WAITED_S:
            _waited().inc()
        return late

    @thread_root("interpreter-probe")
    def _run(self) -> None:
        while not self._stop.is_set():
            self.probe()

    def start(self) -> "InterpreterProbe":
        _waited().inc(0)        # reads 0, not nothing, before a wait
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="interpreter-probe")
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()


# once a process, not once a server: tests build many servers in one
# interpreter, and a second timer or probe would count everything twice.
# The timer stays for good; the probe is a thread that wakes a hundred
# times a second, so it is counted in and out and joined with the last
# server: a process that has stopped its servers (a test run, a host
# program that embeds one) keeps no thread of ours
GC_TIMER = GcTimer()
_instruments_lock = threading.Lock()
_instrument_users = 0
_probe: Optional[InterpreterProbe] = None


def acquire_instruments() -> None:
    """Called by every ``FiloServer.start()``: registers the collection
    timer (once a process, for good) and starts the probe thread with
    the first server."""
    global _instrument_users, _probe
    with _instruments_lock:
        if GC_TIMER not in gc.callbacks:
            gc.callbacks.append(GC_TIMER)
        _instrument_users += 1
        if _probe is None:
            _probe = InterpreterProbe().start()


def release_instruments() -> None:
    """Called by the ``stop()`` of a server that acquired: the probe
    thread is joined when the last one stops."""
    global _instrument_users, _probe
    with _instruments_lock:
        _instrument_users -= 1
        if _instrument_users > 0:
            return
        probe, _probe = _probe, None
    if probe is not None:
        probe.stop()


def collect_process(builder) -> None:
    """The collector body: sample current process state into an
    ExpositionBuilder (called per exposition build)."""
    vsz, rss = _statm()
    if rss is not None:
        builder.sample("filodb_process_resident_memory_bytes", {}, rss,
                       help="Resident set size in bytes "
                            "(/proc/self/statm)")
    if vsz is not None:
        builder.sample("filodb_process_virtual_memory_bytes", {}, vsz,
                       help="Virtual memory size in bytes "
                            "(/proc/self/statm)")
    fds = _open_fds()
    if fds is not None:
        builder.sample("filodb_process_open_fds", {}, fds,
                       help="Open file descriptors (/proc/self/fd)")
    builder.sample("filodb_process_threads", {},
                   threading.active_count(),
                   help="Live Python threads in this process")
    for gen, st in enumerate(gc.get_stats()):
        builder.sample("filodb_process_gc_collections_total",
                       {"generation": str(gen)},
                       int(st.get("collections", 0)), mtype="counter",
                       help="Garbage-collector collections per "
                            "generation")
        builder.sample("filodb_gc_pause_seconds_total",
                       {"generation": str(gen)},
                       GC_TIMER.pause_ns[gen] / 1e9, mtype="counter",
                       help="Seconds the garbage collector held the "
                            "interpreter, per generation (timed in "
                            "gc.callbacks)")
    builder.sample("filodb_gc_stalls_total", {}, GC_TIMER.stalls,
                   mtype="counter",
                   help="Collections that held the interpreter for at "
                        "least 20 ms")
    builder.sample("filodb_gc_stall_seconds_total", {},
                   GC_TIMER.stall_ns / 1e9, mtype="counter",
                   help="Seconds of the collections counted in "
                        "filodb_gc_stalls_total")
    builder.sample("filodb_process_uptime_seconds", {},
                   round(time.monotonic() - _START_MONOTONIC, 3),
                   help="Seconds since the obs layer was imported "
                        "(process startup)")
    builder.sample(
        "filodb_build_info",
        {"version": BUILD_VERSION,
         "python": "%d.%d.%d" % sys.version_info[:3]},
        1,
        help="Constant 1; build/runtime identity rides the labels")


def register_process_collector(registry=None) -> None:
    """Idempotently attach the process collector to ``registry``
    (default: the global registry)."""
    reg = registry if registry is not None else obs_metrics.GLOBAL_REGISTRY
    reg.register_collector(collect_process)
