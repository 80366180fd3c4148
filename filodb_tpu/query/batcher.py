"""Concurrent-query micro-batching + async device dispatch.

The serving fast path's admission layer in front of the TPU backend
(the Orca-style iteration-batching idea from the accelerator-serving
literature, applied to a TSDB's query kernels): requests that arrive
while the device executor is busy and resolve to the same bucketed
kernel shape are stacked — along the grid axis for the aligned
tilestore evaluators (one vmapped dispatch computes B step grids over
shared device tiles), along the series axis for the packed general
path (one kernel launch over the concatenated [S_total, N] tile with
per-row window vectors and per-query segment offsets) — executed as
ONE device dispatch, and split back per request.

Three cooperating pieces:

  * :class:`MicroBatcher` — admission. The first thread to submit a
    given batch key becomes the *leader*; when other query threads are
    concurrently inside the backend, the open batch is queued to the
    device executor and later arrivals keep joining it until the
    executor actually picks it up — the executor's busy time IS the
    gather window (continuous batching), so batching emerges exactly
    when there is queueing and costs nothing when there is none. When
    the executor is idle, an explicit residual gather window
    (``gather_window_s``, default 1ms, configurable) holds the batch
    open briefly so a concurrent same-shape arrival can still pair.
    A lone request (no concurrent traffic) bypasses all of it and runs
    the single-query kernel path inline.
  * :class:`DeviceExecutor` — a single dedicated thread that owns
    device submission. Batched dispatches run here; JAX async dispatch
    returns device futures immediately, so the executor is free to
    close and submit the NEXT batch while the device still computes
    the current one — host-side pack/stack overlaps device compute.
  * :class:`SplitResult` — the per-batch result holder. The device →
    host sync (``np.asarray`` on the stacked output) happens ONCE per
    batch, lazily, on the first *worker* thread that asks — never on
    the executor thread, and never per member.

Latency/deadline semantics: batching adds at most one gather window
(plus executor queueing that concurrent singles would pay as lock
contention anyway) to a query; a query whose deadline budget expires
fails in its own exec tree — a query hitting its deadline leaves the
batch, not the reverse.

Failure semantics: an exception in a batched dispatch fails every
member (they would all have taken the same kernel); callers surface it
exactly as a single-query kernel failure.

Priority classes (tenant QoS, query/qos.py): the executor's dispatch
queue orders by the submitting query's priority class — interactive <
rules/background < over-budget best-effort — so a brownout's monster
scans never head-of-line block cheap interactive queries. A batch's
class is the BEST (lowest) among its members at queue time: an
interactive arrival joining an open best-effort batch rides that
batch's already-queued position (PriorityQueue entries are immutable),
but the common case — a best-effort leader queueing behind interactive
leaders — reorders exactly as intended. On the CPU-inline path there
is no queue to reorder; best-effort leaders instead yield the GIL a
few extra rounds under concurrency so interactive threads pass them.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from filodb_tpu.lint.hotpath import hot_path
from filodb_tpu.lint.locks import guarded_by
from filodb_tpu.lint.threads import thread_root
from filodb_tpu.obs import metrics as obs_metrics
from filodb_tpu.obs import trace as obs_trace
from filodb_tpu.query import qos

# the batcher-queue-wait stage span observes the parked waits into this
# family; inline dispatches add their 0 here so _count stays the number
# of queries admitted
_QWAIT = obs_trace.STAGE_HISTOGRAMS["batcher-queue-wait"][:2]
_OCC_HELP = "Members per micro-batch dispatch (batch occupancy)"


class DeviceExecutor:
    """One dedicated thread owns device submission (the async-dispatch
    pipeline): HTTP worker threads enqueue batch closures and park on
    futures instead of holding the GIL through device sync.

    The queue orders by ``(priority, arrival)``: within a class it
    stays FIFO, across classes a waiting interactive dispatch always
    precedes a waiting best-effort one — the executor's busy time IS
    the gather window, so under brownout queueing this is exactly
    where head-of-line blocking would otherwise happen."""

    def __init__(self, name: str = "filodb-device-exec"):
        self._q: "queue.PriorityQueue[Tuple[int, int, Optional[Callable[[], None]]]]" \
            = queue.PriorityQueue()
        self._seq = itertools.count()   # FIFO tiebreak within a class
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._started = False
        self._start_lock = threading.Lock()

    def submit(self, fn: Callable[[], None],
               priority: int = qos.PRIORITY_INTERACTIVE) -> None:
        """Enqueue a closure for the executor thread (fire-and-forget:
        result delivery is the closure's business)."""
        with self._start_lock:
            if not self._started:
                self._started = True
                self._thread.start()
        self._q.put((int(priority), next(self._seq), fn))

    def idle(self) -> bool:
        """True when nothing is queued (the executor may still be
        finishing its current closure)."""
        return self._q.empty()

    @thread_root("device-executor")
    def _run(self) -> None:
        while True:
            _prio, _seq, fn = self._q.get()
            if fn is None:
                return
            try:
                fn()
            except BaseException:  # noqa: BLE001 — closures own delivery
                pass

    def stop(self) -> None:
        if self._started:
            # sorts behind every real priority class: queued work
            # drains before the executor exits
            self._q.put((1 << 30, next(self._seq), None))


class _TransferCounts:
    """``filodb_device_to_host_bytes_total``: bytes the ``device-sync``
    stages brought to the host (here and at the backend's syncs of its
    own), and ``filodb_device_to_host_arrays_total``: the arrays they
    materialised, one a transfer. Plain adds, like the backend's
    counters."""

    __slots__ = ("d2h_bytes", "d2h_arrays")

    def __init__(self):
        self.d2h_bytes = 0
        self.d2h_arrays = 0


transfer_counts = _TransferCounts()


class SplitResult:
    """Stacked device output of one batch, split back per member.

    ``get(i)`` returns member *i*'s numpy slice; the single device→host
    sync for the whole batch happens under ``_lock`` on the first
    caller's thread."""

    def __init__(self, stacked, n: int,
                 split: Optional[Callable[[np.ndarray, int], np.ndarray]]
                 = None):
        self._stacked = stacked
        self._n = n
        self._split = split
        self._host: Optional[np.ndarray] = None
        self._lock = threading.Lock()

    @hot_path
    def get(self, i: int) -> np.ndarray:
        with self._lock:
            if self._host is None:
                # the one amortized sync point for the whole batch
                # graftlint: disable=host-transfer-in-hot-loop,oversized-transfer (single per-batch sync for the whole batch; the device buffer is dropped right after, so no resident channel is being re-pulled)
                self._host = np.asarray(self._stacked)
                if self._host is not self._stacked:     # came off a device
                    transfer_counts.d2h_bytes += self._host.nbytes
                    transfer_counts.d2h_arrays += 1
                self._stacked = None
        if self._split is not None:
            return self._split(self._host, i)
        return self._host[i]


@guarded_by("_lock", "batches", "queries", "batched_queries",
            "occupancy_sum", "occupancy_max", "gather_wait_ns",
            "by_size", "by_priority")
class BatchStats:
    """Occupancy/throughput counters surfaced in /metrics."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.batches = 0            # dispatches issued
        self.queries = 0            # member queries admitted
        self.batched_queries = 0    # members of batches with size >= 2
        self.occupancy_sum = 0      # sum of batch sizes
        self.occupancy_max = 0
        self.gather_wait_ns = 0     # total residual gather-window time
        self.by_size: Dict[int, int] = {}
        # dispatches per priority class (tenant QoS): operators read
        # the brownout's best-effort share straight off /metrics
        self.by_priority: Dict[int, int] = {}

    def record(self, size: int, wait_ns: int,
               priority: int = qos.PRIORITY_INTERACTIVE) -> None:
        with self._lock:
            self.batches += 1
            self.queries += size
            if size >= 2:
                self.batched_queries += size
            self.occupancy_sum += size
            self.occupancy_max = max(self.occupancy_max, size)
            self.gather_wait_ns += wait_ns
            self.by_size[size] = self.by_size.get(size, 0) + 1
            self.by_priority[priority] = \
                self.by_priority.get(priority, 0) + size
        # occupancy distribution: p50/p95 batch sizes straight off a
        # /metrics scrape instead of the avg/max point gauges alone
        obs_metrics.observe("filodb_batcher_batch_size", _OCC_HELP,
                            float(size), obs_metrics.OCCUPANCY_BUCKETS)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            avg = (self.occupancy_sum / self.batches) if self.batches \
                else 0.0
            return {"batches": self.batches, "queries": self.queries,
                    "batched_queries": self.batched_queries,
                    "occupancy_avg": round(avg, 4),
                    "occupancy_max": self.occupancy_max,
                    "gather_wait_ms":
                        round(self.gather_wait_ns / 1e6, 3),
                    "by_size": dict(self.by_size),
                    "by_priority": {
                        qos.PRIORITY_NAMES.get(p, str(p)): n
                        for p, n in self.by_priority.items()}}


class _Pending:
    """One open batch: members join under the batcher lock until the
    executor closes it; the result flows through one shared future.
    ``priority`` is the best (lowest) class among members — set at
    open, promoted by joins under the batcher lock."""

    __slots__ = ("members", "future", "closed", "opened_ns", "priority")

    def __init__(self, priority: int = qos.PRIORITY_INTERACTIVE) -> None:
        self.members: List[object] = []
        self.future: Future = Future()
        self.closed = False
        self.opened_ns = time.perf_counter_ns()
        self.priority = int(priority)


@guarded_by("_lock", "_pending", "_active")
class MicroBatcher:
    """Gathers concurrent same-key kernel dispatches into one device
    submission (see module docstring).

    ``submit(key, member, run_batch)`` blocks until the member's result
    is available. ``run_batch(members) -> SplitResult`` executes the
    whole batch; with one member it routes to the single-query kernel
    path (bit-for-bit identical — the batched-vs-unbatched parity test
    pins this)."""

    def __init__(self, gather_window_s: float = 1e-3,
                 max_batch: int = 8, enabled: bool = True,
                 executor: Optional[DeviceExecutor] = None,
                 use_executor: Optional[bool] = None):
        self.gather_window_s = float(gather_window_s)
        self.max_batch = int(max_batch)
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._pending: Dict[object, _Pending] = {}
        self._active = 0        # query threads currently inside the backend
        # On an accelerator, ONE thread must own device submission (the
        # async-dispatch pipeline: queueing there is also the natural
        # gather window). On the CPU backend the "device" compute runs
        # inside the dispatch call on whatever thread makes it, GIL-
        # free — funnelling through one executor thread would serialize
        # compute that otherwise runs on multiple cores, so leaders
        # execute inline and gather via a bounded GIL yield instead.
        if use_executor is None:
            import jax
            use_executor = jax.default_backend() != "cpu"
        self.use_executor = bool(use_executor)
        self.executor = executor or DeviceExecutor()
        self.stats = BatchStats()

    # -- concurrency tracking --------------------------------------------
    def enter(self) -> None:
        """A query thread entered the backend (one per periodic_samples)."""
        with self._lock:
            self._active += 1

    def exit(self) -> None:
        with self._lock:
            self._active -= 1

    # -- admission --------------------------------------------------------
    @hot_path
    def submit(self, key: object, member: object,
               run_batch: Callable[[Sequence[object]], SplitResult],
               use_executor: Optional[bool] = None) -> np.ndarray:
        """Join (or open) the batch for ``key``; returns this member's
        split of the batch result.

        ``use_executor`` overrides the batcher-wide executor choice for
        this batch key: mesh-sharded dispatches pass True so ONE thread
        owns multi-device submission even on the CPU backend — a
        sharded program already spans every device, and N query threads
        running sharded programs inline would only oversubscribe the
        per-device compute threads (single-device CPU dispatches keep
        the inline path: there, per-thread execution IS the
        parallelism)."""
        prio = qos.current_priority()
        exec_here = self.use_executor if use_executor is None \
            else bool(use_executor)
        if not self.enabled:
            res = run_batch([member])
            self.stats.record(1, 0, prio)
            obs_metrics.observe(*_QWAIT, 0.0)
            return res.get(0)
        idx = None
        with self._lock:
            p = self._pending.get(key)
            if p is not None and not p.closed \
                    and len(p.members) < self.max_batch:
                idx = len(p.members)
                p.members.append(member)
                # a higher-class join promotes the OPEN batch's class
                # (an already-queued entry keeps its position — the
                # PriorityQueue entry is immutable; see module doc)
                if prio < p.priority:
                    p.priority = prio
            else:
                p = _Pending(priority=prio)
                p.members.append(member)
                concurrent = self._active > 1
                if concurrent:
                    self._pending[key] = p
        if idx is not None:     # follower: park outside the lock
            return self._wait(p, idx)
        if not concurrent:
            # lone request: single-query kernel path, inline — no
            # executor hop, no gather window
            obs_metrics.observe(*_QWAIT, 0.0)
            return self._execute(key, p, run_batch, queued=False)
        if exec_here:
            # leader under concurrency: queue the OPEN batch — arrivals
            # keep joining until the executor picks it up (its busy
            # time is the gather window), then park on the future.
            # The trace context hops threads with the closure so device
            # spans recorded on the executor land in the same trace;
            # the executor queue orders by the batch's priority class.
            # The leader parks INSIDE its batcher-queue-wait span before
            # the capture, so the batch's stages on the executor thread
            # are that span's children (self time: the pure wait).
            with obs_trace.span("batcher-queue-wait", leader=True):
                tctx = obs_trace.capture()
                self.executor.submit(
                    lambda: self._execute(key, p, run_batch, queued=True,
                                          tctx=tctx),
                    priority=p.priority)
                res = p.future.result()
            with obs_trace.span("device-sync"):
                return res.get(0)
        # CPU: gather by yielding the GIL a few times (concurrent
        # same-shape submitters join during the yields; no fixed sleep
        # enters the latency path), then execute on THIS thread so the
        # XLA-CPU compute of independent batches still uses all cores.
        # Best-effort work yields extra rounds under concurrency so
        # interactive threads overtake it at the GIL (there is no
        # dispatch queue to reorder on this path).
        yields = 3 if prio < qos.PRIORITY_BEST_EFFORT else 12
        for _ in range(yields):
            if len(p.members) >= self.max_batch:
                break
            time.sleep(0)
        obs_metrics.observe(*_QWAIT, 0.0)
        return self._execute(key, p, run_batch, queued=False)

    @hot_path
    def _wait(self, p: _Pending, idx: int) -> np.ndarray:
        with obs_trace.span("batcher-queue-wait"):
            res = p.future.result()
        with obs_trace.span("device-sync"):
            return res.get(idx)

    def _execute(self, key: object, p: _Pending, run_batch,
                 queued: bool, tctx=None) -> np.ndarray:
        """Close + run one batch; on the executor thread when
        ``queued`` (leader parks on the future), inline otherwise."""
        wait_ns = 0
        if queued and self.gather_window_s > 0 and self.executor.idle():
            # idle executor: hold the batch open for the residual
            # explicit gather window so a concurrent same-shape arrival
            # can still pair (skipped entirely when traffic keeps the
            # queue non-empty — batching is already emerging naturally)
            rem_s = self.gather_window_s \
                - (time.perf_counter_ns() - p.opened_ns) / 1e9
            if rem_s > 0 and len(p.members) < self.max_batch:
                t0 = time.perf_counter_ns()
                time.sleep(rem_s)
                wait_ns = time.perf_counter_ns() - t0
        with self._lock:
            p.closed = True
            if self._pending.get(key) is p:
                del self._pending[key]
            members = list(p.members)
            active = self._active
        try:
            # reinstall the submitting thread's trace context when this
            # runs on the executor thread (no-op for tctx=None/inline)
            with obs_trace.use(tctx):
                # batcher occupancy at dispatch (&explain=analyze): how
                # many members shared this device submission and how
                # many query threads were concurrently inside the
                # backend when it closed (no-op event when untraced)
                obs_trace.event("batcher-dispatch", size=len(members),
                                active=active, priority=p.priority,
                                queued=queued)
                res = run_batch(members)
        except BaseException as e:  # noqa: BLE001 — fail all members
            self.stats.record(len(members), wait_ns, p.priority)
            p.future.set_exception(e)
            if not queued:
                raise
            return None
        self.stats.record(len(members), wait_ns, p.priority)
        p.future.set_result(res)
        if queued:
            return None
        with obs_trace.span("device-sync"):
            return res.get(0)
