"""What the interpreter itself costs a request (filodb_tpu.obs.process):
collections timed in ``gc.callbacks`` and charged to the stage they
interrupt, and the probe that reads how late a sleeping thread gets the
interpreter back. Every clock a test judges by is a fake: no assertion
here is on a measured duration.
"""

import gc
import threading
import urllib.request

import pytest

from filodb_tpu.lint.threads import THREAD_ROOTS
from filodb_tpu.obs import metrics as obm
from filodb_tpu.obs import process as obp
from filodb_tpu.obs import trace as obt
from filodb_tpu.standalone.server import FiloServer


@pytest.fixture
def timer():
    """The process's timer, registered as ``FiloServer.start()`` does,
    with automatic collections off so that only the test's own
    ``gc.collect()`` runs."""
    if obp.GC_TIMER not in gc.callbacks:
        gc.callbacks.append(obp.GC_TIMER)
    was = gc.isenabled()
    gc.disable()
    yield obp.GC_TIMER
    if was:
        gc.enable()


def _stage_gc():
    out = {}
    for name, st in obt._STAGE_TABLE.items():
        with st.lock:
            out[name] = st.gc_ns
    return out


# -- collections ---------------------------------------------------------------

def test_collection_is_charged_to_the_innermost_stage_alone(timer):
    b, p0 = _stage_gc(), list(timer.pause_ns)
    with obt.span("query"):
        with obt.span("parse"):
            gc.collect()
    a, p1 = _stage_gc(), list(timer.pause_ns)
    rose = {n for n in a if a[n] != b[n]}
    assert rose == {"parse"}                # nothing of its parent
    # one full collection: the stage holds what generation 2 gained
    assert a["parse"] - b["parse"] == p1[2] - p0[2] > 0
    assert p1[:2] == p0[:2]
    assert obt.stage_totals()["parse"][3] == a["parse"] / 1e9


def _collect_on_a_hop():
    """As the batcher's executor: under ``use(capture())`` of a stage
    another thread holds open, before any stage opens under the hop."""
    def executor(ctx):
        with obt.use(ctx):
            gc.collect()

    with obt.span("batcher-queue-wait"):
        t = threading.Thread(target=executor, args=(obt.capture(),))
        t.start()
        t.join(timeout=60)
    assert not t.is_alive()


@pytest.mark.parametrize("collect", [gc.collect, _collect_on_a_hop],
                         ids=["no-stage", "hop-frame"])
def test_collection_outside_any_stage_is_in_the_process_family_only(
        timer, collect):
    b, p0 = _stage_gc(), list(timer.pause_ns)
    collect()
    assert _stage_gc() == b                 # the waiting leader's too
    assert timer.pause_ns[2] > p0[2]


def test_stages_never_hold_more_than_the_generations(timer):
    for stage in ("execute", "encode"):
        with obt.span(stage):
            gc.collect()
    gc.collect()
    assert 0 < sum(_stage_gc().values()) <= sum(timer.pause_ns)


@pytest.mark.parametrize("pause_ns, stalls", [
    (0, 0), (19_999_999, 0), (20_000_000, 1), (350_000_000, 1)])
def test_stall_pair_rises_only_from_the_threshold(pause_ns, stalls):
    now = [5_000]
    t = obp.GcTimer(clock=lambda: now[0])
    t("start", {"generation": 2})
    now[0] += pause_ns
    t("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    assert t.pause_ns == [0, 0, pause_ns]
    assert (t.stalls, t.stall_ns) == (stalls, stalls * pause_ns)
    t("start", {"generation": 0})
    now[0] += 7
    t("stop", {"generation": 0, "collected": 0, "uncollectable": 0})
    assert t.pause_ns == [7, 0, pause_ns] and t.stalls == stalls
    assert obp.GC_STALL_NS == 20_000_000


# -- the probe -----------------------------------------------------------------

class _Script:
    """A clock and a sleep that share one fake time: each sleep takes
    what was asked plus the next scripted lateness, and the last one
    stops the loop."""

    def __init__(self, probe_of, lateness_s):
        self.now, self.left, self.asked = 0, list(lateness_s), []
        self.probe = probe_of(self)

    def clock(self):
        return self.now

    def sleep(self, asked):
        self.asked.append(asked)
        self.now += round((asked + self.left.pop(0)) * 1e9)
        if not self.left:
            self.probe._stop.set()


def _wait_hist():
    snap = obm.GLOBAL_REGISTRY.histogram(
        obp.WAIT_FAMILY, obp.WAIT_HELP, obp.WAIT_BUCKETS_S).snapshot()
    return snap["counts"], snap["sum"], snap["count"]


def _waited():
    return sum(v for _, v in obm.GLOBAL_REGISTRY.counter(
        obp.WAITED_FAMILY, obp.WAITED_HELP).series())


def test_probe_loop_counts_waits_and_fills_the_right_buckets():
    # on time, early (clamped to 0), 2 ms (a late wake-up: not a
    # wait), 3 ms, 30 ms, 2 s
    late = [0.0, -0.001, 0.002, 0.003, 0.03, 2.0]
    draws = iter([0.005, 0.015, 0.01, 0.01, 0.0075, 0.0125])
    s = _Script(lambda s: obp.InterpreterProbe(
        sleep=s.sleep, clock=s.clock,
        uniform=lambda lo, hi: next(draws)), late)
    (c0, sum0, n0), w0 = _wait_hist(), _waited()
    s.probe._run()                          # returns when the script ends
    (c1, sum1, n1), w1 = _wait_hist(), _waited()
    assert s.asked == [0.005, 0.015, 0.01, 0.01, 0.0075, 0.0125]
    assert n1 - n0 == 6 and w1 - w0 == 3
    assert sum1 - sum0 == pytest.approx(0.002 + 0.003 + 0.03 + 2.0,
                                        abs=1e-6)
    le = obp.WAIT_BUCKETS_S
    want = [0] * (len(le) + 1)
    want[le.index(0.00005)] = 2
    want[le.index(0.0025)] = 1
    want[le.index(0.005)] = 1
    want[le.index(0.05)] = 1
    want[-1] = 1                            # past 1 s: +Inf
    assert [a - b for a, b in zip(c1, c0)] == want
    # the line is an edge of the histogram: the counter is what the
    # buckets past it hold
    assert obp.PROBE_WAITED_S == 0.0025 and obp.PROBE_WAITED_S in le
    assert obp.PROBE_SLEEP_S == (0.005, 0.015)
    assert le == (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                  0.01, 0.025, 0.05, 0.1, 0.25, 1.0)


def test_probe_sleeps_are_drawn_from_the_declared_range():
    s = _Script(lambda s: obp.InterpreterProbe(sleep=s.sleep,
                                               clock=s.clock), [0.0] * 50)
    s.probe._run()
    lo, hi = obp.PROBE_SLEEP_S
    assert len(s.asked) == 50 and len(set(s.asked)) > 40
    assert all(lo <= a <= hi for a in s.asked)


# -- once a process ------------------------------------------------------------

def _server():
    return FiloServer({"num-shards": 2, "port": 0})


def _probe_running():
    return obp._probe is not None and obp._probe.running


def test_timer_is_registered_once_however_many_servers_run():
    servers = [_server().start() for _ in range(3)]
    try:
        assert gc.callbacks.count(obp.GC_TIMER) == 1
    finally:
        for s in servers:
            s.stop()
    again = _server().start()
    again.stop()
    again.stop()                            # a second stop gives nothing back
    assert gc.callbacks.count(obp.GC_TIMER) == 1


def test_probe_thread_lives_from_the_first_start_to_the_last_stop(
        monkeypatch):
    # a process of its own as far as the instruments go: servers that
    # other tests of this worker hold keep their share
    monkeypatch.setattr(obp, "_instrument_users", 0)
    monkeypatch.setattr(obp, "_probe", None)
    a, b = _server(), _server()
    assert not _probe_running()
    a.start()
    try:
        assert _probe_running()
        thread = obp._probe._thread
        assert thread.daemon and thread.name == "interpreter-probe"
        b.start()
        assert obp._probe._thread is thread          # one, not two
        a.stop()
        assert _probe_running() and thread.is_alive()
    finally:
        a.stop()
        b.stop()
    assert not _probe_running() and not thread.is_alive()
    root = THREAD_ROOTS["InterpreterProbe._run"]
    assert root["name"] == "interpreter-probe"
    assert root["module"] == "filodb_tpu.obs.process"


# -- exposition ----------------------------------------------------------------

def test_new_families_are_on_metrics_with_help_and_type():
    s = _server().start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{s.port}/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        s.stop()
    samples = [ln.rsplit(" ", 1)[0] for ln in text.splitlines()
               if ln and not ln.startswith("#")]
    for fam, mtype in (("filodb_gc_pause_seconds_total", "counter"),
                       ("filodb_gc_stalls_total", "counter"),
                       ("filodb_gc_stall_seconds_total", "counter"),
                       (obp.WAITED_FAMILY, "counter"),
                       (obp.WAIT_FAMILY, "histogram")):
        assert f"# HELP {fam} " in text, fam
        assert f"# TYPE {fam} {mtype}" in text, fam
    for gen in "012":
        assert f'filodb_gc_pause_seconds_total{{generation="{gen}"}}' \
            in samples
    # what a reader that sums label sets needs is unlabelled
    for fam in ("filodb_gc_stalls_total", "filodb_gc_stall_seconds_total",
                obp.WAITED_FAMILY, obp.WAIT_FAMILY + "_sum",
                obp.WAIT_FAMILY + "_count"):
        assert fam in samples, fam
    assert obp.WAIT_FAMILY + '_bucket{le="0.0025"}' in samples
    assert obm.validate_histogram_families(text) == []
    assert len(samples) == len(set(samples))
