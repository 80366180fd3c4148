"""``promperf``'s counters for the node that owns 128 shards: the samples are
``promperf_counters.make``'s, to the letter.

Such a node runs 128 ingest drivers. A program whose idle drivers each poll
their stream fifty times a second on a thread of their own cannot run this
deployment inside a run's time limit: the pollers hold the one interpreter
against the node's own set-up and queries (PERF.md section 6, PR 34: on that
program the backfill of 49,152 series took 226 s on one chip's host, five
times cell 1's rate a series, the first request was not answered 194 s later,
and on four chips the run was killed at 350 s with its node left behind). So
before any sample is made this refuses, at once and with the reason, a
program that lacks drivers woken by their stream
(``IngestionDriver(idle_wait_s=..)``, ``filodb_tpu/ingest/driver.py``): the
run ends with an error instead of being killed. The look is at the source
text: the harness's parent process imports nothing of the program."""

import os

from datagen import promperf_counters

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEEDS = "idle_wait_s"


def can_run_128_shards(root=None):
    """Does the program of this checkout (or of the one at ``root``) wake
    its idle ingest drivers?"""
    try:
        with open(os.path.join(root or ROOT, "filodb_tpu", "ingest",
                               "driver.py")) as f:
            return NEEDS in f.read()
    except OSError:
        return False


def make(cfg, seed, scale=None):
    if not can_run_128_shards():
        raise RuntimeError(
            "this program cannot run the 128-shard node inside a run's "
            "time limit: its 128 idle ingest drivers poll their streams "
            f"(no {NEEDS} in filodb_tpu/ingest/driver.py); see "
            "benchmarks/datagen/shards128_counters.py")
    return promperf_counters.make(cfg, seed, scale)
