"""The harness end to end at a toy size, with no chip.

``run_cell(look_for_chip=False)`` skips the harness's look for a chip and
drives the rest of a run against a CPU node. The control (the reference at
lower precision, or a stale answer, in the program's place) and every fault
planted under the timed path have to come out as not correct, by the number
that is there to catch them; the same run without them passes that number.

    python -m pytest benchmarks/tests -q        (about three minutes)
"""

import subprocess
import sys

import pytest

import rehearse
import run

CELLS = ["promperf.history-sumby", "tsbs-devops.host-dashboards",
         "promperf.live-scrape"]


# cells whose entries are not in BENCHMARK.json yet (candidates/<cell>.json)
CANDIDATES = {"promperf.live-scrape"}


def toy_run(cell, **kw):
    candidate = cell if cell in CANDIDATES else None
    spec = run.Spec(cell, candidate)
    code, result = run.run_cell(cell, 7, 3, 0, look_for_chip=False,
                                scale=rehearse.TOY[spec.config["datagen"]],
                                candidate=candidate, **kw)
    return code, result


def failed(result, check):
    c = result["checks"][check]
    return c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_passes_its_numbers(cell):
    code, result = toy_run(cell)
    assert not failed(result, "max_rel_err")
    assert result["checks"]["answers_compared"]["value"] >= 1
    if cell == "promperf.live-scrape":
        assert not failed(result, "readback_samples_differ")
    if cell != "promperf.history-sumby":
        # (a CPU node never takes the fused path cell 1 has to be served by)
        assert result["correct"] and code == 0


@pytest.mark.parametrize("control", ["bf16", "stale"])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, control):
    code, result = toy_run(cell, control=control)
    assert failed(result, "max_rel_err")
    assert not result["correct"] and code != 0


@pytest.mark.parametrize("cell,fault,check", [
    ("promperf.history-sumby", "alter_answer", "max_rel_err"),
    ("tsbs-devops.host-dashboards", "alter_answer", "max_rel_err"),
    ("promperf.live-scrape", "alter_answer", "max_rel_err"),
    ("promperf.live-scrape", "drop_rows", "readback_samples_differ"),
])
def test_fault_under_the_timed_path_is_not_correct(cell, fault, check):
    code, result = toy_run(cell, fault=fault)
    assert failed(result, check)
    assert not result["correct"] and code != 0


def test_no_chip_no_result():
    """``run.py`` itself never falls back: on a machine without a TPU it
    prints no result and exits non-zero."""
    p = subprocess.run(
        [sys.executable, run.__file__, "--workload", CELLS[1], "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == b""
