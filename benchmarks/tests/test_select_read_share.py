"""``select_read_share`` at a toy size on the CPU: the fused cell answers its
window from tile-cache entries and reads no handle (0.0, a reading, not
``None``); the packed cell reads every one (100); a program without the two
counters gives ``None`` and the metric is left out of the line.

    python -m pytest benchmarks/tests/test_select_read_share.py -q
"""

import pytest

import rehearse
import run

CELLS = {"promperf.history-sumby": ("fused_interpret", 0.0),
         "tsbs-devops.host-dashboards": (None, 100.0)}


def _ctx(handles, reads, before=0.0):
    m0 = {"filodb_select_series_total": before,
          "filodb_select_series_read_total": before}
    return run.Ctx(m0=m0, m1={
        "filodb_select_series_total": before + handles,
        "filodb_select_series_read_total": before + reads})


@pytest.mark.parametrize("handles, reads, want", [
    (4096, 0, 0.0), (4096, 4096, 100.0), (4096, 1024, 25.0), (0, 0, None)])
def test_reader(handles, reads, want):
    read = run.load_module("layers", "select_read_share").read
    assert read(_ctx(handles, reads, before=7.0)) == want


def test_program_without_the_counters_reads_nothing():
    read = run.load_module("layers", "select_read_share").read
    assert read(run.Ctx(m0={}, m1={"filodb_fused_aggs_total": 3.0})) is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_it(cell):
    fault, want = CELLS[cell]
    spec = run.Spec(cell)
    assert "select_read_share" in [m["name"]
                                   for m in spec.metrics("per_layer")]
    code, result = run.run_cell(cell, 13, 3, 1, look_for_chip=False,
                                scale=rehearse.TOY[spec.config["datagen"]],
                                fault=fault)
    assert result["correct"] and code == 0, result["checks"]
    got = result["metrics"]["select_read_share"]
    assert got == {"value": want, "unit": "%"}
