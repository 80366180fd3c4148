"""``selection_facts_hit_share`` at a toy size on the CPU: the fused cell
repeats a few selectors over a store that does not change and reads no
handle, so after each selector's first sights a request takes the tile key,
the tail bound and the histogram flag from the selection memo's entry (a
high share); the packed cell reads every handle, so every selection is new
and its facts are made once and used once (0.0, a reading, not ``None``); a
program without the two counters gives ``None`` and the metric is left out
of the line.

    python -m pytest benchmarks/tests/test_selection_facts_hit_share.py -q
"""

import pytest

import rehearse
import run

NAME = "selection_facts_hit_share"
CELLS = {"promperf.history-sumby": "fused_interpret",
         "tsbs-devops.host-dashboards": None}


def _ctx(hits, misses, before=0.0):
    m0 = {"filodb_selection_facts_hits_total": before,
          "filodb_selection_facts_misses_total": before}
    return run.Ctx(m0=m0, m1={
        "filodb_selection_facts_hits_total": before + hits,
        "filodb_selection_facts_misses_total": before + misses})


@pytest.mark.parametrize("hits, misses, want", [
    (1000, 0, 100.0), (0, 8000, 0.0), (300, 100, 75.0), (0, 0, None)])
def test_reader(hits, misses, want):
    read = run.load_module("layers", NAME).read
    assert read(_ctx(hits, misses, before=7.0)) == want


def test_program_without_the_counters_reads_nothing():
    read = run.load_module("layers", NAME).read
    assert read(run.Ctx(m0={}, m1={"filodb_select_memo_hits_total": 3.0})) \
        is None


def test_every_cell_lists_it():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["layer"] == "backend dispatch"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_it(cell):
    spec = run.Spec(cell)
    assert NAME in [m["name"] for m in spec.metrics("per_layer")]
    code, result = run.run_cell(cell, 13, 3, 1, look_for_chip=False,
                                scale=rehearse.TOY[spec.config["datagen"]],
                                fault=CELLS[cell])
    assert result["correct"] and code == 0, result["checks"]
    got = result["metrics"][NAME]
    assert got["unit"] == "%"
    if cell == "promperf.history-sumby":
        # three apps: at most a handful of first sights in the window
        assert got["value"] >= 90.0
        assert result["metrics"]["select_memo_hit_share"]["value"] >= 90.0
    else:
        assert got["value"] == 0.0
